"""The expert layer: a router over all experts, the share of them held here.

An expert-parallel rank holds ``experts_held = (first, count)`` of a
layer's ``num_experts`` experts. It routes every token over ALL of them
(the router keeps its published width and its experts per token, and the
weights are normalised over every expert selected, held or not), computes
its own experts' part of the result for the tokens routed to them, and
adds the shared expert, which every rank computes alike. What the absent
experts would add is the other ranks' part; on one chip there is no
exchange and nothing stands in for it.

Dropless by construction: the assignments are sorted by expert, the group
sizes come from the routing, and ``ops.grouped_matmul`` runs over exactly
the rows routed here; there is no capacity factor. The buffers have static
shapes sized for the worst case the routing allows: a token's ``k`` choices
can all fall on experts held here, so ``tokens x min(k, count)`` rows
(rounded to the kernels' row tile). The rows past the routed ones cost
memory, and a pass wherever XLA (not a kernel) sweeps the whole buffer;
they are never read into a result (selected away, never multiplied).

Scopes (``telemetry.tracing.LAYER_SCOPES``), nested in the caller's
``apex_tpu.mlp``: ``apex_tpu.moe_router`` (scores, top-k, weights),
``apex_tpu.moe_dispatch`` (sort, gather into expert order, the weighted
gather back), ``apex_tpu.moe_experts`` (the grouped products and their
activation), ``apex_tpu.moe_shared``.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import grouped_matmul, row_tile

HIGHEST = jax.lax.Precision.HIGHEST


class Plan(NamedTuple):
    """Where each assignment ``(token, choice)`` goes."""

    token_of_row: jax.Array     # [rows] the token a buffer row holds
    choice_of_row: jax.Array    # [rows] which of the token's k choices
    row_of: jax.Array           # [tokens, k] the row of an assignment
    held: jax.Array             # [tokens, k] its expert is held here
    group_sizes: jax.Array      # [count] rows of each expert held


def buffer_rows(tokens: int, per_token: int, count: int) -> int:
    """Rows of the dispatch buffer: the most assignments ``tokens`` can send
    to ``count`` experts at ``per_token`` distinct experts a token, rounded
    up to the grouped product's row tile."""
    worst = tokens * min(per_token, count)
    tile = row_tile(-(-worst // 8) * 8)
    return -(-worst // tile) * tile


@jax.named_scope("apex_tpu.moe_router")
def route(x32: jax.Array, router_w: jax.Array, *, per_token: int,
          score: str = "sigmoid", route_norm: bool = True,
          route_scale: float = 1.0, bias: Optional[jax.Array] = None
          ) -> Tuple[jax.Array, jax.Array]:
    """``(selected [tokens, k] int32, weights [tokens, k] float32)`` from
    float32 inputs ``[tokens, hidden]`` and the router ``[experts,
    hidden]``: scores in float32 at full precision (a score rounded to
    bf16 picks other experts where the k-th and the next lie close), the
    ``k`` largest of ``score + bias`` (the bias moves the choice, never the
    weight), and ``scale * score / sum of the selected scores``."""
    logits = jnp.einsum("th,eh->te", x32.astype(jnp.float32),
                        router_w.astype(jnp.float32), precision=HIGHEST)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router score function {score!r}")
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    _, selected = jax.lax.top_k(jax.lax.stop_gradient(choice), per_token)
    picked = jnp.take_along_axis(scores, selected, axis=-1)
    if route_norm:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return selected.astype(jnp.int32), picked * route_scale


def plan(selected: jax.Array, held: Tuple[int, int], rows: int) -> Plan:
    """Sort the assignments by expert held (those of absent experts last)
    and size the groups."""
    first, count = held
    tokens, k = selected.shape
    n = tokens * k
    local = selected - first
    is_held = (local >= 0) & (local < count)
    key = jnp.where(is_held, local, count).reshape(n)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    row_of = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    if rows > n:
        order = jnp.concatenate([order, jnp.zeros((rows - n,), jnp.int32)])
    order = order[:rows]
    sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=jnp.int32)[None],
                    axis=0, dtype=jnp.int32)
    return Plan(order // k, order % k, row_of.reshape(tokens, k), is_held,
                sizes)


def _gather_sum(rows: jax.Array, p: Plan, weights: Optional[jax.Array]):
    """``out[t] = sum over the choices j held here of weights[t, j] *
    rows[row_of[t, j]]`` in float32; rows of absent experts are selected
    away (they may hold anything)."""
    picked = jnp.take(rows, p.row_of, axis=0, mode="clip").astype(jnp.float32)
    if weights is not None:
        picked = picked * weights[..., None]
    return jnp.sum(jnp.where(p.held[..., None], picked, 0.0), axis=1)


def _in_use(p: Plan):
    n_rows = p.token_of_row.shape[0]
    return jnp.arange(n_rows, dtype=jnp.int32) < jnp.sum(p.group_sizes)


@jax.custom_vjp
def dispatch(x: jax.Array, p: Plan) -> jax.Array:
    """``[tokens, hidden] -> [rows, hidden]`` in expert order."""
    return jnp.take(x, p.token_of_row, axis=0)


def _dispatch_fwd(x, p):
    return dispatch(x, p), p


def _dispatch_bwd(p, d_rows):
    # the transpose of a gather is a scatter-add; each row in use belongs to
    # one assignment, so it is a gather-sum over the token's own choices
    return _gather_sum(d_rows, p, None).astype(d_rows.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(rows: jax.Array, weights: jax.Array, p: Plan) -> jax.Array:
    """``[rows, hidden] -> [tokens, hidden]``: each token's held choices,
    weighted, summed in float32."""
    return _gather_sum(rows, p, weights).astype(rows.dtype)


def _combine_fwd(rows, weights, p):
    return combine(rows, weights, p), (rows, weights, p)


def _combine_bwd(res, d_out):
    rows, weights, p = res
    w_row = weights[p.token_of_row, p.choice_of_row]
    d_rows = jnp.take(d_out, p.token_of_row, axis=0).astype(jnp.float32)
    d_rows = jnp.where(_in_use(p)[:, None], d_rows * w_row[:, None], 0.0)
    picked = jnp.take(rows, p.row_of, axis=0, mode="clip")
    d_w = jnp.einsum("tkh,th->tk", picked.astype(jnp.float32),
                     d_out.astype(jnp.float32))
    d_w = jnp.where(p.held, d_w, 0.0)
    return d_rows.astype(rows.dtype), d_w.astype(weights.dtype), None


combine.defvjp(_combine_fwd, _combine_bwd)


def gated_mlp(x, gate_w, up_w, down_w):
    """``down(silu(gate x) * up x)`` with ``[out, in]`` weights."""
    dt = x.dtype
    g = jnp.einsum("...h,fh->...f", x, gate_w.astype(dt))
    u = jnp.einsum("...h,fh->...f", x, up_w.astype(dt))
    return jnp.einsum("...f,hf->...h", jax.nn.silu(g) * u, down_w.astype(dt))


def expert_mlp(
    x: jax.Array,               # [tokens, hidden], compute dtype
    x32: jax.Array,             # [tokens, hidden], the router's float32 input
    lp: Dict[str, jax.Array],
    *,
    num_experts: int,
    held: Tuple[int, int],
    per_token: int,
    score: str = "sigmoid",
    route_norm: bool = True,
    route_scale: float = 1.0,
    interpret: bool = False,
):
    """This rank's part of the expert layer: ``shared(x) + sum over the
    selected experts held here of w_e E_e(x)``. ``lp`` holds ``router_w
    [num_experts, hidden]``, the held experts' ``experts_gate_w``,
    ``experts_up_w`` ``[count, hidden, ffn]`` and ``experts_down_w``
    ``[count, ffn, hidden]``, optionally ``expert_bias [num_experts]`` and
    the shared expert's ``shared_gate_w``, ``shared_up_w``,
    ``shared_down_w``. Returns ``(y, stats)``; ``stats`` are float32
    scalars: assignments routed here, the largest expert's load over the
    mean load, assignments that found no row (0: the buffer holds the
    worst case)."""
    first, count = held
    if lp["router_w"].shape[0] != num_experts or not (
            0 <= first and first + count <= num_experts):
        raise ValueError(
            f"experts_held {held} of a router {lp['router_w'].shape[0]} wide "
            f"(num_experts {num_experts})")
    tokens = x.shape[0]
    rows = buffer_rows(tokens, per_token, count)
    selected, weights = route(
        x32, lp["router_w"], per_token=per_token, score=score,
        route_norm=route_norm, route_scale=route_scale,
        bias=lp.get("expert_bias"))
    with jax.named_scope("apex_tpu.moe_dispatch"):
        p = jax.tree_util.tree_map(
            jax.lax.stop_gradient, plan(selected, held, rows))
        xs = dispatch(x, p)
    with jax.named_scope("apex_tpu.moe_experts"):
        gmm = functools.partial(grouped_matmul, group_sizes=p.group_sizes,
                                interpret=interpret)
        act = jax.nn.silu(gmm(xs, lp["experts_gate_w"])) * gmm(
            xs, lp["experts_up_w"])
        ys = gmm(act, lp["experts_down_w"])
    with jax.named_scope("apex_tpu.moe_dispatch"):
        y = combine(ys, weights, p)
    if "shared_gate_w" in lp:
        with jax.named_scope("apex_tpu.moe_shared"):
            y = y + gated_mlp(x, lp["shared_gate_w"], lp["shared_up_w"],
                              lp["shared_down_w"])
    routed = jnp.sum(p.group_sizes).astype(jnp.float32)
    stats = {
        "routed": routed,
        "max_over_mean_load": jnp.max(p.group_sizes).astype(jnp.float32)
        * count / jnp.maximum(routed, 1.0),
        "dropped": jnp.sum(p.held & (p.row_of >= rows)).astype(jnp.float32),
    }
    return y, stats
