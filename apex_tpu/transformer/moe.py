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
memory and nothing else: one traced bound, the row tiles in use
(``ops.moe_rows.tiles_in_use``), governs every sweep between the router and
the layer's output as it governs the grouped products - the gather into
expert order, the activation, the weighted sum back into token order, and
the transposes of all three. What such a sweep leaves past its bound is
never written and may hold anything, NaN included; every reader selects by
range and never multiplies by a mask. ``stats["rows_walked"]`` says how far
the bound reached (an eighth of the buffer where 16 of 128 experts are held
and the load is even; all of it when every choice falls on them).

Selection is ``grouped_matmul``'s contract: on TPU the kernels of
``ops/moe_rows.py``; off it the XLA formulation below (``jnp.take`` and
``_gather_sum``: identical math over every ``tokens x k`` slot, the tests'
oracle); ``interpret=True`` runs the kernels' bodies under the Pallas
interpreter.

Scopes (``telemetry.tracing.LAYER_SCOPES``), nested in the caller's
``apex_tpu.mlp``: ``apex_tpu.moe_router`` (scores, top-k, weights),
``apex_tpu.moe_dispatch`` (one sort of the assignments with their weights,
``apex_tpu_moe_gather`` into expert order, ``apex_tpu_moe_add`` back into
token order, their transposes), ``apex_tpu.moe_experts`` (the grouped
products and ``apex_tpu_moe_act_*`` between them), ``apex_tpu.moe_shared``.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import grouped_matmul, row_tile
from ..ops.moe_rows import add_rows, gated_act, gather_rows, tiles_in_use

HIGHEST = jax.lax.Precision.HIGHEST

# jitted: traced and lowered once for each signature, not once a call (a
# layer makes three products, forward, recomputed and backward)
_grouped_matmul = jax.jit(grouped_matmul, static_argnames=("interpret",))


class Plan(NamedTuple):
    """Where each assignment ``(token, choice)`` goes, and with what weight."""

    token_of_row: jax.Array     # [rows] the token a buffer row holds
    choice_of_row: jax.Array    # [rows] which of the token's k choices
    row_of: jax.Array           # [tokens, k] the row of an assignment
    held: jax.Array             # [tokens, k] its expert is held here
    group_sizes: jax.Array      # [count] rows of each expert held
    order: jax.Array            # [tokens * k] assignments (token * k + choice) by row, absent ones last
    weight_of_row: jax.Array    # [rows] float32, the assignment's weight


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


def _fit(v: jax.Array, n: int) -> jax.Array:
    """``v`` cut to its first ``n`` elements, or filled up to ``n`` with
    zeros."""
    if v.shape[0] < n:
        v = jnp.concatenate([v, jnp.zeros((n - v.shape[0],), v.dtype)])
    return v[:n]


def plan(selected: jax.Array, weights: jax.Array, held: Tuple[int, int],
         rows: int) -> Plan:
    """Sort the assignments by expert held (those of absent experts last),
    their weights with them, and size the groups. One stable sort carries
    the weights, so no sweep gathers them by row; ``row_of`` and
    ``choice_of_row`` serve the XLA formulation alone and cost nothing
    where no one reads them."""
    first, count = held
    tokens, k = selected.shape
    n = tokens * k
    local = selected - first
    is_held = (local >= 0) & (local < count)
    key = jnp.where(is_held, local, count).reshape(n)
    _, order, by_row = jax.lax.sort(
        (key, jnp.arange(n, dtype=jnp.int32),
         weights.astype(jnp.float32).reshape(n)), num_keys=1, is_stable=True)
    row_of = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    padded, by_row = _fit(order, rows), _fit(by_row, rows)
    sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=jnp.int32)[None],
                    axis=0, dtype=jnp.int32)
    return Plan(padded // k, padded % k, row_of.reshape(tokens, k), is_held,
                sizes, order, by_row)


def _gather_sum(rows: jax.Array, p: Plan, weights: Optional[jax.Array]):
    """``out[t] = sum over the choices j held here of weights[t, j] *
    rows[row_of[t, j]]`` in float32; rows of absent experts are selected
    away (they may hold anything). The XLA formulation: it gathers every
    ``tokens x k`` slot."""
    picked = jnp.take(rows, p.row_of, axis=0, mode="clip").astype(jnp.float32)
    if weights is not None:
        picked = picked * weights[..., None]
    return jnp.sum(jnp.where(p.held[..., None], picked, 0.0), axis=1)


def _in_use(p: Plan):
    n_rows = p.token_of_row.shape[0]
    return jnp.arange(n_rows, dtype=jnp.int32) < jnp.sum(p.group_sizes)


def _kernels(interpret: bool) -> bool:
    """``grouped_matmul``'s contract: the kernels on TPU, their bodies
    under the interpreter where asked, else the XLA formulation."""
    return interpret or jax.default_backend() == "tpu"


def _tiles(p: Plan) -> jax.Array:
    return tiles_in_use(p.group_sizes, p.token_of_row.shape[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def dispatch(x: jax.Array, p: Plan, readers: int = 1,
             interpret: bool = False) -> Tuple[jax.Array, ...]:
    """``[tokens, hidden] -> [rows, hidden]`` in expert order, handed out
    once for each of its ``readers`` (one buffer): the backward then gets
    each reader's gradient by itself and adds them row by row, inside the
    bound, where autodiff's own sum would sweep the whole buffer. On the
    kernels' path the rows past the tiles in use are not written."""
    if _kernels(interpret):
        rows = gather_rows(x, p.token_of_row, _tiles(p), out_dtype=x.dtype,
                           interpret=interpret)
    else:
        rows = jnp.take(x, p.token_of_row, axis=0)
    return (rows,) * readers


def _dispatch_fwd(x, p, readers, interpret):
    return dispatch(x, p, readers, interpret), p


def _dispatch_bwd(readers, interpret, p, d_rows):
    # the transpose of a gather is a scatter-add: each row in use is added
    # into its token, in float32
    if _kernels(interpret):
        d_x = add_rows(d_rows, p.token_of_row, p.group_sizes,
                       p.held.shape[0], interpret=interpret)
    else:
        d_x = _gather_sum(functools.reduce(jnp.add, d_rows), p, None)
    return d_x.astype(d_rows[0].dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine(rows: jax.Array, weights: jax.Array, p: Plan,
            interpret: bool = False) -> jax.Array:
    """``[rows, hidden] -> [tokens, hidden]``: each token's held choices,
    weighted, summed in float32 (``p.weight_of_row`` holds ``weights`` by
    row). Its backward hands ``d rows`` back unwritten past the tiles in
    use (the grouped kernels mask both operands)."""
    if not _kernels(interpret):
        return _gather_sum(rows, p, weights).astype(rows.dtype)
    return add_rows((rows,), p.token_of_row, p.group_sizes, weights.shape[0],
                    scale=p.weight_of_row, interpret=interpret
                    ).astype(rows.dtype)


def _combine_fwd(rows, weights, p, interpret):
    return combine(rows, weights, p, interpret), (rows, weights, p)


def _by_assignment(of_row: jax.Array, p: Plan) -> jax.Array:
    """``[rows] -> [tokens, k]``: a sort by ``order`` undoes the sort that
    made it (what stands on rows no assignment owns goes to absent
    assignments, or nowhere)."""
    of_row = _fit(of_row, p.order.shape[0])
    _, back = jax.lax.sort((p.order, of_row), num_keys=1)
    return back.reshape(p.held.shape)


def _combine_bwd(interpret, res, d_out):
    rows, weights, p = res
    if _kernels(interpret):
        d_rows, dots = gather_rows(
            d_out, p.token_of_row, _tiles(p), out_dtype=rows.dtype,
            scale=p.weight_of_row, dot_with=rows, interpret=interpret)
        d_w = _by_assignment(dots, p)
    else:
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
    scalars: assignments ``routed`` here, the largest expert's load over
    the mean load, assignments that found no row (``dropped``, 0: the
    buffer holds the worst case), and how much of the buffer the sweeps
    walked: ``rows_walked`` (their common bound, whole row tiles; all of
    it on the XLA formulation) of ``buffer_rows``."""
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
            jax.lax.stop_gradient, plan(selected, weights, held, rows))
        for_gate, for_up = dispatch(x, p, 2, interpret)
    with jax.named_scope("apex_tpu.moe_experts"):
        gmm = functools.partial(_grouped_matmul, group_sizes=p.group_sizes,
                                interpret=interpret)
        gate = gmm(for_gate, lp["experts_gate_w"])
        up = gmm(for_up, lp["experts_up_w"])
        if _kernels(interpret):
            act = gated_act(gate, up, _tiles(p), interpret)
        else:
            act = jax.nn.silu(gate) * up
        ys = gmm(act, lp["experts_down_w"])
    with jax.named_scope("apex_tpu.moe_dispatch"):
        y = combine(ys, weights, p, interpret)
    if "shared_gate_w" in lp:
        with jax.named_scope("apex_tpu.moe_shared"):
            y = y + gated_mlp(x, lp["shared_gate_w"], lp["shared_up_w"],
                              lp["shared_down_w"])
    routed = jnp.sum(p.group_sizes).astype(jnp.float32)
    walked = _tiles(p) * row_tile(rows) if _kernels(interpret) else rows
    stats = {
        "routed": routed,
        "rows_walked": jnp.asarray(walked, jnp.float32),
        "buffer_rows": jnp.float32(rows),
        "max_over_mean_load": jnp.max(p.group_sizes).astype(jnp.float32)
        * count / jnp.maximum(routed, 1.0),
        "dropped": jnp.maximum(routed - rows, 0.0),
    }
    return y, stats
