"""True 1F1B pipeline schedule: O(pp·vpp) in-flight activations.

Reference: ``apex/transformer/pipeline_parallel/schedules/
fwd_bwd_pipelining_without_interleaving.py:241-597`` — warmup
(``pp - rank - 1`` forwards), steady 1F1B (one forward + one backward per
step), cooldown; each rank holds at most ``pp`` in-flight microbatch
activation sets, so pipeline memory is independent of the number of
microbatches — and its interleaved sibling
``fwd_bwd_pipelining_with_interleaving.py:27-744``, whose scheduler runs
backward inside the schedule with at most ``pp * vpp`` in-flight
microbatch×chunk activation sets.

The scan-autodiff schedules in this package
(:func:`..fwd_bwd_pipelining_without_interleaving.pipeline_forward_backward`)
differentiate THROUGH the schedule, so reverse-mode saves O(n_micro·vpp)
stage-boundary activations (O(total/K) with ``tick_checkpoint``). This
module instead runs the backward INSIDE the forward scan — the schedule
itself computes gradients — which restores the reference's memory bound,
for both the plain (``num_chunks=1``) and interleaved/virtual-pipeline
(``num_chunks=vpp``) schedules:

- Each scan iteration is one (F, B) double-tick over ``T = n·vpp + D +
  pp − 1`` ticks, ``D = (vpp−1)·pp + (pp−1)``. Rank ``r`` forwards
  stream item ``uf = t − r`` (chunk ``(uf//pp) % vpp``, microbatch
  ``(uf//(vpp·pp))·pp + uf%pp`` — the reference interleaved scheduler's
  group-of-``pp`` order) and backwards stream item ``vb = t − D −
  (pp−1−r)``, which walks chunks in REVERSE order (``vpp−1`` → 0).
  Activations hop rank-to-rank by ``ppermute`` (+1 forward, −1 backward;
  the 0 → pp−1 wrap carries the inter-chunk backward hand-off). The last
  stage closes the loop in the same iteration: whenever the B sub-tick
  needs a loss gradient (a final-chunk backward item), its own F
  sub-tick just produced exactly that microbatch's final-chunk output —
  ``uf − vb = (vpp−1)·pp`` ticks apart, which is one whole final-chunk
  lead — textbook 1F1B at every vpp.
- Per-(microbatch, chunk) stage residuals (the ``jax.vjp`` closure's
  arrays, minus leaves that ARE the chunk parameters — weights are
  shared, not per-microbatch; at B time they are re-sliced from the
  stacked ``[vpp, ...]`` tree by backward chunk index) live in a
  ``W = 2·vpp·pp − 1``-slot ring buffer. A residual written at tick
  ``tf`` is read at ``tf + (2(vpp−1−c))·pp + 2(pp−1) − 2r < W`` ticks
  later, so slots never collide and peak activation memory is
  O(pp·vpp) — independent of ``n_micro`` (asserted by
  ``tests/test_pipeline_1f1b.py`` via ``compile().memory_analysis()``
  for vpp = 1 and vpp = 2).

SPMD note: all ranks share one program and one (static) buffer size, so
the uniform window is the worst rank's rather than the reference's
per-rank staggered count — the same O(pp·vpp) class, paid once per rank.
Bubble: ``D + pp − 1`` double-ticks over ``n·vpp + D + pp − 1`` total —
the reference's ``(pp−1)/(m·vpp)``-class fraction at large ``n``.

Residual caveat: leaves are deduplicated against the chunk parameters by
trace-time object identity. A stage that casts its weights (e.g.
``w.astype(bf16)``) stores the CAST copy per slot; pass pre-cast
parameters to 1F1B stages (as Megatron's bf16 training does) to keep the
ring buffer to activations only.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ... import parallel_state
from ...._vma import pvary_union_like
from .common import emit_tick

Pytree = Any


@jax.named_scope("apex_tpu.pipeline_1f1b")
def pipeline_forward_backward_1f1b(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params: Pytree,
    inputs: jax.Array,  # [n_micro, ...] first-stage activations
    extras: Optional[Pytree] = None,  # [n_micro, ...] loss inputs (labels)
    *,
    axis_name: Optional[str] = None,
    grad_scaler: Optional[Callable] = None,
    with_dinputs: bool = True,
    num_chunks: int = 1,
    tick_hook=None,
):
    """1F1B forward+backward inside ``shard_map``; same contract as
    :func:`pipeline_forward_backward`: returns ``(mean_loss, grads,
    dinputs)`` with the loss psum-broadcast, ``grads`` w.r.t. the local
    ``stage_params`` (summed over microbatches of the 1/n-scaled loss)
    and ``dinputs`` the gradient w.r.t. ``inputs`` (nonzero on stage 0,
    synced over the axis). ``grad_scaler`` must be linear (loss scaling).

    ``num_chunks=vpp > 1`` is the interleaved/virtual-pipeline schedule:
    ``stage_params`` leaves carry a leading ``[vpp]`` chunk axis (chunk
    ``c`` on stage ``s`` holds global layer block ``c*pp + s``, the
    reference layout); ``grads`` come back in the same stacked shape.
    Requires ``n_micro % pp == 0`` (the reference asserts the same).

    ``tick_hook`` (e.g. ``apex_tpu.telemetry.TickTimeline``) receives an
    async per-double-tick ``(t, rank, active_f, active_b)`` emission —
    the measured warmup (F-only) / steady (1F1B) / cooldown (B-only)
    timeline. This schedule's scan is never differentiated (the backward
    runs inside it), so unlike the autodiff schedules the hook always
    fires; zero host syncs added (``jax.debug.callback``).

    ``with_dinputs=False`` skips the input-gradient accumulation and
    returns ``dinputs=None``. The dinputs buffer is ``[n_micro, ...]`` —
    inherently O(n_micro), exactly like ``inputs`` itself — so a trainer
    that handles the embedding gradient separately (the reference layout)
    should disable it to keep the schedule's TEMP memory strictly
    O(pp·vpp).
    """
    a = axis_name if axis_name is not None else parallel_state.PIPELINE_AXIS
    pp = jax.lax.axis_size(a)
    rank = jax.lax.axis_index(a)
    n = inputs.shape[0]
    vpp = int(num_chunks)
    if vpp < 1:
        raise ValueError(f"num_chunks must be >= 1, got {num_chunks}")
    if vpp > 1 and n % pp != 0:
        raise ValueError(
            f"interleaved 1F1B requires n_micro ({n}) divisible by the "
            f"pipeline size (reference asserts the same)"
        )
    if extras is None:
        extras = jnp.zeros((n,))
    nv = n * vpp  # stream length
    W = max(2 * vpp * pp - 1, 1)
    D = (vpp - 1) * pp + (pp - 1)
    T = nv + D + (pp - 1)
    perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]
    perm_bwd = [(i, (i - 1) % pp) for i in range(pp)]

    def chunk_params(c):
        if vpp == 1:
            return stage_params
        return jax.tree_util.tree_map(
            lambda p: jax.lax.dynamic_index_in_dim(p, c, 0, keepdims=False),
            stage_params,
        )

    def scaled_loss(y, ex):
        val = loss_fn(y, ex) / n
        if grad_scaler is not None:
            val = grad_scaler(val)
        return val

    def stage_vjp_flat(params_c, x):
        y, vjp_fn = jax.vjp(stage_fn, params_c, x)
        flat, treedef = jax.tree_util.tree_flatten(vjp_fn)
        return y, flat, treedef

    # which residual leaves are the chunk parameters themselves (weights
    # are shared across microbatches — never ring-buffered; at B time
    # they are re-sliced by the BACKWARD chunk index, which differs from
    # the same tick's forward chunk when vpp > 1)?
    probe_params = chunk_params(0)
    param_leaves = jax.tree_util.tree_leaves(probe_params)
    id_to_leaf = {id(p): i for i, p in enumerate(param_leaves)}
    x0 = jnp.zeros_like(inputs[0])
    y0, flat0, treedef = stage_vjp_flat(probe_params, x0)
    # The fwd/bwd ring messages are sized off the stage INPUT; a stage
    # whose output dtype/shape differs would be silently cast on every
    # hop (shape errors are loud, dtype coercion is not) — refuse it.
    if y0.shape != x0.shape or y0.dtype != x0.dtype:
        raise TypeError(
            "1F1B stage_fn must map activations to the same shape/dtype "
            f"(stages are homogeneous across ranks): got {x0.dtype}"
            f"{list(x0.shape)} -> {y0.dtype}{list(y0.shape)}. Cast inside "
            "the stage so the pipeline messages carry one dtype."
        )
    param_pos = [id_to_leaf.get(id(r), -1) for r in flat0]
    buf_shapes = [
        (r.shape, r.dtype) for r, pi in zip(flat0, param_pos) if pi < 0
    ]
    del y0, flat0

    def body(carry, t):
        fwd_msg, bwd_msg, res_buf, grad_acc, loss_acc, dinputs = carry

        # ---- F sub-tick: rank r forwards stream item t - r ------------
        uf = jnp.clip(t - rank, 0, nv - 1)
        active_f = (t - rank >= 0) & (t - rank < nv)
        cf = (uf // pp) % vpp
        m_f = (uf // (vpp * pp)) * pp + uf % pp
        inj = jax.lax.dynamic_index_in_dim(inputs, m_f, 0, keepdims=False)
        x = jnp.where((rank == 0) & (cf == 0), inj,
                      fwd_msg).astype(inputs.dtype)
        y, flat, _ = stage_vjp_flat(chunk_params(cf), x)
        slot_w = jnp.mod(t, W)
        acts = [r for r, pi in zip(flat, param_pos) if pi < 0]
        res_buf = [
            jax.lax.dynamic_update_index_in_dim(
                b, r.astype(b.dtype), slot_w, 0
            )
            for b, r in zip(res_buf, acts)
        ]

        # ---- last stage: loss + its own backward seed -----------------
        # (on a final-chunk F tick, y IS that microbatch's model output)
        ex = jax.tree_util.tree_map(
            lambda e: jax.lax.dynamic_index_in_dim(
                e, m_f, 0, keepdims=False
            ),
            extras,
        )
        loss_m, dy_self = jax.value_and_grad(scaled_loss)(y, ex)
        active_l = active_f & (rank == pp - 1) & (cf == vpp - 1)
        loss_acc = loss_acc + jnp.where(active_l, loss_m, 0.0)

        # ---- B sub-tick: rank r backwards stream item t - D - (pp-1-r),
        # which visits chunks in reverse order (vpp-1 first) ------------
        vb_raw = t - D - (pp - 1 - rank)
        active_b = (vb_raw >= 0) & (vb_raw < nv)
        if tick_hook is not None:
            emit_tick(tick_hook, t, rank, active_f, active_b)
        vb = jnp.clip(vb_raw, 0, nv - 1)
        kb = (vb // pp) % vpp
        cb = (vpp - 1) - kb
        m_b = (vb // (vpp * pp)) * pp + vb % pp
        seed = (rank == pp - 1) & (kb == 0)
        dy = jnp.where(seed, dy_self.astype(bwd_msg.dtype), bwd_msg)
        # the ring slot this residual was written to: its forward tick
        # at this rank, mod W (lifetime < W, so never collided)
        uf_b = (m_b // pp) * (vpp * pp) + cb * pp + m_b % pp
        slot_r = jnp.mod(uf_b + rank, W)
        read = [
            jax.lax.dynamic_index_in_dim(b, slot_r, 0, keepdims=False)
            for b in res_buf
        ]
        # reassemble the vjp closure: chunk-cb param leaves where the
        # residual IS a parameter (positions are static — same stage_fn,
        # same shapes every iteration), ring-buffered activations
        # elsewhere
        pb_leaves = jax.tree_util.tree_leaves(chunk_params(cb))
        merged = []
        read_iter = iter(read)
        for pi in param_pos:
            merged.append(pb_leaves[pi] if pi >= 0 else next(read_iter))
        vjp_fn = jax.tree_util.tree_unflatten(treedef, merged)
        dparams, dx = vjp_fn(dy.astype(y.dtype))

        def acc_leaf(g, d):
            d = jnp.where(active_b, d.astype(g.dtype), 0.0)
            if vpp == 1:
                return g + d
            cur = jax.lax.dynamic_index_in_dim(g, cb, 0, keepdims=False)
            return jax.lax.dynamic_update_index_in_dim(g, cur + d, cb, 0)

        grad_acc = jax.tree_util.tree_map(acc_leaf, grad_acc, dparams)
        # stage-0 chunk-0 input gradients accumulate into the [n, ...]
        # output
        if dinputs is not None:
            dinputs = jax.lax.dynamic_update_index_in_dim(
                dinputs,
                jnp.where(
                    active_b & (rank == 0) & (cb == 0),
                    dx.astype(dinputs.dtype),
                    jax.lax.dynamic_index_in_dim(
                        dinputs, m_b, 0, keepdims=False
                    ),
                ),
                m_b, 0,
            )

        # ---- ring hops ------------------------------------------------
        fwd_next = jax.lax.ppermute(y.astype(fwd_msg.dtype), a, perm_fwd)
        bwd_next = jax.lax.ppermute(dx.astype(bwd_msg.dtype), a, perm_bwd)
        return (fwd_next, bwd_next, res_buf, grad_acc, loss_acc,
                dinputs), None

    operands = (stage_params, inputs)
    fwd0 = pvary_union_like(jnp.zeros_like(inputs[0]), operands, (a,))
    bwd0 = pvary_union_like(jnp.zeros_like(inputs[0]), operands, (a,))
    res0 = [
        pvary_union_like(jnp.zeros((W,) + s, d), operands, (a,))
        for s, d in buf_shapes
    ]
    grad0 = jax.tree_util.tree_map(
        lambda p: pvary_union_like(
            jnp.zeros(p.shape, jnp.float32), operands, (a,)
        ),
        stage_params,
    )
    loss0 = pvary_union_like(jnp.zeros((), jnp.float32), operands, (a,))
    din0 = (
        pvary_union_like(jnp.zeros_like(inputs), operands, (a,))
        if with_dinputs else None
    )

    (_, _, _, grads, loss, dinputs), _ = jax.lax.scan(
        body, (fwd0, bwd0, res0, grad0, loss0, din0), jnp.arange(T)
    )
    loss = jax.lax.psum(loss, a)
    if dinputs is not None:
        dinputs = jax.lax.psum(dinputs, a)
    # grads accumulate in fp32 across microbatches (the reference's
    # fp32 main-grad discipline) but return in the PARAM dtype to match
    # pipeline_forward_backward's contract exactly
    grads = jax.tree_util.tree_map(
        lambda g, p: g.astype(p.dtype), grads, stage_params
    )
    return loss, grads, dinputs
