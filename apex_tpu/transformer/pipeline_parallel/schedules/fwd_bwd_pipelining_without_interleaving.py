"""1F1B-equivalent pipelining schedule, single-jit SPMD.

Reference:
``apex/transformer/pipeline_parallel/schedules/fwd_bwd_pipelining_without_interleaving.py:241-597``
— warmup (``pp − rank − 1`` microbatches), steady 1F1B
(send_forward_recv_backward / backward / send_backward_recv_forward),
cooldown drain; hand-written backward_step per microbatch.

TPU-native: the forward pipeline is ONE ``lax.scan`` over
``n_micro·vpp + pp − 1`` ticks in which every stage applies its per-tick
chunk and ``ppermute``s the activation to its successor; stage 0 injects a
fresh microbatch on its chunk-0 ticks and consumes ring wrap-arounds on the
rest (see :func:`pipeline_rounds` for the exact schedule). The *backward*
schedule is not written at all: differentiating the scan transposes every
ppermute into the reverse hop and replays stages in reverse tick order —
structurally the same drain the reference's cooldown loop implements. With
``checkpoint_stages=True`` each stage call is rematerialised in backward.

Honest memory note: autodiff through the scan saves the per-tick stage
*boundary* activations — O(n_micro·vpp) of them (the final outputs are
accumulated into an O(n_micro) carry buffer rather than stacked per tick).
``tick_checkpoint=K`` cuts the saved boundaries to O(total/K)
(sqrt-style nested remat; chunk outputs leave the remat region as
compressed emission slots) at the cost of replaying tick forwards in
backward. That is still not
the O(pp) in-flight bound true 1F1B achieves by interleaving each
microbatch's backward into the steady state — a re-circulating custom-vjp
schedule would be needed for the exact 1F1B footprint.

This function is the *local* (inside-``shard_map``) form so it composes
with TP/SP/DP axes; ``run_pipeline`` wraps it in a shard_map for the
single-axis case.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ... import parallel_state
from ...._vma import pvary_union_like
from ..utils import vma_tracking_active
from .common import (
    emit_tick,
    warn_hook_under_autodiff,
    warn_ignored_parity_kwargs,
)

Pytree = Any


@jax.named_scope("apex_tpu.pipeline_rounds")
def pipeline_rounds(
    stage_fn: Callable,
    stage_params_chunks,  # tuple of per-chunk trees, or stacked tree + num_chunks
    inputs: jax.Array,  # [n, ...] microbatched first-stage activations
    axis_name: str,
    checkpoint_stages: bool,
    num_chunks: Optional[int] = None,
    tick_checkpoint: Optional[int] = None,
    tick_hook=None,
) -> jax.Array:
    """Stream all microbatches through ``vpp = len(chunks)`` traversals of
    the stage ring in ONE continuous scan of ``n·vpp + pp − 1`` ticks —
    the interleaved (virtual-pipeline) schedule with no inter-round barrier.

    Work layout (matches the reference interleaved scheduler,
    ``fwd_bwd_pipelining_with_interleaving.py:27-744``): microbatches are
    processed in groups of ``pp``; the item entering stage 0 at tick ``t``
    is microbatch ``(t // (vpp·pp))·pp + t % pp`` on chunk
    ``(t // pp) % vpp`` — i.e. group ``g``'s chunk-``c`` pass begins the
    tick chunk ``c−1``'s first wrap-around arrives, while group ``g+1``
    starts injecting the tick group ``g`` finishes. Stage 0 is never idle
    between warmup and drain, so the pipeline bubble is ``pp − 1`` *ticks*
    (vs ``(pp−1)·vpp`` for the non-interleaved schedule at the same total
    work): the reference's ``(pp−1)/(m·vpp)`` bubble fraction.

    Every stage selects its per-tick chunk params by dynamic index into the
    stacked ``[vpp, ...]`` chunk axis (the SPMD spelling of the reference's
    model-chunk bookkeeping).

    Requires ``n % pp == 0`` when ``vpp > 1`` (the reference asserts the
    same). Returns the final-chunk outputs ``[n, ...]`` microbatch-ordered,
    valid on the last stage.

    ``tick_checkpoint=K`` nests the scan into remat'd K-tick chunks
    (sqrt-style checkpointing): backward saves only the chunk-boundary
    ring states — O(total/K) boundary activations instead of O(total) —
    at the cost of replaying each tick's forward in backward (twice with
    ``checkpoint_stages``). Chunk outputs leave the remat region as
    compressed emission slots, so the [n, ...] output buffer is never
    part of a saved carry.
    """
    pp = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    n = inputs.shape[0]
    if isinstance(stage_params_chunks, (tuple, list)):
        # legacy per-chunk-tuple interface: stack once here
        vpp = len(stage_params_chunks)
        if vpp == 1:
            stacked = stage_params_chunks[0]
        else:
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *stage_params_chunks
            )
    else:
        # already-stacked tree: leaves carry a leading [num_chunks] axis
        # (none for num_chunks == 1) — no slice/re-stack round-trip
        if num_chunks is None:
            raise ValueError("num_chunks required with a stacked params tree")
        vpp = num_chunks
        stacked = stage_params_chunks
    if vpp > 1 and n % pp != 0:
        raise ValueError(
            f"interleaved schedule requires n_micro ({n}) divisible by the "
            f"pipeline size (reference asserts the same)"
        )
    fwd = jax.checkpoint(stage_fn) if checkpoint_stages else stage_fn
    perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]
    total = n * vpp + pp - 1  # ticks

    def tick(state, t):
        """One pipeline tick: (ring state, t) -> (new state, this tick's
        stage output y + its output bookkeeping)."""
        # the item this rank processes entered stage 0 at tick u
        u = jnp.clip(t - rank, 0, n * vpp - 1)
        c = (u // pp) % vpp  # chunk this rank applies at tick t
        if tick_hook is not None:
            # telemetry: async per-tick emission (t, rank, active, no-B);
            # inactive ticks are this schedule's masked-garbage bubble
            emit_tick(tick_hook, t, rank,
                      (t - rank >= 0) & (t - rank < n * vpp),
                      jnp.asarray(False))
        # stage 0 injects a fresh microbatch on its chunk-0 ticks; on other
        # ticks it consumes the wrap-around from the last stage
        inject_now = (t // pp) % vpp == 0
        m_inj = jnp.clip((t // (vpp * pp)) * pp + t % pp, 0, n - 1)
        injected = jax.lax.dynamic_index_in_dim(inputs, m_inj, 0, keepdims=False)
        x = jnp.where((rank == 0) & inject_now, injected, state)
        if vpp == 1:
            params_c = stacked
        else:
            params_c = jax.tree_util.tree_map(
                lambda p: jax.lax.dynamic_index_in_dim(p, c, 0, keepdims=False),
                stacked,
            )
        y = fwd(params_c, x)
        new_state = jax.lax.ppermute(y, axis_name, perm_fwd)
        # microbatch m = g·pp + i finishes its final chunk at tick
        # g·vpp·pp + (vpp−1)·pp + i + (pp−1) (on the LAST stage; other
        # ranks' emissions are garbage rows the masked loss never reads)
        uo = t - (pp - 1)
        is_out = (uo >= 0) & (uo < n * vpp) & (
            ((jnp.clip(uo, 0, n * vpp - 1) // pp) % vpp) == vpp - 1
        )
        uo = jnp.clip(uo, 0, n * vpp - 1)
        m_out = jnp.clip((uo // (vpp * pp)) * pp + uo % pp, 0, n - 1)
        return new_state, (y, m_out, is_out)

    def body(carry, t):
        """Plain-path body: accumulate final outputs into an [n, ...]
        carry buffer instead of stacking every tick's y ([total, ...]) —
        forward live memory O(n) output rows."""
        state, outs = carry
        new_state, (y, m_out, is_out) = tick(state, t)
        cur = jax.lax.dynamic_index_in_dim(outs, m_out, 0, keepdims=False)
        row = jnp.where(is_out, y, cur)
        outs = jax.lax.dynamic_update_index_in_dim(outs, row, m_out, 0)
        return (new_state, outs), None

    # the carry is pipeline-varying (it came through a ppermute), and under a
    # composed mesh the stage output inherits whatever axes the params or
    # inputs vary on — mark the zeros init with the union so the scan carry
    # types close under shard_map's vma tracking
    init = pvary_union_like(
        jnp.zeros_like(inputs[0]), (inputs, stacked), (axis_name,)
    )
    outs0 = pvary_union_like(
        jnp.zeros_like(inputs), (inputs, stacked), (axis_name,)
    )
    if tick_checkpoint is None:
        (_, outs), _ = jax.lax.scan(body, (init, outs0), jnp.arange(total))
        return outs  # [n, ...] microbatch-ordered, valid on last stage

    # sqrt-style nested remat over K-tick chunks. The remat'd region's
    # carry is the ring state ONLY (one boundary activation per chunk) —
    # NOT the [n, ...] outs buffer, which an outer-scan carry would re-save
    # at every boundary (O(n_outer * n) residuals, defeating the point).
    # Instead each chunk emits its (at most n_emit) final-output rows as
    # compressed remat-region OUTPUTS, scattered into [n, ...] once after
    # the scan. Residuals: O(total/K) boundary states; recompute: each
    # tick's forward replays in backward (twice with checkpoint_stages).
    # Padding ticks (K not dividing total) recompute clipped indices
    # harmlessly with is_out masked off. NB the emission machinery itself
    # carries ~2x the [n, ...] output rows through the outer scan, so the
    # net win needs the ring states to dominate — i.e. vpp > 2 or large
    # per-tick states (pinned by tests/test_pipeline_1f1b.py's
    # memory_analysis assertion at vpp=4: ~5x lower peak temp).
    k = int(tick_checkpoint)
    if k <= 0:
        raise ValueError(f"tick_checkpoint must be positive, got {k}")
    n_outer = -(-total // k)
    # emissions within K ticks: one pp-tick block per vpp*pp period
    n_emit = min(k, (k // (vpp * pp) + 2) * pp)

    @jax.checkpoint
    def outer_body(state, t0):
        emit0 = (
            pvary_union_like(
                jnp.zeros((n_emit,) + inputs.shape[1:], inputs.dtype),
                (inputs, stacked), (axis_name,)
            ),
            jnp.zeros((n_emit,), jnp.int32),
            jnp.zeros((n_emit,), jnp.bool_),
            jnp.int32(0),  # next free slot
        )

        def inner(carry, t):
            state, (rows, idxs, valids, slot) = carry
            new_state, (y, m_out, is_out) = tick(state, t)
            s = jnp.clip(slot, 0, n_emit - 1)
            cur = jax.lax.dynamic_index_in_dim(rows, s, 0, keepdims=False)
            rows = jax.lax.dynamic_update_index_in_dim(
                rows, jnp.where(is_out, y, cur), s, 0)
            idxs = jnp.where(
                is_out, idxs.at[s].set(m_out.astype(jnp.int32)), idxs)
            valids = jnp.where(is_out, valids.at[s].set(True), valids)
            slot = slot + is_out.astype(jnp.int32)
            return (new_state, (rows, idxs, valids, slot)), None

        (state, emits), _ = jax.lax.scan(
            inner, (state, emit0), t0 + jnp.arange(k))
        return state, emits[:3]

    _, (rows, idxs, valids) = jax.lax.scan(
        outer_body, init, jnp.arange(n_outer) * k)
    # scatter all chunk emissions into the [n, ...] output buffer; invalid
    # slots go to row n (dropped)
    flat_rows = rows.reshape((n_outer * n_emit,) + inputs.shape[1:])
    dest = jnp.where(
        valids.reshape(-1), idxs.reshape(-1), n).astype(jnp.int32)
    outs = jnp.zeros_like(
        jnp.concatenate([outs0, outs0[:1]], axis=0))
    outs = outs.at[dest].set(flat_rows, mode="drop")
    return outs[:n]  # [n, ...] microbatch-ordered, valid on last stage


def pipeline_forward_backward(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params: Pytree,
    inputs: jax.Array,
    extras: Optional[Pytree] = None,
    *,
    forward_only: bool = False,
    axis_name: Optional[str] = None,
    checkpoint_stages: bool = True,
    grad_scaler: Optional[Callable] = None,
    num_chunks: int = 1,
    tick_checkpoint: Optional[int] = None,
    tick_hook=None,
    **parity_kwargs,
):
    """Local (inside-shard_map) 1F1B-equivalent forward+backward.

    Args:
      stage_fn: ``(stage_params, hidden) -> hidden`` — one microbatch through
        this stage's chunk. Uniform across stages (SPMD); per-stage weights
        live in ``stage_params`` (already the local shard).
      loss_fn: ``(hidden, extra) -> scalar`` — applied on the last stage.
      stage_params: local chunk params; with ``num_chunks > 1`` (virtual
        pipelining, handled by the interleaved wrapper) a leading chunk axis.
      inputs: ``[n_micro, ...]`` microbatched activations entering stage 0
        (embedding output; compute embeddings outside, replicated or
        TP-sharded).
      extras: per-microbatch loss inputs (labels), leading axis ``n_micro``.

    Returns ``(mean_loss, grads, dinputs)``; the loss is psum-broadcast so
    every stage reports the same value; grads are wrt the local
    ``stage_params`` (zero for ticks that never reached the loss);
    ``dinputs`` is the gradient wrt ``inputs`` (nonzero on stage 0 — for
    chaining into an embedding backward). With ``forward_only=True`` returns
    ``(mean_loss, None, None)``.

    Mechanical parity kwargs are ignored silently; semantic ones
    (``custom_sync_context_handler``, ...) warn once.

    ``tick_hook`` (e.g. ``apex_tpu.telemetry.TickTimeline``) receives an
    async per-tick ``(t, rank, active_f, active_b)`` emission for bubble
    accounting — forward-only runs only: jax drops debug callbacks from
    the differentiated scan (warned once).
    """
    warn_ignored_parity_kwargs("pipeline_forward_backward", parity_kwargs)
    if tick_hook is not None and not forward_only:
        warn_hook_under_autodiff("pipeline_forward_backward")
    a = axis_name if axis_name is not None else parallel_state.PIPELINE_AXIS
    pp = jax.lax.axis_size(a)
    rank = jax.lax.axis_index(a)
    n = inputs.shape[0]
    if extras is None:
        extras = jnp.zeros((n,))

    def local_loss(params, inputs):
        outs = pipeline_rounds(
            stage_fn, params, inputs, a, checkpoint_stages,
            num_chunks=num_chunks, tick_checkpoint=tick_checkpoint,
            tick_hook=tick_hook,
        )

        # emit per-microbatch losses and sum after — no carry, so neither
        # the loss dtype (may differ from the stage-output dtype in mixed
        # precision) nor its vma set needs pre-declaring
        def per_micro(carry, xs):
            y, ex = xs
            return carry, loss_fn(y, ex)

        _, per_losses = jax.lax.scan(per_micro, None, (outs, extras))
        total = jnp.sum(per_losses)
        # only the last stage's outputs are real; mask others to zero so
        # their (garbage) loss neither reports nor back-propagates
        masked = jnp.where(rank == pp - 1, total / n, 0.0)
        if grad_scaler is not None:
            masked = grad_scaler(masked)
        return masked

    if forward_only:
        loss = local_loss(stage_params, inputs)
        return jax.lax.psum(loss, a), None, None

    loss, (grads, dinputs) = jax.value_and_grad(local_loss, argnums=(0, 1))(
        stage_params, inputs
    )

    # dinputs is nonzero only on stage 0 (the inject path); a psum makes the
    # embedding gradient identical everywhere for chaining outside shard_map.
    # Under check_vma=True the transpose already inserted that psum (inputs
    # are unvarying, so their cotangent comes back unvarying) — psum only the
    # leaves vma still marks as varying, else we'd scale by pp. With vma
    # tracking OFF every aval has an empty vma, so fall back to the
    # unconditional psum (distinguished via the axis_index probe).
    tracking = vma_tracking_active(a)

    def _sync(g):
        if tracking and a not in getattr(g.aval, "vma", ()):
            return g
        return jax.lax.psum(g, a)

    dinputs = jax.tree_util.tree_map(_sync, dinputs)
    return _sync(loss), grads, dinputs


def run_pipeline(
    mesh,
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params: Pytree,
    inputs: jax.Array,
    extras: Optional[Pytree] = None,
    *,
    forward_only: bool = False,
    checkpoint_stages: bool = True,
    num_chunks: int = 1,
    tick_checkpoint: Optional[int] = None,
    tick_hook=None,
):
    """Convenience single-axis wrapper: shard_map the local schedule over the
    ``pipeline`` mesh axis. ``stage_params`` leaves carry a leading ``[pp]``
    (or ``[pp, num_chunks]`` with virtual chunks) axis sharded across stages.

    Returns ``(loss,)`` if ``forward_only`` else ``(loss, grads, dinputs)``
    with grads stacked ``[pp, ...]`` like ``stage_params``.
    """
    from jax.sharding import PartitionSpec as P

    ax = parallel_state.PIPELINE_AXIS
    pspec = jax.tree_util.tree_map(lambda _: P(ax), stage_params)
    if extras is None:
        n = jax.tree_util.tree_leaves(inputs)[0].shape[0]
        extras = jnp.zeros((n,))

    if forward_only:
        def local_f(params, inputs, extras):
            params = jax.tree_util.tree_map(lambda p: p[0], params)
            loss, _, _ = pipeline_forward_backward(
                stage_fn, loss_fn, params, inputs, extras,
                forward_only=True, axis_name=ax,
                checkpoint_stages=checkpoint_stages, num_chunks=num_chunks,
                tick_checkpoint=tick_checkpoint, tick_hook=tick_hook,
            )
            return loss

        return jax.shard_map(
            local_f, mesh=mesh, in_specs=(pspec, P(), P()),
            out_specs=P(), check_vma=True,
        )(stage_params, inputs, extras)

    def local(params, inputs, extras):
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        loss, grads, dinp = pipeline_forward_backward(
            stage_fn, loss_fn, params, inputs, extras,
            forward_only=False, axis_name=ax,
            checkpoint_stages=checkpoint_stages, num_chunks=num_chunks,
            tick_checkpoint=tick_checkpoint, tick_hook=tick_hook,
        )
        grads = jax.tree_util.tree_map(lambda g: g[None], grads)
        return loss, grads, dinp

    grads_spec = jax.tree_util.tree_map(lambda _: P(ax), stage_params)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(pspec, P(), P()),
        out_specs=(P(), grads_spec, P()), check_vma=True,
    )(stage_params, inputs, extras)
