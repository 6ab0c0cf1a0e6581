"""Jit-resident training metrics: accumulate on device, drain async.

The reference instruments training with host-side timers and rank-0 print
loops (``apex/transformer/pipeline_parallel/_timers.py``, the fork's
scaling scripts scraping "Average Iteration Time" from stdout) — every
readout is a ``cudaDeviceSynchronize``-class stall. Here the metrics ARE
part of the jitted step: a :class:`MetricsState` pytree rides the train
step carry, every statistic is accumulated by on-device arithmetic that
XLA fuses into the step, and the only host interaction is
:func:`drain` — an **async** ``jax.debug.callback`` under ``lax.cond``
that fires every ``every_n`` steps and never blocks the device.
Instrumentation therefore adds ZERO extra host syncs to the hot path
(``analysis.audit_step``'s ``ungated_callback`` rule holds a step to it;
what it costs in step time is not measured on the chip).

Usage::

    from apex_tpu import telemetry

    rec = telemetry.JsonlRecorder("train_metrics.jsonl")
    metrics = telemetry.init_metrics()

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, opt_state, metrics, ...):
        loss, grads = ...
        params, opt_state = opt.step(grads, opt_state, params)
        metrics = telemetry.accumulate(
            metrics, loss=loss, tokens=batch * seq)
        metrics = telemetry.drain(metrics, rec, every_n=10)
        return params, opt_state, metrics, loss

Window statistics (loss, norms, tokens) reset at every drain; the
overflow-skip / scale-growth counters are cumulative for the whole run
(the ``amp.LossScaler`` contract — see
:meth:`apex_tpu.amp.LossScaler.update_scale` with ``metrics=``).
"""
from __future__ import annotations

import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .recorder import stamp_wall

Pytree = Any


class MetricsState(NamedTuple):
    """Device-resident metric accumulators (all scalars, jit-friendly).

    ``total_*`` fields are cumulative; the rest are window accumulators
    reset by :func:`drain`.
    """

    total_steps: jax.Array      # i32, never reset
    window_steps: jax.Array     # i32, steps since last drain
    loss_sum: jax.Array         # f32 window sum
    loss_last: jax.Array        # f32 most recent loss
    grad_norm_sum: jax.Array    # f32 window sum of global grad L2 norms
    param_norm_sum: jax.Array   # f32 window sum of global param L2 norms
    tokens: jax.Array           # f32 window token count
    total_tokens: jax.Array     # f32 cumulative token count
    loss_scale: jax.Array       # f32 last observed loss scale (0 = none)
    overflow_skips: jax.Array   # i32 cumulative skipped (overflowed) steps
    scale_growths: jax.Array    # i32 cumulative loss-scale growth events
    # the expert layers' counters (transformer/moe.py: gpt_loss(...,
    # moe_stats=True)); all zero for a model without experts
    moe_routed: jax.Array       # f32 window sum: assignments routed to the experts held here
    moe_max_load: jax.Array     # f32 last step's largest expert load over the mean load
    moe_dropped: jax.Array      # i32 cumulative assignments that found no row (dropless: 0)
    moe_rows_walked: jax.Array  # f32 window sum: buffer rows the expert layers' sweeps walked
    moe_buffer_rows: jax.Array  # f32 last step's buffer rows (the worst case the routing allows)


def init_metrics() -> MetricsState:
    # one fresh array per field: reusing a single zero scalar would alias
    # the same device buffer across fields, and donating the state into a
    # jitted step then donates one buffer twice (an XLA error)
    f = lambda: jnp.float32(0.0)  # noqa: E731
    i = lambda: jnp.int32(0)  # noqa: E731
    return MetricsState(
        total_steps=i(), window_steps=i(), loss_sum=f(), loss_last=f(),
        grad_norm_sum=f(), param_norm_sum=f(), tokens=f(),
        total_tokens=f(), loss_scale=f(), overflow_skips=i(),
        scale_growths=i(), moe_routed=f(), moe_max_load=f(),
        moe_dropped=i(), moe_rows_walked=f(), moe_buffer_rows=f(),
    )


def _global_l2(tree: Pytree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves
    ))


def accumulate(
    m: MetricsState,
    *,
    loss: Optional[jax.Array] = None,
    grads: Optional[Pytree] = None,
    grad_norm: Optional[jax.Array] = None,
    params: Optional[Pytree] = None,
    param_norm: Optional[jax.Array] = None,
    tokens=None,
    moe_stats: Optional[dict] = None,
) -> MetricsState:
    """Fold one step's statistics into the window (pure, in-jit).

    ``moe_stats`` is what ``gpt_loss(..., moe_stats=True)`` returns beside
    the loss: ``routed``, ``max_over_mean_load``, ``dropped``,
    ``rows_walked``, ``buffer_rows``.

    ``loss``/``tokens`` are free — they fuse into work the step already
    does. ``grads=``/``params=`` compute a global L2 norm, which costs one
    extra read sweep over the tree; pass a precomputed ``grad_norm``/
    ``param_norm`` instead when the step already has one (e.g. from
    ``clip_grad_norm``) to keep instrumentation sweep-free.
    """
    if grads is not None:
        if grad_norm is not None:
            raise ValueError("pass grads= or grad_norm=, not both")
        grad_norm = _global_l2(grads)
    if params is not None:
        if param_norm is not None:
            raise ValueError("pass params= or param_norm=, not both")
        param_norm = _global_l2(params)
    f32 = lambda v: jnp.asarray(v, jnp.float32)  # noqa: E731
    tok = f32(tokens) if tokens is not None else jnp.float32(0.0)
    if moe_stats is not None:
        m = m._replace(
            moe_routed=m.moe_routed + f32(moe_stats["routed"]),
            moe_max_load=f32(moe_stats["max_over_mean_load"]),
            moe_dropped=m.moe_dropped
            + jnp.asarray(moe_stats["dropped"], jnp.int32),
            moe_rows_walked=m.moe_rows_walked + f32(moe_stats["rows_walked"]),
            moe_buffer_rows=f32(moe_stats["buffer_rows"]))
    return m._replace(
        total_steps=m.total_steps + 1,
        window_steps=m.window_steps + 1,
        loss_sum=m.loss_sum + (f32(loss) if loss is not None else 0.0),
        loss_last=f32(loss) if loss is not None else m.loss_last,
        grad_norm_sum=m.grad_norm_sum
        + (f32(grad_norm) if grad_norm is not None else 0.0),
        param_norm_sum=m.param_norm_sum
        + (f32(param_norm) if param_norm is not None else 0.0),
        tokens=m.tokens + tok,
        total_tokens=m.total_tokens + tok,
    )


def observe_scale_update(
    m: MetricsState,
    found_inf,
    old_scale,
    new_scale,
) -> MetricsState:
    """Fold one loss-scale update into the cumulative counters.

    ``found_inf`` is the overflow flag the update consumed (a skipped
    step); a growth event is ``new_scale > old_scale``. Called by
    :meth:`apex_tpu.amp.LossScaler.update_scale` when given ``metrics=``.
    """
    return m._replace(
        loss_scale=jnp.asarray(new_scale, jnp.float32),
        overflow_skips=m.overflow_skips
        + jnp.asarray(found_inf, jnp.int32),
        scale_growths=m.scale_growths + jnp.asarray(
            jnp.asarray(new_scale, jnp.float32)
            > jnp.asarray(old_scale, jnp.float32),
            jnp.int32),
    )


def summarize(m: MetricsState) -> dict:
    """Window means + cumulative counters as device scalars (reading them
    on the host IS a sync — use :func:`drain` on the hot path)."""
    n = jnp.maximum(m.window_steps, 1).astype(jnp.float32)
    return {
        "step": m.total_steps,
        "steps_in_window": m.window_steps,
        "loss": m.loss_sum / n,
        "loss_last": m.loss_last,
        "grad_norm": m.grad_norm_sum / n,
        "param_norm": m.param_norm_sum / n,
        "tokens": m.tokens,
        "total_tokens": m.total_tokens,
        "loss_scale": m.loss_scale,
        "overflow_skips": m.overflow_skips,
        "scale_growths": m.scale_growths,
        "moe_routed": m.moe_routed / n,
        "moe_max_load": m.moe_max_load,
        "moe_dropped": m.moe_dropped,
        "moe_rows_walked": m.moe_rows_walked / n,
        "moe_walked_share": m.moe_rows_walked
        / (n * jnp.maximum(m.moe_buffer_rows, 1.0)),
    }


def _reset_window(m: MetricsState) -> MetricsState:
    z = jnp.float32(0.0)
    return m._replace(
        window_steps=jnp.int32(0), loss_sum=z, grad_norm_sum=z,
        param_norm_sum=z, tokens=z, moe_routed=z, moe_rows_walked=z,
    )


def drain(
    m: MetricsState,
    sink,
    *,
    every_n: int = 1,
    tag: Optional[str] = None,
    bytes_per_step: Optional[float] = None,
    extra: Optional[dict] = None,
) -> MetricsState:
    """Emit the window to ``sink`` every ``every_n`` steps, async.

    In-jit: the emission is a ``jax.debug.callback`` inside a
    ``lax.cond`` — it fires only on drain steps, runs on the host when
    the device reaches this point in the program, and never blocks the
    step (no device->host readback on the hot path; call
    ``jax.effects_barrier()`` once at end of training to flush pending
    emissions).

    ``sink`` is a recorder (anything with ``.record(dict)``) or a bare
    ``callable(dict)``. Records carry window means, cumulative counters,
    ``t_wall`` and — from the second drain on — ``wall_dt_s`` (host wall
    time since the previous drain from this call site) plus derived
    ``steps_per_sec`` / ``tokens_per_sec``. With ``bytes_per_step`` (e.g.
    :meth:`PackedState.sweep_bytes` for a packed-optimizer step) each
    drain also reports ``achieved_gbps`` — measured HBM sweep throughput
    per drain window. ``extra`` adds static key/values to every record.

    Note the drain cadence (and the closure holding the previous drain
    timestamp) is baked in at trace time; a retrace restarts the
    ``wall_dt_s`` baseline, nothing else.
    """
    record = sink.record if hasattr(sink, "record") else sink
    if not callable(record):
        raise TypeError(
            f"sink must expose .record(dict) or be callable, got {sink!r}")
    host_state = {"last_t": None}

    def _emit(total_steps, window_steps, loss_sum, loss_last,
              grad_norm_sum, param_norm_sum, tokens, total_tokens,
              loss_scale, overflow_skips, scale_growths, moe_routed,
              moe_max_load, moe_dropped, moe_rows_walked, moe_buffer_rows):
        now = time.perf_counter()
        n = max(int(window_steps), 1)
        rec = {
            "event": "metrics",
            "step": int(total_steps),
            "steps_in_window": int(window_steps),
            "loss": float(loss_sum) / n,
            "loss_last": float(loss_last),
            "grad_norm": float(grad_norm_sum) / n,
            "param_norm": float(param_norm_sum) / n,
            "tokens": float(tokens),
            "total_tokens": float(total_tokens),
            "loss_scale": float(loss_scale),
            "overflow_skips": int(overflow_skips),
            "scale_growths": int(scale_growths),
        }
        if float(moe_routed) or int(moe_dropped):
            rec.update(moe_routed=float(moe_routed) / n,
                       moe_max_load=float(moe_max_load),
                       moe_dropped=int(moe_dropped),
                       moe_rows_walked=float(moe_rows_walked) / n,
                       moe_walked_share=float(moe_rows_walked)
                       / (n * max(float(moe_buffer_rows), 1.0)))
        # one wall-timestamp choke point for the whole record schema
        # (recorder.stamp_wall) — tools/lint_determinism.py enforces it
        stamp_wall(rec)
        if tag is not None:
            rec["tag"] = tag
        if extra:
            rec.update(extra)
        last = host_state["last_t"]
        if last is not None:
            dt = max(now - last, 1e-12)
            rec["wall_dt_s"] = dt
            rec["steps_per_sec"] = int(window_steps) / dt
            if float(tokens):
                rec["tokens_per_sec"] = float(tokens) / dt
            if bytes_per_step:
                rec["achieved_gbps"] = (
                    float(bytes_per_step) * int(window_steps) / dt / 1e9)
        host_state["last_t"] = now
        record(rec)

    def _drain(mm: MetricsState) -> MetricsState:
        jax.debug.callback(_emit, *mm)
        return _reset_window(mm)

    should = (m.window_steps > 0) & (m.total_steps % every_n == 0)
    return jax.lax.cond(should, _drain, lambda mm: mm, m)
