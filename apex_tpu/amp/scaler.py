"""Dynamic / static loss scaling, functional-state edition.

Reference: ``apex/amp/scaler.py:33-217`` (``LossScaler``) and
``csrc/update_scale_hysteresis.cu``. The CUDA implementation mutates device
buffers and does one D2H readback per step (``update_scale`` ``scaler.py:197``);
here the scaler is a pure state machine — a ``LossScaleState`` pytree carried
through the jitted train step — and overflow handling is a ``lax.cond`` (no
host sync at all). Skip-step composes with any optimizer via
``apex_tpu.amp.handle.scale_loss`` / the O2 frontend.

bf16 on TPU does not need loss scaling (same exponent range as fp32); the
scaler exists for fp16 parity and for API compatibility, and ``loss_scale=1.0``
static mode makes it a no-op XLA removes entirely.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.multi_tensor import (
    multi_tensor_axpby,
    multi_tensor_scale,
    update_scale_hysteresis,
)

Pytree = Any


class LossScaleState(NamedTuple):
    """Carried scaler state (all device scalars, jit-friendly).

    ``unskipped`` mirrors ``apex/amp/scaler.py``'s growth counter; the
    hysteresis tracker mirrors ``update_scale_hysteresis.cu``.
    """

    loss_scale: jax.Array  # f32 scalar
    unskipped: jax.Array  # i32 scalar, clean steps since last scale change
    hysteresis: jax.Array  # i32 scalar, overflow allowance remaining
    found_inf: jax.Array  # bool scalar, overflow seen in the current step
    consecutive_skips: jax.Array  # i32 scalar, skipped steps in a row


class LossScaler:
    """Static or dynamic loss scaler.

    Parameters mirror ``apex/amp/scaler.py:33-60``: ``loss_scale`` is either a
    float (static) or ``"dynamic"``; dynamic scaling starts at ``init_scale``
    (2**16), grows by ``scale_factor`` (2) every ``scale_window`` (2000) clean
    steps, backs off by ``1/scale_factor`` on overflow, clamped to
    ``[min_loss_scale, max_loss_scale]`` (max default 2**24,
    ``apex/amp/scaler.py:42``). ``hysteresis`` extends the reference with the
    fork's ``update_scale_hysteresis`` tolerance for repeated infs (default 1
    == classic behaviour).
    """

    def __init__(
        self,
        loss_scale: float | str = "dynamic",
        init_scale: float = 2.0 ** 16,
        scale_factor: float = 2.0,
        scale_window: int = 2000,
        min_loss_scale: Optional[float] = None,
        max_loss_scale: float = 2.0 ** 24,
        hysteresis: int = 1,
    ):
        self.dynamic = loss_scale == "dynamic"
        self._init_scale = float(init_scale) if self.dynamic else float(loss_scale)
        self.scale_factor = float(scale_factor)
        self.scale_window = int(scale_window)
        self.min_loss_scale = min_loss_scale
        self.max_loss_scale = float(max_loss_scale)
        self.hysteresis = int(hysteresis)

    # -- state ------------------------------------------------------------
    def init_state(self) -> LossScaleState:
        return LossScaleState(
            loss_scale=jnp.float32(self._init_scale),
            unskipped=jnp.int32(0),
            hysteresis=jnp.int32(self.hysteresis),
            found_inf=jnp.asarray(False),
            consecutive_skips=jnp.int32(0),
        )

    # -- step-time ops (pure, jittable) ------------------------------------
    @jax.named_scope("apex_tpu.amp_scaler")
    def scale_loss(self, state: LossScaleState, loss: jax.Array) -> jax.Array:
        """loss * scale (``apex/amp/handle.py:107-113``)."""
        return loss * state.loss_scale.astype(loss.dtype)

    @jax.named_scope("apex_tpu.amp_scaler")
    def unscale(
        self, state: LossScaleState, grads: Pytree, out_dtype=None,
        numerics=None,
    ):
        """Unscale grads by 1/scale, recording overflow.

        Reference ``apex/amp/scaler.py:94-150`` (``unscale`` via
        ``multi_tensor_scale`` with inf screening).

        With ``numerics=`` — a ``(NumericsMonitor, NumericsState)`` pair
        from ``apex_tpu.telemetry.numerics`` — the per-leaf non-finite
        flags this sweep already computes (the screening behind
        ``found_inf``) are folded into the numerics state for overflow
        PROVENANCE: when the scaler trips, the drained anomaly event
        names exactly the non-finite leaves, at zero extra sweeps.
        Returns ``(grads, new_state, new_numerics_state)`` instead of the
        2-tuple.
        """
        inv = 1.0 / state.loss_scale
        if numerics is None:
            out, found = multi_tensor_scale(grads, inv, out_dtype=out_dtype)
            return out, state._replace(found_inf=state.found_inf | found)
        monitor, nstate = numerics
        out, found, leaf_flags = multi_tensor_scale(
            grads, inv, out_dtype=out_dtype, per_tensor=True)
        nstate = monitor.observe(nstate, leaf_nonfinite=leaf_flags)
        return out, state._replace(found_inf=state.found_inf | found), nstate

    @jax.named_scope("apex_tpu.amp_scaler")
    def unscale_flat(
        self, state: LossScaleState, flat_grads, out_dtype=None,
        numerics=None, *, chunk_size: Optional[int] = None,
        use_kernel: Optional[bool] = None, interpret: bool = False,
    ):
        """Unscale a PACKED flat gradient buffer, recording overflow —
        the scaler-over-flat-buffers leg of the bucketed gradient
        lifecycle (``parallel.GradBuckets``).

        One chunked ``multi_tensor_scale_flat(per_row_flags=True)``
        sweep yields the unscaled buffer, the step's ``found_inf`` AND
        per-ROW non-finite flags; pass ``out_dtype=jnp.float32`` to make
        this sweep the lifecycle's single upcast (the packed optimizer
        then reads fp32 straight from the same buffer — no
        ``double_cast`` round-trip anywhere between backward and the
        update).

        With ``numerics=`` — a ``(NumericsMonitor, NumericsState)`` pair
        whose monitor was built from the matching ``PackSpec`` — the
        per-row flags become exact per-LEAF overflow provenance through
        the row-aligned offsets (``observe(row_nonfinite=...)``), at
        zero extra sweeps; returns ``(flat, new_state,
        new_numerics_state)`` instead of the 2-tuple.
        """
        from ..ops.packed_optimizer import (
            DEFAULT_CHUNK,
            multi_tensor_scale_flat,
        )

        inv = 1.0 / state.loss_scale
        out, found, row_bad = multi_tensor_scale_flat(
            flat_grads, inv, out_dtype=out_dtype, per_row_flags=True,
            chunk_size=chunk_size or DEFAULT_CHUNK,
            use_kernel=use_kernel, interpret=interpret)
        new_state = state._replace(found_inf=state.found_inf | found)
        if numerics is None:
            return out, new_state
        monitor, nstate = numerics
        nstate = monitor.observe(nstate, row_nonfinite=row_bad)
        return out, new_state, nstate

    @jax.named_scope("apex_tpu.amp_scaler")
    def found_inf_flat(self, state: LossScaleState, flat_grads):
        """Record overflow from flat SCALED gradients without unscaling
        them — the read-only half of the fused one-sweep lifecycle.

        The leanest spelling of the bucketed gradient lifecycle defers
        the unscale multiply into the packed optimizer kernel
        (``opt.step(..., grad_scale=state.loss_scale)`` — the kernels'
        ``inv_scale`` operand), so all the scaler needs beforehand is the
        overflow verdict: one read-only non-finite reduction, no write
        sweep. The verdict is identical to :meth:`unscale_flat`'s while
        ``scale >= 1`` — ``g`` and ``g / scale`` are then non-finite for
        exactly the same inputs. Dynamic backoff can drive the scale
        BELOW 1 (no ``min_loss_scale`` floor by default), where a
        finite ``g`` CAN overflow under the deferred ``1/scale``
        multiply — so the probe also flags ``|g| > fp32_max * scale``.
        That term is identically false while ``scale >= 1`` (the
        verdict-parity regime) and conservative below it: it prices the
        ``1/scale`` multiply alone, so a fused step that also defers
        the gradient average may skip a step the per-leaf reference
        would have taken — a skipped step, never a poisoned one.

        ``flat_grads`` is the reduced global buffer or the
        ``BucketBuffers`` handoff (``reduce_flat(concat=False)``) — the
        per-bucket form keeps this reduction off the concatenated
        buffer, so the concat itself can stay fused inside the
        optimizer's overflow-skip branch.
        """
        bufs = (flat_grads.buffers if hasattr(flat_grads, "buffers")
                else (flat_grads,))
        # fp32_max * scale: inf above scale 1 (comparison always false),
        # fp32_max at exactly 1 — the term only fires collapsed-scale
        lim = jnp.float32(jnp.finfo(jnp.float32).max) * jnp.asarray(
            state.loss_scale, jnp.float32)
        found = state.found_inf
        for b in bufs:
            # one fused predicate -> one reduction per buffer (a second
            # jnp.any would double the sweep in XLA's cost model)
            b32 = b.astype(jnp.float32)
            found = found | jnp.any(~jnp.isfinite(b) | (jnp.abs(b32) > lim))
        return state._replace(found_inf=found)

    @jax.named_scope("apex_tpu.amp_scaler")
    def unscale_with_stashed(
        self, state: LossScaleState, new_scaled_grads: Pytree, stashed_grads: Pytree
    ) -> Tuple[Pytree, LossScaleState]:
        """out = new/scale + stashed — gradient accumulation across backwards.

        Reference ``apex/amp/scaler.py:152-196`` (``unscale_with_stashed`` via
        ``multi_tensor_axpby``).
        """
        inv = 1.0 / state.loss_scale
        out, found = multi_tensor_axpby(inv, 1.0, new_scaled_grads, stashed_grads)
        return out, state._replace(found_inf=state.found_inf | found)

    @jax.named_scope("apex_tpu.amp_scaler")
    def update_scale(self, state: LossScaleState, metrics=None,
                     numerics=None):
        """End-of-step scale adjustment (``apex/amp/scaler.py:197-216``).

        Consumes ``found_inf`` and resets it for the next step. Static mode
        only clears the flag.

        With ``metrics=`` (an ``apex_tpu.telemetry.MetricsState``) the
        scaler also folds this update into the cumulative telemetry
        counters — ``overflow_skips`` increments when the consumed
        ``found_inf`` skipped the step, ``scale_growths`` when the scale
        grew. With ``numerics=`` (an
        ``apex_tpu.telemetry.numerics.NumericsState``) the consumed flag
        and the old/new scales feed the anomaly engine (overflow latch,
        first-bad-step, the edge-triggered scale-collapse rule). Pure
        in-jit arithmetic either way: no extra host syncs. Returns
        ``new_state`` alone, or ``(new_state, metrics)``, ``(new_state,
        numerics)``, ``(new_state, metrics, numerics)`` matching what was
        passed.
        """
        new_state = self._update_scale(state)
        out = (new_state,)
        if metrics is not None:
            from ..telemetry.metrics import observe_scale_update

            out += (observe_scale_update(
                metrics, state.found_inf, state.loss_scale,
                new_state.loss_scale),)
        if numerics is not None:
            from ..telemetry.numerics import (
                observe_scale_update as numerics_scale_update,
            )

            out += (numerics_scale_update(
                numerics, state.found_inf, state.loss_scale,
                new_state.loss_scale,
                consecutive_skips=new_state.consecutive_skips),)
        return out if len(out) > 1 else new_state

    def _update_scale(self, state: LossScaleState) -> LossScaleState:
        # consecutive-skip run length: the death-spiral tell. A single
        # clean step resets it; persistent non-finite grads (a poisoned
        # data window that outlives hysteresis) grow it without bound —
        # the resilience rewind trigger and the numerics engine's
        # edge-triggered ``scaler_stall`` rule both read this counter.
        consec = jnp.where(
            state.found_inf, state.consecutive_skips + 1, jnp.int32(0))
        if not self.dynamic:
            return state._replace(
                found_inf=jnp.asarray(False), consecutive_skips=consec)
        scale, unskipped, hyst = update_scale_hysteresis(
            state.loss_scale,
            state.unskipped,
            state.hysteresis,
            state.found_inf,
            growth_factor=self.scale_factor,
            backoff_factor=1.0 / self.scale_factor,
            growth_interval=self.scale_window,
            hysteresis=self.hysteresis,
        )
        scale = jnp.minimum(scale, self.max_loss_scale)
        if self.min_loss_scale is not None:
            scale = jnp.maximum(scale, self.min_loss_scale)
        return LossScaleState(
            loss_scale=scale, unskipped=unskipped, hysteresis=hyst,
            found_inf=jnp.asarray(False), consecutive_skips=consec,
        )

    def loss_scale(self, state: LossScaleState) -> jax.Array:
        return state.loss_scale

    # -- checkpointing (``apex/amp/frontend.py:365-404`` parity) -----------
    def state_dict(self, state: LossScaleState) -> dict:
        return {
            "loss_scale": float(jax.device_get(state.loss_scale)),
            "unskipped": int(jax.device_get(state.unskipped)),
            "hysteresis": int(jax.device_get(state.hysteresis)),
            "consecutive_skips": int(
                jax.device_get(state.consecutive_skips)),
            "dynamic": self.dynamic,
        }

    def load_state_dict(self, sd: dict) -> LossScaleState:
        return LossScaleState(
            loss_scale=jnp.float32(sd["loss_scale"]),
            unskipped=jnp.int32(sd.get("unskipped", 0)),
            hysteresis=jnp.int32(sd.get("hysteresis", self.hysteresis)),
            found_inf=jnp.asarray(False),
            consecutive_skips=jnp.int32(sd.get("consecutive_skips", 0)),
        )
