"""FusedAdam / AdamW — single-jit pytree Adam with overflow noop.

Reference behaviour: ``apex/optimizers/fused_adam.py:4-488`` over
``csrc/multi_tensor_adam.cu``. Covered here:

- ``adam_w_mode`` (decoupled weight decay) vs classic Adam L2 (decay folded
  into the gradient) — kernel modes ADAM_MODE_1/ADAM_MODE_0.
- ``bias_correction`` on/off.
- "capturable" semantics are the default and only mode: ``step`` is a device
  scalar, incremented only on non-overflow steps, and the whole update is a
  traced ``lax.cond`` — the reason the reference needed capturable (CUDA
  graphs) is just ``jit`` here.
- ``master_weights``: fp32 master params in state; returned params are
  re-cast masters (O2 path).
- the fork's ``no_update_mv_step`` (``fused_adam.py:310-488``,
  ``csrc/multi_tensor_adam.cu:514-986``): m/v and the bias-correction step
  count are computed transiently for the param update but **not** persisted.
- ``grad_scale``/``found_inf`` hooks matching the capturable-master kernel's
  ``inv_scale``/``noop_flag`` arguments.
- ``packed=True``: state becomes flat fp32 buffers
  (:class:`~apex_tpu.optimizers._packed.PackedState`) and the whole step —
  unscale + Adam + master->param recast — is ONE chunked Pallas sweep
  (``apex_tpu.ops.packed_optimizer.packed_adam_apply``), the actual
  ``multi_tensor_apply`` contract instead of trusting XLA to fuse the
  per-leaf chain. Donate params+state into your jitted step.

Moments are fp32 regardless of param/grad dtype (kernel ``MATH_T float``).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.packed_optimizer import packed_adam_apply
from ..telemetry import numerics as _numerics
from ._common import (
    FusedOptimizer,
    Pytree,
    multi_tree_update,
    resolve_scale,
    skip_on_overflow,
    tree_f32,
    tree_zeros_like,
)
from ._packed import (
    PackedState,
    as_flat_grads,
    packed_init,
    packed_src,
)


class FusedAdamState(NamedTuple):
    step: jax.Array  # i32 scalar, shared across the pytree (fused_adam.py:333 "same step across group")
    exp_avg: Pytree  # fp32
    exp_avg_sq: Pytree  # fp32
    master_params: Optional[Pytree]  # fp32 when master_weights else None


class FusedAdam(FusedOptimizer):
    def __init__(
        self,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-8,
        adam_w_mode: bool = True,
        weight_decay: float = 0.0,
        amsgrad: bool = False,
        set_grad_none: bool = True,  # accepted for parity; meaningless functionally
        capturable: bool = True,  # always-on under jit; accepted for parity
        master_weights: bool = False,
        packed: bool = False,
        packed_chunk_size: Optional[int] = None,
        packed_interpret: bool = False,
        packed_spec=None,
    ):
        if amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.master_weights = master_weights
        self.packed = packed
        self.packed_chunk_size = packed_chunk_size
        self.packed_interpret = packed_interpret
        # external layout adoption (GradBuckets.spec): step() then takes
        # the reduced flat gradient buffer directly
        self.packed_spec = packed_spec
        if packed_spec is not None and not packed:
            raise ValueError("packed_spec requires packed=True")

    def init(self, params: Pytree):
        if self.packed:
            return packed_init(
                params,
                chunk_size=self.packed_chunk_size,
                master_weights=self.master_weights,
                spec=self.packed_spec,
            )
        return FusedAdamState(
            step=jnp.int32(0),
            exp_avg=tree_zeros_like(params, jnp.float32),
            exp_avg_sq=tree_zeros_like(params, jnp.float32),
            master_params=tree_f32(params) if self.master_weights else None,
        )

    # -- core math ---------------------------------------------------------
    def _update_leaf(self, g, p, m, v, step, lr, wd):
        beta1, beta2 = self.betas
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        if self.bias_correction:
            t = step.astype(jnp.float32)
            bc1 = 1.0 - beta1 ** t
            bc2 = 1.0 - beta2 ** t
        else:
            bc1 = bc2 = jnp.float32(1.0)
        if not self.adam_w_mode and wd != 0.0:
            g = g + wd * p32  # ADAM_MODE_0: L2 into the gradient
        new_m = beta1 * m + (1.0 - beta1) * g
        new_v = beta2 * v + (1.0 - beta2) * g * g
        denom = jnp.sqrt(new_v / bc2) + self.eps
        update = (new_m / bc1) / denom
        if self.adam_w_mode and wd != 0.0:
            update = update + wd * p32  # ADAM_MODE_1: decoupled decay
        new_p32 = p32 - lr * update
        return new_p32, new_m, new_v

    def _stepped(self, grads, state, params, lr, wd, inv_scale):
        new_step = state.step + 1
        lr = jnp.asarray(lr, jnp.float32)
        src = state.master_params if self.master_weights else params

        def leaf(g, p, m, v):
            g = g.astype(jnp.float32) * inv_scale
            return self._update_leaf(g, p, m, v, new_step, lr, wd)

        p32s, ms, vs = multi_tree_update(
            leaf, 3, grads, src, state.exp_avg, state.exp_avg_sq
        )
        new_params = jax.tree_util.tree_map(
            lambda p32, p: p32.astype(p.dtype), p32s, params
        )
        new_state = FusedAdamState(
            step=new_step,
            exp_avg=ms,
            exp_avg_sq=vs,
            master_params=p32s if self.master_weights else None,
        )
        return new_params, new_state

    def _bias_corrections(self, step):
        beta1, beta2 = self.betas
        if not self.bias_correction:
            return jnp.float32(1.0), jnp.float32(1.0)
        t = step.astype(jnp.float32)
        return 1.0 - beta1 ** t, 1.0 - beta2 ** t

    def _packed_stepped(self, grads, state: PackedState, params, lr, wd,
                        inv_scale, write_mv=True):
        """One fused chunked sweep over the flat buffers (the
        ``multi_tensor_adam`` launch). ``write_mv=False`` is the fork's
        transient-m/v mode: only params are written."""
        spec = state.spec
        beta1, beta2 = self.betas
        new_step = state.step + 1
        bc1, bc2 = self._bias_corrections(new_step)
        # grads may arrive PRE-PACKED (the bucketed-allreduce handoff:
        # the reduced flat buffer in this state's own spec layout) — the
        # packing sweep then disappears entirely
        flat_g = as_flat_grads(grads, spec)
        # opt-in activation-watch tap on the packed grad buffer: identity
        # (no trace difference) unless a numerics.activation_watch is
        # active; then one extra row-stats sweep names non-finite leaves
        # through the spec's row-aligned offsets
        flat_g = _numerics.tap_flat(
            "apex_tpu.packed_adam/grads", flat_g, spec=spec,
            inv_scale=inv_scale, interpret=self.packed_interpret)
        p_out, ms, vs, master = packed_adam_apply(
            flat_g,
            state.exp_avg,
            state.exp_avg_sq,
            packed_src(state, params, self.master_weights),
            param_dtype=spec.common_dtype(),
            lr=jnp.asarray(lr, jnp.float32),
            bc1=bc1,
            bc2=bc2,
            inv_scale=inv_scale,
            beta1=beta1,
            beta2=beta2,
            eps=self.eps,
            wd=wd,
            adam_w_mode=self.adam_w_mode,
            write_mv=write_mv,
            # no_update_mv (write_mv=False) must not advance masters
            # either — and the discarded output would cost a full dead
            # fp32 write plus a defensive copy of the aliased buffer
            write_master=write_mv and self.master_weights,
            chunk_size=spec.chunk_size,
            interpret=self.packed_interpret,
        )
        # off-TPU, unpack the new params from the fp32 MASTER buffer
        # when one exists: identical values (p_out is recast(master)),
        # but slicing a bf16 buffer on XLA CPU/GPU pays a whole-buffer
        # f32-emulation convert chain PER LEAF, which both the cost
        # model and the runtime bill. On TPU bf16 slices are native and
        # the half-width p_out read is the cheaper source.
        unpack_src = p_out
        if master is not None and jax.default_backend() != "tpu" \
                and jnp.dtype(spec.common_dtype()) == jnp.bfloat16:
            unpack_src = master
        new_params = spec.unpack(unpack_src)
        if not write_mv:
            return new_params, state
        new_state = PackedState(
            step=new_step,
            exp_avg=ms,
            exp_avg_sq=vs,
            master_params=master if self.master_weights else None,
            spec=spec,
        )
        return new_params, new_state

    # -- public API --------------------------------------------------------
    @jax.named_scope("apex_tpu.optimizer_step")
    def step(
        self,
        grads: Pytree,
        state: FusedAdamState,
        params: Pytree,
        lr: Optional[jax.Array] = None,
        weight_decay: Optional[float] = None,
        found_inf: Optional[jax.Array] = None,
        grad_scale=None,
    ) -> Tuple[Pytree, FusedAdamState]:
        lr = self.lr if lr is None else lr
        wd = self.weight_decay if weight_decay is None else weight_decay
        inv_scale = resolve_scale(grad_scale)
        stepped = (self._packed_stepped if self.packed else self._stepped)
        return skip_on_overflow(
            found_inf,
            lambda: stepped(grads, state, params, lr, wd, inv_scale),
            (params, state),
        )

    @jax.named_scope("apex_tpu.optimizer_step")
    def step_flat(
        self,
        grads,
        state: PackedState,
        lr: Optional[jax.Array] = None,
        weight_decay: Optional[float] = None,
        found_inf: Optional[jax.Array] = None,
        grad_scale=None,
    ) -> PackedState:
        """Flat-carry step: reduced gradient buffer in, new STATE out.

        The endpoint of the bucketed gradient lifecycle, in which the
        fp32 master buffer IS the parameter store (apex O2 semantics
        taken literally): the forward takes its leaf views from
        ``state.master_params`` via ``spec.unpack`` (the reference DDP's
        flat-buffer-with-views design), ``grads`` is the reduced flat
        buffer or the ``BucketBuffers`` handoff, and nothing is ever
        unpacked or re-packed between the collective and the update:

            bufs, _ = ddp.reduce_flat(grads, buckets=buckets, concat=False)
            sstate = scaler.found_inf_flat(sstate, bufs)
            opt_state = opt.step_flat(bufs, opt_state,
                                      found_inf=sstate.found_inf,
                                      grad_scale=sstate.loss_scale)
            # next forward: buckets.unpack(opt_state.master_params)

        Two deliberate departures from :meth:`step`:

        - overflow skip uses the kernels' IN-SWEEP ``noop`` flag (the
          CUDA ``noop_flag`` contract) instead of a ``lax.cond`` around
          the update — a fused select costs nothing extra and, unlike a
          cond, never breaks XLA's in-place aliasing of the donated
          state buffers (a cond boundary forces defensive copies of
          every carried buffer on some backends);
        - the unscale multiply rides ``grad_scale`` into the kernel's
          ``inv_scale`` operand, so deferred scalings (loss scale, and a
          deferred gradient average — fold ``world`` into ``grad_scale``
          when both are powers of two and the division commutes
          bit-exactly) all collapse into the sweep's one multiply.

        Requires ``packed=True`` with ``master_weights=True``.
        """
        if not (self.packed and self.master_weights):
            raise ValueError(
                "step_flat requires packed=True and master_weights=True "
                "(the fp32 update source must live in the optimizer state)")
        lr = self.lr if lr is None else lr
        wd = self.weight_decay if weight_decay is None else weight_decay
        inv_scale = resolve_scale(grad_scale)
        spec = state.spec
        beta1, beta2 = self.betas
        has_noop = found_inf is not None
        stepped = state.step + 1
        bc1, bc2 = self._bias_corrections(stepped)
        flat_g = as_flat_grads(grads, spec)
        flat_g = _numerics.tap_flat(
            "apex_tpu.packed_adam/grads", flat_g, spec=spec,
            inv_scale=inv_scale, interpret=self.packed_interpret)
        _, ms, vs, master = packed_adam_apply(
            flat_g,
            state.exp_avg,
            state.exp_avg_sq,
            state.master_params,
            param_dtype=spec.common_dtype(),
            lr=jnp.asarray(lr, jnp.float32),
            bc1=bc1,
            bc2=bc2,
            inv_scale=inv_scale,
            noop=found_inf if has_noop else None,
            beta1=beta1,
            beta2=beta2,
            eps=self.eps,
            wd=wd,
            adam_w_mode=self.adam_w_mode,
            write_mv=True,
            write_master=True,
            chunk_size=spec.chunk_size,
            interpret=self.packed_interpret,
        )
        if has_noop:
            # the noop contract covers the step counter too: a skipped
            # step must not advance bias correction
            stepped = jnp.where(jnp.asarray(found_inf, jnp.bool_),
                                state.step, stepped)
        return PackedState(
            step=stepped,
            exp_avg=ms,
            exp_avg_sq=vs,
            master_params=master,
            spec=spec,
        )

    @jax.named_scope("apex_tpu.optimizer_step")
    def no_update_mv_step(
        self,
        grads: Pytree,
        state: FusedAdamState,
        params: Pytree,
        lr: Optional[jax.Array] = None,
        weight_decay: Optional[float] = None,
        found_inf: Optional[jax.Array] = None,
        grad_scale=None,
    ) -> Tuple[Pytree, FusedAdamState]:
        """Fork-added step: params move, m/v (and step) stay.

        Matches ``AdamFunctorNoUpdateMV`` (``csrc/multi_tensor_adam.cu:514``):
        the moment updates and bias corrections are computed with this step's
        gradient, used for the param update, then discarded.
        """
        lr = self.lr if lr is None else lr
        wd = self.weight_decay if weight_decay is None else weight_decay
        inv_scale = resolve_scale(grad_scale)

        def do():
            if self.packed:
                # kernel-level transient m/v: only params are written
                return self._packed_stepped(
                    grads, state, params, lr, wd, inv_scale, write_mv=False)
            new_params, _ = self._stepped(grads, state, params, lr, wd, inv_scale)
            return new_params, state

        return skip_on_overflow(found_inf, do, (params, state))


def FusedAdamW(*args, **kwargs) -> FusedAdam:
    kwargs.setdefault("adam_w_mode", True)
    return FusedAdam(*args, **kwargs)
