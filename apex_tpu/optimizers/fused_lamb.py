"""FusedLAMB — two-phase LAMB with global grad clipping and trust ratios.

Reference: ``apex/optimizers/fused_lamb.py:4-215`` over
``csrc/multi_tensor_lamb.cu`` (and the grad-scaler-aware
``fused_mixed_precision_lamb.py:8-259`` / ``multi_tensor_lamb_mp.cu``).

Phase 1 (reference ``fused_lamb.py:124-137``): global L2 norm over all grads
(``multi_tensor_l2norm``). Phase 2 (the LAMB kernel): gradients are divided by
``clipped_ratio = max(1, global_norm / max_grad_norm)``; Adam-style moments
with optional bias correction and ``grad_averaging`` (beta3 = 1-beta1); the
update ``m_hat/(sqrt(v_hat)+eps) + wd*p`` is rescaled per tensor by the trust
ratio ``||p|| / ||update||`` — applied to every tensor under ``use_nvlamb``,
otherwise only to tensors with weight decay (the NVLAMB note in the kernel).

``FusedMixedPrecisionLamb`` is the same math with the scaler folded in:
``grad_scale``/``found_inf`` mirror the mp kernel's ``inv_scale``/``noop``
tensor arguments, and lr/step live as device scalars (trivially true here).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.multi_tensor import multi_tensor_l2norm
from ..ops.packed_optimizer import (
    packed_lamb_stage1,
    packed_row_reduce,
    packed_scale_update,
)
from ._common import (
    FusedOptimizer,
    Pytree,
    multi_tree_update,
    resolve_scale,
    skip_on_overflow,
    tree_f32,
    tree_zeros_like,
)
from ._packed import PackedState, packed_init, packed_src, tree_common_dtype


class FusedLAMBState(NamedTuple):
    step: jax.Array
    exp_avg: Pytree
    exp_avg_sq: Pytree
    master_params: Optional[Pytree]


class FusedLAMB(FusedOptimizer):
    def __init__(
        self,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-6,
        weight_decay: float = 0.01,
        amsgrad: bool = False,
        adam_w_mode: bool = True,
        grad_averaging: bool = True,
        set_grad_none: bool = True,  # parity
        max_grad_norm: float = 1.0,
        use_nvlamb: bool = False,
        master_weights: bool = False,
        packed: bool = False,
        packed_chunk_size: Optional[int] = None,
        packed_interpret: bool = False,
    ):
        if amsgrad:
            raise RuntimeError("FusedLAMB does not support the AMSGrad variant.")
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.adam_w_mode = adam_w_mode
        self.grad_averaging = grad_averaging
        self.max_grad_norm = max_grad_norm
        self.use_nvlamb = use_nvlamb
        self.master_weights = master_weights
        self.packed = packed
        self.packed_chunk_size = packed_chunk_size
        self.packed_interpret = packed_interpret

    def init(self, params: Pytree):
        if self.packed:
            return packed_init(
                params,
                chunk_size=self.packed_chunk_size,
                master_weights=self.master_weights,
            )
        return FusedLAMBState(
            step=jnp.int32(0),
            exp_avg=tree_zeros_like(params, jnp.float32),
            exp_avg_sq=tree_zeros_like(params, jnp.float32),
            master_params=tree_f32(params) if self.master_weights else None,
        )

    def _stepped(self, grads, state, params, lr, inv_scale):
        beta1, beta2 = self.betas
        beta3 = 1.0 - beta1 if self.grad_averaging else 1.0
        lr = jnp.asarray(lr, jnp.float32)
        new_step = state.step + 1
        t = new_step.astype(jnp.float32)
        bc1 = 1.0 - beta1 ** t if self.bias_correction else jnp.float32(1.0)
        bc2 = 1.0 - beta2 ** t if self.bias_correction else jnp.float32(1.0)
        wd = self.weight_decay

        grads32 = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) * inv_scale, grads
        )
        # phase 1: global grad norm (fused_lamb.py:124-137)
        global_norm, _ = multi_tensor_l2norm(grads32)
        if self.max_grad_norm > 0:
            clip = jnp.maximum(global_norm / self.max_grad_norm, 1.0)
        else:
            clip = jnp.float32(1.0)

        src = state.master_params if self.master_weights else params

        def leaf(g, p, m, v):
            g = g / clip
            p32 = p.astype(jnp.float32)
            if not self.adam_w_mode and wd != 0.0:
                g = g + wd * p32
            new_m = beta1 * m + beta3 * g
            new_v = beta2 * v + (1.0 - beta2) * g * g
            update = (new_m / bc1) / (jnp.sqrt(new_v / bc2) + self.eps)
            if self.adam_w_mode and wd != 0.0:
                update = update + wd * p32
            if wd != 0.0 or self.use_nvlamb:
                w_norm = jnp.sqrt(jnp.sum(p32 * p32))
                u_norm = jnp.sqrt(jnp.sum(update * update))
                ratio = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
            else:
                ratio = jnp.float32(1.0)
            return p32 - lr * ratio * update, new_m, new_v

        p32s, ms, vs = multi_tree_update(
            leaf, 3, grads32, src, state.exp_avg, state.exp_avg_sq
        )
        new_params = jax.tree_util.tree_map(lambda p32, p: p32.astype(p.dtype), p32s, params)
        return new_params, FusedLAMBState(
            step=new_step,
            exp_avg=ms,
            exp_avg_sq=vs,
            master_params=p32s if self.master_weights else None,
        )

    def _packed_stepped(self, grads, state: PackedState, params, lr,
                        inv_scale):
        """Flat-buffer LAMB in three chunked sweeps, mirroring the CUDA
        structure (``multi_tensor_l2norm`` -> ``lamb`` stage1 -> stage2):
        grad-norm partials, moments + unratioed update + per-row norm
        partials, then the trust-ratio apply + recast. Per-tensor trust
        ratios come from ``segment_sum`` over ``PackSpec.row_leaf_ids()``
        — rows are leaf-aligned, so the partials never straddle tensors."""
        spec = state.spec
        beta1, beta2 = self.betas
        beta3 = 1.0 - beta1 if self.grad_averaging else 1.0
        new_step = state.step + 1
        t = new_step.astype(jnp.float32)
        bc1 = 1.0 - beta1 ** t if self.bias_correction else jnp.float32(1.0)
        bc2 = 1.0 - beta2 ** t if self.bias_correction else jnp.float32(1.0)
        wd = self.weight_decay
        kw = dict(chunk_size=spec.chunk_size, interpret=self.packed_interpret)

        flat_g = spec.pack(grads, tree_common_dtype(grads))
        # phase 1: global unscaled grad norm (fused_lamb.py:124-137)
        row_g_sq = packed_row_reduce(
            flat_g, op="sqsum", inv_scale=inv_scale, **kw)
        global_norm = jnp.sqrt(jnp.sum(row_g_sq))
        if self.max_grad_norm > 0:
            clip = jnp.maximum(global_norm / self.max_grad_norm, 1.0)
        else:
            clip = jnp.float32(1.0)

        src = packed_src(state, params, self.master_weights)
        update, ms, vs, row_u_sq, row_p_sq = packed_lamb_stage1(
            flat_g, state.exp_avg, state.exp_avg_sq, src,
            clip=clip, bc1=bc1, bc2=bc2, inv_scale=inv_scale,
            beta1=beta1, beta2=beta2, beta3=beta3, eps=self.eps,
            wd=wd, adam_w_mode=self.adam_w_mode, **kw)

        if wd != 0.0 or self.use_nvlamb:
            seg = jnp.asarray(spec.row_leaf_ids())
            n_seg = spec.n_leaves + 1  # last segment = padding rows
            u_norm = jnp.sqrt(jax.ops.segment_sum(
                row_u_sq, seg, num_segments=n_seg))
            w_norm = jnp.sqrt(jax.ops.segment_sum(
                row_p_sq, seg, num_segments=n_seg))
            ratio = jnp.where(
                (w_norm > 0) & (u_norm > 0),
                w_norm / jnp.maximum(u_norm, jnp.float32(1e-38)), 1.0)
            ratio = ratio.at[-1].set(1.0)  # padding segment
            row_coef = ratio[seg]
        else:
            row_coef = jnp.ones((spec.n_rows,), jnp.float32)

        p_out, master = packed_scale_update(
            update, src, row_coef,
            param_dtype=spec.common_dtype(),
            lr=jnp.asarray(lr, jnp.float32),
            write_master=self.master_weights, **kw)
        return spec.unpack(p_out), PackedState(
            step=new_step,
            exp_avg=ms,
            exp_avg_sq=vs,
            master_params=master if self.master_weights else None,
            spec=spec,
        )

    @jax.named_scope("apex_tpu.optimizer_step")
    def step(
        self,
        grads: Pytree,
        state: FusedLAMBState,
        params: Pytree,
        lr: Optional[jax.Array] = None,
        found_inf: Optional[jax.Array] = None,
        grad_scale=None,
    ) -> Tuple[Pytree, FusedLAMBState]:
        lr = self.lr if lr is None else lr
        inv_scale = resolve_scale(grad_scale)
        stepped = (self._packed_stepped if self.packed else self._stepped)
        return skip_on_overflow(
            found_inf,
            lambda: stepped(grads, state, params, lr, inv_scale),
            (params, state),
        )


class FusedMixedPrecisionLamb(FusedLAMB):
    """Grad-scaler-aware LAMB (``apex/optimizers/fused_mixed_precision_lamb.py``).

    The reference keeps lr/step as device tensors and feeds
    ``found_inf``/``inv_scale`` straight into ``multi_tensor_l2norm_mp`` /
    ``multi_tensor_lamb_mp``; here that is exactly ``step(..., found_inf=...,
    grad_scale=...)`` on the base class, with ``reduced_precision_dtype``
    grads accepted naturally (everything is upcast to fp32 in the update).
    """

    def __init__(self, *args, **kwargs):
        kwargs.setdefault("master_weights", True)
        super().__init__(*args, **kwargs)
