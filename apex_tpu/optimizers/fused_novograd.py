"""FusedNovoGrad — layer-wise second-moment NovoGrad.

Reference: ``apex/optimizers/fused_novograd.py:4-214`` over
``csrc/multi_tensor_novograd.cu``. The second moment ``exp_avg_sq`` is a
*scalar per tensor* (layer-wise), not elementwise. Covered: ``norm_type`` 2
(L2) and 0 (max/inf-norm), ``init_zero`` (v starts at 0 vs the first grad
norm), ``grad_averaging`` (beta3 = 1-beta1), ``reg_inside_moment`` (weight
decay folded into the moment input vs added to the update), bias correction,
and the amp hooks (``grad_scale``/``found_inf``).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.packed_optimizer import packed_novograd_apply, packed_row_reduce
from ._common import (
    FusedOptimizer,
    Pytree,
    multi_tree_update,
    resolve_scale,
    skip_on_overflow,
    tree_zeros_like,
)
from ._packed import PackedState, packed_init, tree_common_dtype


class FusedNovoGradState(NamedTuple):
    step: jax.Array
    exp_avg: Pytree  # fp32, elementwise
    exp_avg_sq: Pytree  # fp32 scalar per leaf


class FusedNovoGrad(FusedOptimizer):
    def __init__(
        self,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.95, 0.98),
        eps: float = 1e-8,
        weight_decay: float = 0.0,
        amsgrad: bool = False,
        reg_inside_moment: bool = False,
        grad_averaging: bool = True,
        norm_type: int = 2,
        init_zero: bool = False,
        set_grad_none: bool = True,  # parity
        packed: bool = False,
        packed_chunk_size: Optional[int] = None,
        packed_interpret: bool = False,
    ):
        if amsgrad:
            raise RuntimeError("FusedNovoGrad does not support the AMSGrad variant.")
        if norm_type not in (0, 2):
            raise RuntimeError(f"FusedNovoGrad only supports l2/inf norm now, got {norm_type}")
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.reg_inside_moment = reg_inside_moment
        self.grad_averaging = grad_averaging
        self.norm_type = norm_type
        self.init_zero = init_zero
        self.packed = packed
        self.packed_chunk_size = packed_chunk_size
        self.packed_interpret = packed_interpret

    def init(self, params: Pytree):
        if self.packed:
            # exp_avg_sq is per-LEAF (layer-wise), a (n_leaves,) vector
            return packed_init(
                params,
                chunk_size=self.packed_chunk_size,
                per_leaf_exp_avg_sq=True,
            )
        return FusedNovoGradState(
            step=jnp.int32(0),
            exp_avg=tree_zeros_like(params, jnp.float32),
            exp_avg_sq=jax.tree_util.tree_map(
                lambda p: jnp.zeros((), jnp.float32), params
            ),
        )

    def _norm(self, g):
        if self.norm_type == 2:
            return jnp.sum(g * g)  # squared L2, like the kernel's running v
        return jnp.max(jnp.abs(g)) ** 2

    def _stepped(self, grads, state, params, lr, inv_scale):
        beta1, beta2 = self.betas
        beta3 = 1.0 - beta1 if self.grad_averaging else 1.0
        lr = jnp.asarray(lr, jnp.float32)
        new_step = state.step + 1
        t = new_step.astype(jnp.float32)
        bc1 = 1.0 - beta1 ** t if self.bias_correction else jnp.float32(1.0)
        wd = self.weight_decay
        first = state.step == 0

        def leaf(g, p, m, v):
            g = g.astype(jnp.float32) * inv_scale
            p32 = p.astype(jnp.float32)
            gnorm_sq = self._norm(g)
            if self.init_zero:
                new_v = beta2 * v + (1.0 - beta2) * gnorm_sq
            else:
                # reference: v materialised as the first grad norm on step 1
                new_v = jnp.where(first, gnorm_sq, beta2 * v + (1.0 - beta2) * gnorm_sq)
            denom = jnp.sqrt(new_v) + self.eps
            moment_in = g / denom
            if wd != 0.0 and self.reg_inside_moment:
                moment_in = moment_in + wd * p32
            new_m = beta1 * m + beta3 * moment_in
            update = new_m / bc1
            if wd != 0.0 and not self.reg_inside_moment:
                update = update + wd * p32
            return p32 - lr * update, new_m, new_v

        p32s, ms, vs = multi_tree_update(
            leaf, 3, grads, params, state.exp_avg, state.exp_avg_sq
        )
        new_params = jax.tree_util.tree_map(lambda p32, p: p32.astype(p.dtype), p32s, params)
        return new_params, FusedNovoGradState(step=new_step, exp_avg=ms, exp_avg_sq=vs)

    def _packed_stepped(self, grads, state: PackedState, params, lr,
                        inv_scale):
        """Flat-buffer NovoGrad in two chunked sweeps: per-row grad-norm
        partials (sq-sum for L2, max-abs for inf-norm), segment-reduced to
        the layer-wise ``v`` vector, then the fused elementwise stage with
        the per-tensor denominator delivered per row."""
        spec = state.spec
        beta1, beta2 = self.betas
        beta3 = 1.0 - beta1 if self.grad_averaging else 1.0
        new_step = state.step + 1
        t = new_step.astype(jnp.float32)
        bc1 = 1.0 - beta1 ** t if self.bias_correction else jnp.float32(1.0)
        first = state.step == 0
        kw = dict(chunk_size=spec.chunk_size, interpret=self.packed_interpret)

        flat_g = spec.pack(grads, tree_common_dtype(grads))
        seg = jnp.asarray(spec.row_leaf_ids())
        n_seg = spec.n_leaves + 1  # last segment = padding rows
        if self.norm_type == 2:
            row = packed_row_reduce(flat_g, op="sqsum",
                                    inv_scale=inv_scale, **kw)
            gnorm_sq = jax.ops.segment_sum(row, seg, num_segments=n_seg)
        else:  # inf norm: (max |g|)^2, like the kernel's running v
            row = packed_row_reduce(flat_g, op="maxabs",
                                    inv_scale=inv_scale, **kw)
            gnorm_sq = jax.ops.segment_max(row, seg, num_segments=n_seg) ** 2
        gnorm_sq = gnorm_sq[:spec.n_leaves]

        if self.init_zero:
            new_v = beta2 * state.exp_avg_sq + (1.0 - beta2) * gnorm_sq
        else:
            new_v = jnp.where(
                first, gnorm_sq,
                beta2 * state.exp_avg_sq + (1.0 - beta2) * gnorm_sq)
        denom = jnp.sqrt(new_v) + self.eps
        # per-row denominator; padding rows get 1 (their g is 0 anyway)
        row_denom = jnp.concatenate([denom, jnp.ones((1,), jnp.float32)])[seg]

        src = spec.pack(params, jnp.float32)
        p_out, ms = packed_novograd_apply(
            flat_g, state.exp_avg, src, row_denom,
            param_dtype=spec.common_dtype(),
            lr=jnp.asarray(lr, jnp.float32), bc1=bc1, inv_scale=inv_scale,
            beta1=beta1, beta3=beta3, wd=self.weight_decay,
            reg_inside_moment=self.reg_inside_moment, **kw)
        return spec.unpack(p_out), PackedState(
            step=new_step, exp_avg=ms, exp_avg_sq=new_v,
            master_params=None, spec=spec)

    @jax.named_scope("apex_tpu.optimizer_step")
    def step(
        self,
        grads: Pytree,
        state: FusedNovoGradState,
        params: Pytree,
        lr: Optional[jax.Array] = None,
        found_inf: Optional[jax.Array] = None,
        grad_scale=None,
    ) -> Tuple[Pytree, FusedNovoGradState]:
        lr = self.lr if lr is None else lr
        inv_scale = resolve_scale(grad_scale)
        stepped = (self._packed_stepped if self.packed else self._stepped)
        return skip_on_overflow(
            found_inf,
            lambda: stepped(grads, state, params, lr, inv_scale),
            (params, state),
        )
