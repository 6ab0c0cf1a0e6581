"""DistributedFusedLAMB — ZeRO-2 LAMB over a mesh axis.

Reference: ``apex/contrib/optimizers/distributed_fused_lamb.py:1-1061`` —
sharded LAMB with a fused reduce-scatter/all-gather pipeline, global grad-norm
clipping (optionally computed after the all-reduce, ``clip_after_ar``), and
``set_global_scale`` for external loss scaling.

Same substrate as :class:`DistributedFusedAdam` (see
``distributed_fused_adam.py`` for the mechanism map). The LAMB-specific
difficulty is the **per-tensor trust ratio** ``||p|| / ||update||``
(``apex/optimizers/fused_lamb.py:124-137`` semantics): every element of a
shard must be scaled by a ratio computed over its whole tensor, whose other
elements live on other devices. The reference solves it with fixed chunk
metadata into a two-stage kernel (``multi_tensor_lamb_stage_1/2.cu``); here a
shard-local ``segment_sum`` over per-position leaf ids followed by one
``psum`` yields exact per-tensor squared norms, and the ratio is gathered back
per position — O(shard) work, no full-param materialisation.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...optimizers._common import resolve_scale, skip_on_overflow
from ._sharded import Pytree, ShardedLayout
from .distributed_fused_adam import DistributedFusedAdam


class DistributedFusedLAMBState(NamedTuple):
    step: jax.Array  # i32 scalar, replicated
    exp_avg: jax.Array  # (padded,) sharded
    exp_avg_sq: jax.Array  # (padded,) sharded
    param_shard: Optional[jax.Array]  # (padded,) fp32 masters
    segment_ids: jax.Array  # (padded,) i32 leaf ids, sharded


class DistributedFusedLAMB(DistributedFusedAdam):
    """ZeRO-2 LAMB. Inherits the grad-sync / shard / gather / checkpoint
    machinery from :class:`DistributedFusedAdam`; overrides the shard-local
    update with the two-phase LAMB math of ``apex/optimizers/fused_lamb.py``
    (global-norm clip, bias-corrected moments with ``grad_averaging``,
    per-tensor trust ratios, ``use_nvlamb`` gating).

    ``set_global_scale``/``_fused_norm_clip`` options from the reference
    collapse into the shared ``grad_scale``/``max_grad_norm`` protocol;
    ``clip_after_ar=True`` (the reference default) is the only mode — the
    norm is always computed on fully reduced gradients, which is exact.
    """

    def __init__(
        self,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-6,
        weight_decay: float = 0.01,
        *,
        adam_w_mode: bool = True,
        grad_averaging: bool = True,
        max_grad_norm: float = 1.0,
        use_nvlamb: bool = False,
        **kw,
    ):
        super().__init__(
            lr=lr,
            bias_correction=bias_correction,
            betas=betas,
            eps=eps,
            adam_w_mode=adam_w_mode,
            weight_decay=weight_decay,
            max_grad_norm=max_grad_norm,
            **kw,
        )
        self.grad_averaging = grad_averaging
        self.use_nvlamb = use_nvlamb

    def init(self, params: Pytree) -> DistributedFusedLAMBState:
        layout = self.layout_for(params)
        return DistributedFusedLAMBState(
            step=jnp.int32(0),
            exp_avg=layout.zeros(jnp.float32),
            exp_avg_sq=layout.zeros(jnp.float32),
            param_shard=layout.flatten(params, jnp.float32)
            if self.store_params
            else None,
            segment_ids=layout.segment_ids(),
        )

    def state_specs(self) -> DistributedFusedLAMBState:
        ax = self.distributed_axis
        return DistributedFusedLAMBState(
            step=P(),
            exp_avg=P(ax),
            exp_avg_sq=P(ax),
            param_shard=P(ax) if self.store_params else None,
            segment_ids=P(ax),
        )

    def _stepped(self, grads, state, params, lr, wd, inv_scale):
        layout = self.layout_for(params)
        g = self._reduce_grads(grads, layout, inv_scale)
        g = g * self._clip_coef(g)  # clip_after_ar: norm of reduced grads
        p32 = self._param_shard_f32(state, params, layout)

        beta1, beta2 = self.betas
        beta3 = 1.0 - beta1 if self.grad_averaging else 1.0
        new_step = state.step + 1
        lr = jnp.asarray(lr, jnp.float32)
        t = new_step.astype(jnp.float32)
        bc1 = 1.0 - beta1 ** t if self.bias_correction else jnp.float32(1.0)
        bc2 = 1.0 - beta2 ** t if self.bias_correction else jnp.float32(1.0)

        if not self.adam_w_mode and wd != 0.0:
            g = g + wd * p32
        m = beta1 * state.exp_avg + beta3 * g
        v = beta2 * state.exp_avg_sq + (1.0 - beta2) * g * g
        update = (m / bc1) / (jnp.sqrt(v / bc2) + self.eps)
        if self.adam_w_mode and wd != 0.0:
            update = update + wd * p32

        if wd != 0.0 or self.use_nvlamb:
            # per-tensor ||p||, ||update||: shard-local segment sums + psum
            n_seg = layout.n_leaves + 1  # +1 for the padding segment
            seg = state.segment_ids
            p_sq = jax.ops.segment_sum(p32 * p32, seg, num_segments=n_seg)
            u_sq = jax.ops.segment_sum(update * update, seg, num_segments=n_seg)
            p_sq = jax.lax.psum(p_sq, self.distributed_axis)
            u_sq = jax.lax.psum(u_sq, self.distributed_axis)
            w_norm = jnp.sqrt(p_sq)
            u_norm = jnp.sqrt(u_sq)
            ratios = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / jnp.maximum(u_norm, 1e-30), 1.0)
            ratio = ratios[seg]
        else:
            ratio = jnp.float32(1.0)

        new_p32 = p32 - lr * ratio * update
        new_params = self._gather_params(new_p32, params, layout)
        new_state = DistributedFusedLAMBState(
            step=new_step,
            exp_avg=m,
            exp_avg_sq=v,
            param_shard=new_p32 if self.store_params else None,
            segment_ids=state.segment_ids,
        )
        return new_params, new_state

    @jax.named_scope("apex_tpu.optimizer_step")
    def step(
        self,
        grads: Pytree,
        state: DistributedFusedLAMBState,
        params: Pytree,
        lr: Optional[jax.Array] = None,
        weight_decay: Optional[float] = None,
        found_inf: Optional[jax.Array] = None,
        grad_scale=None,
    ) -> Tuple[Pytree, DistributedFusedLAMBState]:
        lr = self.lr if lr is None else lr
        wd = self.weight_decay if weight_decay is None else weight_decay
        if grad_scale is None and self._global_scale is not None:
            grad_scale = self._global_scale
        inv_scale = resolve_scale(grad_scale)
        return skip_on_overflow(
            found_inf,
            lambda: self._stepped(grads, state, params, lr, wd, inv_scale),
            (params, state),
        )

    # `set_global_scale` parity (reference drives loss scaling by handing the
    # optimizer a scale tensor): the stored scale is the default grad_scale
    # for subsequent step() calls (an explicit grad_scale argument wins).
    _global_scale = None

    def set_global_scale(self, scale):
        self._global_scale = jnp.asarray(scale, jnp.float32)

    @property
    def global_scale(self):
        return self._global_scale if self._global_scale is not None else jnp.float32(1.0)

    def state_dict(self, state: DistributedFusedLAMBState, format: str = "v2"):
        out = super().state_dict(state, format=format)
        # segment_ids are layout-derived; recomputed on load
        return out

    def load_state_dict(self, sd) -> DistributedFusedLAMBState:
        if self._layout is None:
            raise RuntimeError("load_state_dict before init/layout_for")
        base = super().load_state_dict(sd)
        return DistributedFusedLAMBState(
            step=base.step,
            exp_avg=base.exp_avg,
            exp_avg_sq=base.exp_avg_sq,
            param_shard=base.param_shard,
            segment_ids=self._layout.segment_ids(),
        )
