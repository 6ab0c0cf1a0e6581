"""FusedSGD — momentum SGD as one fused pytree update.

Reference: ``apex/optimizers/fused_sgd.py:6-227`` over
``csrc/multi_tensor_sgd_kernel.cu``. Covered: momentum, dampening, nesterov,
weight decay with ``wd_after_momentum`` placement, first-run momentum-buffer
materialisation (buffer = d_p on the first step, reference lazily allocates
at first step), amp integration via ``grad_scale``/``found_inf`` (the kernel's
``scale`` argument), and ``master_weights`` (fp16-model + fp32-master lists,
the kernel's 4-list variant).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.packed_optimizer import packed_sgd_apply
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


class FusedSGDState(NamedTuple):
    step: jax.Array  # i32; 0 means momentum buffers are unmaterialised
    momentum_buffer: Pytree  # fp32
    master_params: Optional[Pytree]


class FusedSGD(FusedOptimizer):
    def __init__(
        self,
        lr: float,
        momentum: float = 0.0,
        dampening: float = 0.0,
        weight_decay: float = 0.0,
        nesterov: bool = False,
        wd_after_momentum: bool = False,
        materialize_master_grads: bool = True,  # parity; grads are functional here
        set_grad_none: bool = False,  # parity
        master_weights: bool = False,
        packed: bool = False,
        packed_chunk_size: Optional[int] = None,
        packed_interpret: bool = False,
        packed_spec=None,
    ):
        if nesterov and (momentum <= 0 or dampening != 0):
            raise ValueError("Nesterov momentum requires a momentum and zero dampening")
        self.lr = lr
        self.momentum = momentum
        self.dampening = dampening
        self.weight_decay = weight_decay
        self.nesterov = nesterov
        self.wd_after_momentum = wd_after_momentum
        self.master_weights = master_weights
        self.packed = packed
        self.packed_chunk_size = packed_chunk_size
        self.packed_interpret = packed_interpret
        self.packed_spec = packed_spec
        if packed_spec is not None and not packed:
            raise ValueError("packed_spec requires packed=True")

    def init(self, params: Pytree):
        if self.packed:
            # exp_avg doubles as the momentum buffer; no second moment
            return packed_init(
                params,
                chunk_size=self.packed_chunk_size,
                with_exp_avg_sq=False,
                master_weights=self.master_weights,
                spec=self.packed_spec,
            )
        return FusedSGDState(
            step=jnp.int32(0),
            momentum_buffer=tree_zeros_like(params, jnp.float32),
            master_params=tree_f32(params) if self.master_weights else None,
        )

    def _stepped(self, grads, state, params, lr, inv_scale):
        lr = jnp.asarray(lr, jnp.float32)
        first_run = state.step == 0
        src = state.master_params if self.master_weights else params
        wd = self.weight_decay

        def leaf(g, p, buf):
            g = g.astype(jnp.float32) * inv_scale
            p32 = p.astype(jnp.float32)
            d_p = g
            if wd != 0.0 and not self.wd_after_momentum:
                d_p = d_p + wd * p32
            if self.momentum != 0.0:
                new_buf = jnp.where(
                    first_run,
                    d_p,  # reference materialises buf = d_p on first step
                    self.momentum * buf + (1.0 - self.dampening) * d_p,
                )
                d_p = d_p + self.momentum * new_buf if self.nesterov else new_buf
            else:
                new_buf = buf
            if wd != 0.0 and self.wd_after_momentum:
                d_p = d_p + wd * p32
            return p32 - lr * d_p, new_buf

        p32s, bufs = multi_tree_update(leaf, 2, grads, src, state.momentum_buffer)
        new_params = jax.tree_util.tree_map(lambda p32, p: p32.astype(p.dtype), p32s, params)
        return new_params, FusedSGDState(
            step=state.step + 1,
            momentum_buffer=bufs,
            master_params=p32s if self.master_weights else None,
        )

    def _packed_stepped(self, grads, state: PackedState, params, lr,
                        inv_scale):
        """One fused chunked sweep (``multi_tensor_sgd_kernel.cu``)."""
        spec = state.spec
        # pre-packed flat grads (the bucketed-allreduce handoff) skip
        # the packing sweep — see fused_adam._packed_stepped
        flat_g = as_flat_grads(grads, spec)
        p_out, bufs, master = packed_sgd_apply(
            flat_g,
            state.exp_avg,
            packed_src(state, params, self.master_weights),
            param_dtype=spec.common_dtype(),
            lr=jnp.asarray(lr, jnp.float32),
            first_run=state.step == 0,
            inv_scale=inv_scale,
            momentum=self.momentum,
            dampening=self.dampening,
            nesterov=self.nesterov,
            wd=self.weight_decay,
            wd_after_momentum=self.wd_after_momentum,
            write_master=self.master_weights,
            chunk_size=spec.chunk_size,
            interpret=self.packed_interpret,
        )
        return spec.unpack(p_out), PackedState(
            step=state.step + 1,
            exp_avg=bufs,
            exp_avg_sq=None,
            master_params=master if self.master_weights else None,
            spec=spec,
        )

    @jax.named_scope("apex_tpu.optimizer_step")
    def step(
        self,
        grads: Pytree,
        state: FusedSGDState,
        params: Pytree,
        lr: Optional[jax.Array] = None,
        found_inf: Optional[jax.Array] = None,
        grad_scale=None,
    ) -> Tuple[Pytree, FusedSGDState]:
        lr = self.lr if lr is None else lr
        inv_scale = resolve_scale(grad_scale)
        stepped = (self._packed_stepped if self.packed else self._stepped)
        return skip_on_overflow(
            found_inf,
            lambda: stepped(grads, state, params, lr, inv_scale),
            (params, state),
        )
