"""FusedAdagrad — fused pytree Adagrad.

Reference: ``apex/optimizers/fused_adagrad.py:5`` over
``csrc/multi_tensor_adagrad.cu``. Covered: ``adagrad_w_mode`` (decoupled
weight decay, kernel MODE_1) vs classic L2 (MODE_0), amp hooks.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ._common import (
    FusedOptimizer,
    Pytree,
    multi_tree_update,
    resolve_scale,
    skip_on_overflow,
    tree_zeros_like,
)


class FusedAdagradState(NamedTuple):
    step: jax.Array
    sum: Pytree  # fp32 accumulated squared grads


class FusedAdagrad(FusedOptimizer):
    def __init__(
        self,
        lr: float = 1e-2,
        eps: float = 1e-10,
        weight_decay: float = 0.0,
        set_grad_none: bool = True,  # parity
        adagrad_w_mode: bool = False,
    ):
        self.lr = lr
        self.eps = eps
        self.weight_decay = weight_decay
        self.adagrad_w_mode = adagrad_w_mode

    def init(self, params: Pytree) -> FusedAdagradState:
        return FusedAdagradState(
            step=jnp.int32(0), sum=tree_zeros_like(params, jnp.float32)
        )

    def _stepped(self, grads, state, params, lr, inv_scale):
        lr = jnp.asarray(lr, jnp.float32)
        wd = self.weight_decay

        def leaf(g, p, h):
            g = g.astype(jnp.float32) * inv_scale
            p32 = p.astype(jnp.float32)
            if wd != 0.0 and not self.adagrad_w_mode:
                g = g + wd * p32
            new_h = h + g * g
            update = g / (jnp.sqrt(new_h) + self.eps)
            if wd != 0.0 and self.adagrad_w_mode:
                update = update + wd * p32
            return p32 - lr * update, new_h

        p32s, hs = multi_tree_update(leaf, 2, grads, params, state.sum)
        new_params = jax.tree_util.tree_map(lambda p32, p: p32.astype(p.dtype), p32s, params)
        return new_params, FusedAdagradState(step=state.step + 1, sum=hs)

    @jax.named_scope("apex_tpu.optimizer_step")
    def step(
        self,
        grads: Pytree,
        state: FusedAdagradState,
        params: Pytree,
        lr: Optional[jax.Array] = None,
        found_inf: Optional[jax.Array] = None,
        grad_scale=None,
    ) -> Tuple[Pytree, FusedAdagradState]:
        lr = self.lr if lr is None else lr
        inv_scale = resolve_scale(grad_scale)
        return skip_on_overflow(
            found_inf,
            lambda: self._stepped(grads, state, params, lr, inv_scale),
            (params, state),
        )
