"""LARC: layer-wise adaptive rate clipping/scaling.

Reference: ``apex/parallel/LARC.py:5-100`` — an optimizer *wrapper* that, per
parameter tensor, computes

    adaptive_lr = trust_coefficient * ||p|| / (||g|| + weight_decay*||p|| + eps)

and either clips the effective LR (``clip=True``: scale grads by
``min(adaptive_lr / lr, 1)``) or replaces it (``clip=False``: scale grads by
``adaptive_lr / lr``), folding weight decay into the gradient first so the
wrapped optimizer must run with wd=0.

TPU-native spelling: a pure gradient transform applied before any optimizer
following the ``apex_tpu.optimizers`` protocol (or as an optax chain link via
``larc_transform``). All per-tensor norms trace into one fused XLA reduction
sweep — the moral equivalent of the reference's single pass over
``optimizer.param_groups``.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Pytree = Any


def larc_adjust_gradients(
    grads: Pytree,
    params: Pytree,
    lr: float,
    *,
    trust_coefficient: float = 0.02,
    clip: bool = True,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
) -> Pytree:
    """Apply the LARC gradient adjustment (reference ``LARC.py:71-100``).

    Weight decay is folded into the returned grads exactly as the reference
    temporarily zeroes the group's wd and adds ``wd * p`` itself.
    """

    def _adjust(g, p):
        g32, p32 = g.astype(jnp.float32), p.astype(jnp.float32)
        p_norm = jnp.linalg.norm(p32.ravel())
        g_norm = jnp.linalg.norm(g32.ravel())
        adaptive_lr = (
            trust_coefficient * p_norm / (g_norm + p_norm * weight_decay + eps)
        )
        # clip: effective lr becomes min(adaptive_lr, lr) → grads scaled by
        # min(adaptive_lr/lr, 1); otherwise grads scaled by adaptive_lr so the
        # effective lr is lr*adaptive_lr (reference LARC.py:91-99).
        scale = (
            jnp.minimum(adaptive_lr / lr, 1.0) if clip else adaptive_lr
        )
        adjusted = (g32 + weight_decay * p32) * scale
        # reference LARC.py:84: adapt only when both norms are nonzero;
        # otherwise the gradient is left entirely untouched (no wd fold).
        out = jnp.where((p_norm > 0) & (g_norm > 0), adjusted, g32)
        return out.astype(g.dtype)

    return jax.tree_util.tree_map(_adjust, grads, params)


class LARC:
    """Wrapper over an ``apex_tpu.optimizers`` fused optimizer.

    Usage mirrors the reference (wrap, then use like the inner optimizer):

        opt = LARC(FusedSGD(lr=0.1, momentum=0.9), trust_coefficient=1e-3)
        state = opt.init(params)
        params, state = opt.step(grads, state, params)
    """

    def __init__(
        self,
        optimizer,
        trust_coefficient: float = 0.02,
        clip: bool = True,
        eps: float = 1e-8,
    ):
        self.optim = optimizer
        self.trust_coefficient = trust_coefficient
        self.clip = clip
        self.eps = eps

    def __getattr__(self, name):
        return getattr(self.optim, name)

    def init(self, params: Pytree):
        return self.optim.init(params)

    @jax.named_scope("apex_tpu.optimizer_step")
    def step(self, grads: Pytree, state, params: Pytree, **kwargs):
        lr = getattr(self.optim, "lr", None)
        wd = getattr(self.optim, "weight_decay", 0.0) or 0.0
        grads = larc_adjust_gradients(
            grads, params, lr,
            trust_coefficient=self.trust_coefficient,
            clip=self.clip, eps=self.eps, weight_decay=wd,
        )
        # wd handled here, exactly like the reference zeroes group wd
        saved_wd = getattr(self.optim, "weight_decay", None)
        if saved_wd is not None:
            self.optim.weight_decay = 0.0
        try:
            return self.optim.step(grads, state, params, **kwargs)
        finally:
            if saved_wd is not None:
                self.optim.weight_decay = saved_wd


def larc_transform(
    lr: float,
    trust_coefficient: float = 0.02,
    clip: bool = True,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
):
    """optax ``GradientTransformation`` form, for chaining:
    ``optax.chain(larc_transform(lr), optax.sgd(lr))``."""
    import optax

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("larc_transform requires params")
        return (
            larc_adjust_gradients(
                updates, params, lr,
                trust_coefficient=trust_coefficient,
                clip=clip, eps=eps, weight_decay=weight_decay,
            ),
            state,
        )

    return optax.GradientTransformation(init_fn, update_fn)
