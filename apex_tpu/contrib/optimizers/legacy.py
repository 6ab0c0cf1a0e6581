"""Legacy contrib optimizer API: the explicit-scale step surface.

Reference: ``apex/contrib/optimizers/fused_adam.py:64-78`` and
``fused_sgd.py:115-127`` — the DEPRECATED older duplicates of the core
optimizers, kept upstream because their ``step`` signature differs from
the maintained ones: gradients are passed EXPLICITLY, divided by a
caller-provided ``scale``, optionally clipped by a combined scale derived
from precomputed ``grad_norms`` against ``max_grad_norm``
(``fused_adam.py:119-124``: ``clip = ((norm / scale) + 1e-6) / max_norm``,
``combined = clip * scale`` when ``clip > 1`` — NB the incoming norms are
norms of the SCALED grads), and a reduced-precision copy of the updated
weights can be emitted alongside (``output_params``). The legacy Adam
also exposes ``eps_inside_sqrt`` (``fused_adam_cuda`` kernel mode 0:
``denom = sqrt(v + eps)`` instead of mode 1's ``sqrt(v) + eps`` — raw
second moment in both, see the next paragraph).

The legacy Adam kernel's update differs from BOTH maintained modes
(``fused_adam_cuda_kernel.cu:60-70``): the denominator comes from the
RAW second moment (``sqrt(v + eps)`` inside / ``sqrt(v) + eps``
outside), the bias corrections fold into the step size
(``lr * sqrt(bc2) / bc1``), and weight decay applies POST-denominator
(``update = m/denom + decay*p``) — not L2-into-the-gradient and not
AdamW. The leaf here reproduces that exactly.

Functionally spelled as thin subclasses of the maintained optimizers:
same pytree state, legacy step semantics and leaf math. ``use_mt`` /
``amp_scale_adjustment`` are accepted for parity; the latter is NEVER
applied — the reference only uses it on the amp-stash path, which the
explicit-grads ``step`` does not take (``fused_adam.py:83-86``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...optimizers._common import Pytree
from ...optimizers.fused_adam import FusedAdam, FusedAdamState
from ...optimizers.fused_sgd import FusedSGD


def _combined_scale(scale, grad_norms, max_grad_norm):
    """The legacy clip: grad_norms are norms of the SCALED grads."""
    if max_grad_norm <= 0 or grad_norms is None:
        return scale
    scale = jnp.asarray(scale, jnp.float32)
    norm = jnp.asarray(grad_norms, jnp.float32)
    clip = ((norm / scale) + 1e-6) / max_grad_norm
    return jnp.where(clip > 1.0, clip * scale, scale)


def _output_copy(params, output_params_dtype):
    if output_params_dtype is None:
        return None
    return jax.tree_util.tree_map(
        lambda p: p.astype(output_params_dtype), params
    )


def _legacy_returns(new_params, new_state, output_params_dtype):
    """The shared legacy return contract: 2-tuple, or 3-tuple with the
    reduced-precision copy when ``output_params_dtype`` is given."""
    out = _output_copy(new_params, output_params_dtype)
    if out is not None:
        return new_params, new_state, out
    return new_params, new_state


class LegacyFusedAdam(FusedAdam):
    """``apex.contrib.optimizers.FusedAdam`` — the legacy step surface
    over the maintained fused update."""

    def __init__(
        self,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-8,
        eps_inside_sqrt: bool = False,
        weight_decay: float = 0.0,
        max_grad_norm: float = 0.0,
        amsgrad: bool = False,
        use_mt: bool = False,
        amp_scale_adjustment: float = 1.0,
    ):
        super().__init__(
            lr=lr, bias_correction=bias_correction, betas=betas, eps=eps,
            adam_w_mode=False, weight_decay=weight_decay, amsgrad=amsgrad,
        )
        del use_mt  # launch batching is XLA's
        self.eps_inside_sqrt = bool(eps_inside_sqrt)
        self.max_grad_norm = float(max_grad_norm)
        # kept for attribute parity; never applied (reference: amp-stash
        # path only, which the explicit-grads step does not take)
        self.amp_scale_adjustment = float(amp_scale_adjustment)

    def _update_leaf(self, g, p, m, v, step, lr, wd):
        # the legacy kernel exactly (fused_adam_cuda_kernel.cu:60-70):
        #   denom = sqrt(v + eps)            [eps_inside_sqrt]
        #         | sqrt(v) + eps            [otherwise]
        #   step_size = lr * sqrt(bc2) / bc1 [bias corrections in the lr]
        #   update = m / denom + decay * p   [decay POST-denominator]
        #   p -= step_size * update
        beta1, beta2 = self.betas
        g = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        new_m = beta1 * m + (1.0 - beta1) * g
        new_v = beta2 * v + (1.0 - beta2) * g * g
        if self.eps_inside_sqrt:
            denom = jnp.sqrt(new_v + self.eps)
        else:
            denom = jnp.sqrt(new_v) + self.eps
        if self.bias_correction:
            t = step.astype(jnp.float32)
            step_size = lr * jnp.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
        else:
            step_size = lr
        update = new_m / denom
        if wd != 0.0:
            update = update + wd * p32
        new_p32 = p32 - step_size * update
        return new_p32, new_m, new_v

    @jax.named_scope("apex_tpu.optimizer_step")
    def step(  # legacy signature
        self,
        grads: Pytree,
        state: FusedAdamState,
        params: Pytree,
        scale=1.0,
        grad_norms=None,
        output_params_dtype=None,
        lr: Optional[jax.Array] = None,
    ):
        """Legacy semantics: ``update = adam(grads / combined_scale)``.

        Returns ``(params, state)``, or ``(params, state, output_params)``
        when ``output_params_dtype`` is given (the reference's
        reduced-precision ``output_params`` write-out, as a returned copy
        in the functional spelling).
        """
        scale = jnp.asarray(scale, jnp.float32)
        combined = _combined_scale(scale, grad_norms, self.max_grad_norm)
        new_params, new_state = super().step(
            grads, state, params, lr=lr, grad_scale=combined
        )
        return _legacy_returns(new_params, new_state, output_params_dtype)


class LegacyFusedSGD(FusedSGD):
    """``apex.contrib.optimizers.FusedSGD`` — the legacy step surface
    (explicit grads + scale + optional reduced-precision output copy)."""

    def __init__(
        self,
        lr: float,
        momentum: float = 0.0,
        dampening: float = 0.0,
        weight_decay: float = 0.0,
        nesterov: bool = False,
        wd_after_momentum: bool = False,
        materialize_master_grads: bool = True,
    ):
        super().__init__(
            lr=lr, momentum=momentum, dampening=dampening,
            weight_decay=weight_decay, nesterov=nesterov,
            wd_after_momentum=wd_after_momentum,
        )
        del materialize_master_grads  # CUDA master-grad plumbing; n/a

    @jax.named_scope("apex_tpu.optimizer_step")
    def step(  # legacy signature
        self,
        grads: Pytree,
        state,
        params: Pytree,
        scale=1.0,
        grad_norms=None,
        output_params_dtype=None,
        lr: Optional[jax.Array] = None,
    ):
        del grad_norms  # the legacy SGD accepts but never clips
        new_params, new_state = super().step(
            grads, state, params, lr=lr, grad_scale=scale
        )
        return _legacy_returns(new_params, new_state, output_params_dtype)
