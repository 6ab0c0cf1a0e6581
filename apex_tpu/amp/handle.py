"""Loss scaling entry points — the ``amp.scale_loss`` analogue.

Reference: ``apex/amp/handle.py:16-152``. The torch version is a context
manager around ``loss.backward()`` that patches ``optimizer.step`` to skip on
overflow. In JAX the backward pass is ``jax.grad``, so the workhorse here is
:func:`scaled_value_and_grad`: it differentiates the *scaled* loss, unscales
the grads, records overflow into the scaler state, and the optimizer step is
skipped via ``lax.cond`` (see ``apex_tpu.optimizers``' ``found_inf`` argument
or :func:`apply_updates_skip_on_overflow`).
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from .scaler import LossScaler, LossScaleState

Pytree = Any


@jax.named_scope("apex_tpu.amp_scaler")
def scale_loss(loss: jax.Array, scaler_state: LossScaleState) -> jax.Array:
    """loss * current scale (use inside your loss function)."""
    return loss * scaler_state.loss_scale.astype(loss.dtype)


def scaled_value_and_grad(
    loss_fn: Callable,
    scaler: LossScaler,
    argnums: int = 0,
    has_aux: bool = False,
):
    """Build a value-and-grad function with loss scaling folded in.

    Returns ``fn(scaler_state, *args) -> ((loss, aux?), grads, new_state)``
    where ``grads`` are already unscaled and ``new_state.found_inf`` is set if
    any gradient overflowed. Equivalent control flow to the reference's

        with amp.scale_loss(loss, optimizer) as scaled_loss:
            scaled_loss.backward()

    (``apex/amp/handle.py:17-124``) but purely functional and jittable.
    """

    def scaled_loss_fn(*args):
        scaler_state = args[-1]
        out = loss_fn(*args[:-1])
        if has_aux:
            loss, aux = out
            return scale_loss(loss.astype(jnp.float32), scaler_state), (loss, aux)
        return scale_loss(out.astype(jnp.float32), scaler_state), (out, None)

    grad_fn = jax.value_and_grad(scaled_loss_fn, argnums=argnums, has_aux=True)

    def fn(scaler_state: LossScaleState, *args):
        (_, (loss, aux)), scaled_grads = grad_fn(*args, scaler_state)
        grads, scaler_state = scaler.unscale(scaler_state, scaled_grads)
        if has_aux:
            return (loss, aux), grads, scaler_state
        return loss, grads, scaler_state

    return fn


@jax.named_scope("apex_tpu.amp_scaler")
def apply_updates_skip_on_overflow(
    params: Pytree, new_params: Pytree, found_inf: jax.Array
) -> Pytree:
    """Select old params when the step overflowed — the functional analogue of
    the reference's patched ``optimizer.step`` skipping on ``noop_flag``
    (``apex/amp/handle.py:126-146``)."""
    return jax.tree_util.tree_map(
        lambda old, new: jnp.where(found_inf, old, new), params, new_params
    )
