"""Shared machinery for the fused optimizer family.

The reference's optimizers are a Python loop building chunked tensor lists for
one CUDA launch per dtype group (``apex/optimizers/fused_adam.py:160-200``).
Here each optimizer's ``step`` is a single pure function over the whole param
pytree — XLA fuses the per-leaf update chains the way ``multi_tensor_apply``
hand-fused them — and overflow skip-step is a ``lax.cond`` over the entire
update (the ``noop_flag`` semantics of ``csrc/multi_tensor_apply.cuh``).

All optimizers follow one protocol:

    opt = FusedAdam(lr=1e-3, ...)
    state = opt.init(params)
    new_params, new_state = opt.step(grads, state, params,
                                     found_inf=..., grad_scale=...)

``params`` may be bf16/fp16; optimizer moments are always fp32 (the CUDA
kernels' ``MATH_T float``). With ``master_weights=True`` the state carries
fp32 master params and ``step`` returns params re-cast from the masters
(O2 semantics, ``apex/amp/_process_optimizer.py``).

Every optimizer also exposes ``as_gradient_transformation()`` returning an
optax ``GradientTransformation`` for ecosystem interop.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


def tree_zeros_like(tree: Pytree, dtype=jnp.float32) -> Pytree:
    return jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree)


def tree_f32(tree: Pytree) -> Pytree:
    # force a copy even for leaves already fp32 (astype would alias the
    # input buffer, and master copies aliasing params break buffer donation
    # of params+opt_state into a jitted step)
    return jax.tree_util.tree_map(
        lambda x: jnp.array(x, jnp.float32, copy=True), tree)


def multi_tree_update(fn: Callable, n_out: int, grads: Pytree, *trees: Pytree):
    """Map ``fn(g, *leaves) -> n_out-tuple`` over grads + parallel trees,
    returning ``n_out`` pytrees shaped like ``grads``.

    The shared skeleton of every fused optimizer's update: the leaf function
    is the "kernel", this is the list iteration ``multi_tensor_apply`` did on
    the CUDA side. Validates that the companion trees match the grads
    structure (mismatched pytrees were a silent zip-truncation hazard).
    """
    gl, treedef = jax.tree_util.tree_flatten(grads)
    leaf_lists = []
    for t in trees:
        tl = jax.tree_util.tree_leaves(t)
        if len(tl) != len(gl):
            raise ValueError(
                f"pytree mismatch: grads have {len(gl)} leaves, companion tree has {len(tl)}"
            )
        leaf_lists.append(tl)
    outs = [fn(g, *leaves) for g, *leaves in zip(gl, *leaf_lists)]
    return tuple(
        jax.tree_util.tree_unflatten(treedef, [o[i] for o in outs]) for i in range(n_out)
    )


def skip_on_overflow(
    found_inf: Optional[jax.Array],
    do_step: Callable[[], Tuple[Pytree, Pytree]],
    unchanged: Tuple[Pytree, Pytree],
):
    """Run ``do_step`` unless ``found_inf`` — the noop_flag contract.

    Uses ``lax.cond`` so the skipped branch costs nothing at runtime; with
    ``found_inf=None`` the step is unconditional and the cond disappears.
    """
    if found_inf is None:
        return do_step()
    return jax.lax.cond(
        jnp.asarray(found_inf, jnp.bool_), lambda: unchanged, do_step
    )


def resolve_scale(grad_scale) -> jax.Array:
    """Normalise a grad (loss) scale argument to an fp32 inverse multiplier."""
    if grad_scale is None:
        return jnp.float32(1.0)
    return 1.0 / jnp.asarray(grad_scale, jnp.float32)


class FusedOptimizer:
    """Base: functional step protocol + optax interop."""

    def init(self, params: Pytree):  # pragma: no cover - abstract
        raise NotImplementedError

    def step(self, grads: Pytree, state, params: Pytree, **kw):  # pragma: no cover
        raise NotImplementedError

    def as_gradient_transformation(self) -> "optax.GradientTransformation":
        """Adapt to optax: update() returns (new_params - params) deltas."""
        import optax  # on first use: a step needs no optax (0.6 s to import)

        def init_fn(params):
            return self.init(params)

        def update_fn(grads, state, params=None):
            assert params is not None, "fused optimizers need params"
            new_params, new_state = self.step(grads, state, params)
            updates = jax.tree_util.tree_map(
                lambda n, p: n.astype(p.dtype) - p, new_params, params
            )
            return updates, new_state

        return optax.GradientTransformation(init_fn, update_fn)
