"""Varying-manual-axes helpers for code that runs under
``shard_map(check_vma=True)``: a loss, a kernel's rule and the pipeline
schedules mark values varying with these, so none of them depends on
another's package for it."""
from __future__ import annotations

import jax


def pvary(x: jax.Array, axis_names) -> jax.Array:
    """Mark ``x`` varying over ``axis_names`` (``jax.lax.pcast``)."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if not axis_names:
        return x
    return jax.lax.pcast(x, tuple(axis_names), to="varying")


def pvary_union_like(init: jax.Array, operands, extra_axes=()) -> jax.Array:
    """pvary ``init`` with every axis any of ``operands``' leaves vary on,
    plus ``extra_axes`` — the closure rule for zero-initialised scan carries
    whose body mixes the operands (carry in/out types must match)."""
    want = set(extra_axes)
    for op in operands:
        for leaf in jax.tree_util.tree_leaves(op):
            want |= set(getattr(leaf.aval, "vma", ()))
    missing = tuple(a for a in want if a not in getattr(init.aval, "vma", ()))
    return pvary(init, missing)
