"""Synchronised BatchNorm over a mesh axis.

The reference ships two paths: a CUDA "optimized" SyncBatchNorm using custom
Welford kernels + all-gather of per-rank (mean, inv_std, count)
(``apex/parallel/optimized_sync_batchnorm.py:9-108``,
``csrc/welford.cu``) and a pure-Python fallback
(``apex/parallel/sync_batchnorm.py``). Features: process-group restriction,
``channel_last`` (NHWC) layout, and a ``fuse_relu`` epilogue.

TPU-native design: batch statistics are combined across the data-parallel
mesh axis with Chan's parallel-Welford merge over ``psum`` of
``(count, count*mean, m2 + count*mean^2)`` — numerically the same combination
order as ``welford.cu``'s parallel reduction, but carried by an XLA collective
on ICI instead of an allgather + host loop. NHWC is the *native* TPU layout
(the MXU consumes channels-minor), so ``channel_last`` is the default here and
NCHW is the conversion case — the inverse of the CUDA situation.

Functional core + a flax module. The backward pass is JAX autodiff through
the psum (which differentiates to another psum) — matching the reference's
hand-written ``welford_backward`` collective structure for free.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .._lazy import forward


def _moments_over_axis(
    x: jax.Array,
    reduce_dims: Sequence[int],
    axis_name: Optional[str],
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(mean, biased var, total count) over local dims + the mesh axis.

    Cross-device combine mirrors ``welford_parallel`` in
    ``csrc/welford.cu``: counts and first moments sum linearly; second
    central moments combine as m2_total = Σm2_i + Σn_i·mean_i² − N·mean².
    """
    x32 = x.astype(jnp.float32)
    n_local = jnp.asarray(
        jnp.prod(jnp.array([x.shape[d] for d in reduce_dims])), jnp.float32
    )
    mean_local = jnp.mean(x32, axis=tuple(reduce_dims))
    m2_local = jnp.sum(
        (x32 - jnp.expand_dims(mean_local, tuple(reduce_dims))) ** 2,
        axis=tuple(reduce_dims),
    )
    if axis_name is None:
        return mean_local, m2_local / n_local, n_local
    n = jax.lax.psum(n_local, axis_name)
    mean = jax.lax.psum(n_local * mean_local, axis_name) / n
    m2 = (
        jax.lax.psum(m2_local + n_local * mean_local**2, axis_name)
        - n * mean**2
    )
    return mean, m2 / n, n


@jax.named_scope("apex_tpu.sync_batch_norm")
def sync_batch_norm(
    x: jax.Array,
    weight: Optional[jax.Array],
    bias: Optional[jax.Array],
    running_mean: jax.Array,
    running_var: jax.Array,
    *,
    training: bool,
    momentum: float = 0.1,
    eps: float = 1e-5,
    axis_name: Optional[str] = "data",
    channel_last: bool = True,
    fuse_relu: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Functional sync BN. Returns ``(y, new_running_mean, new_running_var)``.

    Mirrors ``SyncBatchNorm.forward``
    (``apex/parallel/optimized_sync_batchnorm.py:85-108``): in training mode
    batch stats are computed across all devices on ``axis_name``; running
    stats use the *unbiased* variance (count/(count-1) correction, reference
    ``optimized_sync_batchnorm_kernel.py:35-39``); eval mode normalises with
    running stats. ``fuse_relu`` applies the epilogue the CUDA kernel fused.
    """
    if channel_last:
        reduce_dims = list(range(x.ndim - 1))
        bshape = (1,) * (x.ndim - 1) + (-1,)
    else:
        reduce_dims = [0] + list(range(2, x.ndim))
        bshape = (1, -1) + (1,) * (x.ndim - 2)

    if training:
        mean, var, count = _moments_over_axis(x, reduce_dims, axis_name)
        unbiased = var * count / jnp.maximum(count - 1.0, 1.0)
        new_rm = (1 - momentum) * running_mean + momentum * mean.astype(
            running_mean.dtype
        )
        new_rv = (1 - momentum) * running_var + momentum * unbiased.astype(
            running_var.dtype
        )
    else:
        mean = running_mean.astype(jnp.float32)
        var = running_var.astype(jnp.float32)
        new_rm, new_rv = running_mean, running_var

    inv_std = jax.lax.rsqrt(var + eps)
    y = (x.astype(jnp.float32) - mean.reshape(bshape)) * inv_std.reshape(bshape)
    if weight is not None:
        y = y * weight.astype(jnp.float32).reshape(bshape)
    if bias is not None:
        y = y + bias.astype(jnp.float32).reshape(bshape)
    if fuse_relu:
        y = jax.nn.relu(y)
    return y.astype(x.dtype), new_rm, new_rv


# the flax module over the functional core lives apart: importing this file
# (and ``apex_tpu.parallel``) does not import flax
__getattr__ = forward(__package__, ".sync_batchnorm_flax",
                      ("SyncBatchNorm", "convert_syncbn_model"))
