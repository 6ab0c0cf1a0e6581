"""The flax module over :func:`sync_batch_norm` and the model converter
(``apex.parallel.SyncBatchNorm`` / ``convert_syncbn_model``). Reached as
``apex_tpu.parallel.SyncBatchNorm``; apart from the functional core so
that importing the core does not import flax."""
from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from .sync_batchnorm import sync_batch_norm


class SyncBatchNorm(nn.Module):
    """Flax module over :func:`sync_batch_norm`.

    Drop-in for ``flax.linen.BatchNorm`` with cross-device statistics,
    mirroring ``apex.parallel.SyncBatchNorm``
    (``apex/parallel/optimized_sync_batchnorm.py:9``). ``axis_name``
    plays the role of the reference's ``process_group``; restrict sync
    to a subgroup by meshing that subgroup as its own axis.
    """

    num_features: Optional[int] = None  # inferred from input if None
    eps: float = 1e-5
    momentum: float = 0.1
    affine: bool = True
    use_bias: bool = True
    track_running_stats: bool = True
    axis_name: Optional[str] = "data"
    channel_last: bool = True
    fuse_relu: bool = False

    @nn.compact
    def __call__(self, x, use_running_average: bool = False):
        c = self.num_features or (
            x.shape[-1] if self.channel_last else x.shape[1]
        )
        weight = (
            self.param("scale", nn.initializers.ones, (c,))
            if self.affine
            else None
        )
        bias = (
            self.param("bias", nn.initializers.zeros, (c,))
            if self.affine and self.use_bias
            else None
        )
        ra_mean = self.variable(
            "batch_stats", "mean",
            lambda: jnp.zeros((c,), jnp.float32),
        )
        ra_var = self.variable(
            "batch_stats", "var",
            lambda: jnp.ones((c,), jnp.float32),
        )
        training = not use_running_average
        y, new_rm, new_rv = sync_batch_norm(
            x, weight, bias, ra_mean.value, ra_var.value,
            training=training, momentum=self.momentum, eps=self.eps,
            axis_name=self.axis_name if training else None,
            channel_last=self.channel_last, fuse_relu=self.fuse_relu,
        )
        if training and self.track_running_stats and not self.is_initializing():
            ra_mean.value = new_rm
            ra_var.value = new_rv
        return y


def convert_syncbn_model(
    module: "nn.Module", axis_name: str = "data", channel_last: bool = True
) -> "nn.Module":
    """Recursively replace ``flax.linen.BatchNorm`` layers with
    :class:`SyncBatchNorm` (reference ``apex/parallel/__init__.py:22-44``).

    Flax modules are immutable dataclass definitions, so conversion
    clones the module tree rather than mutating in place.
    """
    import dataclasses

    if isinstance(module, nn.BatchNorm):
        # flax BatchNorm carries no feature count (shape is inferred at
        # first call); SyncBatchNorm infers it the same way.
        return SyncBatchNorm(
            eps=module.epsilon,
            momentum=1.0 - module.momentum,
            affine=module.use_scale or module.use_bias,
            use_bias=module.use_bias,
            axis_name=axis_name,
            channel_last=channel_last,
        )
    if not dataclasses.is_dataclass(module):
        return module
    changes = {}
    for f in dataclasses.fields(module):
        v = getattr(module, f.name, None)
        if isinstance(v, nn.Module):
            converted = convert_syncbn_model(v, axis_name, channel_last)
            if converted is not v:
                changes[f.name] = converted
    return dataclasses.replace(module, **changes) if changes else module
