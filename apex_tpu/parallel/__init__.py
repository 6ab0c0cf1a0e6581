"""Data-parallel layer: DDP-style grad sync, SyncBatchNorm, LARC.

TPU-native re-design of ``apex/parallel/__init__.py:9-21``.
"""
from .distributed import (  # noqa: F401
    BucketBuffers,
    DistributedDataParallel,
    GradBuckets,
    Reducer,
    flatten,
    sync_gradients,
    sync_gradients_bucketed,
    unflatten,
)
from .LARC import LARC, larc_adjust_gradients, larc_transform  # noqa: F401
from .sync_batchnorm import sync_batch_norm  # noqa: F401

from .sync_batchnorm import __getattr__  # noqa: F401  SyncBatchNorm, convert_syncbn_model (flax, on first use)

from .multiproc import initialize_distributed  # noqa: F401
