"""Tensor-parallel layer library (reference
``apex/transformer/tensor_parallel/__init__.py``)."""
from .cross_entropy import vocab_parallel_cross_entropy  # noqa: F401
from .data import broadcast_data  # noqa: F401
from .mappings import (  # noqa: F401
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
    gather_from_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
    reduce_scatter_to_sequence_parallel_region,
    scatter_to_sequence_parallel_region,
    scatter_to_tensor_model_parallel_region,
)
from .memory import MemoryBuffer, RingMemBuffer  # noqa: F401
from .random import (  # noqa: F401
    CheckpointFunction,
    checkpoint,
    get_cuda_rng_tracker,
    get_rng_state_tracker,
    model_parallel_cuda_manual_seed,
    model_parallel_manual_seed,
    model_parallel_rng_key,
)
from .layers import (  # noqa: F401
    column_parallel_linear,
    init_affine_weight_shard,
    row_parallel_linear,
    vocab_parallel_embedding,
)
from .grad_accumulation import (  # noqa: F401
    accumulate_main_grads,
    init_main_grads,
    wgrad_gemm_accum_fp16,
    wgrad_gemm_accum_fp32,
)
from .utils import (  # noqa: F401
    VocabUtility,
    divide,
    ensure_divisibility,
    split_tensor_along_last_dim,
)

from .layers import __getattr__  # noqa: F401  Column/RowParallelLinear, VocabParallelEmbedding (flax, on first use)
