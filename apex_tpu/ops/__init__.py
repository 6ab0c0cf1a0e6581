"""apex_tpu.ops: the kernel layer.

TPU-native replacement for the reference's ``csrc/`` CUDA extension modules
(``amp_C``, ``fused_layer_norm_cuda``, megatron softmax/rope kernels, ...).
Elementwise/reduction "multi-tensor" ops are single-jit pytree computations —
XLA fuses the chains that the CUDA build hand-fused — and the genuinely hot ops
(normalization, softmax, attention, optimizer updates) additionally have Pallas
TPU kernels, selected automatically on TPU backends with an XLA fallback
elsewhere (CPU tests, interpret mode).
"""
from .multi_tensor import (  # noqa: F401
    multi_tensor_scale,
    multi_tensor_axpby,
    multi_tensor_l2norm,
    multi_tensor_unscale_l2norm,
    update_scale_hysteresis,
    l2norm,
    has_inf_or_nan,
)
from .packed_optimizer import (  # noqa: F401
    multi_tensor_axpby_flat,
    multi_tensor_l2norm_flat,
    multi_tensor_scale_flat,
    packed_adam_apply,
    packed_lamb_stage1,
    packed_novograd_apply,
    packed_row_reduce,
    packed_row_stats,
    packed_scale_update,
    packed_sgd_apply,
)
from .flash_attention import (  # noqa: F401
    flash_attention,
    flash_attention_bshd,
    flash_attention_qkv,
    flash_attention_sbhd,
    flash_attention_available,
)
from .fused_block import (  # noqa: F401
    bias_dropout_residual,
    bias_gelu,
    fused_block_available,
    residual_add_layer_norm,
)
from .flash_decode import (  # noqa: F401
    flash_decode,
    flash_decode_available,
    paged_decode_reference,
)
