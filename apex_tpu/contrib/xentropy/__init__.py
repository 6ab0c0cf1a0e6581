from .softmax_xentropy import (
    SoftmaxCrossEntropyLoss,
    lm_head_cross_entropy,
    lm_head_cross_entropy_sum,
    softmax_cross_entropy_loss,
)

__all__ = [
    "SoftmaxCrossEntropyLoss",
    "softmax_cross_entropy_loss",
    "lm_head_cross_entropy",
    "lm_head_cross_entropy_sum",
]
