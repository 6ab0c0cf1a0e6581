"""Fused scale + mask + softmax, Pallas-TPU with XLA fallback.

Reference: ``apex/transformer/functional/fused_softmax.py`` +
``csrc/megatron/scaled_{upper_triang_masked,masked,}_softmax*`` — four warp
kernels fusing ``softmax(scale * x + mask)`` fwd/bwd for attention scores:

- causal (upper-triangular) masked, ``sq == sk`` (``scaled_upper_triang_…``)
- arbitrary additive byte-mask [b, 1, sq, sk] (``scaled_masked_softmax``)
- no mask (``scaled_softmax``)
- a "generic" kernel for shapes outside the fast kernels' limits

TPU-native: one Pallas kernel family blocked over rows with the full key
dim resident in VMEM (the row-parallel structure the CUDA warp kernels use,
re-tiled for the VPU's (8, 128) lanes). The backward kernel computes
``dx = scale * y * (dy - rowsum(dy * y))`` from the saved probabilities —
identical to the CUDA bwd contract, and valid for every mask variant since
masked probabilities are exactly zero. On non-TPU backends or non-conforming
shapes, the same math runs as plain XLA ops (which XLA fuses well — the
Pallas path exists to also fuse the mask generation and avoid materialising
the [sq, sk] mask in HBM).

The ``FusedScaleMaskSoftmax`` dispatcher mirrors the reference module's
availability heuristics (``fused_softmax.py:165-212``).
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...ops.layer_norm import _row_block as _ln_row_block
from ..enums import AttnMaskType

_NEG_INF = -10000.0  # reference mask fill value (scaled_masked_softmax.h)


# --------------------------------------------------------------------------
# Pallas kernels
# --------------------------------------------------------------------------

def _use_pallas(sk: int, interpret: bool) -> bool:
    if os.environ.get("APEX_TPU_DISABLE_PALLAS"):
        return False
    if interpret:
        return True
    return jax.default_backend() == "tpu" and sk % 128 == 0 and sk <= 16384


def _row_block(rows: int, sk: int) -> int:
    # whole sk row stays in VMEM; one fp32 block under ~4MB. The block
    # rule (a multiple of 8 rows or the whole array) is layer_norm's.
    return _ln_row_block(rows, sk, budget_bytes=4 << 20)


def _softmax_fwd_kernel(x_ref, y_ref, *, scale, causal, sq, sk, br):
    x = x_ref[...].astype(jnp.float32) * scale
    if causal:
        start = pl.program_id(0) * br
        rows = jax.lax.broadcasted_iota(jnp.int32, (br, sk), 0) + start
        q_idx = rows % sq
        cols = jax.lax.broadcasted_iota(jnp.int32, (br, sk), 1)
        x = jnp.where(cols > q_idx, _NEG_INF, x)
    x = x - jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x)
    y_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(y_ref.dtype)


def _softmax_masked_fwd_kernel(x_ref, m_ref, y_ref, *, scale, sk):
    x = x_ref[...].astype(jnp.float32) * scale
    x = jnp.where(m_ref[...] != 0, _NEG_INF, x)
    x = x - jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x)
    y_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(y_ref.dtype)


def _softmax_bwd_kernel(dy_ref, y_ref, dx_ref, *, scale):
    dy = dy_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    s = jnp.sum(dy * y, axis=-1, keepdims=True)
    dx_ref[...] = (scale * y * (dy - s)).astype(dx_ref.dtype)


def _fwd_pallas(x2d, scale, causal, sq, interpret):
    rows, sk = x2d.shape
    br = _row_block(rows, sk)
    return pl.pallas_call(
        functools.partial(
            _softmax_fwd_kernel, scale=scale, causal=causal, sq=sq, sk=sk, br=br
        ),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, sk), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, sk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, sk), x2d.dtype),
        interpret=interpret,
    )(x2d)


def _fwd_masked_pallas(x2d, m2d, scale, interpret):
    rows, sk = x2d.shape
    br = _row_block(rows, sk)
    return pl.pallas_call(
        functools.partial(_softmax_masked_fwd_kernel, scale=scale, sk=sk),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, sk), lambda i: (i, 0)),
            pl.BlockSpec((br, sk), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, sk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, sk), x2d.dtype),
        interpret=interpret,
    )(x2d, m2d)


def _bwd_pallas(dy2d, y2d, scale, interpret):
    rows, sk = dy2d.shape
    br = _row_block(rows, sk)
    return pl.pallas_call(
        functools.partial(_softmax_bwd_kernel, scale=scale),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, sk), lambda i: (i, 0)),
            pl.BlockSpec((br, sk), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, sk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, sk), dy2d.dtype),
        interpret=interpret,
    )(dy2d, y2d)


# --------------------------------------------------------------------------
# XLA fallbacks
# --------------------------------------------------------------------------

def _fwd_xla(x, scale, causal, mask):
    xf = x.astype(jnp.float32) * scale
    if causal:
        sq, sk = x.shape[-2], x.shape[-1]
        q = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        xf = jnp.where(k > q, _NEG_INF, xf)
    if mask is not None:
        xf = jnp.where(mask != 0, _NEG_INF, xf)
    return jax.nn.softmax(xf, axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# custom-vjp wrappers (one per reference extension module)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def scaled_upper_triang_masked_softmax(x, scale: float = 1.0, interpret: bool = False):
    """softmax(scale·x) with causal mask; x is [..., sq, sk], sq == sk
    (reference ``scaled_upper_triang_masked_softmax_cuda``)."""
    y, _ = _sutms_fwd(x, scale, interpret)
    return y


def _sutms_fwd(x, scale, interpret):
    sq, sk = x.shape[-2], x.shape[-1]
    if _use_pallas(sk, interpret):
        y = _fwd_pallas(
            x.reshape(-1, sk), scale, True, sq, interpret
        ).reshape(x.shape)
    else:
        y = _fwd_xla(x, scale, True, None)
    return y, y


def _sutms_bwd(scale, interpret, y, dy):
    sk = y.shape[-1]
    if _use_pallas(sk, interpret):
        dx = _bwd_pallas(
            dy.reshape(-1, sk), y.reshape(-1, sk), scale, interpret
        ).reshape(y.shape)
    else:
        yf, dyf = y.astype(jnp.float32), dy.astype(jnp.float32)
        dx = (scale * yf * (dyf - jnp.sum(dyf * yf, -1, keepdims=True))).astype(
            y.dtype
        )
    return (dx,)


scaled_upper_triang_masked_softmax.defvjp(_sutms_fwd, _sutms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def scaled_masked_softmax(x, mask, scale: float = 1.0, interpret: bool = False):
    """softmax(scale·x + mask): x [b, np, sq, sk], mask broadcastable
    [b, 1, sq, sk], nonzero = masked out
    (reference ``scaled_masked_softmax_cuda``)."""
    y, _ = _sms_fwd(x, mask, scale, interpret)
    return y


def _sms_fwd(x, mask, scale, interpret):
    sk = x.shape[-1]
    if _use_pallas(sk, interpret):
        m = (jnp.broadcast_to(mask, x.shape) != 0).astype(jnp.int8)
        y = _fwd_masked_pallas(
            x.reshape(-1, sk), m.reshape(-1, sk), scale, interpret
        ).reshape(x.shape)
    else:
        y = _fwd_xla(x, scale, False, mask)
    return y, y


def _sms_bwd(scale, interpret, y, dy):
    (dx,) = _sutms_bwd(scale, interpret, y, dy)
    return (dx, None)


scaled_masked_softmax.defvjp(_sms_fwd, _sms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def scaled_softmax(x, scale: float = 1.0, interpret: bool = False):
    """softmax(scale·x), no mask (reference ``scaled_softmax_cuda``)."""
    y, _ = _ss_fwd(x, scale, interpret)
    return y


def _ss_fwd(x, scale, interpret):
    sk = x.shape[-1]
    if _use_pallas(sk, interpret):
        y = _fwd_pallas(
            x.reshape(-1, sk), scale, False, x.shape[-2], interpret
        ).reshape(x.shape)
    else:
        y = _fwd_xla(x, scale, False, None)
    return y, y


scaled_softmax.defvjp(_ss_fwd, _sutms_bwd)


def generic_scaled_masked_softmax(x, mask, scale: float = 1.0):
    """Arbitrary-shape fallback (reference
    ``generic_scaled_masked_softmax_cuda``): plain XLA, differentiable."""
    return _fwd_xla(x, scale, False, mask)


# --------------------------------------------------------------------------
# Dispatcher module
# --------------------------------------------------------------------------

class FusedScaleMaskSoftmax:
    """Fused scale+mask+softmax dispatcher.

    Mirrors ``apex/transformer/functional/fused_softmax.py:137-274``:
    picks the causal kernel, the masked kernel, the unmasked kernel, or a
    pure-XLA fallback based on dtype/shape/flags. Input is
    ``[b, np, sq, sk]``.

    Args mirror the reference: ``mask_func`` is used only on the fallback
    path (as in the reference's ``forward_torch_softmax``);
    ``softmax_in_fp32`` upcasts before the fallback softmax;
    ``scaled_masked_softmax_fusion`` gates kernel use.
    """

    def __init__(
        self,
        input_in_fp16: bool = False,
        input_in_bf16: bool = True,
        attn_mask_type: AttnMaskType = AttnMaskType.padding,
        scaled_masked_softmax_fusion: bool = True,
        mask_func: Optional[Callable] = None,
        softmax_in_fp32: bool = True,
        scale: Optional[float] = None,
    ):
        if input_in_fp16 and input_in_bf16:
            raise RuntimeError("both fp16 and bf16 flags cannot be active")
        self.input_in_fp16 = input_in_fp16
        self.input_in_bf16 = input_in_bf16
        self.input_in_float16 = input_in_fp16 or input_in_bf16
        self.attn_mask_type = attn_mask_type
        self.scaled_masked_softmax_fusion = scaled_masked_softmax_fusion
        self.mask_func = mask_func
        self.softmax_in_fp32 = softmax_in_fp32
        self.scale = scale
        if self.scale is not None and not self.softmax_in_fp32:
            raise RuntimeError("softmax should be in fp32 when scaled")

    def is_kernel_available(self, mask, b, np_, sq, sk) -> bool:
        """Reference heuristics ``fused_softmax.py:165-200``, re-tuned for
        the Pallas kernel's constraints (sk multiple of 128 ≤ 16k)."""
        attn_batches = b * np_
        if not (
            self.scaled_masked_softmax_fusion
            and self.input_in_float16
            and 16 < sk <= 16384
            and sk % 128 == 0
        ):
            return False
        if self.attn_mask_type == AttnMaskType.causal and sq != sk:
            return False
        del attn_batches
        return True

    def __call__(self, input, mask=None):
        b, np_, sq, sk = input.shape
        scale = self.scale if self.scale is not None else 1.0
        if self.is_kernel_available(mask, b, np_, sq, sk):
            if self.attn_mask_type == AttnMaskType.causal:
                return scaled_upper_triang_masked_softmax(input, scale)
            if mask is not None:
                return scaled_masked_softmax(input, mask, scale)
            return scaled_softmax(input, scale)
        return self.forward_softmax(input, mask)

    # reference ``forward_torch_softmax`` (:246-266)
    def forward_softmax(self, input, mask):
        x = input
        if self.input_in_float16 and self.softmax_in_fp32:
            x = x.astype(jnp.float32)
        if self.scale is not None:
            x = x * self.scale
        if self.attn_mask_type == AttnMaskType.causal:
            sq, sk = x.shape[-2], x.shape[-1]
            q = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
            k = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
            x = jnp.where(k > q, _NEG_INF, x)
        elif mask is not None:
            x = self.mask_func(x, mask) if self.mask_func else jnp.where(
                mask != 0, _NEG_INF, x
            )
        probs = jax.nn.softmax(x, axis=-1)
        if self.input_in_float16 and self.softmax_in_fp32:
            probs = probs.astype(input.dtype)
        return probs

    @staticmethod
    def get_batch_per_block(sq, sk, b, np_):
        """CUDA occupancy helper (reference ``fused_softmax.py:272-274``).
        On TPU the analogous quantity is rows per Pallas block."""
        return _row_block(b * np_ * sq, sk)
