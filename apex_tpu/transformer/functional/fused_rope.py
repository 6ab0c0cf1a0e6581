"""Fused rotary positional embeddings in sbhd / cached / thd / 2d layouts.

Reference: ``apex/transformer/functional/fused_rope.py`` +
``csrc/megatron/fused_rotary_positional_embedding.{h,_cuda.cu}`` — 8 CUDA
ops applying NeoX-style rotate-half RoPE:

    out[d] = t[d]·cos(f[s,d]) + rot(t)[d]·sin(f[s,d]),   d < d2
    rot(t)[d] = -t[d + d2/2]  if d < d2/2  else  t[d - d2/2]
    out[d] = t[d]                                         d ≥ d2  (pass-through)

in four layouts: ``sbhd`` [s,b,h,d] with freqs [s,1,1,d2]; cached cos/sin;
``thd`` packed varlen (positions restart at each ``cu_seqlens`` boundary);
and 2d image RoPE (height freqs on the first half of the head dim, width
freqs on the second).

TPU-native: one pass over ``t``, written so that XLA can make it one.
The CUDA kernels exist to fuse the sincos + gather + rotate into one
launch. XLA does not do that for the slice / negate / concatenate form of
``rot``: compiled for a v5e at ``[2, 16, 8192, 192]`` bf16 with 64 rotary
lanes it is five passes over the activation (a float32 copy of the rotary
lanes, two float32 half-width slices that fill 32 of a tile's 128 lanes,
their join, a copy, and a pad to join the pass-through lanes back), 0.92
GB accessed for 0.2 GB of input and output, and on the chip 37 ms of a
586 ms training step (``PERF.md`` §6, PRs 35 and 36). So ``rot`` is
written as what it is, a product with a constant signed permutation:

    out = t * C + (t @ P) * S

``P [d, d]`` holds ``-1`` at ``[first + d2/2 + i, first + i]``, ``+1`` at
``[first + i, first + d2/2 + i]`` and zeros elsewhere; ``C`` / ``S`` are
the float32 tables as wide as the row, cos / sin on the rotary lanes and
1 / 0 on the pass-through ones. No lane of ``t`` is sliced, split or
joined, and the whole thing compiles to one fusion that reads ``t`` once
and writes it once (the product, with the multiply-add as its epilogue).
The product is exact: every element of ``t @ P`` is plus or minus one
element of ``t``, accumulated in float32 and asked at ``highest``
precision, because a default-precision product on the chip rounds a
float32 operand to bfloat16: a bfloat16 ``t`` goes through the MXU once
either way, a wider ``t`` and the backward pass's float32 ``g * S`` are
not rounded. No Pallas and no hand-written VJPs: autodiff gives ``dt = g *
C + (g * S) @ P^T``, the CUDA ``fused_rope_block_backward`` rotation, in
the same single fusion, *and* correct gradients for
``freqs``/``cos``/``sin`` (which the reference's backward silently drops —
its autograd.Function returns None for them). ``t`` has two uses and each
hands its cotangent back in ``t``'s dtype: of a bfloat16 ``t`` the two
terms of ``dt`` are rounded before they are added, a pair of conversions
the chip's compiler elides (there ``dt`` is the split form's bit for bit)
and the CPU keeps (an ulp of each term).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _half_swap(d: int, first: int, d2: int) -> np.ndarray:
    """NeoX rotate-half (``fused_rotary_positional_embedding.h:43-46``) of
    lanes ``[first, first + d2)`` of a ``d``-wide row as a matrix:
    ``(t @ P)[first + i] = -t[first + d2/2 + i]``, ``(t @ P)[first + d2/2
    + i] = t[first + i]``, zero on every other lane."""
    half, i = d2 // 2, np.arange(d2 // 2)
    p = np.zeros((d, d), np.float32)
    p[first + half + i, first + i] = -1.0
    p[first + i, first + half + i] = 1.0
    return p


def _apply_rope(t, cos, sin, first: int = 0):
    """Rotate lanes ``[first, first + d2)`` of ``t [..., d]``, ``d2 =
    cos.shape[-1]``, by ``cos`` / ``sin`` (broadcast against ``t`` but for
    their last dimension); the other lanes pass through."""
    d, d2 = t.shape[-1], cos.shape[-1]
    lanes = [(0, 0)] * (cos.ndim - 1) + [(first, d - first - d2)]
    c = jnp.pad(cos.astype(jnp.float32), lanes, constant_values=1.0)
    s = jnp.pad(sin.astype(jnp.float32), lanes)
    rot = jnp.matmul(
        t, jnp.asarray(_half_swap(d, first, d2), t.dtype),
        precision="highest", preferred_element_type=jnp.float32)
    return (t.astype(jnp.float32) * c + rot * s).astype(t.dtype)


# --- sbhd (reference FusedRoPEFunc, fused_rope.py:19-81) ---------------------

def fused_apply_rotary_pos_emb(t: jax.Array, freqs: jax.Array) -> jax.Array:
    """RoPE on ``t`` [s, b, h, d] with ``freqs`` [s, 1, 1, d2] (float).

    ``transpose_output_memory`` from the reference is a CUDA memory-format
    knob with no XLA analogue (layouts are compiler-assigned) and is omitted.
    """
    return _apply_rope(t, jnp.cos(freqs), jnp.sin(freqs))


# --- cached cos/sin (reference FusedRoPECachedFunc, fused_rope.py:84-150) ----

def fused_apply_rotary_pos_emb_cached(
    t: jax.Array, cos_: jax.Array, sin_: jax.Array
) -> jax.Array:
    """RoPE on ``t`` [s, b, h, d] with precomputed ``cos_``/``sin_``
    [s, 1, 1, d2]."""
    return _apply_rope(t, cos_, sin_)


# --- thd packed varlen (reference FusedRoPETHDFunc, fused_rope.py:153-211) ---

def fused_apply_rotary_pos_emb_thd(
    t: jax.Array, cu_seqlens: jax.Array, freqs: jax.Array
) -> jax.Array:
    """RoPE on packed ``t`` [total_tokens, h, d] where positions restart at
    every ``cu_seqlens`` boundary (cu_seqlens [b+1], cumulative lengths).

    Per-token position = token_index − cu_seqlens[seq_of(token)], resolved
    with a searchsorted instead of the CUDA kernel's per-sequence grid.
    """
    tok = jnp.arange(t.shape[0])
    seq_id = jnp.searchsorted(cu_seqlens, tok, side="right") - 1
    pos = tok - cu_seqlens[seq_id]
    f = freqs.reshape(freqs.shape[0], -1)[pos]  # [total, d2]
    return _apply_rope(t, jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :])


# --- 2d image rope (reference FusedRoPE2DFunc, fused_rope.py:214-305) --------

def fused_apply_rotary_pos_emb_2d(
    t: jax.Array,
    img_h: int,
    img_w: int,
    cos_h: jax.Array,
    sin_h: jax.Array,
    cos_w: jax.Array,
    sin_w: jax.Array,
) -> jax.Array:
    """2D RoPE on ``t`` [b, s, h, d] with ``s == img_h * img_w``:
    height-axis freqs rotate the first d/2 of the head dim, width-axis freqs
    the second (cos/sin_h [1, H≥img_h, 1, d//2], cos/sin_w [1, W≥img_w, 1, d//2])."""
    b, s, h, d = t.shape
    assert s == img_h * img_w, "sequence length must equal img_h * img_w"
    x = t.reshape(b, img_h, img_w, h, d)
    ch = cos_h[:, :img_h, None, :, :]   # [1, img_h, 1, 1, d//2]
    sh = sin_h[:, :img_h, None, :, :]
    cw = cos_w[:, None, :img_w, :, :]   # [1, 1, img_w, 1, d//2]
    sw = sin_w[:, None, :img_w, :, :]
    # two lane ranges of one row: each call passes the other's lanes through
    x = _apply_rope(_apply_rope(x, ch, sh), cw, sw, first=d // 2)
    return x.reshape(b, s, h, d)
