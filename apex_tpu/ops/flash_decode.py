"""Flash *decode*: single-query attention over a paged KV cache.

The serving-side sibling of ``ops/flash_attention.py``. Training
attention streams ``[block_q, block_k]`` score tiles of one contiguous
sequence; decode attention has exactly ONE query row per request (the
token being generated) and its keys/values live in fixed-size *pages*
scattered through a shared pool (``apex_tpu.serving.kv_cache``) — the
PagedAttention/vLLM layout. The kernel therefore grids over
``(slot, page)`` and runs the online-softmax recurrence *across page
blocks*: per slot a running row-max ``m``, normalizer ``l`` and value
accumulator are carried in VMEM scratch while each grid step loads one
page of K/V.

The page indirection uses Pallas **scalar prefetch**
(``pltpu.PrefetchScalarGridSpec``): the per-slot page table and kv
lengths are SMEM-prefetched so each grid step's BlockSpec index map can
point the K/V DMA at ``page_table[slot, i]`` — the pool page is fetched
directly, never gathered into a contiguous copy. Page-table entries past
a request's length MUST still be valid pool indices (the serving layer
points them at the reserved garbage page 0): the block is DMA'd either
way, and the compute is ``pl.when``-gated off for fully-invalid pages,
with in-page masking (``pos < kv_len``) for the ragged tail page.

Layouts (head-major pages — keeps the in-kernel dots transpose-free):

- ``q``        ``[n_slots, n_heads, head_dim]``
- ``k_pages``  ``[n_pages, n_heads, page_size, head_dim]``
- ``v_pages``  ``[n_pages, n_heads, page_size, head_dim]``
- ``page_table`` ``[n_slots, pages_per_seq]`` int32
- ``kv_lens``  ``[n_slots]`` int32 (valid tokens; 0 = inactive slot)

Rows with ``kv_lens == 0`` output zeros (the training kernels'
fully-masked-row convention, ``flash_attention.py``).

**Tensor parallelism** (``serving/engine.py``, TP engines): heads are a
pure batch dimension here — nothing in the grid, the online-softmax
recurrence, or the page DMA ever mixes two heads. A head-sharded pool
(``PagedKVSpec.shard(tp)``) therefore needs NO kernel changes: each
shard runs this identical kernel over its local ``n_heads / tp`` head
slice of q and of every page, and the per-head attention outputs are
already final (the cross-shard ``psum`` lives in the projection GEMM
tail that follows, not in attention).

Like ``packed_optimizer.py``, every entry point has an XLA fallback
(``use_kernel=False``, auto-selected off-TPU) computing identical fp32
math via a gather, and the kernel body runs under the Pallas interpreter
(``interpret=True``) so CPU tests exercise the real kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel_ok(use_kernel: Optional[bool], interpret: bool) -> bool:
    """Kernel path on TPU or when explicitly interpreted; XLA fallback
    elsewhere (the ``packed_optimizer.py`` selection contract)."""
    if use_kernel is not None:
        return bool(use_kernel)
    return bool(interpret) or jax.default_backend() == "tpu"


def page_sublanes(dtype) -> int:
    """Rows of one vreg tile for ``dtype``: 8 at 32 bits, 16 at 16 bits
    (bf16 packs two rows per sublane), 32 at 8 bits."""
    return 32 // jnp.dtype(dtype).itemsize


def flash_decode_available(page_size: int, head_dim: int,
                           dtype=jnp.float32) -> bool:
    """Kernel tileability: the page is the sublane dim of the K/V blocks,
    so it must be a whole number of ``dtype`` tiles (8 tokens of bf16 is
    half a 16 x 128 tile), and head_dim <= 256 (the
    ``flash_attention_available`` bound)."""
    return page_size % page_sublanes(dtype) == 0 and head_dim <= 256


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _decode_kernel(
    pt_ref, len_ref,  # scalar-prefetch: [b, mp] page table, [b] kv lens
    q_ref,            # [1, n, 1, d] this slot's query
    k_ref, v_ref,     # [1, n, ps, d] the page pt_ref[b, i]
    o_ref,            # [1, n, 1, d]
    m_scr, l_scr, acc_scr,  # [n, 1, 1], [n, 1, 1], [n, 1, d]
    *, scale, page_size, n_pages_per_seq,
):
    """One query row per head is a matrix-VECTOR product, so the scores
    and the value sum run on the VPU as broadcast-multiply-reduce. Every
    intermediate stays rank 3 ``[head, token, dim]`` with ``keepdims``
    reductions — heads on the untiled major dim, tokens on sublanes, dim
    on lanes, the K/V page's own layout — so nothing is relaid out
    between the page load and the accumulator."""
    b, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[b]

    # pages wholly past the sequence are skipped (their DMA still ran —
    # the table points them at the garbage page — but no flops/scratch)
    @pl.when(i * page_size < kv_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [n, 1, d]
        k = k_ref[0].astype(jnp.float32)                  # [n, ps, d]
        s = jnp.sum(q * k, axis=2, keepdims=True)         # [n, ps, 1]
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, _NEG_INF)

        m_prev = m_scr[:]                                 # [n, 1, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= _NEG_INF / 2, 0.0, p)          # ragged tail
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= _NEG_INF / 2, 0.0, alpha)
        # p is rounded to the pool dtype before it weighs V — the MXU
        # flash kernels' convention, kept so all paths agree
        p_v = p.astype(v_ref.dtype).astype(jnp.float32)
        pv = jnp.sum(p_v * v_ref[0].astype(jnp.float32), axis=1,
                     keepdims=True)                       # [n, 1, d]
        acc_scr[:] = acc_scr[:] * alpha + pv
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_new

    @pl.when(i == n_pages_per_seq - 1)
    def _finish():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        # kv_len == 0 slots never ran _compute: acc/l are zero -> zeros out
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def _decode_pallas(q, k_pages, v_pages, page_table, kv_lens, scale,
                   interpret):
    b, n, d = q.shape
    ps = k_pages.shape[2]
    mp = page_table.shape[1]
    kernel = functools.partial(
        _decode_kernel, scale=scale, page_size=ps, n_pages_per_seq=mp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((1, n, 1, d), lambda b, i, pt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, n, ps, d),
                         lambda b, i, pt, ln: (pt[b, i], 0, 0, 0)),
            pl.BlockSpec((1, n, ps, d),
                         lambda b, i, pt, ln: (pt[b, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, 1, d),
                               lambda b, i, pt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n, 1, 1), jnp.float32),
            pltpu.VMEM((n, 1, 1), jnp.float32),
            pltpu.VMEM((n, 1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="apex_tpu_flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
      q.reshape(b, n, 1, d), k_pages, v_pages)
    return out.reshape(b, n, d)


# ---------------------------------------------------------------------------
# XLA fallback / reference
# ---------------------------------------------------------------------------


def _decode_xla(q, k_pages, v_pages, page_table, kv_lens, scale):
    """Gather-based paged decode attention: identical math, O(b * mp * ps)
    gathered K/V copies (the fallback honesty note: the kernel exists to
    avoid exactly this materialisation)."""
    b, n, d = q.shape
    ps = k_pages.shape[2]
    mp = page_table.shape[1]
    k = k_pages[page_table]  # [b, mp, n, ps, d]
    v = v_pages[page_table]
    s = jnp.einsum(
        "bnd,bmnpd->bnmp", q.astype(jnp.float32) * scale,
        k.astype(jnp.float32), preferred_element_type=jnp.float32,
    ).reshape(b, n, mp * ps)
    pos = jnp.arange(mp * ps, dtype=jnp.int32)
    s = jnp.where(pos[None, None, :] < kv_lens[:, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    # fully-masked rows (kv_len == 0): zeros out, matching the kernel
    m_safe = jnp.where(m <= _NEG_INF / 2, 0.0, m)
    p = jnp.exp(s - m_safe)
    p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    ctx = jnp.einsum(
        "bnk,bnkd->bnd", p.astype(jnp.float32),
        v.astype(jnp.float32).transpose(0, 2, 1, 3, 4).reshape(
            b, n, mp * ps, d),
        preferred_element_type=jnp.float32,
    )
    return (ctx / jnp.maximum(l, 1.0e-37)).astype(q.dtype) * (
        l > 0.0).astype(q.dtype)


def paged_decode_reference(q, k_pages, v_pages, page_table, kv_lens,
                           scale=None):
    """Materialised reference (tests): dense softmax over the gathered
    pages with the zeros-for-empty-slots convention."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _decode_xla(q, k_pages, v_pages, page_table, kv_lens,
                       float(scale))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@jax.named_scope("apex_tpu.flash_decode")
def flash_decode(
    q: jax.Array,            # [n_slots, n_heads, head_dim]
    k_pages: jax.Array,      # [n_pages, n_heads, page_size, head_dim]
    v_pages: jax.Array,      # [n_pages, n_heads, page_size, head_dim]
    page_table: jax.Array,   # [n_slots, pages_per_seq] int32
    kv_lens: jax.Array,      # [n_slots] int32
    *,
    scale: Optional[float] = None,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-query paged attention: ``softmax(q @ K_pages^T * scale) @
    V_pages`` per slot, online-softmax across page blocks. Returns
    ``[n_slots, n_heads, head_dim]`` in ``q.dtype``.

    ``page_table[slot, i]`` is the pool index of the slot's i-th page;
    entries past ``ceil(kv_len / page_size)`` must still be valid pool
    indices (point them at the reserved garbage page — they are loaded
    but never read). Slots with ``kv_lens == 0`` return zeros.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if k_pages.shape != v_pages.shape:
        raise ValueError(
            f"k_pages {k_pages.shape} and v_pages {v_pages.shape} differ")
    if k_pages.shape[1] != q.shape[1] or k_pages.shape[3] != q.shape[2]:
        raise ValueError(
            f"pages [P, n, ps, d] = {k_pages.shape} do not match q "
            f"[b, n, d] = {q.shape}")
    # NO pool-level dtype cast: materializing a q.dtype copy of the
    # whole [P, n, ps, d] pool per call is exactly the O(pool) work the
    # paged design avoids. Both paths handle mixed dtypes themselves —
    # the kernel upcasts q/scores to fp32 in VMEM and dots bf16 K/V
    # blocks directly; the XLA fallback casts AFTER the gather.
    if not _kernel_ok(use_kernel, interpret):
        return _decode_xla(q, k_pages, v_pages, page_table,
                           kv_lens.astype(jnp.int32), float(scale))
    if not interpret and jax.default_backend() != "tpu":
        interpret = True
    if not flash_decode_available(k_pages.shape[2], q.shape[2],
                                  k_pages.dtype):
        raise ValueError(
            f"flash_decode kernel needs page_size {k_pages.shape[2]} % "
            f"{page_sublanes(k_pages.dtype)} == 0 ({k_pages.dtype} tiles) "
            f"and head_dim {q.shape[2]} <= 256 "
            "(use_kernel=False for the XLA fallback)")
    return _decode_pallas(q, k_pages, v_pages, page_table,
                          kv_lens.astype(jnp.int32), float(scale),
                          interpret)
