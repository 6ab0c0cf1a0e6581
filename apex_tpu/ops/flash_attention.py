"""Flash attention — tiled online-softmax Pallas TPU kernels, fwd + bwd.

TPU-native replacement for the reference's two fused-attention generations:
``apex/contrib/csrc/fmha/`` (~6k LoC CUDA, seq<=512, fp16, varlen) and
``apex/contrib/csrc/multihead_attn/`` (~9k LoC incl. ``softmax.cuh``).
Python consumers in the reference: ``apex/contrib/fmha/fmha.py:33-92`` and
``apex/contrib/multihead_attn/``.

Instead of the CUDA kernels' per-seqlen template instantiations, one tiled
kernel handles any sequence length: attention is computed in
``[block_q, block_k]`` score tiles with the online-softmax recurrence
(running row max ``m``, normalizer ``l``, rescaled accumulator), so the
full ``[b, n, s, s]`` score tensor is never materialised — O(s) memory per
row block instead of O(s^2) per head. Backward recomputes score tiles from
the saved logsumexp (the flash-attention-2 scheme): one kernel accumulates
dq over key blocks, a second accumulates dk/dv over query blocks, with
``delta = rowsum(dO * O)`` precomputed in XLA.

Layouts: one kernel body a pass (forward, ``bwd_dq``, ``bwd_dkv``) reads
q, k, v as two-dimensional ``[rows, lanes]`` arrays cut into ``[block, hp x
d]`` blocks, and which rows and lanes a block is follows from the entry
point (``_Layout``): head-major ``[b, n, s, d]`` via :func:`flash_attention`
(one head a block; the reference's API), batch-major ``[b, s, n, d]`` via
:func:`flash_attention_bshd` (what a projection GEMM over ``[b, s, hidden]``
writes and an output projection reads: ``hp`` heads side by side in 128
lanes, two at ``d = 64``; nothing is transposed, split or copied round the
kernels), Megatron's ``[s, b, n, d]`` via :func:`flash_attention_sbhd`
(batch-major after a swap of the first two axes), and the packed-varlen
layout ``[total, n, d]`` + ``cu_seqlens`` via :func:`flash_attention_varlen`
(the reference fmha's primary mode, ``contrib/fmha/fmha.py:33-92``;
batch-major with one batch row) — implemented with per-token segment ids so
tokens only attend within their own sequence.

Supports: causal masking (block-skipped: tiles strictly above the diagonal
are neither loaded nor computed; a causal square that is one tile is
walked as its lower triangle in chunks of rows, ``dense_walk_share``),
a key-padding mask ``[b, s_k]`` (True =
attend), an **additive logit bias** ``[b|1, n|1, s_q|1, s_k]`` streamed in
``[block_q, block_k]`` tiles (never fully VMEM-resident) with gradients —
the AlphaFold pair bias / ALiBi / T5 relative-position case, and the
capability behind the reference's openfold MHA
(``apex/contrib/openfold_triton/mha.py:133`` takes ``bias=``) and the
``multihead_attn`` additive-mask variants — softmax scale, and
**in-kernel attention dropout**: the keep mask
is a counter-based hash of ``(seed, head, global_q, global_k)`` computed in
plain vector ops inside each tile — the Philox analogue of the reference
``fmha``/``multihead_attn`` kernels — so the forward never materialises the
[s, s] probability tensor and the backward regenerates bit-identical masks
from the same counters (block-size independent, interpret-mode exact).

Fully-masked rows (a key-padding mask removing every key) output zeros with
``lse = -inf`` — NOT the uniform average a plain XLA softmax would produce
from an all ``-inf`` row; :func:`mha_reference` pins the same convention.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _pick_block(s: int, want: int) -> int:
    # Defaults (1024/1024) A/B-measured in-jit on v5e at seq 1024/d 64:
    # whole-sequence tiles beat 512/512 by ~20% fwd+bwd (per-program
    # overhead and the fp32 exp dominate; fewer, larger tiles win). VMEM
    # stays comfortable: a [1024, 1024] fp32 score tile is 4 MB.
    for cand in (want, 1024, 512, 256, 128, 64, 32, 16, 8):
        if cand <= want and s % cand == 0:
            return cand
    return s


def _lane_block(s: int, blk: int) -> int:
    """Constrain a block that lands on the LANE dim of a mask/segment/bias
    BlockSpec: Mosaic requires lane-dim block sizes to be a multiple of
    128 or equal to the whole array dim. Returns the divisor of ``s``
    among (128, 256, 512, 1024) closest to the requested block, else the
    whole dim (always legal)."""
    if blk % 128 == 0 or blk == s:
        return blk
    cands = [c for c in (128, 256, 512, 1024) if s % c == 0]
    if cands:
        return min(cands, key=lambda c: abs(c - blk))
    return s


def _sds(shape, dtype, *inputs):
    """ShapeDtypeStruct for a pallas_call output, carrying the union of
    the inputs' shard_map varying-manual-axes: under ``check_vma=True``
    (e.g. ring attention calling these kernels inside shard_map) pallas
    requires outputs to declare their vma explicitly."""
    vma = set()
    for x in inputs:
        vma |= set(getattr(getattr(x, "aval", None), "vma", None) or ())
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def require_kernel_tileable(s: int, d: int, context: str) -> None:
    """Raise the loud every-backend ValueError for shapes the Pallas
    kernels cannot tile (seq % 8, head dim <= 256) — shared by every
    caller that force-enables the kernels so the rule lives in one place."""
    if s % 8 != 0 or d > 256:
        raise ValueError(
            f"{context} needs kernel-tileable shapes "
            f"(seq {s} % 8 == 0 and head dim {d} <= 256)"
        )


def flash_attention_available(
    s_q: int, s_k: int, d: int, interpret: bool = False
) -> bool:
    """Availability heuristic (the analogue of the reference fmha's
    fp16/seq<=512 gate, ``contrib/fmha/fmha.py`` + ``fused_softmax.py``
    ``is_kernel_available``)."""
    if os.environ.get("APEX_TPU_DISABLE_FLASH"):
        return False
    if interpret:
        return True
    if jax.default_backend() != "tpu":
        return False
    # need tileable seq blocks and a head dim the MXU can use
    return s_q % 8 == 0 and s_k % 8 == 0 and d <= 256


# ---------------------------------------------------------------------------
# in-tile dropout mask: counter-based hash (murmur3 finalizer), keyed on
# (seed, batch*heads+head, global_q_index, global_k_index) — identical
# between forward and backward and independent of block sizes
# ---------------------------------------------------------------------------


def _i32(v):
    # constants given as unsigned patterns, reinterpreted int32 (wrapping
    # multiply has the same low-32 bits either way)
    return jnp.int32(v - 0x100000000 if v >= 0x80000000 else v)


def _shr_logical(x, n):
    return jax.lax.shift_right_logical(x, jnp.int32(n))


def _hash_keep_bits(seed, bh, qi, ki):
    """32-bit hash per (q, k) element, computed entirely in int32 with
    explicit logical shifts — Mosaic and the interpreter agree on these
    (uint32 shifts do not lower identically on TPU). ``qi``/``ki`` are
    int32 tiles of GLOBAL indices; ``seed`` an int32 scalar; ``bh`` the
    flattened batch-head index."""
    x = qi * _i32(0x9E3779B1)
    x = x ^ (ki * _i32(0x85EBCA77))
    x = x ^ (seed.astype(jnp.int32) + bh.astype(jnp.int32) * _i32(0x27D4EB2F))
    # murmur3 fmix32
    x = x ^ _shr_logical(x, 16)
    x = x * _i32(0x85EBCA6B)
    x = x ^ _shr_logical(x, 13)
    x = x * _i32(0xC2B2AE35)
    x = x ^ _shr_logical(x, 16)
    return x


def _keep_mask(seed, bh, qi, ki, dropout_p):
    """float32 {0,1} keep mask: P(drop) = dropout_p (unsigned compare of the
    hash bits against p·2^32, via the sign-flip trick)."""
    t = int(round(dropout_p * 4294967296.0)) & 0xFFFFFFFF
    # unsigned(a) >= unsigned(b)  <=>  (a ^ 0x80000000) >= (b ^ 0x80000000)
    thresh_flipped = _i32(t ^ 0x80000000)
    bits = _hash_keep_bits(seed, bh, qi, ki) ^ _i32(0x80000000)
    return (bits >= thresh_flipped).astype(jnp.float32)


def dropout_mask_reference(seed: int, b: int, n: int, s_q: int, s_k: int,
                           dropout_p: float) -> jax.Array:
    """The exact keep mask the kernels use, materialised (tests only)."""
    qi = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
    seed = jnp.int32(seed)
    masks = []
    for ib in range(b):
        row = []
        for ih in range(n):
            bh = jnp.int32(ib * n + ih)
            row.append(_keep_mask(seed, bh, qi, ki, dropout_p))
        masks.append(jnp.stack(row))
    return jnp.stack(masks)  # [b, n, s_q, s_k]


# ---------------------------------------------------------------------------
# shared tile masking
# ---------------------------------------------------------------------------


def _tile_indices(iq, ik, block_q, block_k, part=None):
    """Global ``(q, k)`` indices of tile ``(iq, ik)``, or of ``part``, the
    ``(first row, rows, keys)`` of it a chunk of the triangular walk reads
    (``_walk_chunks``)."""
    r0, rows, keys = part or (0, block_q, block_k)
    q0 = iq * block_q + r0 if r0 else iq * block_q
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
    ki = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
    return qi, ki


def _mask_scores(s, qi, ki, *, causal, have_mask, mask_ref, have_segs,
                 segq_ref, segk_ref, rows=slice(None), keys=slice(None)):
    """``rows`` / ``keys``: the part of the q / k block ``s`` covers."""
    if causal:
        s = jnp.where(ki > qi, _NEG_INF, s)
    if have_mask:
        keep = mask_ref[0, :, keys] != 0  # [1, bk]
        s = jnp.where(keep, s, _NEG_INF)
    if have_segs:
        seg_q = segq_ref[0, 0, rows][:, None]  # [bq, 1]
        seg_k = segk_ref[0, 0, keys][None, :]  # [1, bk]
        s = jnp.where(seg_q == seg_k, s, _NEG_INF)
    return s


# ---------------------------------------------------------------------------
# the dense kernels' layouts
# ---------------------------------------------------------------------------


def heads_per_block(n: int, d: int) -> int:
    """Heads that one lane block of the batch-major ``[b, s, n x d]`` layout
    holds side by side: one where ``d`` is a multiple of 128, ``128 // d``
    (two at 64, four at 32) where ``n`` divides into such groups, and 0
    where that layout cannot be cut into blocks the chip addresses (a
    block's lane width is a multiple of 128): ``d`` of neither kind, or
    one head of 64 on a tensor-parallel rank."""
    if d % 128 == 0:
        return 1
    if d in (32, 64) and n % (128 // d) == 0:
        return 128 // d
    return 0


class _Layout(NamedTuple):
    """How q, k, v, o and their gradients are cut into blocks. Every such
    operand is seen as a two-dimensional ``[rows, lanes]`` array (a reshape
    that moves nothing) and a kernel gets ``[block, hp x d]`` blocks of it,
    ``hp`` heads side by side in the lanes. By ``kind``:

    - ``"bnsd"``, head-major ``[b, n, s, d]`` as ``[b x n x s, d]``, one
      head a block;
    - ``"bshd"``, batch-major ``[b, s, n, d]`` as ``[b x s, n x d]``, the
      array a projection GEMM over ``[b, s, hidden]`` writes: a block is
      ``hp = heads_per_block(n, d)`` heads of one batch row;
    - ``"qkv"``, one array ``[b, s, 3, n, d]`` as ``[b x s, 3 x n x d]``,
      what ONE fused projection writes when the rows of its weight run
      ``[(q, k, v), head, d]``: q, k and v are three views of it (a block
      of ``hp`` heads of k lies ``n x d`` lanes after that of q), the
      context and ``do`` are batch-major.

    The grids walk ``(batch, block of heads, ., .)``."""
    b: int
    n: int
    d: int
    hp: int
    kind: str

    @classmethod
    def of(cls, q, kind):
        if kind == "bnsd":
            b, n, _, d = q.shape
            return cls(b, n, d, 1, kind)
        b, n, d = q.shape[0], q.shape[-2], q.shape[-1]
        hp = heads_per_block(n, d)
        if not hp:
            raise ValueError(
                f"{n} heads of {d} do not cut into 128-lane blocks: "
                "take the head-major layout")
        return cls(b, n, d, hp, kind)

    def seq(self, x):
        return x.shape[2] if self.kind == "bnsd" else x.shape[1]

    def flat(self, x):
        if self.kind == "bnsd":
            return x.reshape(-1, self.d)
        return x.reshape(x.shape[0] * x.shape[1], -1)

    def qkv(self, q, k, v):
        """The three flat operands, and where each one's blocks start in
        the lanes (in blocks; None: at the array's own first lane)."""
        if self.kind == "qkv":
            x = self.flat(q)
            return (x, x, x), (0, 1, 2)
        return (self.flat(q), self.flat(k), self.flat(v)), (None,) * 3

    def spec(self, rows, n_blocks, pick, part=None):
        """BlockSpec of a ``[rows, hp x d]`` block; ``pick(i2, i3)`` is the
        block's place along the sequence (of ``n_blocks``) from the grid's
        last two indices, ``part`` the view (q, k or v: 0, 1, 2) of a
        ``"qkv"`` array."""
        n, head_major = self.n, self.kind == "bnsd"
        first = 0 if part is None else part * (n // self.hp)

        def index(ib, ip, i2, i3):
            i = pick(i2, i3)
            if head_major:
                return (ib * n + ip) * n_blocks + i, 0
            return ib * n_blocks + i, first + ip

        return pl.BlockSpec((rows, self.hp * self.d), index)

    def row_spec(self, rows, pick):
        """Row statistics ``[b, n, s, 1]`` (lse, delta): ``[hp, rows, 1]``
        blocks, a head's column at ``ref[j]``."""
        return pl.BlockSpec(
            (None, self.hp, rows, 1),
            lambda ib, ip, i2, i3: (ib, ip, pick(i2, i3), 0))

    def row_stat(self, x):
        """``[b, n, s]`` from a per-head row reduction of ``o``'s layout."""
        return x if self.kind == "bnsd" else jnp.swapaxes(x, 1, 2)

    def context(self, s_q):
        """Shape of the context (and of ``do``)."""
        if self.kind == "bnsd":
            return self.b, self.n, s_q, self.d
        return self.b, s_q, self.n, self.d

    def flat_shape(self, s):
        """Of a flat operand that is not the ``"qkv"`` array."""
        if self.kind == "bnsd":
            return self.b * self.n * s, self.d
        return self.b * s, self.n * self.d


def _first(i2, i3):
    return i2


def _second(i2, i3):
    return i3


# ---------------------------------------------------------------------------
# the triangular walk: a causal square that is ONE tile (s <= 1024 at the
# default blocks: one grid step a batch row and block of heads) is walked
# in static chunks of rows inside the kernel body. Chunk r reads keys
# [0, (r+1) c): the sub-blocks above the diagonal, all -1e30 under the mask
# (their exp underflows to exactly 0), are not computed. The one-pass
# forward softmax and the fused backward (dq beside dk, dv) stay.
# ---------------------------------------------------------------------------


def _chunk_rows(bq: int) -> int:
    """Rows of a chunk of the triangular walk of a ``bq``-row tile: a
    quarter of it, at least 256 (``tools/flash_block_sweep.py walk`` on
    v5e, ``docs/flash_block_sweep.md``: at 1024 chunks of 256 beat 512
    and 128, whose narrower products slow the forward more than the 1/16
    of the square they save; at 512, chunks of 128 lose to the square)."""
    return max(bq // 4, 256)


def _walk_rows(s_q, s_k, bq, bk, causal, has_bias) -> int:
    """Rows of a chunk where the dense kernels walk the lower triangle of
    their tile, 0 where they walk the whole tile. The triangle engages on
    a causal square that is one tile (``s_q == s_k == bq == bk``) without
    a bias: the bias path keeps the square, its ``dbias`` tiles are owned
    ``(iq, ik)``."""
    if not causal or has_bias or not s_q == s_k == bq == bk:
        return 0
    c = _chunk_rows(bq)
    return c if c < bq and bq % c == 0 else 0


def _walk_chunks(bq, bk, walk):
    """``(first row, rows, keys)`` of each static chunk a kernel body walks
    in its tile: the whole tile as one chunk (``walk`` 0), or chunk ``r``
    rows ``[r walk, (r+1) walk)`` against keys ``[0, (r+1) walk)``."""
    if not walk:
        return ((0, bq, bk),)
    return tuple((r0, walk, r0 + walk) for r0 in range(0, bq, walk))


def dense_walk_share(s_q: int, s_k: int, block_q: int = 1024,
                     block_k: int = 1024, causal: bool = False,
                     has_bias: bool = False) -> float:
    """Share of the ``[s_q, s_k]`` score square the dense kernels compute a
    (batch row, head): ``(n_c + 1) / (2 n_c)`` where a one-tile causal
    square is walked as its lower triangle in ``n_c`` chunks (5/8 at
    1024), 1.0 everywhere else. Decided from the static shapes and
    arguments alone, at trace time."""
    bq, bk = _pick_block(s_q, block_q), _pick_block(s_k, block_k)
    return _walk_share(bq, bk, _walk_rows(s_q, s_k, bq, bk, causal,
                                          has_bias))


def _walk_share(bq, bk, walk):
    return sum(rows * keys for _, rows, keys in _walk_chunks(bq, bk, walk)
               ) / (bq * bk)


class _Tile(NamedTuple):
    """What the three dense kernel bodies share (all static)."""
    scale: float
    causal: bool
    block_q: int
    block_k: int
    n_heads: int
    d: int
    hp: int
    bias_heads: int     # heads in a bias block: hp, or 1 (broadcast over heads)
    have_bias: bool
    have_mask: bool
    have_segs: bool
    dropout_p: float
    walk: int           # rows of a chunk of the triangular walk; 0: the square

    @property
    def chunks(self):
        return _walk_chunks(self.block_q, self.block_k, self.walk)

    @property
    def share(self):
        """Of the tile's scores, the share the kernels compute."""
        return _walk_share(self.block_q, self.block_k, self.walk)

    def slices(self, part=None):
        """The rows of the q block and the keys of the k block a chunk
        (``part``, one of ``chunks``; None: the whole tile) covers."""
        r0, rows, keys = part or (0, self.block_q, self.block_k)
        return slice(r0, r0 + rows), slice(0, keys)


def _lanes_of(t: _Tile, j: int):
    """``[1, hp x d]`` bool, the lanes of a block's head ``j``; None where
    a block is one head."""
    if t.hp == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, t.hp * t.d), 1)
    return (lane >= j * t.d) & (lane < (j + 1) * t.d)


def _only(x, lanes):
    """``x [rows, hp x d]`` with every other head's lanes zeroed: a product
    that contracts over the lanes then sees one head, and one that writes
    them leaves the others' at zero. Selected in float32, where the
    scaling of q is done too."""
    if lanes is None:
        return x
    return jnp.where(lanes, x.astype(jnp.float32), 0.0).astype(x.dtype)


def _scaled_q(q, scale, lanes=None):
    """The softmax scale folded into the [bq, .] q block (16x cheaper than
    scaling the [bq, bk] score tile; fp32 mul before the cast keeps the
    rounding to one step), with the other heads' lanes of a shared block
    zeroed in the same pass. Shared by fwd/dq/dkv so the score computation
    cannot desynchronise between kernels.

    Numerics: for bf16 inputs the scaled q rounds back to bf16 BEFORE the
    MXU dot, a ~1-ulp-per-element divergence from designs that scale the
    fp32 score tile (fp32 q is scaled in fp32, so is exact). It is
    self-consistent across fwd/dq/dkv — lse/logits shift together — and
    sits well inside the bf16 attention test tolerances; flagging it here
    because it shifts lse by ~1e-3 vs a score-tile-scaled revision, which
    matters only if a test ever pins lse against an external oracle."""
    q32 = q.astype(jnp.float32) * scale
    if lanes is not None:
        q32 = jnp.where(lanes, q32, 0.0)
    return q32.astype(q.dtype)


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b
_TN = ((0,), (0,))      # a.T @ b


def _head_scores(t: _Tile, j, iq, ik, q_ref, k_ref, bias_ref, mask_ref,
                 segq_ref, segk_ref, part=None):
    """Head ``j`` of the block: its lanes, its scaled q (the other heads'
    lanes zero, so the 128-lane contraction is this head's), the masked
    float32 ``[bq, bk]`` scores and the tile's global indices — one
    implementation for the three kernels, so the score and mask semantics
    cannot desynchronise. ``part``, a chunk of ``t.chunks``: its rows of
    q against its keys alone (``[rows, keys]`` scores; no bias: the walk
    never takes one). Dots run in the INPUT dtype with fp32 accumulation:
    bf16 inputs hit the MXU's native rate."""
    rs, ks = t.slices(part)
    lanes = _lanes_of(t, j)
    q = _scaled_q(q_ref[rs], t.scale, lanes)
    s = _dot(q, k_ref[ks], _NT)
    if t.have_bias:
        s = s + bias_ref[0, j if t.bias_heads > 1 else 0].astype(jnp.float32)
    qi, ki = _tile_indices(iq, ik, t.block_q, t.block_k, part)
    s = _mask_scores(
        s, qi, ki, causal=t.causal, have_mask=t.have_mask, mask_ref=mask_ref,
        have_segs=t.have_segs, segq_ref=segq_ref, segk_ref=segk_ref,
        rows=rs, keys=ks)
    return lanes, q, s, qi, ki


def _probs(t: _Tile, s, m):
    """exp(s - m) with the fully-masked-row guard: a masked tile (or a
    bias row folded to -1e30) must contribute exactly zero (such rows have
    lse = -inf); on the pure-causal/unmasked hot path the -1e30 entries
    underflow exp to exact 0 already, so the extra [bq, bk] pass is
    skipped."""
    p = jnp.exp(s - m)
    if t.have_mask or t.have_segs or t.have_bias:
        p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
    return p


def _keep_scaled(t: _Tile, seed_ref, ib, ip, j, qi, ki):
    """The head's dropout keep mask times 1 / (1 - p), or None."""
    if t.dropout_p == 0.0:
        return None
    bh = ib * t.n_heads + ip * t.hp + j
    keep = _keep_mask(seed_ref[0], bh, qi, ki, t.dropout_p)
    return keep * (1.0 / (1.0 - t.dropout_p))


def _for_heads(t: _Tile, body):
    """``body(j)`` for every head ``j`` of a block: a loop the compiler
    keeps as one (a kernel's code is that of one head, whatever ``hp``)."""
    if t.hp == 1:
        body(0)
    else:
        jax.lax.fori_loop(0, t.hp, lambda j, c: (body(j), c)[1], 0)


def _write_lanes(ref, lanes, val, rows=slice(None)):
    """``val`` on the head's lanes of ``rows`` of the block in ``ref``; the
    other heads' lanes stay as they are (unwritten ones until their head's
    turn: every lane belongs to one head of the loop)."""
    if lanes is not None:
        val = jnp.where(lanes, val, ref[rows].astype(val.dtype))
    ref[rows] = val.astype(ref.dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, bias_ref, mask_ref, segq_ref, segk_ref, seed_ref,
    o_ref, lse_ref, *scratch, t, n_k,
):
    ib, ip = pl.program_id(0), pl.program_id(1)
    iq, ik = pl.program_id(2), pl.program_id(3)
    # single-k-block fast path: every (iq) sees its whole key range in one
    # tile, so the online-softmax recurrence (scratch buffers, running
    # m/l, alpha rescale, deferred finish) collapses to one direct
    # softmax — _fwd passes NO scratch in that case
    single = n_k == 1
    if not single:
        m_scr, l_scr, acc_scr = scratch

        @pl.when(ik == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def head(j, part=None):
        return _head_scores(t, j, iq, ik, q_ref, k_ref, bias_ref, mask_ref,
                            segq_ref, segk_ref, part)

    def pv(p, j, qi, ki):
        # softmax normalizer uses the UNDROPPED probabilities; dropout
        # hits only the value accumulation (standard attention-dropout
        # semantics: out = dropout(softmax(s)) @ v). The product fills
        # every head's lanes of the block; the caller keeps this head's.
        keep = _keep_scaled(t, seed_ref, ib, ip, j, qi, ki)
        if keep is not None:
            p = p * keep
        return _dot(p.astype(v_ref.dtype), v_ref[:p.shape[1]], _NN)

    def finish(j, lanes, acc, m, l, rows=slice(None)):
        safe_l = jnp.where(l == 0.0, 1.0, l)
        lse_ref[j, rows] = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(safe_l))
        _write_lanes(o_ref, lanes, acc / safe_l, rows)

    if single:
        # with n_k == 1 the (causal) tile skip never fires: ik == 0
        # always intersects the diagonal band of every q block. Each chunk
        # of rows sees its whole key range (keys past its last row are
        # masked, or, walking the triangle, not computed): one direct
        # softmax a chunk
        def direct(j):
            for part in t.chunks:
                lanes, _, s, qi, ki = head(j, part)
                m = jnp.max(s, axis=1, keepdims=True)
                p = _probs(t, s, m)
                l = jnp.sum(p, axis=1, keepdims=True)
                finish(j, lanes, pv(p, j, qi, ki), m, l, t.slices(part)[0])

        _for_heads(t, direct)
        return

    def online(j):
        lanes, _, s, qi, ki = head(j)
        m_prev = m_scr[j][:, :1]  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = _probs(t, s, m_new)
        alpha = jnp.exp(m_prev - m_new)
        if t.have_mask or t.have_segs or t.have_bias:
            alpha = jnp.where(m_prev <= _NEG_INF / 2, 0.0, alpha)
        l_new = alpha * l_scr[j][:, :1] + jnp.sum(p, axis=1, keepdims=True)
        _write_lanes(acc_scr, lanes, acc_scr[...] * alpha + pv(p, j, qi, ki))
        m_scr[j] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[j] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    if t.causal:
        # skip tiles strictly above the diagonal
        @pl.when(ik * t.block_k <= iq * t.block_q + (t.block_q - 1))
        def _():
            _for_heads(t, online)
    else:
        _for_heads(t, online)

    @pl.when(ik == n_k - 1)
    def _finish():
        _for_heads(t, lambda j: finish(
            j, _lanes_of(t, j), acc_scr[...], m_scr[j][:, :1],
            l_scr[j][:, :1]))


def _seg_args(segments, s):
    """(array, have) for an optional [s] / [b, s] int32 segment-id input
    (a dummy [1, 1, 8] array when absent — its BlockSpec pins block 0)."""
    have = segments is not None
    if have:
        arr = segments.astype(jnp.int32)
        if arr.ndim == 1:
            arr = arr[None]
        arr = arr.reshape(arr.shape[0], 1, s)
    else:
        arr = jnp.zeros((1, 1, 8), jnp.int32)
    return arr, have


def _bias_input(bias, bq, bk, hp, pick_q, pick_k):
    """(array, spec) for the optional additive-bias input ``[b|1, n|1,
    s_q|1, s_k]``; broadcast batch/head/row dims pin their block index to 0
    (a row-broadcast bias — e.g. an additive key-padding mask — streams
    [1, bk] tiles and broadcasts in-kernel). A block holds the bias of the
    ``hp`` heads of a q block (one where it is broadcast over heads)."""
    if bias is None:
        return jnp.zeros((1, 1, 8, 128), jnp.float32), pl.BlockSpec(
            (1, 1, 8, 128), lambda ib, ip, i2, i3: (0, 0, 0, 0))
    bb, bn, brow = bias.shape[0], bias.shape[1], bias.shape[2]

    def index(ib, ip, i2, i3):
        return (ib if bb > 1 else 0, ip if bn > 1 else 0,
                pick_q(i2, i3) if brow > 1 else 0, pick_k(i2, i3))

    return bias, pl.BlockSpec(
        (1, hp if bn > 1 else 1, bq if brow > 1 else 1, bk), index)


def _side_inputs(plan, bias, kv_mask, seg_q, seg_k, seed, kmajor):
    """Arrays and BlockSpecs of the bias, the key mask, the two segment-id
    rows and the dropout seed, for a grid whose last two indices are
    ``(iq, ik)`` (``kmajor``: ``(ik, iq)``, the dkv backward kernel's)."""
    (s_q, s_k), (bq, bk) = plan.seqs, plan.blocks
    b = plan.lay.b
    pick_q, pick_k = (_second, _first) if kmajor else (_first, _second)
    bias_arg, bias_spec = _bias_input(bias, bq, bk, plan.lay.hp, pick_q,
                                      pick_k)
    have_mask = kv_mask is not None
    mask_arg = (
        kv_mask.astype(jnp.int8).reshape(b, 1, s_k)
        if have_mask
        else jnp.zeros((b, 1, 8), jnp.int8)
    )
    mask_spec = pl.BlockSpec(
        (1, 1, bk if have_mask else 8),
        lambda ib, ip, i2, i3: (ib, 0, pick_k(i2, i3) if have_mask else 0))
    segq_arg, have_segs = _seg_args(seg_q, s_q)
    segk_arg, _ = _seg_args(seg_k, s_k)

    def seg_spec(arr, blk, pick):
        per_batch = have_segs and arr.shape[0] > 1
        return pl.BlockSpec(
            (1, 1, blk if have_segs else 8),
            lambda ib, ip, i2, i3: (ib if per_batch else 0, 0,
                                    pick(i2, i3) if have_segs else 0))

    seed_arg = jnp.asarray([seed if seed is not None else 0], jnp.int32)
    return (
        (bias_arg, mask_arg, segq_arg, segk_arg, seed_arg),
        [bias_spec, mask_spec, seg_spec(segq_arg, bq, pick_q),
         seg_spec(segk_arg, bk, pick_k),
         pl.BlockSpec(memory_space=pltpu.SMEM)],
    )


class _Plan(NamedTuple):
    """What ``_fwd`` and ``_bwd`` work out alike from their arguments."""
    lay: _Layout
    qkv: tuple          # the flat q, k, v operands
    parts: tuple        # where each one's blocks start (``_Layout.qkv``)
    seqs: tuple         # (s_q, s_k)
    blocks: tuple       # (bq, bk)
    tile: _Tile


def _plan(q, k, v, bias, kv_mask, seg_q, seg_k, scale, causal, dropout_p,
          block_q, block_k, interpret, kind) -> _Plan:
    if (seg_q is None) != (seg_k is None):
        raise ValueError("seg_q and seg_k must be provided together")
    lay = _Layout.of(q, kind)
    flat, parts = lay.qkv(q, k, v)
    s_q, s_k = lay.seq(q), lay.seq(q if k is None else k)
    bq = _pick_block(s_q, block_q)
    bk = _pick_block(s_k, block_k)
    if not interpret:
        # mask/seg/bias blocks put bq/bk on a lane dim (Mosaic: %128 or
        # whole-dim); interpret mode skips this so CPU tests can exercise
        # small multi-tile configs
        if seg_q is not None:
            bq = _lane_block(s_q, bq)
        if kv_mask is not None or bias is not None or seg_k is not None:
            bk = _lane_block(s_k, bk)
    tile = _Tile(
        scale=scale, causal=causal, block_q=bq, block_k=bk, n_heads=lay.n,
        d=lay.d, hp=lay.hp,
        bias_heads=lay.hp if bias is not None and bias.shape[1] > 1 else 1,
        have_bias=bias is not None, have_mask=kv_mask is not None,
        have_segs=seg_q is not None, dropout_p=dropout_p,
        walk=_walk_rows(s_q, s_k, bq, bk, causal, bias is not None))
    return _Plan(lay, flat, parts, (s_q, s_k), (bq, bk), tile)


def _fwd(
    q, k, v, bias, kv_mask, seg_q, seg_k, seed, scale, causal, dropout_p,
    block_q, block_k, interpret, kind="bnsd",
):
    """``(o, lse)`` of the dense forward kernel, ``q``, ``k``, ``v`` in the
    layout ``kind`` names (``_Layout``; ``"qkv"``: ``q`` is the one array,
    ``k`` and ``v`` None): ``o`` head-major ``[b, n, s, d]`` for
    ``"bnsd"``, else ``[b, s, n, d]``; ``lse`` float32 ``[b, n, s_q]``."""
    plan = _plan(q, k, v, bias, kv_mask, seg_q, seg_k, scale, causal,
                 dropout_p, block_q, block_k, interpret, kind)
    lay, parts, (s_q, s_k), (bq, bk) = (
        plan.lay, plan.parts, plan.seqs, plan.blocks)
    b, n, hp = lay.b, lay.n, lay.hp
    n_q, n_k = s_q // bq, s_k // bk
    side_args, side_specs = _side_inputs(
        plan, bias, kv_mask, seg_q, seg_k, seed, False)
    ins = plan.qkv + side_args
    o_shape = lay.context(s_q)
    # the single-k-block fast path (n_k == 1) runs a direct softmax with
    # NO recurrence scratch — keep that ~1.25 MB of VMEM per program free
    # for the data tiles
    scratch = [] if n_k == 1 else [
        pltpu.VMEM((hp, bq, 128), jnp.float32),
        pltpu.VMEM((hp, bq, 128), jnp.float32),
        pltpu.VMEM((bq, hp * lay.d), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, t=plan.tile, n_k=n_k),
        # stable kernel id: remat policies save these outputs by name
        # (standalone_transformer_lm._selective_policy)
        name="apex_tpu_flash_fwd",
        grid=(b, n // hp, n_q, n_k),
        in_specs=[
            lay.spec(bq, n_q, _first, parts[0]),
            lay.spec(bk, n_k, _second, parts[1]),
            lay.spec(bk, n_k, _second, parts[2]),
            *side_specs,
        ],
        out_specs=[lay.spec(bq, n_q, _first), lay.row_spec(bq, _first)],
        out_shape=[
            _sds(lay.flat_shape(s_q), q.dtype, *ins),
            _sds((b, n, s_q, 1), jnp.float32, *ins),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*ins)
    return o.reshape(o_shape), lse[..., 0]  # lse [b, n, s_q]


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref, mask_ref,
    segq_ref, segk_ref, seed_ref, dq_ref, *rest, t, n_k, emit_dbias,
):
    # with dbias: rest = (dbias_ref, acc_scr); without: rest = (acc_scr,)
    dbias_ref = rest[0] if emit_dbias else None
    acc_scr = rest[-1]
    ib, ip = pl.program_id(0), pl.program_id(1)
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    if emit_dbias:
        # each (iq, ik) block is visited exactly once; causal-skipped tiles
        # keep this zero fill
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    def head(j):
        lanes, _, s, qi, ki = _head_scores(
            t, j, iq, ik, q_ref, k_ref, bias_ref, mask_ref, segq_ref,
            segk_ref)
        p = _probs(t, s, lse_ref[j])
        dp = _dot(_only(do_ref[...], lanes), v_ref[...], _NT)
        keep = _keep_scaled(t, seed_ref, ib, ip, j, qi, ki)
        if keep is not None:
            dp = dp * keep
        ds = p * (dp - delta_ref[j])
        if emit_dbias:
            # d(logits): the bias enters the logits additively, so its
            # grad is ds itself (per [bq, bk] tile; broadcast dims summed
            # in XLA)
            dbias_ref[0, j] = ds.astype(dbias_ref.dtype)
        part = _dot(ds.astype(k_ref.dtype), k_ref[...], _NN) * t.scale
        acc_scr[...] += part if lanes is None else jnp.where(lanes, part, 0.0)

    if t.causal:
        @pl.when(ik * t.block_k <= iq * t.block_q + (t.block_q - 1))
        def _():
            _for_heads(t, head)
    else:
        _for_heads(t, head)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[...] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref, mask_ref,
    segq_ref, segk_ref, seed_ref, dk_ref, dv_ref, *rest, t, n_q,
    emit_dq=False,
):
    # with emit_dq (single-k-block fast path): rest = (dq_ref, dk_scr, dv_scr)
    # and delta_ref carries O itself (delta computed in-kernel)
    dq_ref = rest[0] if emit_dq else None
    dk_scr, dv_scr = rest[-2], rest[-1]
    ib, ip = pl.program_id(0), pl.program_id(1)
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def chunk(j, part):
        # q is scaled (the chain rule's *scale for dk rides in with it)
        # and, like do below, zero outside the head's lanes: what the two
        # accumulate lands on this head's lanes of dk and dv alone. A
        # chunk (t.chunks) is rows of the q block against its first keys
        lanes, q, s, qi, ki = _head_scores(
            t, j, iq, ik, q_ref, k_ref, bias_ref, mask_ref, segq_ref,
            segk_ref, part)
        rows, ks = t.slices(part)
        p = _probs(t, s, lse_ref[j, rows])
        do = _only(do_ref[rows], lanes)
        keep = _keep_scaled(t, seed_ref, ib, ip, j, qi, ki)
        p_d = p if keep is None else p * keep
        dv_scr[ks] += _dot(p_d.astype(do.dtype), do, _TN)       # p_d.T @ do
        dp = _dot(do, v_ref[ks], _NT)
        if keep is not None:
            dp = dp * keep
        if emit_dq:
            # delta_ref holds O: delta = rowsum(do * o) computed here, so
            # the XLA-side delta pass (+ its [.., 1] re-layout) disappears
            delta = jnp.sum(
                do.astype(jnp.float32) * delta_ref[rows].astype(jnp.float32),
                axis=1, keepdims=True,
            )
        else:
            delta = delta_ref[j, rows]
        ds = p * (dp - delta)  # [bq, bk]
        dk_scr[ks] += _dot(ds.astype(q.dtype), q, _TN)          # ds.T @ q
        if emit_dq:
            # single-k-block fast path (n_k == 1): every iq block is
            # visited exactly once and a chunk's rows see all their keys,
            # so dq = ds @ k * scale is complete here — the separate dq
            # kernel (a second score recompute, exp, and do@v.T) is
            # skipped entirely
            _write_lanes(
                dq_ref, lanes,
                _dot(ds.astype(k_ref.dtype), k_ref[ks], _NN) * t.scale, rows)

    def head(j):
        for part in t.chunks:
            chunk(j, part)

    if t.causal:
        @pl.when(ik * t.block_k <= iq * t.block_q + (t.block_q - 1))
        def _():
            _for_heads(t, head)
    else:
        _for_heads(t, head)

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[...] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(
    q, k, v, bias, kv_mask, seg_q, seg_k, seed, o, lse, do, scale, causal,
    dropout_p, block_q, block_k, interpret, bias_grad, kind="bnsd",
):
    """``(dq, dk, dv, dbias or None)`` in the layouts of ``q``, ``k``, ``v``
    (see :func:`_fwd`; ``"qkv"``: the one array's gradient, None, None;
    ``o`` and ``do`` in the context's layout); ``dbias`` comes out full
    ``[b, n, s_q, s_k]``."""
    plan = _plan(q, k, v, bias, kv_mask, seg_q, seg_k, scale, causal,
                 dropout_p, block_q, block_k, interpret, kind)
    lay, parts, (s_q, s_k), (bq, bk) = (
        plan.lay, plan.parts, plan.seqs, plan.blocks)
    (q2, k2, v2), tile = plan.qkv, plan.tile
    b, n, hp = lay.b, lay.n, lay.hp
    n_q, n_k = s_q // bq, s_k // bk
    # the dq kernel only emits the O(s^2) dbias buffer when the bias
    # actually needs a gradient (bias_grad=False: ALiBi slopes, folded
    # masks — constants whose cotangent would be discarded)
    emit_dbias = bias is not None and bias_grad
    # single-k-block fast path decided early: it also computes delta
    # in-kernel from O, skipping the XLA delta pass entirely
    fuse_dq = n_k == 1 and not emit_dbias

    # row stats as lane-dim-1 buffers (tiny DMA per block; the same layout
    # trick as ops/layer_norm.py's per-row stat blocks)
    lse_b = lse[..., None]
    if fuse_dq:
        delta_b = None
    else:
        delta_b = lay.row_stat(jnp.sum(
            do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
        ))[..., None]  # [b, n, s_q, 1]
    do2 = lay.flat(do)
    dtype = q.dtype

    # single-k-block fast path: with n_k == 1 every (iq) block is visited
    # exactly once by the dkv kernel, so dq = ds @ k completes in the same
    # pass — the separate dq kernel (a second score recompute + exp +
    # do@v.T) is skipped entirely. dbias emission keeps the two-kernel
    # path (its tile ownership is laid out (iq, ik)).
    dbias_full = None
    if not fuse_dq:
        side_args, side_specs = _side_inputs(
            plan, bias, kv_mask, seg_q, seg_k, seed, False)
        ins = (q2, k2, v2, do2, lse_b, delta_b) + side_args
        out_specs = [lay.spec(bq, n_q, _first)]
        out_shape = [_sds(lay.flat_shape(s_q), dtype, *ins)]
        if emit_dbias:
            # dbias comes out FULL [b, n, s_q, s_k] (each grid step owns
            # one (iq, ik) tile of its heads); broadcast input dims are
            # reduced by the caller. O(s^2) memory, but only on backward
            # and only when the bias itself is an input that needs a
            # gradient — the same cost torch autograd pays for an expanded
            # bias in the reference openfold kernels.
            out_specs.append(pl.BlockSpec(
                (1, hp, bq, bk), lambda ib, ip, iq, ik: (ib, ip, iq, ik)))
            out_shape.append(_sds((b, n, s_q, s_k), jnp.float32, *ins))
        dq_res = pl.pallas_call(
            functools.partial(
                _bwd_dq_kernel, t=tile, n_k=n_k,
                emit_dbias=emit_dbias),
            name="apex_tpu_flash_bwd_dq",
            grid=(b, n // hp, n_q, n_k),
            in_specs=[
                lay.spec(bq, n_q, _first, parts[0]),
                lay.spec(bk, n_k, _second, parts[1]),
                lay.spec(bk, n_k, _second, parts[2]),
                lay.spec(bq, n_q, _first),
                lay.row_spec(bq, _first),
                lay.row_spec(bq, _first),
                *side_specs,
            ],
            out_specs=out_specs if emit_dbias else out_specs[0],
            out_shape=out_shape if emit_dbias else out_shape[0],
            scratch_shapes=[pltpu.VMEM((bq, hp * lay.d), jnp.float32)],
            compiler_params=_compiler_params(),
            interpret=interpret,
        )(*ins)
        if emit_dbias:
            dq, dbias_full = dq_res
        else:
            dq = dq_res

    side_args, side_specs = _side_inputs(
        plan, bias, kv_mask, seg_q, seg_k, seed, True)
    # fused path: the delta slot carries O (delta computed in-kernel);
    # generic path: the precomputed row deltas
    ins = (q2, k2, v2, do2, lse_b,
           lay.flat(o) if fuse_dq else delta_b) + side_args
    out_specs = [lay.spec(bk, n_k, _first), lay.spec(bk, n_k, _first)]
    out_shape = [_sds(lay.flat_shape(s_k), dtype, *ins)] * 2
    if fuse_dq:
        out_specs.append(lay.spec(bq, n_q, _second))
        out_shape.append(_sds(lay.flat_shape(s_q), dtype, *ins))
    dkv_res = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, t=tile, n_q=n_q,
            emit_dq=fuse_dq),
        name="apex_tpu_flash_bwd_dkv",
        grid=(b, n // hp, n_k, n_q),
        in_specs=[
            lay.spec(bq, n_q, _second, parts[0]),
            lay.spec(bk, n_k, _first, parts[1]),
            lay.spec(bk, n_k, _first, parts[2]),
            lay.spec(bq, n_q, _second),
            lay.row_spec(bq, _second),
            lay.spec(bq, n_q, _second) if fuse_dq
            else lay.row_spec(bq, _second),
            *side_specs,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bk, hp * lay.d), jnp.float32),
            pltpu.VMEM((bk, hp * lay.d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*ins)
    if fuse_dq:
        dk, dv, dq = dkv_res
    else:
        dk, dv = dkv_res
    if kind == "qkv":
        # the one array's gradient: the three side by side, as its views lie
        dqkv = jnp.concatenate([dq, dk, dv], axis=-1).reshape(q.shape)
        return dqkv, None, None, dbias_full
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dbias_full)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


# The two outputs of a forward kernel that its backward kernels read, named
# where the custom VJPs' forward rules hand them on (``o`` [b, n, s, d] in the
# compute dtype, ``lse`` float32 [b, n, s]): a ``jax.checkpoint`` policy of
# ``save_only_these_names(*FLASH_RESIDUAL_NAMES)`` keeps them, and the
# replay in backward then holds no forward kernel (``recompute_granularity=
# "full"``, standalone_transformer_lm._remat). Under any other policy, and
# under none, a name is an identity that compiles to nothing.
FLASH_RESIDUAL_NAMES = ("apex_tpu_flash_o", "apex_tpu_flash_lse")


def _name_residuals(o, lse):
    name_o, name_lse = FLASH_RESIDUAL_NAMES
    return checkpoint_name(o, name_o), checkpoint_name(lse, name_lse)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14, 15)
)
def _flash(q, k, v, bias, kv_mask, segs, seed, scale, causal, dropout_p,
           block_q, block_k, interpret, bias_grad=True, bwd_blocks=None,
           kind="bnsd"):
    seg_q, seg_k = segs if segs is not None else (None, None)
    o, _ = _fwd(q, k, v, bias, kv_mask, seg_q, seg_k, seed, scale, causal,
                dropout_p, block_q, block_k, interpret, kind)
    return o


def _flash_fwd(q, k, v, bias, kv_mask, segs, seed, scale, causal, dropout_p,
               block_q, block_k, interpret, bias_grad=True, bwd_blocks=None,
               kind="bnsd"):
    seg_q, seg_k = segs if segs is not None else (None, None)
    o, lse = _name_residuals(*_fwd(
        q, k, v, bias, kv_mask, seg_q, seg_k, seed, scale, causal, dropout_p,
        block_q, block_k, interpret, kind,
    ))
    return o, (q, k, v, bias, kv_mask, segs, seed, o, lse)


def _flash_bwd(scale, causal, dropout_p, block_q, block_k, interpret,
               bias_grad, bwd_blocks, kind, res, do):
    q, k, v, bias, kv_mask, segs, seed, o, lse = res
    seg_q, seg_k = segs if segs is not None else (None, None)
    if bwd_blocks is not None:
        # fwd and bwd kernels have different optimal tiles (the fwd's
        # single-k-block fast path wants whole-sequence tiles; the
        # 5-matmul bwd wants smaller k tiles — see _bwd_block_table)
        block_q, block_k = bwd_blocks
    dq, dk, dv, dbias_full = _bwd(
        q, k, v, bias, kv_mask, seg_q, seg_k, seed, o, lse, do, scale,
        causal, dropout_p, block_q, block_k, interpret, bias_grad, kind,
    )
    dbias = None
    if bias is not None:
        if dbias_full is None:
            # bias_grad=False: a constant bias whose cotangent the caller
            # discards — return symbolic zeros without the O(s^2) buffer
            dbias = jnp.zeros(bias.shape, bias.dtype)
        else:
            dbias = dbias_full
            if bias.shape[0] == 1:
                dbias = dbias.sum(axis=0, keepdims=True)
            if bias.shape[1] == 1:
                dbias = dbias.sum(axis=1, keepdims=True)
            if bias.shape[2] == 1:
                dbias = dbias.sum(axis=2, keepdims=True)
            dbias = dbias.astype(bias.dtype)
    return dq, dk, dv, dbias, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# the banded path: causal attention with a sliding window, grouped K/V
# heads and/or a value width that is not the query/key width (the kernel
# bodies read every width off their blocks). The grid walks a list of the
# score tiles that touch the band
# ``0 <= i - j < window`` (scalar-prefetched: tile t is q block ``qi[t]``
# against k block ``ki[t]``), so a tile wholly outside the band costs no
# grid step and no DMA, in the forward and in both backward kernels. Query
# head ``h`` reads K/V head ``h // (n // n_kv)``; the dkv kernel walks each
# K/V head's group of query heads as part of its tile list, so dk and dv
# come out summed over the group.
# ---------------------------------------------------------------------------


def band_tiles(s: int, bq: int, bk: int, window: Optional[int]):
    """``(iq, ik)`` of every ``[bq, bk]`` score tile of an ``[s, s]`` causal
    attention that holds a pair with ``0 <= i - j < window`` (``window``
    ``None``: every ``j <= i``), q-major."""
    out = []
    for iq in range(s // bq):
        for ik in range(s // bk):
            if ik * bk > iq * bq + bq - 1:
                continue            # wholly above the diagonal
            if window is not None and (ik + 1) * bk <= iq * bq - window + 1:
                continue            # wholly older than the window
            out.append((iq, ik))
    return out


def _tile_lists(tiles, group: int = 1, kmajor: bool = False):
    """The int32 arrays a banded kernel prefetches: per grid step the q
    block, the k block, the query head within its K/V group, and whether
    the step opens / closes the accumulation of its row of tiles (q-major:
    one q block; k-major: one k block over every query head of the group)."""
    import numpy as np

    if kmajor:
        steps = sorted(((iq, ik, g) for iq, ik in tiles for g in range(group)),
                       key=lambda t: (t[1], t[2], t[0]))
        row = [t[1] for t in steps]
    else:
        steps = [(iq, ik, 0) for (iq, ik) in tiles]
        row = [t[0] for t in steps]
    n = len(steps)
    first = [int(i == 0 or row[i] != row[i - 1]) for i in range(n)]
    last = [int(i == n - 1 or row[i] != row[i + 1]) for i in range(n)]
    cols = list(zip(*steps)) + [first, last]
    return [jnp.asarray(np.asarray(c, np.int32)) for c in cols]


def _band_mask(s, qi, ki, window):
    s = jnp.where(ki > qi, _NEG_INF, s)
    if window is not None:
        s = jnp.where(qi - ki >= window, _NEG_INF, s)
    return s


def _band_scores(q_ref, k_ref, iq, ik, *, scale, window, block_q, block_k):
    q = _scaled_q(q_ref[0, 0], scale)
    s = jax.lax.dot_general(
        q, k_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    qi, ki = _tile_indices(iq, ik, block_q, block_k)
    return q, _band_mask(s, qi, ki, window)


def _band_fwd_kernel(
    qi_ref, ki_ref, g_ref, first_ref, last_ref, q_ref, k_ref, v_ref,
    o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, window, block_q,
    block_k,
):
    t = pl.program_id(2)

    @pl.when(first_ref[t] == 1)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    _, s = _band_scores(q_ref, k_ref, qi_ref[t], ki_ref[t], scale=scale,
                        window=window, block_q=block_q, block_k=block_k)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # a row's window may not reach into the first tile of its band: such a
    # row is all -1e30 here and must add exactly nothing
    p = jnp.where(s <= _NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(last_ref[t] == 1)
    def _finish():
        # every row holds its own diagonal key, so l > 0
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(l)


def _band_dq_kernel(
    qi_ref, ki_ref, g_ref, first_ref, last_ref, q_ref, k_ref, v_ref, do_ref,
    lse_ref, delta_ref, dq_ref, acc_scr, *, scale, window, block_q, block_k,
):
    t = pl.program_id(2)

    @pl.when(first_ref[t] == 1)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    _, s = _band_scores(q_ref, k_ref, qi_ref[t], ki_ref[t], scale=scale,
                        window=window, block_q=block_q, block_k=block_k)
    p = jnp.exp(s - lse_ref[0, 0][:, :1])
    dp = jax.lax.dot_general(
        do_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_ref[0, 0][:, :1])
    acc_scr[:] += jax.lax.dot_general(
        ds.astype(k_ref.dtype), k_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale

    @pl.when(last_ref[t] == 1)
    def _finish():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _band_dkv_kernel(
    qi_ref, ki_ref, g_ref, first_ref, last_ref, q_ref, k_ref, v_ref, do_ref,
    lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, window,
    block_q, block_k,
):
    t = pl.program_id(2)

    @pl.when(first_ref[t] == 1)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q, s = _band_scores(q_ref, k_ref, qi_ref[t], ki_ref[t], scale=scale,
                        window=window, block_q=block_q, block_k=block_k)
    p = jnp.exp(s - lse_ref[0, 0][:, :1])
    do = do_ref[0, 0]
    dv_scr[:] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_ref[0, 0][:, :1])
    # the chain rule's *scale rode in with the scaled q
    dk_scr[:] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(last_ref[t] == 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _band_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _band_fwd(q, k, v, scale, window, block_q, block_k, interpret):
    b, n, s, d = q.shape
    dv = v.shape[-1]            # the value's width, and the context's
    group = n // k.shape[1]
    bq, bk = _pick_block(s, block_q), _pick_block(s, block_k)
    lists = _tile_lists(band_tiles(s, bq, bk, window))
    q_map = lambda ib, ih, t, qi, ki, g, f, l: (ib, ih, qi[t], 0)
    k_map = lambda ib, ih, t, qi, ki, g, f, l: (ib, ih // group, ki[t], 0)
    o, lse = pl.pallas_call(
        functools.partial(_band_fwd_kernel, scale=scale, window=window,
                          block_q=bq, block_k=bk),
        name="apex_tpu_flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(b, n, int(lists[0].shape[0])),
            in_specs=[pl.BlockSpec((1, 1, bq, d), q_map),
                      pl.BlockSpec((1, 1, bk, d), k_map),
                      pl.BlockSpec((1, 1, bk, dv), k_map)],
            out_specs=[pl.BlockSpec((1, 1, bq, dv), q_map),
                       pl.BlockSpec((1, 1, bq, 1), q_map)],
            scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, dv), jnp.float32)]),
        out_shape=[_sds((b, n, s, dv), q.dtype, q, k, v),
                   _sds((b, n, s, 1), jnp.float32, q, k, v)],
        compiler_params=_band_params(),
        interpret=interpret,
    )(*lists, q, k, v)
    # the kernel writes lse as [b, n, s, 1], one valid lane of 128 in HBM:
    # what is handed on is the lane-dense [b, n, s], as _fwd's
    return o, lse[..., 0]


def _band_bwd(q, k, v, o, lse, do, scale, window, block_q, block_k,
              interpret):
    b, n, s, d = q.shape
    dv = v.shape[-1]
    n_kv = k.shape[1]
    group = n // n_kv
    bq, bk = _pick_block(s, block_q), _pick_block(s, block_k)
    tiles = band_tiles(s, bq, bk, window)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    lse = lse[..., None]        # row statistics as lane-dim-1 blocks
    kw = dict(scale=scale, window=window, block_q=bq, block_k=bk)

    lists = _tile_lists(tiles)
    q_map = lambda ib, ih, t, qi, ki, g, f, l: (ib, ih, qi[t], 0)
    k_map = lambda ib, ih, t, qi, ki, g, f, l: (ib, ih // group, ki[t], 0)
    dq = pl.pallas_call(
        functools.partial(_band_dq_kernel, **kw),
        name="apex_tpu_flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(b, n, int(lists[0].shape[0])),
            in_specs=[pl.BlockSpec((1, 1, bq, d), q_map),
                      pl.BlockSpec((1, 1, bk, d), k_map),
                      pl.BlockSpec((1, 1, bk, dv), k_map),
                      pl.BlockSpec((1, 1, bq, dv), q_map),
                      pl.BlockSpec((1, 1, bq, 1), q_map),
                      pl.BlockSpec((1, 1, bq, 1), q_map)],
            out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)]),
        out_shape=_sds(q.shape, q.dtype, q, k, v, do),
        compiler_params=_band_params(),
        interpret=interpret,
    )(*lists, q, k, v, do, lse, delta)

    lists = _tile_lists(tiles, group, kmajor=True)
    q_map = lambda ib, ih, t, qi, ki, g, f, l: (
        ib, ih * group + g[t], qi[t], 0)
    k_map = lambda ib, ih, t, qi, ki, g, f, l: (ib, ih, ki[t], 0)
    dk, dv = pl.pallas_call(
        functools.partial(_band_dkv_kernel, **kw),
        name="apex_tpu_flash_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(b, n_kv, int(lists[0].shape[0])),
            in_specs=[pl.BlockSpec((1, 1, bq, d), q_map),
                      pl.BlockSpec((1, 1, bk, d), k_map),
                      pl.BlockSpec((1, 1, bk, dv), k_map),
                      pl.BlockSpec((1, 1, bq, dv), q_map),
                      pl.BlockSpec((1, 1, bq, 1), q_map),
                      pl.BlockSpec((1, 1, bq, 1), q_map)],
            out_specs=[pl.BlockSpec((1, 1, bk, d), k_map),
                       pl.BlockSpec((1, 1, bk, dv), k_map)],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, dv), jnp.float32)]),
        out_shape=[_sds(k.shape, k.dtype, q, k, v, do),
                   _sds(v.shape, v.dtype, q, k, v, do)],
        compiler_params=_band_params(),
        interpret=interpret,
    )(*lists, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_band(q, k, v, scale, window, block_q, block_k, interpret):
    return _band_fwd(q, k, v, scale, window, block_q, block_k, interpret)[0]


def _flash_band_fwd(q, k, v, scale, window, block_q, block_k, interpret):
    o, lse = _name_residuals(
        *_band_fwd(q, k, v, scale, window, block_q, block_k, interpret))
    return o, (q, k, v, o, lse)


def _flash_band_bwd(scale, window, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _band_bwd(q, k, v, o, lse, do, scale, window, block_q, block_k,
                     interpret)


_flash_band.defvjp(_flash_band_fwd, _flash_band_bwd)


def _resolve_seed(dropout_p, dropout_seed):
    if not 0.0 <= dropout_p < 1.0:
        # out-of-range p would wrap the 32-bit keep threshold silently
        raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
    if dropout_p == 0.0:
        return None
    if dropout_seed is None:
        raise ValueError(
            "dropout_p > 0 requires dropout_seed (an int or int32 scalar; "
            "derive a fresh one per step, e.g. from jax.random.randint)"
        )
    return jnp.asarray(dropout_seed, jnp.int32)


def _bwd_block_table(s_q, s_k, d, block_q, block_k):
    """Measured per-shape bwd tile choice (v5e sweep, see
    ``tools/flash_block_sweep.py``; VERDICT r4 #8).

    The measured answer is that the fwd tile choice is also right for
    the bwd: whole-sequence tiles keep the single-k-block fused path
    (dq emitted from the dkv kernel, delta in-kernel), which beat every
    split-tile variant in-model (0.99 vs 1.43 ms/layer at the 345M
    bench shape — the split path pays a second score recompute in the
    separate dq kernel plus the XLA delta pass). Causality is no reason
    to split: a causal whole-sequence tile walks its lower triangle
    inside the body (``_walk_rows``), so it costs less than a
    non-causal one where smaller grid tiles would bring the second
    recompute back. A standalone kernel-only sweep that differentiates
    w.r.t. q alone will tell you otherwise (0.61 ms): XLA
    dead-code-eliminates the dkv kernel there; don't trust it. The hook
    stays so a future chip/shape can diverge fwd and bwd tiles without
    an API change.
    """
    return (block_q, block_k)


@jax.named_scope("apex_tpu.flash_attention")
def flash_attention(
    q: jax.Array,  # [b, n, s_q, d]
    k: jax.Array,  # [b, n, s_k, d]
    v: jax.Array,  # [b, n, s_k, d]
    *,
    causal: bool = False,
    window: Optional[int] = None,  # causal only: keys with 0 <= i - j < window
    kv_mask: Optional[jax.Array] = None,  # [b, s_k]; True/nonzero = attend
    bias: Optional[jax.Array] = None,  # [b|1, n|1, s_q|1, s_k] logit bias
    bias_grad: bool = True,
    scale: Optional[float] = None,
    dropout_p: float = 0.0,
    dropout_seed=None,  # int or int32 scalar; required when dropout_p > 0
    block_q: int = 1024,
    block_k: int = 1024,
    bwd_block_q: Optional[int] = None,  # None = measured per-shape table
    bwd_block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Tiled online-softmax attention, O(s) memory per row block.

    Returns ``dropout(softmax(q @ k.T * scale + bias [masked])) @ v`` in
    ``q.dtype`` without materialising the score tensor. Differentiable
    (custom VJP recomputes score tiles from the saved logsumexp; the
    dropout mask is regenerated in-kernel from the same hash counters).

    ``bias`` is an additive logit bias (AlphaFold pair bias / ALiBi / T5
    relative positions; the reference openfold MHA's ``bias=`` argument,
    ``apex/contrib/openfold_triton/mha.py:133``): batch/head dims may be 1
    (broadcast). It is streamed tile-by-tile in the forward; its gradient
    materialises one fp32 ``[b, n, s_q, s_k]`` buffer in the backward
    (reduced over broadcast dims). Pass ``bias_grad=False`` for a constant
    bias (ALiBi slopes, a folded mask): the backward then skips the O(s^2)
    dbias emission entirely and the bias cotangent is zeros.

    ``window`` (a sliding window: keys with ``0 <= i - j < window``) and
    grouped K/V heads (``k``, ``v`` of ``n_kv`` heads, ``n % n_kv == 0``;
    query head ``h`` reads head ``h // (n // n_kv)``; ``dk``/``dv`` summed
    over the group) take the banded kernels, which walk only the score
    tiles that touch the band: causal self-attention without bias, mask or
    dropout. So does a **value width of its own** (``v [b, n_kv, s, dv]``
    with ``dv != d``, latent attention's 128 beside a query/key width of
    192): scores contract over ``d``; the context, ``do`` and ``dv`` are
    ``dv`` wide, ``dq`` and ``dk`` ``d`` wide, each block at its own width
    (nothing is padded in HBM).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if (window is not None or k.shape[1] != q.shape[1]
            or v.shape[-1] != q.shape[-1]):
        return _banded(q, k, v, causal, window, kv_mask, bias, scale,
                       dropout_p, block_q, block_k, interpret)
    return _dense(
        q, k, v, "bnsd", causal=causal, kv_mask=kv_mask, bias=bias,
        bias_grad=bias_grad, scale=scale, dropout_p=dropout_p,
        dropout_seed=dropout_seed, block_q=block_q, block_k=block_k,
        bwd_block_q=bwd_block_q, bwd_block_k=bwd_block_k,
        interpret=interpret)


def _dense(q, k, v, kind, *, causal=False, kv_mask=None, bias=None,
           bias_grad=True, scale=None, dropout_p=0.0, dropout_seed=None,
           block_q=1024, block_k=1024, bwd_block_q=None, bwd_block_k=None,
           interpret=False):
    """The checks of the dense kernels, and their call: ``q``, ``k``, ``v``
    in the layout ``kind`` names (``_Layout``); the context comes back
    head-major for ``"bnsd"``, else batch-major ``[b, s, n, d]``."""
    lay = _Layout.of(q, kind)
    s_q, s_k = lay.seq(q), lay.seq(q if k is None else k)
    if scale is None:
        scale = 1.0 / (lay.d ** 0.5)
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.int8)
    if bias is not None:
        b, n = lay.b, lay.n
        if (bias.ndim != 4 or bias.shape[0] not in (1, b)
                or bias.shape[1] not in (1, n)
                or bias.shape[2] not in (1, s_q)
                or bias.shape[3] != s_k):
            raise ValueError(
                f"bias shape {bias.shape} must be [b|1, n|1, s_q|1, s_k] = "
                f"[{b}|1, {n}|1, {s_q}|1, {s_k}]"
            )
        # a [1024, 1024] fp32 score tile + bias tile + dbias tile would
        # crowd VMEM; cap blocks at 512 when a bias is present (256 where
        # a block holds the bias and dbias tiles of several heads)
        cap = 512 if lay.hp == 1 else 256
        block_q = min(block_q, cap)
        block_k = min(block_k, cap)
        if bwd_block_q is not None:
            bwd_block_q = min(bwd_block_q, cap)
        if bwd_block_k is not None:
            bwd_block_k = min(bwd_block_k, cap)
    if bwd_block_q is None and bwd_block_k is None:
        bwd_blocks = _bwd_block_table(s_q, s_k, lay.d, block_q, block_k)
    else:
        bwd_blocks = (bwd_block_q or block_q, bwd_block_k or block_k)
    seed = _resolve_seed(dropout_p, dropout_seed)
    # kernel dots run in the operand dtype (MXU-native); normalise mixed
    # inputs to q's dtype so e.g. (fp32 q, bf16 k/v) still compiles
    if k is not None:
        k, v = k.astype(q.dtype), v.astype(q.dtype)
    # off-TPU the kernel runs in the Pallas interpreter (tests exercise the
    # same code path the TPU compiles)
    if not interpret and jax.default_backend() != "tpu":
        interpret = True
    return _flash(
        q, k, v, bias, kv_mask, None, seed, float(scale), bool(causal),
        float(dropout_p), int(block_q), int(block_k), bool(interpret),
        bool(bias_grad), tuple(int(x) for x in bwd_blocks), kind,
    )


def _banded(q, k, v, causal, window, kv_mask, bias, scale, dropout_p,
            block_q, block_k, interpret):
    """The checks of the banded path, and its call."""
    n, n_kv = q.shape[1], k.shape[1]
    if not causal or q.shape[2] != k.shape[2]:
        raise ValueError(
            "a window, grouped K/V heads or a value width of its own need "
            f"causal self-attention (causal={causal}, s_q={q.shape[2]}, "
            f"s_k={k.shape[2]})")
    if kv_mask is not None or bias is not None or dropout_p:
        raise ValueError(
            "the banded flash kernels (window / grouped K/V heads / a value "
            "width of its own) take no kv_mask, bias or dropout")
    if k.shape[-1] != q.shape[-1]:
        raise ValueError(
            f"q is {q.shape[-1]} wide and k {k.shape[-1]}: scores contract "
            "over one width (v alone may have its own)")
    if n % n_kv or v.shape[1] != n_kv:
        raise ValueError(
            f"{n} query heads do not divide into {n_kv} K/V heads "
            f"(v has {v.shape[1]})")
    if window is not None and window < 1:
        raise ValueError(f"window must be at least 1, got {window}")
    if window is not None and window >= q.shape[2]:
        window = None           # every j <= i is inside it: plain causal
    if not interpret and jax.default_backend() != "tpu":
        interpret = True
    return _flash_band(
        q, k.astype(q.dtype), v.astype(q.dtype), float(scale),
        None if window is None else int(window), int(block_q), int(block_k),
        bool(interpret))


@jax.named_scope("apex_tpu.flash_attention")
def flash_attention_bshd(
    q: jax.Array,  # [b, s_q, n, d]
    k: jax.Array,  # [b, s_k, n, d]
    v: jax.Array,
    **kw,
) -> jax.Array:
    """:func:`flash_attention` over batch-major ``[b, s, n, d]``, the
    array a projection over ``[b, s, hidden]`` writes once its last axis
    is seen as ``[n, d]``: context ``[b, s, n, d]``, as an output
    projection reads it.

    The dense kernels read these arrays where they lie: no transpose, copy
    or split of q, k, v, the context or their gradients, in forward,
    backward or replay. A block is ``heads_per_block(n, d)`` heads of one
    batch row side by side in 128 lanes (two at ``d = 64``); a kernel
    zeroes the other heads' lanes of q (of ``do``) before a product that
    contracts over the lanes, and keeps its own lanes of one that writes
    them.

    Where the heads do not cut into 128-lane blocks (``heads_per_block``
    is 0: one head of 64 on a tensor-parallel rank, ``d`` of 96), and for
    a ``window``, grouped K/V heads or a value width of its own (the banded
    kernels are head-major), the arguments are transposed to ``[b, n, s,
    d]`` and the context back, by XLA."""
    n, d = q.shape[2], q.shape[3]
    window = kw.pop("window", None)
    if (window is not None or k.shape[2] != n or v.shape[3] != d
            or not heads_per_block(n, d)):
        o = flash_attention(
            *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), window=window, **kw)
        return jnp.swapaxes(o, 1, 2)
    return _dense(q, k, v, "bshd", **kw)


@jax.named_scope("apex_tpu.flash_attention")
def flash_attention_qkv(qkv: jax.Array, **kw) -> jax.Array:
    """Self-attention over ONE array ``[b, s, 3, n, d]``: what a fused
    q / k / v projection over ``[b, s, hidden]`` writes when the rows of
    its weight run ``[(q, k, v), head, d]``. Context ``[b, s, n, d]``.

    The kernels take the array three times under three index maps (q, k
    and v of a block of heads lie ``n x d`` lanes apart): nothing is split
    or copied in forward or replay, and one GEMM serves all three. In
    backward the kernels write ``dq``, ``dk`` and ``dv`` and XLA puts them
    side by side as the array's gradient, the one copy this entry point
    costs. Otherwise as :func:`flash_attention_bshd`, its fallback
    included (the three parts are then sliced out first)."""
    n, d = qkv.shape[3], qkv.shape[4]
    if kw.get("window") is not None or not heads_per_block(n, d):
        return flash_attention_bshd(*(qkv[:, :, i] for i in range(3)), **kw)
    kw.pop("window", None)
    return _dense(qkv, None, None, "qkv", **kw)


def flash_attention_sbhd(
    q: jax.Array,  # [s, b, n, d]
    k: jax.Array,
    v: jax.Array,
    **kw,
) -> jax.Array:
    """Megatron ``[s, b, n, d]`` in, context ``[s, b, n, d]`` out, through
    :func:`flash_attention_bshd`: the first two axes of each argument and
    of the context are swapped by XLA (rows of ``n x d`` lanes move whole;
    no head is cut out of its row), which a caller that holds ``[b, s,
    hidden]`` avoids by calling ``flash_attention_bshd`` itself
    (``parallel_attention`` does). Where that function falls back to the
    head-major kernels the two moves compose into one transpose to
    ``[b, n, s, d]``."""
    o = flash_attention_bshd(
        *(jnp.swapaxes(x, 0, 1) for x in (q, k, v)), **kw)
    return jnp.swapaxes(o, 0, 1)


def segment_ids_from_cu_seqlens(cu_seqlens: jax.Array, total: int) -> jax.Array:
    """[total] int32 segment ids from ``cu_seqlens`` [b+1] (monotone,
    ``cu_seqlens[0] == 0``). Tokens past ``cu_seqlens[-1]`` get id ``b``
    (a padding segment that only attends to itself)."""
    pos = jnp.arange(total, dtype=jnp.int32)
    return jnp.searchsorted(
        cu_seqlens.astype(jnp.int32)[1:], pos, side="right"
    ).astype(jnp.int32)


def flash_attention_varlen(
    q: jax.Array,  # [total, n, d] packed tokens
    k: jax.Array,
    v: jax.Array,
    cu_seqlens: jax.Array,  # [b+1] cumulative sequence starts, cu[0] == 0
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_p: float = 0.0,
    dropout_seed=None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Packed variable-length self-attention — the reference fmha's primary
    mode (``apex/contrib/fmha/fmha.py:33-92``: qkv ``[total, ...]`` +
    ``cu_seqlens``, seq<=512 fp16; here any length/dtype).

    Tokens attend only within their own sequence (per-token segment ids
    derived from ``cu_seqlens``; causal uses the packed global order, which
    equals local order inside each contiguous segment). O(total) memory —
    no padding to ``[b, s_max]`` and no [s, s] score tensor.
    """
    total, n, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    seed = _resolve_seed(dropout_p, dropout_seed)
    segs = segment_ids_from_cu_seqlens(cu_seqlens, total)
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    # packed tokens are batch-major with one batch row: [1, total, n, d].
    # Where the heads do not cut into 128-lane blocks: head-major.
    kind = "bshd" if heads_per_block(n, d) else "bnsd"
    if kind == "bshd":
        qb, kb, vb = q[None], k[None], v[None]
    else:
        qb, kb, vb = (x.transpose(1, 0, 2)[None] for x in (q, k, v))
    if not interpret and jax.default_backend() != "tpu":
        interpret = True
    o = _flash(
        qb, kb, vb, None, None, (segs, segs), seed, float(scale),
        bool(causal), float(dropout_p), int(block_q), int(block_k),
        bool(interpret), True, None, kind,
    )
    return o[0] if kind == "bshd" else o[0].transpose(1, 0, 2)


def masked_scores(q, k, kv_mask, causal, scale, bias=None,
                  window=None) -> jax.Array:
    """Dense fp32 ``[b, n, s_q, s_k]`` logits with the kernels' exact
    masking conventions (scale -> +bias -> causal/kv_mask as ``_NEG_INF``
    fills). Shared by :func:`mha_reference` and the context-parallel
    interpret path so the conventions cannot drift."""
    s = jnp.einsum(
        "bnqd,bnkd->bnqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        sq, sk = s.shape[-2:]
        qi = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(ki > qi, _NEG_INF, s)
        if window is not None:
            s = jnp.where(qi - ki >= window, _NEG_INF, s)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :] != 0, s, _NEG_INF)
    return s


def mha_reference(
    q, k, v, *, causal=False, kv_mask=None, bias=None, scale=None,
    dropout_p=0.0, dropout_seed=None, window=None,
) -> jax.Array:
    """Materialised-score reference (for tests): same math, O(s^2) — incl.
    the kernels' exact hash-dropout mask and the zeros-for-fully-masked-rows
    convention. Grouped K/V heads are repeated to the query heads; ``v``
    may have a width of its own (the context's)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if k.shape[1] != q.shape[1]:
        group = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = masked_scores(q, k, kv_mask, causal, scale, bias, window)
    p = jax.nn.softmax(s, axis=-1)
    # zeros-for-fully-masked-rows (flash kernel convention): a row whose
    # keys are all masked outputs 0, not the uniform average softmax yields
    row_alive = jnp.any(s > _NEG_INF / 2, axis=-1, keepdims=True)
    p = jnp.where(row_alive, p, 0.0)
    seed = _resolve_seed(dropout_p, dropout_seed)
    if seed is not None:
        b, n, sq, sk = p.shape
        keep = dropout_mask_reference(seed, b, n, sq, sk, dropout_p)
        p = p * keep * (1.0 / (1.0 - dropout_p))
    return jnp.einsum(
        "bnqk,bnkd->bnqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def mha_reference_varlen(
    q, k, v, cu_seqlens, *, causal=False, scale=None
) -> jax.Array:
    """Per-sequence XLA reference for varlen tests: slice each sequence,
    run dense attention, concatenate."""
    total, n, d = q.shape
    segs = segment_ids_from_cu_seqlens(cu_seqlens, total)
    seg_mask = segs[:, None] == segs[None, :]  # [total, total]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s = jnp.einsum(
        "qnd,knd->nqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    s = jnp.where(seg_mask[None], s, _NEG_INF)
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, (total, total), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (total, total), 1)
        s = jnp.where((ki > qi)[None], _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "nqk,knd->qnd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
