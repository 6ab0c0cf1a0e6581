"""Flat-buffer packing: the treedef/offset bookkeeping behind the packed
multi-tensor optimizer path.

Reference: the CUDA ``multi_tensor_apply`` streams *lists of tensor
pointers* through fixed-size chunks (``csrc/multi_tensor_apply.cuh:16-133``)
and ``DistributedFusedAdam`` goes further, flattening params into
contiguous fixed-size buckets (``distributed_fused_adam.py:273-283``) so
one kernel launch sweeps the whole optimizer state. A Pallas TPU grid has
no pointer lists — the equivalent is the bucket design: every pytree in
the optimizer protocol (grads, moments, fp32 masters, param outputs) is
packed into ONE contiguous 1-D buffer per dtype group, and the kernels
grid over fixed-size chunks of it.

:class:`PackSpec` is the static host-side bookkeeping (treedef, shapes,
per-leaf offsets) — an alignment-aware sibling of
``contrib.optimizers._sharded.ShardedLayout``. The extra constraint here:
each leaf's offset is aligned to ``ROW`` (= 8 sublanes x 128 lanes, one
fp32 vreg tile), so when the flat buffer is viewed as ``(rows, ROW)``
every row belongs to exactly ONE leaf. That makes per-tensor reductions
(LAMB trust ratios, NovoGrad layer-wise moments) a cheap
``segment_sum`` over per-row partials — the role the CUDA side's
chunk->tensor metadata tables played (``multi_tensor_apply.cuh:16-27``).

Padding is always ZERO and the kernels preserve that invariant (a zero
gradient leaves a zero moment/param untouched for every supported
update rule), so norms over the padded buffer equal norms over the tree.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


class BucketBuffers(NamedTuple):
    """Per-bucket flat gradient buffers, NOT yet concatenated.

    The handoff type of the bucketed allreduce
    (``parallel.sync_gradients_bucketed(concat=False)``): each element is
    one bucket's reduced flat buffer in the shared :class:`PackSpec`
    layout. Passing this to a packed optimizer (``opt.step`` /
    ``opt.step_flat``) defers the bucket concatenation INTO the update —
    inside the overflow-skip ``lax.cond`` branch the concat has a single
    elementwise consumer, so XLA fuses it into the update sweep's
    gradient read instead of materializing the global buffer first.
    """

    buffers: Tuple[jax.Array, ...]

# One fp32 vector register tile: 8 sublanes x 128 lanes. Leaf offsets are
# aligned to this so (rows, ROW)-shaped kernel blocks never straddle a
# leaf boundary. It is also the tile XLA lays a 1-D buffer out in on the
# chip, which is what makes the elementwise sweeps' (n // 128, 128) view
# of a ROW-multiple buffer a bitcast (``ops/packed_optimizer.py``).
ROW = 8 * 128

# The reference's default chunk: 2048*32 elements
# (``apex/multi_tensor_apply/multi_tensor_apply.py``, every optimizer ctor).
DEFAULT_CHUNK = 2048 * 32


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class PackSpec:
    """Static pytree <-> aligned flat buffer map.

    Hashable and comparable so it can ride through ``jit`` as auxiliary
    pytree data (it is the ``aux_data`` of :class:`PackedState`).

    ``chunk_size`` is the kernel chunk contract: ``total`` is padded up to
    a multiple of it, so a grid of ``total // chunk_size`` fixed-size
    chunks tiles the buffer exactly (the CUDA chunking contract).

    ``bucket_elems`` partitions the layout into contiguous chunk-aligned
    *buckets* of at most that many elements (per-leaf, so one oversized
    leaf still gets its own bucket) — the flat-buffer allreduce bucket
    structure of the reference DDP (``apex/parallel/distributed.py``:
    hook-discovered buckets, here sized up front by
    ``GradBuckets(bucket_cap_mb=...)``). Each bucket's extent is a whole
    number of chunks starting at a chunk-multiple offset, so bucket
    sub-buffers slice out of (and concatenate back into) the global
    buffer with no re-packing, and the SAME layout serves both the
    per-bucket ``psum`` and the whole-buffer optimizer kernels. Without
    ``bucket_elems`` the spec is one bucket covering everything.
    """

    def __init__(self, params_template: Pytree, align: int = ROW,
                 chunk_size: int = DEFAULT_CHUNK,
                 bucket_elems: Optional[int] = None):
        if align % ROW:
            raise ValueError(f"align ({align}) must be a multiple of {ROW}")
        chunk_size = _round_up(int(chunk_size), align)
        leaves, treedef = jax.tree_util.tree_flatten(params_template)
        if not leaves:
            raise ValueError("cannot build a PackSpec over an empty pytree")
        self.treedef = treedef
        self.shapes: Tuple[Tuple[int, ...], ...] = tuple(
            tuple(l.shape) for l in leaves)
        self.dtypes: Tuple[np.dtype, ...] = tuple(
            jnp.dtype(l.dtype) for l in leaves)
        self.sizes: Tuple[int, ...] = tuple(
            int(np.prod(s)) if s else 1 for s in self.shapes)
        self.n_leaves = len(leaves)
        self.align = align
        self.chunk_size = chunk_size
        self.bucket_elems = int(bucket_elems) if bucket_elems else None

        # one walk lays out leaves and closes buckets: a bucket closes
        # (offset rounds up to the next chunk boundary, absorbed into the
        # previous leaf's padding) when the next leaf would overflow the
        # per-bucket capacity and the bucket already holds a leaf
        offsets, padded = [], []
        end = 0
        bounds = [0]
        ranges = []
        start_leaf = 0
        for i, n in enumerate(self.sizes):
            pn = _round_up(n, align)
            if (self.bucket_elems and i > start_leaf
                    and (end - bounds[-1]) + pn > self.bucket_elems):
                b = _round_up(end, chunk_size)
                padded[-1] += b - end
                end = b
                bounds.append(b)
                ranges.append((start_leaf, i))
                start_leaf = i
            offsets.append(end)
            padded.append(pn)
            end += pn
        self.total = _round_up(end, chunk_size)
        bounds.append(self.total)
        ranges.append((start_leaf, self.n_leaves))
        self.offsets: Tuple[int, ...] = tuple(offsets)
        self.padded_sizes: Tuple[int, ...] = tuple(padded)
        self.bucket_bounds: Tuple[int, ...] = tuple(bounds)
        self.bucket_leaf_ranges: Tuple[Tuple[int, int], ...] = tuple(ranges)
        self.n_rows = self.total // ROW

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_bounds) - 1

    # -- identity (jit static-arg / aux-data requirements) -----------------
    def _key(self):
        return (self.treedef, self.shapes,
                tuple(str(d) for d in self.dtypes),
                self.align, self.chunk_size, self.bucket_elems)

    def __eq__(self, other):
        return isinstance(other, PackSpec) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    def __repr__(self):
        return (f"PackSpec(n_leaves={self.n_leaves}, total={self.total}, "
                f"chunk_size={self.chunk_size}, n_buckets={self.n_buckets})")

    # -- dtype bookkeeping -------------------------------------------------
    def common_dtype(self, fallback=jnp.float32) -> np.dtype:
        """The single dtype of the template leaves, or ``fallback`` when
        the template mixes dtypes (the flat buffer must be homogeneous;
        :meth:`unpack` casts each leaf back)."""
        uniq = set(self.dtypes)
        return self.dtypes[0] if len(uniq) == 1 else jnp.dtype(fallback)

    # -- pytree <-> flat ---------------------------------------------------
    def check(self, tree: Pytree) -> None:
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != self.n_leaves or tuple(
                tuple(l.shape) for l in leaves) != self.shapes:
            raise ValueError(
                "pytree does not match PackSpec (same optimizer instance "
                f"reused for a different model?): spec {self!r} vs "
                f"{len(leaves)} leaves")

    @jax.named_scope("apex_tpu.pack")
    def pack(self, tree: Pytree, dtype: Optional[Any] = None) -> jax.Array:
        """Ravel + per-leaf zero-pad + concat to ``(total,)``.

        One XLA concatenate — a single write sweep, fused with the casts.
        ``dtype=None`` packs in the leaves' common dtype (fp32 when mixed).
        """
        self.check(tree)
        dtype = jnp.dtype(dtype) if dtype is not None else self.common_dtype()
        leaves = jax.tree_util.tree_leaves(tree)
        pieces = []
        for leaf, n, pn in zip(leaves, self.sizes, self.padded_sizes):
            pieces.append(leaf.reshape(-1).astype(dtype))
            if pn != n:
                pieces.append(jnp.zeros((pn - n,), dtype))
        tail = self.total - sum(self.padded_sizes)
        if tail:
            pieces.append(jnp.zeros((tail,), dtype))
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)

    @jax.named_scope("apex_tpu.pack")
    def pack_bucket(self, tree: Pytree, bucket: int,
                    dtype: Optional[Any] = None) -> jax.Array:
        """Ravel + zero-pad ONLY bucket ``bucket``'s leaves to its extent
        (``bucket_bounds[b+1] - bucket_bounds[b]`` elements).

        The bucketed sibling of :meth:`pack`: each bucket buffer depends
        on nothing but its own leaves, so a per-bucket collective issued
        on it can overlap the computation still producing other buckets'
        gradients (XLA's latency-hiding scheduler owns the interleaving).
        ``concat_buckets`` of all buckets equals :meth:`pack`.
        """
        self.check(tree)
        dtype = jnp.dtype(dtype) if dtype is not None else self.common_dtype()
        lo, hi = self.bucket_leaf_ranges[bucket]
        leaves = jax.tree_util.tree_leaves(tree)[lo:hi]
        pieces = []
        used = 0
        for leaf, n, pn in zip(leaves, self.sizes[lo:hi],
                               self.padded_sizes[lo:hi]):
            pieces.append(leaf.reshape(-1).astype(dtype))
            if pn != n:
                pieces.append(jnp.zeros((pn - n,), dtype))
            used += pn
        extent = self.bucket_bounds[bucket + 1] - self.bucket_bounds[bucket]
        if extent != used:
            pieces.append(jnp.zeros((extent - used,), dtype))
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)

    def bucket_slice(self, flat: jax.Array, bucket: int) -> jax.Array:
        """Bucket ``bucket``'s sub-buffer of a packed global buffer."""
        b0, b1 = self.bucket_bounds[bucket], self.bucket_bounds[bucket + 1]
        return jax.lax.slice(flat, (b0,), (b1,))

    @jax.named_scope("apex_tpu.pack")
    def concat_buckets(self, buffers) -> jax.Array:
        """Per-bucket buffers (in order) -> the ``(total,)`` global
        buffer; the inverse of packing/slicing bucket-by-bucket."""
        buffers = list(buffers)
        if len(buffers) != self.n_buckets:
            raise ValueError(
                f"expected {self.n_buckets} bucket buffers, "
                f"got {len(buffers)}")
        for b, buf in enumerate(buffers):
            extent = self.bucket_bounds[b + 1] - self.bucket_bounds[b]
            if buf.shape != (extent,):
                raise ValueError(
                    f"bucket {b} buffer has shape {buf.shape}, "
                    f"expected ({extent},)")
        return buffers[0] if len(buffers) == 1 else jnp.concatenate(buffers)

    @jax.named_scope("apex_tpu.unpack")
    def unpack(self, flat: jax.Array, cast: bool = True) -> Pytree:
        """``(total,)`` -> pytree; each leaf cast back to its template
        dtype unless ``cast=False``."""
        leaves = []
        for i in range(self.n_leaves):
            o = self.offsets[i]
            piece = jax.lax.slice(flat, (o,), (o + self.sizes[i],))
            piece = piece.reshape(self.shapes[i])
            leaves.append(piece.astype(self.dtypes[i]) if cast else piece)
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def zeros(self, dtype=jnp.float32) -> jax.Array:
        return jnp.zeros((self.total,), dtype)

    def shard_bounds(self, shard_count: int) -> Tuple[Tuple[int, int], ...]:
        """Equal ROW-aligned per-shard element ranges ``[(lo, hi), ...]``
        partitioning ``[0, total)`` into ``shard_count`` contiguous
        shards — the row-sliced checkpoint shards of the elastic
        multi-host service (``resilience.elastic``) and the ZeRO-sharded
        packed layout. Raises when the layout does not admit equal
        ROW-aligned shards (``analysis.check_pack_spec(spec,
        shard_count=n)`` is the machine check; this is the runtime
        guard on the same invariant)."""
        shard_count = int(shard_count)
        if shard_count <= 0:
            raise ValueError(f"shard_count must be > 0, got {shard_count}")
        if self.total % shard_count:
            raise ValueError(
                f"total {self.total} is not divisible by shard_count "
                f"{shard_count} — build the spec with a chunk_size that "
                f"is a multiple of shard_count*ROW ({shard_count * ROW})")
        size = self.total // shard_count
        if size % ROW:
            raise ValueError(
                f"shard size {size} is not ROW-aligned ({ROW}) — shard "
                "boundaries would split rows")
        return tuple((h * size, (h + 1) * size) for h in range(shard_count))

    def leaf_names(self) -> Tuple[str, ...]:
        """Human-readable leaf path strings in flatten order (via
        ``jax.tree_util.keystr``) — the names overflow-provenance events
        report (``apex_tpu.telemetry.numerics``)."""
        dummy = jax.tree_util.tree_unflatten(
            self.treedef, list(range(self.n_leaves)))
        paths = jax.tree_util.tree_flatten_with_path(dummy)[0]
        return tuple(jax.tree_util.keystr(p) for p, _ in paths)

    # -- per-row metadata (the chunk->tensor tables) -----------------------
    def row_leaf_ids(self) -> np.ndarray:
        """int32 ``(n_rows,)``: leaf index owning each ROW-sized row;
        padding rows (inter-leaf and tail) get segment ``n_leaves``. Host
        numpy — feed to ``segment_sum(..., num_segments=n_leaves + 1)``
        and drop the last segment."""
        ids = np.full((self.n_rows,), self.n_leaves, np.int32)
        for i in range(self.n_leaves):
            r0 = self.offsets[i] // ROW
            # rows containing any real element of leaf i (the tail row may
            # be partially padding; pads are zero so reductions are exact)
            r1 = (self.offsets[i] + self.sizes[i] + ROW - 1) // ROW
            ids[r0:r1] = i
        return ids

    def valid_mask(self) -> np.ndarray:
        """bool ``(total,)``: True at real positions, False at padding."""
        mask = np.zeros((self.total,), bool)
        for o, n in zip(self.offsets, self.sizes):
            mask[o:o + n] = True
        return mask
