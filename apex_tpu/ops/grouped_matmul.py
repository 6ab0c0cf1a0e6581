"""Grouped (ragged) matrix product: ``out[rows of g] = lhs[rows of g] @ rhs[g]``.

The expert layer's GEMM (``apex_tpu/transformer/moe.py``): tokens sorted by
expert, one weight matrix an expert, group sizes from the routing — so the
product runs over exactly the rows routed here, whatever the imbalance, and
nothing is dropped or padded to a capacity. The contract is
``jax.lax.ragged_dot``'s: ``lhs [m, k]`` holds the groups' rows one after
another (group ``g`` owns rows ``[sum(sizes[:g]), sum(sizes[:g + 1]))``),
``rhs [groups, k, n]``, ``group_sizes [groups]`` int32; a group may be
empty, one group may own every row, and ``m`` may exceed the sum — the
buffer is sized for the worst case the routing allows and only the rows in
use cost a grid step. **Rows at or past the sum are not written** by the
kernels (they hold what the buffer held, as in a ``ragged_dot`` whose
caller ignores them); the XLA fallback leaves zeros there. Callers select
(``jnp.where``), never multiply, to drop them.

Three Pallas kernels (the design of megablox's ``gmm``/``tgmm``): the
forward ``apex_tpu_grouped_matmul_fwd``; the product by the input,
``dlhs = dout @ rhs[g].T``, the same kernel reading ``rhs`` transposed
(``apex_tpu_grouped_matmul_dlhs``); the product by the weight,
``drhs[g] = lhs[rows of g].T @ dout[rows of g]``
(``apex_tpu_grouped_matmul_drhs``; an empty group gets zeros). The grid
walks the ``(row tile, group)`` visits the sizes give, their count a traced
value: a row tile two groups share is visited once for each and its rows
masked by the group's range.

Selection contract (``packed_optimizer.py``): the
kernels on TPU; off the TPU the identical-math XLA fallback
(``jax.lax.ragged_dot`` and its autodiff); ``interpret=True`` runs the
kernel bodies under the Pallas interpreter for the CPU tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FWD = "apex_tpu_grouped_matmul_fwd"
DLHS = "apex_tpu_grouped_matmul_dlhs"
DRHS = "apex_tpu_grouped_matmul_drhs"

ROW_TILE = 512          # rows of lhs a grid step takes
_VMEM_LIMIT = 64 * 1024 * 1024


def _tile(size: int, want: int) -> int:
    """The largest of ``want, want/2, ... 128`` that divides ``size``, else
    the whole dimension."""
    t = want
    while t >= 128:
        if size % t == 0:
            return t
        t //= 2
    return size


def _width_tile(size: int) -> int:
    """The tile of a contraction or an output width: ``_tile(size, 1024)``,
    but the whole width where that rule answers under 512 and the width is
    at most 2048. 1408 = 11 x 128 has no larger power-of-two-times-128
    divisor, and eleven tiles of 128 read the rows eleven times: on the
    chip the whole width took the three products from 64.1 to 31.2 ms a
    step (25.2% to 51.9% of their roof; PERF.md, PR 35). Widths of 1024
    and 2048 keep their tile of 1024."""
    t = _tile(size, 1024)
    return size if t < 512 and size <= 2048 else t


def row_tile(m: int) -> int:
    """The row tile the kernels use for ``m`` rows: callers size their
    buffers to a multiple of it."""
    return _tile(m, ROW_TILE)


def _visits(group_sizes, m: int, tm: int, visit_empty: bool):
    """The ``(row tile, group)`` pairs the kernels walk, in order:
    ``(offsets [g+1], group of visit [v], row tile of visit [v], count)``
    with ``v = m // tm + groups - 1`` the most there can be; entries past
    ``count`` are never visited."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first_tile = starts // tm
    tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - first_tile, 0)
    if visit_empty:
        tiles = jnp.where(sizes == 0, 1, tiles)
        first_tile = jnp.minimum(first_tile, m // tm - 1)
    n_visits = m // tm + g - 1 + (g if visit_empty else 0)
    group_of = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                          total_repeat_length=n_visits)
    first_visit = jnp.cumsum(tiles) - tiles
    tile_of = first_tile[group_of] + (
        jnp.arange(n_visits, dtype=jnp.int32) - first_visit[group_of])
    tile_of = jnp.clip(tile_of, 0, m // tm - 1).astype(jnp.int32)
    return offsets, group_of, tile_of, jnp.sum(tiles).astype(jnp.int32)


def _row_mask(offs_ref, group, tile, tm: int, width: int):
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return (rows >= offs_ref[group]) & (rows < offs_ref[group + 1])


def _gmm_kernel(offs_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
                acc_scr, *, tm, tiles_k, transpose_rhs):
    t, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    contract = (((1,), (1,)), ((), ())) if transpose_rhs else (
        ((1,), (0,)), ((), ()))
    acc_scr[:] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0], contract,
        preferred_element_type=jnp.float32)

    @pl.when(kk == tiles_k - 1)
    def _store():
        tile = tile_ref[t]
        mask = _row_mask(offs_ref, group_ref[t], tile, tm, acc_scr.shape[1])
        # a tile two groups share is visited twice in a row and stays in
        # VMEM between: the second visit keeps the first one's rows
        revisit = (t > 0) & (tile_ref[jnp.maximum(t - 1, 0)] == tile)
        kept = jnp.where(revisit, out_ref[...].astype(jnp.float32), 0.0)
        out_ref[...] = jnp.where(mask, acc_scr[:], kept).astype(out_ref.dtype)


def _gmm(lhs, rhs, group_sizes, *, transpose_rhs, interpret, name):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = row_tile(m), _width_tile(k), _width_tile(n)
    offsets, group_of, tile_of, count = _visits(group_sizes, m, tm, False)
    tiles_k = k // tk
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (1, tn, tk), lambda j, t, kk, o, g, ti: (g[t], j, kk))
    else:
        rhs_spec = pl.BlockSpec(
            (1, tk, tn), lambda j, t, kk, o, g, ti: (g[t], kk, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, count, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, t, kk, o, g, ti: (ti[t], kk)),
                rhs_spec],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, t, kk, o, g, ti: (ti[t], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(offsets, group_of, tile_of, lhs, rhs)


def _tgmm_kernel(offs_ref, group_ref, tile_ref, count_ref, lhs_ref, dout_ref,
                 out_ref, acc_scr, *, tm):
    t = pl.program_id(2)
    group = group_ref[t]

    @pl.when((t == 0) | (group_ref[jnp.maximum(t - 1, 0)] != group))
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # both operands: a row outside the group may hold anything (the rows
    # past the sum are never written), and 0 x NaN is NaN on the MXU
    tile = tile_ref[t]
    lhs = jnp.where(_row_mask(offs_ref, group, tile, tm, lhs_ref.shape[1]),
                    lhs_ref[...], 0).astype(lhs_ref.dtype)
    dout = jnp.where(_row_mask(offs_ref, group, tile, tm, dout_ref.shape[1]),
                     dout_ref[...], 0).astype(dout_ref.dtype)
    acc_scr[:] += jax.lax.dot_general(
        lhs, dout, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    last = count_ref[0] - 1
    @pl.when((t == last) | (group_ref[jnp.minimum(t + 1, last)] != group))
    def _store():
        out_ref[0] = acc_scr[:].astype(out_ref.dtype)


def _tgmm(lhs, dout, group_sizes, out_dtype, *, interpret):
    m, k = lhs.shape
    n = dout.shape[1]
    g = group_sizes.shape[0]
    tm, tk, tn = row_tile(m), _width_tile(k), _width_tile(n)
    offsets, group_of, tile_of, count = _visits(group_sizes, m, tm, True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        name=DRHS,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(k // tk, n // tn, count),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda i, j, t, o, gr, ti, c: (ti[t], i)),
                pl.BlockSpec((tm, tn),
                             lambda i, j, t, o, gr, ti, c: (ti[t], j))],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda i, j, t, o, gr, ti, c: (gr[t], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(offsets, group_of, tile_of, count[None], lhs, dout)


def _ragged(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32).astype(lhs.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, transpose_rhs=False,
                interpret=interpret, name=FWD)


def _grouped_fwd(lhs, rhs, group_sizes, interpret):
    return _grouped(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _grouped_bwd(interpret, res, dout):
    lhs, rhs, group_sizes = res
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm(dout, rhs, group_sizes, transpose_rhs=True,
                interpret=interpret, name=DLHS)
    drhs = _tgmm(lhs, dout, group_sizes, rhs.dtype, interpret=interpret)
    return dlhs, drhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@jax.named_scope("apex_tpu.grouped_matmul")
def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, interpret: bool = False) -> jax.Array:
    """``[m, k] x [groups, k, n] -> [m, n]`` by ``group_sizes`` (module
    docstring). Differentiable in ``lhs`` and ``rhs``."""
    m, k = lhs.shape
    if rhs.ndim != 3 or rhs.shape[1] != k or group_sizes.shape != rhs.shape[:1]:
        raise ValueError(
            f"grouped_matmul: lhs {lhs.shape}, rhs {rhs.shape}, group_sizes "
            f"{group_sizes.shape} are not [m, k], [groups, k, n], [groups]")
    if not (interpret or jax.default_backend() == "tpu"):
        return _ragged(lhs, rhs, group_sizes)
    if m % 8:
        raise ValueError(f"grouped_matmul: {m} rows are no multiple of 8")
    return _grouped(lhs, rhs.astype(lhs.dtype), group_sizes, bool(interpret))
