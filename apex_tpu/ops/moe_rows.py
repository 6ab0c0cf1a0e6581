"""The expert layer's row movement, over the rows routed here and no others.

``transformer/moe.py`` keeps its rows in buffers sized for the worst case
the routing allows, in expert order: group ``g`` owns rows ``[sum(sizes[:g]),
sum(sizes[:g + 1]))`` and the rows at or past ``sum(sizes)`` are in no
group. ``ops.grouped_matmul`` walks only the row tiles in use; these kernels
do the same for everything round it, on the same tile (``row_tile``) and
under the same traced bound, ``tiles_in_use``:

- ``gather_rows`` (``apex_tpu_moe_gather``): ``out[r] = src[token_of_row[r]]``,
  optionally scaled by a weight a row and dotted with another buffer's row.
  One grid step a row tile in use; each row is one DMA from ``src`` left in
  HBM, a tile's copies all in flight before the first wait.
- ``add_rows`` (``apex_tpu_moe_add``): ``out[t] = sum of the rows in use
  whose token is t`` in float32, optionally weighted: the transpose of the
  gather. One grid step a ``(row tile, group)`` visit, as the grouped
  products make them: inside one group a token stands once (a token's
  choices are distinct experts), so a visit reads its tokens' sums, adds its
  rows and writes them back with no two copies on one token; the next visit
  starts when the writes have landed. The grid's first step fills the sums
  with zeros (the output is the kernel's own: no pass of XLA's before it).
- ``gated_act`` (``apex_tpu_moe_act_fwd`` / ``_bwd``): ``silu(gate) * up``
  and its backward, float32 inside, one grid step a row tile in use.

**Rows past the tiles in use are not written** by any of them, and rows of
the last tile in use past the sum hold what the kernel made of whatever
stood there: as with ``grouped_matmul``, a reader selects by range and
never multiplies by a mask. No kernel reads such a row into a row in use
or into a token's sum (``add_rows`` bounds its copies by the group's
range; the other two work row by row).

Mosaic (jax 0.9) slices a tiled HBM operand by whole tiles of 8 rows, so a
single row is reached through a leading dimension: the token side of every
gather and sum is float32 ``[tokens, 1, hidden]`` (``apex_tpu_moe_by_row``
lays a gather's source out so: a pass over ``tokens`` rows, an eighth of a
buffer's even when all of the buffer is in use), and the buffers keep their
2-D layout and dtype, read and written by tiles. On a v5e a copy's start
and wait cost about 25 ns a row together (PERF.md, PR 29): the gather and
the sum are bound by the scalar core that issues them, not by memory.

Selection is the caller's (``moe.py``): these are the kernels;
``interpret=True`` runs their bodies under the Pallas interpreter. Each
entry point is jitted: a model calls it once a layer, forward, recomputed
and backward, and a kernel body is traced and lowered once for each
signature instead of once a call (a dozen copies a body: seconds of
``setup_s``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import ROW_TILE, _tile, _visits, row_tile

BY_ROW = "apex_tpu_moe_by_row"
GATHER = "apex_tpu_moe_gather"
ADD = "apex_tpu_moe_add"
ACT_FWD = "apex_tpu_moe_act_fwd"
ACT_BWD = "apex_tpu_moe_act_bwd"

_VMEM_LIMIT = 64 * 1024 * 1024
_UNROLL = 8     # copies started (or awaited) a loop trip; row tiles are multiples of 8


def tiles_in_use(group_sizes: jax.Array, rows: int) -> jax.Array:
    """The traced bound of every sweep: row tiles that hold a routed row."""
    tm = row_tile(rows)
    return (jnp.sum(group_sizes.astype(jnp.int32)) + tm - 1) // tm


def _by_row_kernel(src_ref, out_ref):
    out_ref[...] = src_ref[...].astype(jnp.float32).reshape(out_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _by_row(src: jax.Array, interpret: bool) -> jax.Array:
    """``[tokens, hidden] -> float32 [tokens, 1, hidden]``, a row a tile:
    what a copy can reach one row of. One pass (XLA converts, then lays
    out again)."""
    tokens, h = src.shape
    tb = _tile(tokens, ROW_TILE)
    return pl.pallas_call(
        _by_row_kernel, name=BY_ROW, grid=(tokens // tb,),
        in_specs=[pl.BlockSpec((tb, h), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tb, 1, h), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((tokens, 1, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(src)


def _for_rows(lo, hi, body):
    """``body(r)`` for ``r`` in ``[lo, hi)`` (traced or not), ``_UNROLL`` rows
    a loop trip and the rest one by one: a copy's start or wait is a few
    scalar instructions, and the loop's own are as many."""
    trips = (hi - lo) // _UNROLL

    def unrolled(i, carry):
        for j in range(_UNROLL):
            body(lo + i * _UNROLL + j)
        return carry

    def single(r, carry):
        body(r)
        return carry

    jax.lax.fori_loop(0, trips, unrolled, 0)
    jax.lax.fori_loop(lo + trips * _UNROLL, hi, single, 0)


def _gather_kernel(tok_ref, src_hbm, *refs, tm, scaled, dotted):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    other_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dots_ref = refs.pop(0) if dotted else None
    buf, sem = refs
    base = pl.program_id(0) * tm

    def start(r):
        pltpu.make_async_copy(
            src_hbm.at[tok_ref[base + r]], buf.at[r], sem).start()

    def done(r):
        pltpu.make_async_copy(src_hbm.at[0], buf.at[0], sem).wait()

    _for_rows(0, tm, start)
    _for_rows(0, tm, done)
    got = buf[...].reshape(out_ref.shape)
    if dotted:
        dots_ref[...] = jnp.sum(other_ref[...].astype(jnp.float32) * got,
                                axis=1, keepdims=True)
    if scaled:
        got = got * scale_ref[...]
    out_ref[...] = got.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def gather_rows(src: jax.Array, token_of_row: jax.Array, tiles: jax.Array,
                *, out_dtype, scale: Optional[jax.Array] = None,
                dot_with: Optional[jax.Array] = None,
                interpret: bool = False):
    """``out[r] = scale[r] * src[token_of_row[r]]`` for the rows of the
    first ``tiles`` row tiles, ``[rows, hidden]`` in ``out_dtype`` (the
    product in float32). With ``dot_with [rows, hidden]`` also ``dots[r] =
    sum_h dot_with[r, h] * src[token_of_row[r], h]`` in float32 (unscaled),
    and the pair is returned."""
    rows, (tokens, h) = token_of_row.shape[0], src.shape
    tm = row_tile(rows)
    tile = lambda i, tok: (i, 0)  # noqa: E731
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    operands = [_by_row(src, interpret)]
    if scale is not None:
        in_specs.append(pl.BlockSpec((tm, 1), tile))
        operands.append(scale.astype(jnp.float32).reshape(rows, 1))
    out_specs = [pl.BlockSpec((tm, h), tile)]
    out_shape = [jax.ShapeDtypeStruct((rows, h), out_dtype)]
    if dot_with is not None:
        in_specs.append(pl.BlockSpec((tm, h), tile))
        operands.append(dot_with)
        out_specs.append(pl.BlockSpec((tm, 1), tile))
        out_shape.append(jax.ShapeDtypeStruct((rows, 1), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, tm=tm, scaled=scale is not None,
                          dotted=dot_with is not None),
        name=GATHER,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tm, 1, h), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(token_of_row, *operands)
    if dot_with is None:
        return out[0]
    return out[0], out[1].reshape(rows)


def _add_kernel(tok_ref, offs_ref, group_ref, tile_ref, *refs, tm, sources,
                scaled, zero_rows):
    rows_refs, refs = refs[:sources], list(refs[sources:])
    scale_ref = refs.pop(0) if scaled else None
    sums_hbm, buf, read_sem, write_sem = refs
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _zero():
        # the sums start at zero: the output is this kernel's alone, so it
        # fills it itself, zero_rows tokens a copy
        buf[...] = jnp.zeros_like(buf)
        zeros = buf.at[pl.ds(0, zero_rows)]

        def put(i, carry):
            pltpu.make_async_copy(
                zeros, sums_hbm.at[pl.ds(i * zero_rows, zero_rows)],
                write_sem).start()
            return carry

        def put_done(i, carry):
            pltpu.make_async_copy(
                zeros, sums_hbm.at[pl.ds(0, zero_rows)], write_sem).wait()
            return carry

        chunks = sums_hbm.shape[0] // zero_rows
        jax.lax.fori_loop(0, chunks, put, 0)
        jax.lax.fori_loop(0, chunks, put_done, 0)

    @pl.when(step > 0)
    def _visit():
        visit = step - 1
        base = tile_ref[visit] * tm
        group = group_ref[visit]
        lo = jnp.maximum(offs_ref[group], base) - base
        hi = jnp.minimum(offs_ref[group + 1], base + tm) - base

        def read(r):
            pltpu.make_async_copy(
                sums_hbm.at[tok_ref[base + r]], buf.at[r], read_sem).start()

        def read_done(r):
            pltpu.make_async_copy(sums_hbm.at[0], buf.at[0], read_sem).wait()

        def write(r):
            pltpu.make_async_copy(
                buf.at[r], sums_hbm.at[tok_ref[base + r]], write_sem).start()

        def write_done(r):
            pltpu.make_async_copy(buf.at[0], sums_hbm.at[0], write_sem).wait()

        _for_rows(lo, hi, read)
        add = rows_refs[0][...].astype(jnp.float32)
        for ref in rows_refs[1:]:
            add = add + ref[...].astype(jnp.float32)
        if scaled:
            add = add * scale_ref[...]
        add = add.reshape(buf.shape)
        _for_rows(lo, hi, read_done)
        # rows outside [lo, hi) add what they hold to what the buffer held:
        # they are not written back
        buf[...] = buf[...] + add
        _for_rows(lo, hi, write)
        _for_rows(lo, hi, write_done)


@functools.partial(jax.jit, static_argnames=("tokens", "interpret"))
def add_rows(sources: Sequence[jax.Array], token_of_row: jax.Array,
             group_sizes: jax.Array, tokens: int, *,
             scale: Optional[jax.Array] = None,
             interpret: bool = False) -> jax.Array:
    """``out[t] = sum over the rows r in use with token_of_row[r] == t of
    scale[r] * sum of the sources' row r``: ``[tokens, hidden]`` float32.
    ``sources`` are ``[rows, hidden]`` buffers in expert order by
    ``group_sizes``; inside one group no token may stand twice."""
    rows, h = sources[0].shape
    tm = row_tile(rows)
    offsets, group_of, tile_of, count = _visits(group_sizes, rows, tm, False)
    # grid step 0 zeroes the sums, step v + 1 is visit v
    tile = lambda s, tok, o, g, ti: (ti[jnp.maximum(s - 1, 0)], 0)  # noqa: E731
    in_specs = [pl.BlockSpec((tm, h), tile) for _ in sources]
    operands = list(sources)
    if scale is not None:
        in_specs.append(pl.BlockSpec((tm, 1), tile))
        operands.append(scale.astype(jnp.float32).reshape(rows, 1))
    sums = pl.pallas_call(
        functools.partial(_add_kernel, tm=tm, sources=len(sources),
                          scaled=scale is not None,
                          zero_rows=math.gcd(tokens, tm)),
        name=ADD,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(count + 1,), in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((tm, 1, h), jnp.float32),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((tokens, 1, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(token_of_row, offsets, group_of, tile_of, *operands)
    return sums.reshape(tokens, h)


def _act_fwd_kernel(g_ref, u_ref, out_ref):
    g = g_ref[...].astype(jnp.float32)
    out_ref[...] = (g * jax.nn.sigmoid(g) * u_ref[...].astype(jnp.float32)
                    ).astype(out_ref.dtype)


def _act_bwd_kernel(g_ref, u_ref, d_ref, dg_ref, du_ref):
    g = g_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    s = jax.nn.sigmoid(g)
    dg_ref[...] = (d * u_ref[...].astype(jnp.float32) * s
                   * (1.0 + g * (1.0 - s))).astype(dg_ref.dtype)
    du_ref[...] = (d * g * s).astype(du_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kernel", "name", "n_out",
                                               "interpret"))
def _by_tile(kernel, name, tiles, operands, n_out, interpret):
    rows, f = operands[0].shape
    spec = pl.BlockSpec((row_tile(rows), f), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct((rows, f), operands[0].dtype)
    return pl.pallas_call(
        kernel, name=name, grid=(tiles,), in_specs=[spec] * len(operands),
        out_specs=[spec] * n_out, out_shape=[shape] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gated_act(gate: jax.Array, up: jax.Array, tiles: jax.Array,
              interpret: bool = False) -> jax.Array:
    """``silu(gate) * up`` over the first ``tiles`` row tiles of two
    ``[rows, ffn]`` buffers. Its backward hands back ``d gate`` and ``d up``
    over the same tiles, unwritten past them."""
    return _by_tile(_act_fwd_kernel, ACT_FWD, tiles, (gate, up), 1,
                    interpret)[0]


def _gated_act_fwd(gate, up, tiles, interpret):
    return gated_act(gate, up, tiles, interpret), (gate, up, tiles)


def _gated_act_bwd(interpret, res, d_out):
    gate, up, tiles = res
    d_gate, d_up = _by_tile(_act_bwd_kernel, ACT_BWD, tiles,
                            (gate, up, d_out.astype(gate.dtype)), 2, interpret)
    return d_gate, d_up, None


gated_act.defvjp(_gated_act_fwd, _gated_act_bwd)
