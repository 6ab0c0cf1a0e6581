"""Packed flat-buffer optimizer kernels: one HBM sweep per step.

The reference's ``multi_tensor_apply`` (``csrc/multi_tensor_apply.cuh``,
``csrc/multi_tensor_adam.cu``, ``csrc/multi_tensor_sgd_kernel.cu``, ...)
exists to stream optimizer state through memory ONCE per step: one launch
walks fixed-size chunks of every tensor and fuses unscale + update +
recast. The pytree path in ``apex_tpu.optimizers`` leaves that fusion to
XLA, and the round-5 GPT-2 345M profile shows XLA does NOT deliver it:
42.7% of step time is elementwise fusion sweeps (grad unscale, Adam
update, master->bf16 recast each walk ~GBs of fp32 state separately).

This module is the real TPU ``multi_tensor_apply``: optimizer state lives
in contiguous 1-D flat buffers (see
``apex_tpu.multi_tensor_apply.packing.PackSpec``), and one Pallas kernel
per optimizer step grids over fixed-size chunks and fuses grad unscale
(``inv_scale``), the noop_flag overflow contract, the optimizer math, and
the fp32-master -> param-dtype recast into a single read-modify-write
pass. ``input_output_aliases`` donate m/v/master so the update is in
place, exactly the CUDA kernels' contract.

Two views of a flat buffer (``docs/packed_optimizers.md``): the
elementwise sweeps (Adam, SGD) take ``(n // LANES, LANES)``, whose
``(8, 128)`` tile IS the 1-D buffer's 1024-element HBM tile, so the view
is a bitcast and the aliasing reaches the buffers the step was handed;
the kernels with per-row side values (LAMB, NovoGrad, the reductions,
the ``amp_C`` flat ops) take ``(n // ROW, ROW)``, which XLA relayouts.

Kernel inventory (CUDA counterparts in parens):

- :func:`packed_adam_apply`     Adam/AdamW incl. the fork's transient
  no-write-m/v mode (``multi_tensor_adam.cu`` ``AdamFunctor`` +
  ``AdamFunctorNoUpdateMV:514``)
- :func:`packed_sgd_apply`      momentum SGD (``multi_tensor_sgd_kernel.cu``)
- :func:`packed_lamb_stage1` /
  :func:`packed_scale_update`   LAMB's two stages
  (``multi_tensor_lamb.cu`` stage1/stage2)
- :func:`packed_novograd_apply` NovoGrad elementwise stage
  (``multi_tensor_novograd.cu``)
- :func:`packed_row_reduce`     per-row sq-sum / max-abs partials — the
  per-tensor-norm machinery (``multi_tensor_l2norm_kernel.cu``)
- :func:`packed_row_stats`      per-row sq-sum + max-abs + non-finite
  count in ONE sweep — the numerics-monitor observation pass
  (``apex_tpu.telemetry.numerics``); segment-reduce the rows with
  ``PackSpec.row_leaf_ids()`` for exact per-tensor overflow provenance
  (rows are leaf-aligned, so a non-finite row names exactly one leaf)
- :func:`multi_tensor_scale_flat` / :func:`multi_tensor_axpby_flat` /
  :func:`multi_tensor_l2norm_flat`  the ``amp_C`` utility ops over flat
  buffers; these honor the ``chunk_size`` that
  ``MultiTensorApply(chunk_size=...)`` forwards (``accepts_chunk_size``).

Every op has an XLA fallback (``use_kernel=False``, auto-selected off-TPU)
computing identical fp32 math over the 1-D buffers, and every kernel runs
under the Pallas interpreter (``interpret=True``) so CPU tests exercise
the real kernel bodies.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..multi_tensor_apply.packing import DEFAULT_CHUNK, ROW, _round_up

_NSCAL = 8  # fixed-width SMEM scalar bundle


# ---------------------------------------------------------------------------
# plumbing
# ---------------------------------------------------------------------------
def _kernel_ok(use_kernel: Optional[bool], interpret: bool) -> bool:
    """Kernel path on TPU or when explicitly interpreted; XLA fallback
    elsewhere. ``use_kernel`` overrides."""
    if use_kernel is not None:
        return bool(use_kernel)
    return bool(interpret) or jax.default_backend() == "tpu"


def _scalars(*vals) -> jax.Array:
    """Bundle traced scalars into the (1, _NSCAL) fp32 SMEM block."""
    vals = list(vals) + [0.0] * (_NSCAL - len(vals))
    return jnp.stack(
        [jnp.asarray(v, jnp.float32).reshape(()) for v in vals]
    ).reshape(1, _NSCAL)


# Lane width of one vreg: the minor dim of the elementwise sweeps' view.
LANES = 128

# Sublane multiple every block keeps: bf16 tiles are 16 x 128
# (fp32's 8 x 128 divides it), and grads/params ride these kernels in bf16.
_SUBLANES = 16


def _block_rows(n_rows: int, chunk_size: int, width: int = ROW) -> int:
    """Rows of ``width`` elements per grid step: ``chunk_size`` elements
    rounded up to a ``_SUBLANES`` multiple — or the whole buffer when it
    is smaller (Mosaic accepts a block dim that is a tile multiple or the
    array's own). The grid is ``pl.cdiv(n_rows, block)``: a ragged last
    block reads past the end and its out-of-range rows are dropped on
    write, which is exact here because every output is row-aligned with
    the input."""
    want = _round_up(max(1, int(chunk_size) // width), _SUBLANES)
    return n_rows if n_rows <= want else want


def _sspec():
    return pl.BlockSpec((1, _NSCAL), lambda i: (0, 0),
                        memory_space=pltpu.SMEM)


def _tspec(b, width=ROW):
    return pl.BlockSpec((b, width), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def _cspec(b):
    """One value per row, as a ``(rows, 1)`` column: the block's rows sit
    on sublanes exactly like the ``(b, ROW)`` data block's, so a
    ``keepdims`` lane reduction stores (and a per-row coefficient
    broadcasts) with no relayout."""
    return pl.BlockSpec((b, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)


def _rows(flat: jax.Array, width: int = ROW) -> jax.Array:
    """``(n,)`` -> ``(n // width, width)``. At ``width=LANES`` an
    ``(8, 128)`` tile of the view is 1024 consecutive elements — the 1-D
    buffer's own HBM tile — so this reshape, and the ``reshape(-1)`` of a
    result, move no data; at ``ROW`` both are relayouts."""
    n = flat.shape[0]
    if n % ROW:
        raise ValueError(
            f"flat buffer length {n} is not a multiple of ROW ({ROW}); "
            "pack with PackSpec (or pad) first")
    return flat.reshape(n // width, width)


def _pad_to_rows(flat: jax.Array) -> Tuple[jax.Array, int]:
    """Zero-pad an arbitrary 1-D buffer to a ROW multiple (zeros are
    neutral for every op here: finite, |.|=0, scale->0). No chunk
    padding: ``_block_rows`` keeps full-size blocks for any row count
    and the grid's ragged last block covers the remainder."""
    n = flat.shape[0]
    total = _round_up(max(n, 1), ROW)
    if total != n:
        flat = jnp.concatenate([flat, jnp.zeros((total - n,), flat.dtype)])
    return flat, n


def _nonfinite(x: jax.Array) -> jax.Array:
    """fp32 {0, 1}: 1 where ``x`` is inf or nan."""
    return jnp.where(jnp.isfinite(x), 0.0, 1.0)


def _row_flags(x: jax.Array) -> jax.Array:
    """``(rows, 1)`` fp32, > 0 where the row holds a non-finite value.
    The flat scale/axpby kernels always flag per ROW (1/1024 of the
    sweep's traffic): a per-chunk scalar would have to leave the kernel
    through a one-element vector store, which Mosaic does not tile."""
    return jnp.max(_nonfinite(x), axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# fused Adam (the headline one-sweep step)
# ---------------------------------------------------------------------------
@jax.named_scope("apex_tpu.packed_adam")
def packed_adam_apply(
    flat_g: jax.Array,
    flat_m: jax.Array,
    flat_v: jax.Array,
    flat_src: jax.Array,  # fp32 masters (or fp32-packed params)
    *,
    param_dtype,
    lr,
    bc1,
    bc2,
    inv_scale=1.0,
    noop=None,
    beta1: float = 0.9,
    beta2: float = 0.999,
    eps: float = 1e-8,
    wd: float = 0.0,
    adam_w_mode: bool = True,
    write_mv: bool = True,
    write_master: bool = True,
    chunk_size: int = DEFAULT_CHUNK,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
):
    """One fused pass: unscale + Adam/AdamW + master->param recast.

    Reads g/m/v/src once, writes p_out (+ m/v/master when enabled) once —
    the ``AdamFunctor`` contract over flat buffers. ``write_mv=False`` is
    the fork's ``no_update_mv`` mode (``multi_tensor_adam.cu:514``): m/v
    are computed transiently in-kernel, only params are written.

    ``noop`` (the CUDA ``noop_flag``): when given and true, every output
    equals its input (p_out = recast(src)). Callers holding the original
    params should prefer a ``lax.cond`` around the whole step (see
    ``skip_on_overflow``) — the in-kernel gate exists for direct users of
    the chunked contract.

    Returns ``(flat_p_out, new_m | None, new_v | None, new_master | None)``.
    """
    param_dtype = jnp.dtype(param_dtype)
    has_noop = noop is not None
    noop_s = jnp.asarray(noop if has_noop else False)

    if not _kernel_ok(use_kernel, interpret):
        g = flat_g.astype(jnp.float32) * jnp.asarray(inv_scale, jnp.float32)
        p32 = flat_src.astype(jnp.float32)
        if not adam_w_mode and wd != 0.0:
            g = g + wd * p32
        new_m = beta1 * flat_m + (1.0 - beta1) * g
        new_v = beta2 * flat_v + (1.0 - beta2) * g * g
        u = (new_m / bc1) / (jnp.sqrt(new_v / bc2) + eps)
        if adam_w_mode and wd != 0.0:
            u = u + wd * p32
        new_p = p32 - jnp.asarray(lr, jnp.float32) * u
        if has_noop:
            sel = lambda new, old: jnp.where(noop_s, old, new)  # noqa: E731
            new_p = sel(new_p, p32)
            new_m = sel(new_m, flat_m)
            new_v = sel(new_v, flat_v)
        return (
            new_p.astype(param_dtype),
            new_m if write_mv else None,
            new_v if write_mv else None,
            new_p if write_master else None,
        )

    R = flat_g.shape[0] // LANES
    B = _block_rows(R, chunk_size, LANES)
    tile = _tspec(B, LANES)

    def body(s_ref, g_ref, m_ref, v_ref, p_ref, *outs):
        keep = s_ref[0, 0] >= 0.5 if has_noop else None
        inv = s_ref[0, 1]
        lr_ = s_ref[0, 2]
        bc1_ = s_ref[0, 3]
        bc2_ = s_ref[0, 4]
        g = g_ref[:].astype(jnp.float32) * inv
        p32 = p_ref[:].astype(jnp.float32)
        if not adam_w_mode and wd != 0.0:
            g = g + wd * p32
        new_m = beta1 * m_ref[:] + (1.0 - beta1) * g
        new_v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
        u = (new_m / bc1_) / (jnp.sqrt(new_v / bc2_) + eps)
        if adam_w_mode and wd != 0.0:
            u = u + wd * p32
        new_p = p32 - lr_ * u
        if has_noop:
            new_p = jnp.where(keep, p32, new_p)
            new_m = jnp.where(keep, m_ref[:], new_m)
            new_v = jnp.where(keep, v_ref[:], new_v)
        k = 0
        outs[k][:] = new_p.astype(param_dtype)
        k += 1
        if write_mv:
            outs[k][:] = new_m
            outs[k + 1][:] = new_v
            k += 2
        if write_master:
            outs[k][:] = new_p

    out_shape = [jax.ShapeDtypeStruct((R, LANES), param_dtype)]
    out_specs = [tile]
    aliases = {}
    if write_mv:
        out_shape += [jax.ShapeDtypeStruct((R, LANES), jnp.float32)] * 2
        out_specs += [tile, tile]
        aliases[2] = 1  # flat_m -> new_m (input idx: scalars=0, g=1, m=2...)
        aliases[3] = 2
    if write_master:
        out_shape.append(jax.ShapeDtypeStruct((R, LANES), jnp.float32))
        out_specs.append(tile)
        aliases[4] = len(out_shape) - 1

    outs = pl.pallas_call(
        body,
        name="apex_tpu_packed_adam",
        grid=(pl.cdiv(R, B),),
        in_specs=[_sspec(), tile, tile, tile, tile],
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(
        _scalars(noop_s.astype(jnp.float32) if has_noop else 0.0,
                 inv_scale, lr, bc1, bc2),
        _rows(flat_g, LANES), _rows(flat_m, LANES), _rows(flat_v, LANES),
        _rows(flat_src, LANES),
    )
    outs = [o.reshape(-1) for o in outs]
    p_out = outs[0]
    k = 1
    new_m = new_v = master = None
    if write_mv:
        new_m, new_v = outs[k], outs[k + 1]
        k += 2
    if write_master:
        master = outs[k]
    return p_out, new_m, new_v, master


# ---------------------------------------------------------------------------
# fused SGD
# ---------------------------------------------------------------------------
@jax.named_scope("apex_tpu.packed_sgd")
def packed_sgd_apply(
    flat_g: jax.Array,
    flat_buf: jax.Array,  # fp32 momentum buffer
    flat_src: jax.Array,  # fp32 masters (or fp32-packed params)
    *,
    param_dtype,
    lr,
    first_run,
    inv_scale=1.0,
    noop=None,
    momentum: float = 0.0,
    dampening: float = 0.0,
    nesterov: bool = False,
    wd: float = 0.0,
    wd_after_momentum: bool = False,
    write_master: bool = True,
    chunk_size: int = DEFAULT_CHUNK,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
):
    """One fused pass of momentum SGD over flat buffers
    (``multi_tensor_sgd_kernel.cu``'s 4-list variant). Returns
    ``(flat_p_out, new_buf, new_master | None)``."""
    param_dtype = jnp.dtype(param_dtype)
    has_noop = noop is not None
    noop_s = jnp.asarray(noop if has_noop else False)

    def math(g, buf, p32, inv, lr_, first):
        g = g.astype(jnp.float32) * inv
        p32 = p32.astype(jnp.float32)
        d_p = g
        if wd != 0.0 and not wd_after_momentum:
            d_p = d_p + wd * p32
        if momentum != 0.0:
            new_buf = jnp.where(
                first, d_p, momentum * buf + (1.0 - dampening) * d_p)
            d_p = d_p + momentum * new_buf if nesterov else new_buf
        else:
            new_buf = buf
        if wd != 0.0 and wd_after_momentum:
            d_p = d_p + wd * p32
        return p32 - lr_ * d_p, new_buf

    if not _kernel_ok(use_kernel, interpret):
        first = jnp.asarray(first_run, jnp.bool_)
        new_p, new_buf = math(
            flat_g, flat_buf, flat_src,
            jnp.asarray(inv_scale, jnp.float32),
            jnp.asarray(lr, jnp.float32), first)
        if has_noop:
            new_p = jnp.where(noop_s, flat_src.astype(jnp.float32), new_p)
            new_buf = jnp.where(noop_s, flat_buf, new_buf)
        return (new_p.astype(param_dtype), new_buf,
                new_p if write_master else None)

    R = flat_g.shape[0] // LANES
    B = _block_rows(R, chunk_size, LANES)
    tile = _tspec(B, LANES)

    def body(s_ref, g_ref, b_ref, p_ref, *outs):
        keep = s_ref[0, 0] >= 0.5 if has_noop else None
        new_p, new_buf = math(
            g_ref[:], b_ref[:], p_ref[:], s_ref[0, 1], s_ref[0, 2],
            s_ref[0, 3] >= 0.5)
        if has_noop:
            new_p = jnp.where(keep, p_ref[:].astype(jnp.float32), new_p)
            new_buf = jnp.where(keep, b_ref[:], new_buf)
        outs[0][:] = new_p.astype(param_dtype)
        outs[1][:] = new_buf
        if write_master:
            outs[2][:] = new_p

    out_shape = [
        jax.ShapeDtypeStruct((R, LANES), param_dtype),
        jax.ShapeDtypeStruct((R, LANES), jnp.float32),
    ]
    out_specs = [tile, tile]
    aliases = {2: 1}  # flat_buf -> new_buf
    if write_master:
        out_shape.append(jax.ShapeDtypeStruct((R, LANES), jnp.float32))
        out_specs.append(tile)
        aliases[3] = 2

    outs = pl.pallas_call(
        body,
        name="apex_tpu_packed_sgd",
        grid=(pl.cdiv(R, B),),
        in_specs=[_sspec(), tile, tile, tile],
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(
        _scalars(noop_s.astype(jnp.float32) if has_noop else 0.0, inv_scale,
                 lr, jnp.asarray(first_run, jnp.float32)),
        _rows(flat_g, LANES), _rows(flat_buf, LANES), _rows(flat_src, LANES),
    )
    outs = [o.reshape(-1) for o in outs]
    return outs[0], outs[1], (outs[2] if write_master else None)


# ---------------------------------------------------------------------------
# LAMB stages
# ---------------------------------------------------------------------------
@jax.named_scope("apex_tpu.packed_lamb_stage1")
def packed_lamb_stage1(
    flat_g: jax.Array,
    flat_m: jax.Array,
    flat_v: jax.Array,
    flat_src: jax.Array,
    *,
    clip,
    bc1,
    bc2,
    inv_scale=1.0,
    beta1: float = 0.9,
    beta2: float = 0.999,
    beta3: float = 0.1,
    eps: float = 1e-6,
    wd: float = 0.01,
    adam_w_mode: bool = True,
    chunk_size: int = DEFAULT_CHUNK,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
):
    """LAMB stage 1 (``multi_tensor_lamb.cu`` stage1 + the per-tensor norm
    kernel, fused): moments + unratioed update in one sweep, emitting
    per-ROW sq-sums of the update and of p32 — ``segment_sum`` over
    ``PackSpec.row_leaf_ids()`` turns those into the per-tensor trust-ratio
    norms. Returns ``(flat_update, new_m, new_v, row_u_sq, row_p_sq)``
    with the row arrays shaped ``(rows,)``."""

    def math(g, m, v, p32, inv, clip_, bc1_, bc2_):
        g = g.astype(jnp.float32) * inv / clip_
        p32 = p32.astype(jnp.float32)
        if not adam_w_mode and wd != 0.0:
            g = g + wd * p32
        new_m = beta1 * m + beta3 * g
        new_v = beta2 * v + (1.0 - beta2) * g * g
        u = (new_m / bc1_) / (jnp.sqrt(new_v / bc2_) + eps)
        if adam_w_mode and wd != 0.0:
            u = u + wd * p32
        return u, new_m, new_v, p32

    if not _kernel_ok(use_kernel, interpret):
        u, new_m, new_v, p32 = math(
            flat_g, flat_m, flat_v, flat_src,
            jnp.asarray(inv_scale, jnp.float32),
            jnp.asarray(clip, jnp.float32),
            jnp.asarray(bc1, jnp.float32), jnp.asarray(bc2, jnp.float32))
        u2 = jnp.sum(u.reshape(-1, ROW) ** 2, axis=1)
        p2 = jnp.sum(p32.reshape(-1, ROW) ** 2, axis=1)
        return u, new_m, new_v, u2, p2

    R = flat_g.shape[0] // ROW
    B = _block_rows(R, chunk_size)

    def body(s_ref, g_ref, m_ref, v_ref, p_ref,
             u_out, m_out, v_out, ru_out, rp_out):
        u, new_m, new_v, p32 = math(
            g_ref[:], m_ref[:], v_ref[:], p_ref[:],
            s_ref[0, 0], s_ref[0, 1], s_ref[0, 2], s_ref[0, 3])
        u_out[:] = u
        m_out[:] = new_m
        v_out[:] = new_v
        ru_out[:] = jnp.sum(u * u, axis=1, keepdims=True)
        rp_out[:] = jnp.sum(p32 * p32, axis=1, keepdims=True)

    u, new_m, new_v, ru, rp = pl.pallas_call(
        body,
        name="apex_tpu_packed_lamb_stage1",
        grid=(pl.cdiv(R, B),),
        in_specs=[_sspec(), _tspec(B), _tspec(B), _tspec(B), _tspec(B)],
        out_specs=[_tspec(B), _tspec(B), _tspec(B), _cspec(B), _cspec(B)],
        out_shape=[
            jax.ShapeDtypeStruct((R, ROW), jnp.float32),
            jax.ShapeDtypeStruct((R, ROW), jnp.float32),
            jax.ShapeDtypeStruct((R, ROW), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        input_output_aliases={2: 1, 3: 2},
        interpret=interpret,
    )(_scalars(inv_scale, clip, bc1, bc2),
      _rows(flat_g), _rows(flat_m), _rows(flat_v), _rows(flat_src))
    return (u.reshape(-1), new_m.reshape(-1), new_v.reshape(-1),
            ru.reshape(-1), rp.reshape(-1))


@jax.named_scope("apex_tpu.packed_scale_update")
def packed_scale_update(
    flat_u: jax.Array,
    flat_src: jax.Array,
    row_coef: jax.Array,  # (rows,) fp32, e.g. LAMB trust ratios
    *,
    param_dtype,
    lr,
    write_master: bool = True,
    chunk_size: int = DEFAULT_CHUNK,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
):
    """LAMB stage 2 (``multi_tensor_lamb.cu`` stage2): apply a per-row
    coefficient — ``p32 -= lr * coef[row] * u`` — recasting to the param
    dtype in the same sweep. Returns ``(flat_p_out, new_master | None)``."""
    param_dtype = jnp.dtype(param_dtype)

    if not _kernel_ok(use_kernel, interpret):
        coef = jnp.repeat(row_coef, ROW)
        new_p = (flat_src.astype(jnp.float32)
                 - jnp.asarray(lr, jnp.float32) * coef * flat_u)
        return new_p.astype(param_dtype), (new_p if write_master else None)

    R = flat_u.shape[0] // ROW
    B = _block_rows(R, chunk_size)

    def body(s_ref, u_ref, p_ref, c_ref, *outs):
        new_p = (p_ref[:].astype(jnp.float32)
                 - s_ref[0, 0] * c_ref[:] * u_ref[:])
        outs[0][:] = new_p.astype(param_dtype)
        if write_master:
            outs[1][:] = new_p

    out_shape = [jax.ShapeDtypeStruct((R, ROW), param_dtype)]
    out_specs = [_tspec(B)]
    aliases = {}
    if write_master:
        out_shape.append(jax.ShapeDtypeStruct((R, ROW), jnp.float32))
        out_specs.append(_tspec(B))
        aliases[2] = 1  # flat_src -> new master
    outs = pl.pallas_call(
        body,
        name="apex_tpu_packed_scale_update",
        grid=(pl.cdiv(R, B),),
        in_specs=[_sspec(), _tspec(B), _tspec(B), _cspec(B)],
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(_scalars(lr), _rows(flat_u), _rows(flat_src),
      row_coef.reshape(R, 1))
    p_out = outs[0].reshape(-1)
    return p_out, (outs[1].reshape(-1) if write_master else None)


# ---------------------------------------------------------------------------
# NovoGrad elementwise stage
# ---------------------------------------------------------------------------
@jax.named_scope("apex_tpu.packed_novograd")
def packed_novograd_apply(
    flat_g: jax.Array,
    flat_m: jax.Array,
    flat_src: jax.Array,
    row_denom: jax.Array,  # (rows,) fp32: sqrt(per-tensor v) + eps
    *,
    param_dtype,
    lr,
    bc1,
    inv_scale=1.0,
    beta1: float = 0.95,
    beta3: float = 0.05,
    wd: float = 0.0,
    reg_inside_moment: bool = False,
    chunk_size: int = DEFAULT_CHUNK,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
):
    """NovoGrad's elementwise stage (``multi_tensor_novograd.cu``) with the
    layer-wise denominator delivered per row. Returns
    ``(flat_p_out, new_m)``."""
    param_dtype = jnp.dtype(param_dtype)

    def math(g, m, p, denom, inv, lr_, bc1_):
        g = g.astype(jnp.float32) * inv
        p32 = p.astype(jnp.float32)
        moment_in = g / denom
        if wd != 0.0 and reg_inside_moment:
            moment_in = moment_in + wd * p32
        new_m = beta1 * m + beta3 * moment_in
        u = new_m / bc1_
        if wd != 0.0 and not reg_inside_moment:
            u = u + wd * p32
        return p32 - lr_ * u, new_m

    if not _kernel_ok(use_kernel, interpret):
        denom = jnp.repeat(row_denom, ROW)
        new_p, new_m = math(
            flat_g, flat_m, flat_src, denom,
            jnp.asarray(inv_scale, jnp.float32),
            jnp.asarray(lr, jnp.float32), jnp.asarray(bc1, jnp.float32))
        return new_p.astype(param_dtype), new_m

    R = flat_g.shape[0] // ROW
    B = _block_rows(R, chunk_size)

    def body(s_ref, g_ref, m_ref, p_ref, d_ref, p_out, m_out):
        new_p, new_m = math(g_ref[:], m_ref[:], p_ref[:], d_ref[:],
                            s_ref[0, 0], s_ref[0, 1], s_ref[0, 2])
        p_out[:] = new_p.astype(param_dtype)
        m_out[:] = new_m

    p_out, new_m = pl.pallas_call(
        body,
        name="apex_tpu_packed_novograd",
        grid=(pl.cdiv(R, B),),
        in_specs=[_sspec(), _tspec(B), _tspec(B), _tspec(B), _cspec(B)],
        out_specs=[_tspec(B), _tspec(B)],
        out_shape=[
            jax.ShapeDtypeStruct((R, ROW), param_dtype),
            jax.ShapeDtypeStruct((R, ROW), jnp.float32),
        ],
        input_output_aliases={2: 1},
        interpret=interpret,
    )(_scalars(inv_scale, lr, bc1),
      _rows(flat_g), _rows(flat_m), _rows(flat_src),
      row_denom.reshape(R, 1))
    return p_out.reshape(-1), new_m.reshape(-1)


# ---------------------------------------------------------------------------
# reductions + amp_C utility ops over flat buffers
# ---------------------------------------------------------------------------
@jax.named_scope("apex_tpu.packed_row_reduce")
def packed_row_reduce(
    flat: jax.Array,
    *,
    op: str = "sqsum",  # or "maxabs"
    inv_scale=1.0,
    chunk_size: int = DEFAULT_CHUNK,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Per-ROW reduction partials of ``flat * inv_scale`` in one sweep
    (``multi_tensor_l2norm_kernel.cu``'s per-chunk stage). ``sqsum`` rows
    feed global/per-tensor L2 norms; ``maxabs`` feeds NovoGrad's inf-norm
    mode. Returns fp32 ``(rows,)``."""
    if op not in ("sqsum", "maxabs"):
        raise ValueError(f"unknown row reduction {op!r}")

    def red(x):
        return (jnp.sum(x * x, axis=1, keepdims=True) if op == "sqsum"
                else jnp.max(jnp.abs(x), axis=1, keepdims=True))

    if not _kernel_ok(use_kernel, interpret):
        x = flat.reshape(-1, ROW).astype(jnp.float32)
        return red(x * jnp.asarray(inv_scale, jnp.float32)).reshape(-1)

    R = flat.shape[0] // ROW
    B = _block_rows(R, chunk_size)

    def body(s_ref, x_ref, out_ref):
        x = x_ref[:].astype(jnp.float32) * s_ref[0, 0]
        out_ref[:] = red(x)

    out = pl.pallas_call(
        body,
        name="apex_tpu_packed_row_reduce",
        grid=(pl.cdiv(R, B),),
        in_specs=[_sspec(), _tspec(B)],
        out_specs=_cspec(B),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.float32),
        interpret=interpret,
    )(_scalars(inv_scale), _rows(flat))
    return out.reshape(-1)


@jax.named_scope("apex_tpu.packed_row_stats")
def packed_row_stats(
    flat: jax.Array,
    *,
    inv_scale=1.0,
    chunk_size: int = DEFAULT_CHUNK,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(row_sq, row_maxabs, row_nonfinite)`` of ``flat * inv_scale`` in
    ONE chunked sweep — the numerics-monitor observation pass.

    One read of the buffer yields all three per-ROW partials; a
    ``segment_sum``/``segment_max`` over ``PackSpec.row_leaf_ids()`` turns
    them into per-tensor grad norms, max-|g| and non-finite counts (rows
    are leaf-aligned, so non-finite rows attribute to exactly one leaf —
    the overflow-provenance contract). ``row_sq``/``row_maxabs`` are RAW
    reductions: a non-finite element poisons its leaf's norm to nan/inf,
    which is itself signal; ``row_nonfinite`` is the exact element count.
    All outputs fp32 ``(rows,)`` covering the input's rows (zero padding
    added here is finite and reduction-neutral).
    """
    flat, _ = _pad_to_rows(flat)

    def stats(x):
        return (jnp.sum(x * x, axis=1, keepdims=True),
                jnp.max(jnp.abs(x), axis=1, keepdims=True),
                jnp.sum(_nonfinite(x), axis=1, keepdims=True))

    if not _kernel_ok(use_kernel, interpret):
        x = flat.reshape(-1, ROW).astype(jnp.float32)
        x = x * jnp.asarray(inv_scale, jnp.float32)
        return tuple(o.reshape(-1) for o in stats(x))

    R = flat.shape[0] // ROW
    B = _block_rows(R, chunk_size)

    def body(s_ref, x_ref, sq_ref, ma_ref, nf_ref):
        x = x_ref[:].astype(jnp.float32) * s_ref[0, 0]
        sq_ref[:], ma_ref[:], nf_ref[:] = stats(x)

    sq, ma, nf = pl.pallas_call(
        body,
        name="apex_tpu_packed_row_stats",
        grid=(pl.cdiv(R, B),),
        in_specs=[_sspec(), _tspec(B)],
        out_specs=[_cspec(B), _cspec(B), _cspec(B)],
        out_shape=[jax.ShapeDtypeStruct((R, 1), jnp.float32)] * 3,
        interpret=interpret,
    )(_scalars(inv_scale), _rows(flat))
    return sq.reshape(-1), ma.reshape(-1), nf.reshape(-1)


packed_row_stats.accepts_chunk_size = True


@jax.named_scope("apex_tpu.multi_tensor_l2norm_flat")
def multi_tensor_l2norm_flat(
    flat: jax.Array,
    *,
    inv_scale=1.0,
    chunk_size: int = DEFAULT_CHUNK,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Global L2 norm of a flat buffer in one chunked sweep. Returns
    ``(norm, row_sq)`` — ``row_sq`` are the per-ROW partials (segment-sum
    them with ``PackSpec.row_leaf_ids()`` for per-tensor norms, the
    ``per_tensor`` mode of ``multi_tensor_l2norm_kernel.cu``)."""
    flat, _ = _pad_to_rows(flat)
    row_sq = packed_row_reduce(
        flat, op="sqsum", inv_scale=inv_scale, chunk_size=chunk_size,
        use_kernel=use_kernel, interpret=interpret)
    return jnp.sqrt(jnp.sum(row_sq)), row_sq


multi_tensor_l2norm_flat.accepts_chunk_size = True


@jax.named_scope("apex_tpu.multi_tensor_scale_flat")
def multi_tensor_scale_flat(
    flat: jax.Array,
    scale,
    out_dtype=None,
    *,
    per_row_flags: bool = False,
    chunk_size: int = DEFAULT_CHUNK,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
):
    """``out = flat * scale`` with non-finite flagging, one chunked sweep
    (``csrc/multi_tensor_scale_kernel.cu``). Returns ``(out, found_inf)``.

    ``per_row_flags=True`` also returns the sweep's per-ROW flags:
    ``(out, found_inf, row_bad)`` with ``row_bad`` a bool ``(rows,)``
    over the input's rows — same sweep, no extra read.
    Rows are leaf-aligned under ``PackSpec``, so segment-reducing
    ``row_bad`` with ``row_leaf_ids()`` names exactly the non-finite
    leaves (the overflow-provenance path of
    ``apex_tpu.telemetry.numerics``).
    """
    out_dtype = jnp.dtype(out_dtype) if out_dtype is not None else flat.dtype
    padded, n = _pad_to_rows(flat)

    if not _kernel_ok(use_kernel, interpret):
        if not per_row_flags:
            out32 = (flat.astype(jnp.float32)
                     * jnp.asarray(scale, jnp.float32))
            return out32.astype(out_dtype), ~jnp.all(jnp.isfinite(out32))
        # one multiply sweep over the padded buffer serves both outputs
        # (padding is trailing zeros, so the slice recovers the result)
        pad32 = padded.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)
        out = pad32[:n].astype(out_dtype)
        row_bad = ~jnp.all(jnp.isfinite(pad32).reshape(-1, ROW), axis=1)
        return out, jnp.any(row_bad), row_bad

    R = padded.shape[0] // ROW
    B = _block_rows(R, chunk_size)

    def body(s_ref, x_ref, out_ref, flag_ref):
        out32 = x_ref[:].astype(jnp.float32) * s_ref[0, 0]
        flag_ref[:] = _row_flags(out32)
        out_ref[:] = out32.astype(out_dtype)

    out, flags = pl.pallas_call(
        body,
        name="apex_tpu_multi_tensor_scale_flat",
        grid=(pl.cdiv(R, B),),
        in_specs=[_sspec(), _tspec(B)],
        out_specs=[_tspec(B), _cspec(B)],
        out_shape=[
            jax.ShapeDtypeStruct((R, ROW), out_dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(_scalars(scale), _rows(padded))
    out = out.reshape(-1)[:n]
    row_bad = flags.reshape(-1) > 0.0
    if not per_row_flags:
        return out, jnp.any(row_bad)
    return out, jnp.any(row_bad), row_bad


multi_tensor_scale_flat.accepts_chunk_size = True


@jax.named_scope("apex_tpu.multi_tensor_axpby_flat")
def multi_tensor_axpby_flat(
    a,
    b,
    flat_x: jax.Array,
    flat_y: jax.Array,
    out_dtype=None,
    *,
    chunk_size: int = DEFAULT_CHUNK,
    use_kernel: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """``out = a*x + b*y`` with non-finite flagging, one chunked sweep
    (``csrc/multi_tensor_axpby_kernel.cu``). Returns ``(out, found_inf)``."""
    out_dtype = jnp.dtype(out_dtype) if out_dtype is not None \
        else flat_x.dtype
    if flat_x.shape != flat_y.shape:
        raise ValueError(
            f"axpby buffers must match: {flat_x.shape} vs {flat_y.shape}")
    px, n = _pad_to_rows(flat_x)
    py, _ = _pad_to_rows(flat_y)

    if not _kernel_ok(use_kernel, interpret):
        out32 = (jnp.asarray(a, jnp.float32) * flat_x.astype(jnp.float32)
                 + jnp.asarray(b, jnp.float32) * flat_y.astype(jnp.float32))
        return out32.astype(out_dtype), ~jnp.all(jnp.isfinite(out32))

    R = px.shape[0] // ROW
    B = _block_rows(R, chunk_size)

    def body(s_ref, x_ref, y_ref, out_ref, flag_ref):
        out32 = (s_ref[0, 0] * x_ref[:].astype(jnp.float32)
                 + s_ref[0, 1] * y_ref[:].astype(jnp.float32))
        flag_ref[:] = _row_flags(out32)
        out_ref[:] = out32.astype(out_dtype)

    out, flags = pl.pallas_call(
        body,
        name="apex_tpu_multi_tensor_axpby_flat",
        grid=(pl.cdiv(R, B),),
        in_specs=[_sspec(), _tspec(B), _tspec(B)],
        out_specs=[_tspec(B), _cspec(B)],
        out_shape=[
            jax.ShapeDtypeStruct((R, ROW), out_dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(_scalars(a, b), _rows(px), _rows(py))
    return out.reshape(-1)[:n], jnp.any(flags > 0.0)


multi_tensor_axpby_flat.accepts_chunk_size = True
