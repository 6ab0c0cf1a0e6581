"""Fused LayerNorm / RMSNorm forward+backward — Pallas TPU kernels with an
XLA fallback.

TPU-native replacement for ``csrc/layer_norm_cuda_kernel.cu`` (1286 LoC of
warp-shuffle welford + two-pass backward) and the contrib
``csrc/layer_norm/`` FastLayerNorm pack. Design:

- inputs are viewed as (rows, hidden); stats (mean, rstd) are fp32 per row,
  matching the CUDA kernels' fp32 accumulators for any input dtype;
- forward and the dx backward are Pallas kernels gridded over row blocks with
  the whole hidden dimension resident in VMEM (hidden ≤ ~64k fp32, the same
  envelope FastLayerNorm targets); dgamma/dbeta are per-block partial sums
  reduced in XLA — the analogue of the CUDA two-stage column reduction;
- on non-TPU backends (CPU tests) or awkward shapes (hidden not a multiple of
  128) the same math runs as plain XLA, which fuses it into one pass anyway.

The public entry points are ``layer_norm`` / ``rms_norm`` — custom_vjp
functions used by ``apex_tpu.normalization`` — each with a
``memory_efficient`` mode that saves the *output* and re-derives the
normalized input in backward (reference ``apex/normalization/
fused_layer_norm.py`` ``memory_efficient`` flag).

Kernel-dispatch decision table (``_use_pallas``, one gate for the plain
norms here and for the block tails of ``ops/fused_block.py``:
``bias_gelu``, ``residual_add_layer_norm``, ``bias_dropout_residual``):

============================  ==========================================
condition                     what runs
============================  ==========================================
``APEX_TPU_DISABLE_PALLAS``   XLA form
``interpret=True``            the kernel body under the Pallas
                              interpreter (parity tests, the benchmark's
                              CPU rehearsal, ``tools/static_audit.py``)
TPU, hidden % 128 == 0        XLA form **by default**; the compiled
                              kernel only under
                              ``APEX_TPU_FORCE_PALLAS_LN`` (the
                              experimenter's override: how the A/B below
                              is asked again on another chip)
non-TPU / ragged hidden       XLA form
============================  ==========================================

What the chip said (TPU v5e, GPT-2 345M, 8 x 1024 tokens a step):

- plain LayerNorm: XLA's own fusion is ~4x faster than the kernel at
  transformer shapes (279 against 301 ms a step in-model).
- the three block tails (the A/B of PR 34; ``docs/fused_block.md`` "What
  the chip has said" has the table per operation and per cell):
  36,389 tokens/s with the three kernels, 43,432 with XLA's form of all
  three (+19.4%; step 225.1 -> 188.6 ms, the MLP scope 83.0 -> 55.1, the
  compiled program 13.74 -> 11.69 GB). Each kernel loses on its own once
  the other two are XLA's: ``residual_add_layer_norm`` kept as a kernel
  reads 41,771, ``bias_dropout_residual`` 39,800; ``bias_gelu`` alone
  handed to XLA is worth +7.7% of the kernels' rate. XLA fuses the tails
  into the GEMMs either side and needs no ``(rows, n)`` view, which in
  bf16 with 8 rows of batch is a copy before and after every kernel.
  The tails were on by default until then on the strength of a profile
  (``BENCH_r05.json``: 42.7% of device time in elementwise fusions) taken
  before the packed optimizer, the flash layouts and selective
  recomputation; the same record read that model at 177.4 ms a step with
  unfused tails.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl



def _use_pallas(hidden: int, interpret: bool) -> bool:
    """Kernel-dispatch gate of the plain norms and, since the chip was
    asked (decision table in the module docstring), of the block tails in
    ``ops/fused_block.py`` alike: on a TPU the compiler's own fusions are
    the faster form of all of them, so the kernel bodies run where
    ``interpret=True`` asks for them and the compiled program holds XLA's
    code unless ``APEX_TPU_FORCE_PALLAS_LN`` asks for the kernels."""
    if os.environ.get("APEX_TPU_DISABLE_PALLAS"):
        return False
    if interpret:
        return True
    if not os.environ.get("APEX_TPU_FORCE_PALLAS_LN"):
        return False
    return jax.default_backend() == "tpu" and hidden % 128 == 0


def _row_block(rows: int, hidden: int, budget_bytes: int = 1 << 20) -> int:
    # whole hidden stays in VMEM; pick the largest row block that divides
    # rows and keeps the block under ``budget_bytes`` (~1MB) of fp32. Empirically 256-row blocks
    # run at memory bandwidth while 512-row blocks hit a Mosaic DMA
    # pathology ~10x slower (measured on v5e at hidden 1024).
    # Mosaic takes a block's row dim as a multiple of 8 sublanes or the
    # whole array — and the backward kernels accumulate dgamma/dbias over
    # every row they see, so a ragged last block is not an option: a row
    # count no multiple of 8 divides runs as ONE block.
    budget = max(8, budget_bytes // max(hidden * 4, 1))
    for cand in (256, 128, 64, 32, 16, 8):
        if cand <= budget and rows % cand == 0:
            return cand
    return rows


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mu_ref, rstd_ref, *, eps, affine):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    if affine:
        y = xhat * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    else:
        y = xhat
    y_ref[:] = y.astype(y_ref.dtype)
    mu_ref[:] = mu
    rstd_ref[:] = rstd


def _ln_bwd_kernel(dy_ref, x_ref, mu_ref, rstd_ref, w_ref, dx_ref, *out_refs, affine, x_is_xhat):
    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = x if x_is_xhat else (x - mu_ref[:]) * rstd
    wdy = dy * w_ref[:].astype(jnp.float32) if affine else dy
    c1 = jnp.mean(xhat * wdy, axis=1, keepdims=True)
    c2 = jnp.mean(wdy, axis=1, keepdims=True)
    dx = (wdy - xhat * c1 - c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)
    if affine:
        # dw/db accumulate into one (1, hidden) block revisited by every
        # grid step (TPU grid is sequential) — per-block partial outputs
        # would need block rows divisible by 8
        dw_ref, db_ref = out_refs

        @pl.when(pl.program_id(0) == 0)
        def _init():
            dw_ref[:] = jnp.zeros_like(dw_ref)
            db_ref[:] = jnp.zeros_like(db_ref)

        dw_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
        db_ref[:] += jnp.sum(dy, axis=0, keepdims=True)


def _rms_fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps, affine):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    xhat = x * rstd
    y = xhat * w_ref[:].astype(jnp.float32) if affine else xhat
    y_ref[:] = y.astype(y_ref.dtype)
    rstd_ref[:] = rstd


def _rms_bwd_kernel(dy_ref, x_ref, rstd_ref, w_ref, dx_ref, *out_refs, affine, x_is_xhat):
    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = x if x_is_xhat else x * rstd
    wdy = dy * w_ref[:].astype(jnp.float32) if affine else dy
    c1 = jnp.mean(xhat * wdy, axis=1, keepdims=True)
    dx = (wdy - xhat * c1) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)
    if affine:
        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_refs[0][:] = jnp.zeros_like(out_refs[0])

        out_refs[0][:] += jnp.sum(dy * xhat, axis=0, keepdims=True)


def _row_specs(br: int, hidden: int):
    row = pl.BlockSpec((br, hidden), lambda i: (i, 0))
    stat = pl.BlockSpec((br, 1), lambda i: (i, 0))
    vec = pl.BlockSpec((1, hidden), lambda i: (0, 0))
    return row, stat, vec, vec


def _ln_fwd_pallas(x2d, w, b, eps, affine, interpret):
    rows, hidden = x2d.shape
    br = _row_block(rows, hidden)
    row, stat, vec, _ = _row_specs(br, hidden)
    w2 = (w if affine else jnp.ones((hidden,), jnp.float32)).reshape(1, hidden)
    b2 = (b if (affine and b is not None) else jnp.zeros((hidden,), jnp.float32)).reshape(1, hidden)
    y, mu, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps, affine=affine),
        # stable kernel id for name-matching remat policies
        name="apex_tpu_layer_norm_fwd",
        grid=(rows // br,),
        in_specs=[row, vec, vec],
        out_specs=[row, stat, stat],
        out_shape=[
            jax.ShapeDtypeStruct((rows, hidden), x2d.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2d, w2, b2)
    return y, mu, rstd


def _ln_bwd_pallas(dy2d, x2d, mu, rstd, w, affine, x_is_xhat, interpret):
    rows, hidden = x2d.shape
    br = _row_block(rows, hidden)
    nblocks = rows // br
    row, stat, vec, partial = _row_specs(br, hidden)
    w2 = (w if affine else jnp.ones((hidden,), jnp.float32)).reshape(1, hidden)
    xrow = pl.BlockSpec((br, hidden), lambda i: (i, 0))
    out_specs = [row] + ([partial, partial] if affine else [])
    out_shape = [jax.ShapeDtypeStruct((rows, hidden), dy2d.dtype)] + (
        [
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
            jax.ShapeDtypeStruct((1, hidden), jnp.float32),
        ]
        if affine
        else []
    )
    outs = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, affine=affine, x_is_xhat=x_is_xhat),
        grid=(nblocks,),
        in_specs=[row, xrow, stat, stat, vec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(dy2d, x2d, mu, rstd, w2)
    if affine:
        dx, dw_p, db_p = outs
        return dx, dw_p[0], db_p[0]
    return outs[0], None, None


def _rms_fwd_pallas(x2d, w, eps, affine, interpret):
    rows, hidden = x2d.shape
    br = _row_block(rows, hidden)
    row, stat, vec, _ = _row_specs(br, hidden)
    w2 = (w if affine else jnp.ones((hidden,), jnp.float32)).reshape(1, hidden)
    y, rstd = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps, affine=affine),
        # stable kernel id for name-matching remat policies
        name="apex_tpu_rms_norm_fwd",
        grid=(rows // br,),
        in_specs=[row, vec],
        out_specs=[row, stat],
        out_shape=[
            jax.ShapeDtypeStruct((rows, hidden), x2d.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2d, w2)
    return y, rstd


def _rms_bwd_pallas(dy2d, x2d, rstd, w, affine, x_is_xhat, interpret):
    rows, hidden = x2d.shape
    br = _row_block(rows, hidden)
    nblocks = rows // br
    row, stat, vec, partial = _row_specs(br, hidden)
    w2 = (w if affine else jnp.ones((hidden,), jnp.float32)).reshape(1, hidden)
    out_specs = [row] + ([partial] if affine else [])
    out_shape = [jax.ShapeDtypeStruct((rows, hidden), dy2d.dtype)] + (
        [jax.ShapeDtypeStruct((1, hidden), jnp.float32)] if affine else []
    )
    outs = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, affine=affine, x_is_xhat=x_is_xhat),
        grid=(nblocks,),
        in_specs=[row, pl.BlockSpec((br, hidden), lambda i: (i, 0)), stat, vec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(dy2d, x2d, rstd, w2)
    if affine:
        return outs[0], outs[1][0]
    return outs[0], None


# ---------------------------------------------------------------------------
# XLA fallback (same math, fp32 stats)
# ---------------------------------------------------------------------------

def _ln_fwd_xla(x2d, w, b, eps, affine):
    x = x2d.astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd
    if affine:
        y = y * w.astype(jnp.float32) + b.astype(jnp.float32)
    return y.astype(x2d.dtype), mu, rstd


def _ln_bwd_xla(dy2d, x2d, mu, rstd, w, affine, x_is_xhat=False):
    dy = dy2d.astype(jnp.float32)
    x = x2d.astype(jnp.float32)
    xhat = x if x_is_xhat else (x - mu) * rstd
    wdy = dy * w.astype(jnp.float32) if affine else dy
    c1 = jnp.mean(xhat * wdy, axis=1, keepdims=True)
    c2 = jnp.mean(wdy, axis=1, keepdims=True)
    dx = ((wdy - xhat * c1 - c2) * rstd).astype(dy2d.dtype)
    dw = jnp.sum(dy * xhat, axis=0) if affine else None
    db = jnp.sum(dy, axis=0) if affine else None
    return dx, dw, db


def _rms_fwd_xla(x2d, w, eps, affine):
    x = x2d.astype(jnp.float32)
    ms = jnp.mean(x * x, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y = x * rstd
    if affine:
        y = y * w.astype(jnp.float32)
    return y.astype(x2d.dtype), rstd


def _rms_bwd_xla(dy2d, x2d, rstd, w, affine, x_is_xhat=False):
    dy = dy2d.astype(jnp.float32)
    x = x2d.astype(jnp.float32)
    xhat = x if x_is_xhat else x * rstd
    wdy = dy * w.astype(jnp.float32) if affine else dy
    c1 = jnp.mean(xhat * wdy, axis=1, keepdims=True)
    dx = ((wdy - xhat * c1) * rstd).astype(dy2d.dtype)
    dw = jnp.sum(dy * xhat, axis=0) if affine else None
    return dx, dw


# ---------------------------------------------------------------------------
# custom_vjp entry points
# ---------------------------------------------------------------------------

def _flatten(x, normalized_ndim: int):
    lead = x.shape[: x.ndim - normalized_ndim]
    hidden = 1
    for d in x.shape[x.ndim - normalized_ndim:]:
        hidden *= d
    rows = 1
    for d in lead:
        rows *= d
    return x.reshape(rows, hidden), lead


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def layer_norm(
    x,
    weight,
    bias,
    normalized_ndim: int = 1,
    eps: float = 1e-5,
    memory_efficient: bool = False,
    interpret: bool = False,
):
    """Fused LayerNorm over the trailing ``normalized_ndim`` dims.

    ``weight``/``bias`` may be ``None`` (non-affine; reference
    ``layer_norm_cuda.cpp`` non-affine ops). Stats are fp32 per row.
    """
    y, _, _ = _layer_norm_fwd_impl(x, weight, bias, normalized_ndim, eps, interpret)
    return y


def _layer_norm_fwd_impl(x, weight, bias, normalized_ndim, eps, interpret):
    affine = weight is not None
    x2d, lead = _flatten(x, normalized_ndim)
    wf = weight.reshape(-1) if affine else None
    bf = bias.reshape(-1) if (affine and bias is not None) else None
    if _use_pallas(x2d.shape[1], interpret):
        y2d, mu, rstd = _ln_fwd_pallas(x2d, wf, bf, eps, affine, interpret)
    else:
        y2d, mu, rstd = _ln_fwd_xla(x2d, wf, bf, eps, affine)
    return y2d.reshape(x.shape), mu, rstd


def _layer_norm_fwd(x, weight, bias, normalized_ndim, eps, memory_efficient, interpret):
    y, mu, rstd = _layer_norm_fwd_impl(x, weight, bias, normalized_ndim, eps, interpret)
    if memory_efficient:
        # save y, rebuild x in bwd from (y - b)/w * 1/rstd + mu
        res = (y, None, mu, rstd, weight, bias)
    else:
        res = (None, x, mu, rstd, weight, bias)
    return y, res


def _psum_partial_param_grad(grad, cotangent, param):
    """psum ``grad`` over mesh axes the cotangent varies on but the param
    does not (shard_map vma bookkeeping). A replicated param consumed by
    device-varying activations — e.g. LN weights under Megatron sequence
    parallelism, where each TP rank normalises its s/tp sequence slice —
    yields per-device *partial* dgamma/dbeta from the kernel. The reference
    handles this with an explicit TP all-reduce of params tagged
    ``sequence_parallel_enabled`` (``apex/transformer/layers/layer_norm.py``
    + Megatron's allreduce_sequence_parallel_gradients); here the custom
    VJP repairs its own vma so plain autodiff composes.
    """
    if grad is None or param is None:
        return grad
    try:
        c_vma = cotangent.aval.vma
        p_vma = param.aval.vma
    except AttributeError:  # outside shard_map
        return grad
    missing = tuple(a for a in c_vma if a not in p_vma)
    return jax.lax.psum(grad, missing) if missing else grad


def _clamp_by_magnitude(w, floor):
    """Clamp |w| away from zero, preserving sign (reference
    ``layer_norm_cuda_kernel.cu`` ``clamp_by_magnitude`` guard for the
    memory-efficient inverse-affine)."""
    mag = jnp.maximum(jnp.abs(w), floor)
    return jnp.where(w < 0, -mag, mag)


def _layer_norm_bwd(normalized_ndim, eps, memory_efficient, interpret, res, dy):
    y, x, mu, rstd, weight, bias = res
    affine = weight is not None
    x_is_xhat = x is None
    if x_is_xhat:
        # memory_efficient: re-derive xhat (fp32, never re-quantised) from the
        # saved output by inverting the affine with clamped gamma
        y2d, _ = _flatten(y, normalized_ndim)
        yf = y2d.astype(jnp.float32)
        if affine:
            w = _clamp_by_magnitude(weight.reshape(-1).astype(jnp.float32), eps)
            b = (
                bias.reshape(-1).astype(jnp.float32)
                if bias is not None
                else jnp.zeros_like(w)
            )
            x2d = (yf - b) / w  # == xhat
        else:
            x2d = yf
        xshape = y.shape
    else:
        x2d, _ = _flatten(x, normalized_ndim)
        xshape = x.shape
    dy2d, _ = _flatten(dy, normalized_ndim)
    wf = weight.reshape(-1) if affine else None
    if _use_pallas(x2d.shape[1], interpret):
        dx2d, dw, db = _ln_bwd_pallas(dy2d, x2d, mu, rstd, wf, affine, x_is_xhat, interpret)
    else:
        dx2d, dw, db = _ln_bwd_xla(dy2d, x2d, mu, rstd, wf, affine, x_is_xhat)
    dx = dx2d.reshape(xshape)
    dweight = dw.reshape(weight.shape).astype(weight.dtype) if affine else None
    dbias = (
        db.reshape(bias.shape).astype(bias.dtype)
        if (affine and bias is not None)
        else None
    )
    dweight = _psum_partial_param_grad(dweight, dy, weight)
    dbias = _psum_partial_param_grad(dbias, dy, bias)
    return dx, dweight, dbias


layer_norm.defvjp(_layer_norm_fwd, _layer_norm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def rms_norm(
    x,
    weight,
    normalized_ndim: int = 1,
    eps: float = 1e-5,
    memory_efficient: bool = False,
    interpret: bool = False,
):
    """Fused RMSNorm (no mean subtraction), per arXiv:1910.07467 — the
    reference's ``FusedRMSNormAffineFunction`` (``fused_layer_norm.py:195``)."""
    y, _ = _rms_norm_fwd_impl(x, weight, normalized_ndim, eps, interpret)
    return y


def _rms_norm_fwd_impl(x, weight, normalized_ndim, eps, interpret):
    affine = weight is not None
    x2d, _ = _flatten(x, normalized_ndim)
    wf = weight.reshape(-1) if affine else None
    if _use_pallas(x2d.shape[1], interpret):
        y2d, rstd = _rms_fwd_pallas(x2d, wf, eps, affine, interpret)
    else:
        y2d, rstd = _rms_fwd_xla(x2d, wf, eps, affine)
    return y2d.reshape(x.shape), rstd


def _rms_norm_fwd(x, weight, normalized_ndim, eps, memory_efficient, interpret):
    y, rstd = _rms_norm_fwd_impl(x, weight, normalized_ndim, eps, interpret)
    if memory_efficient:
        res = (y, None, rstd, weight)
    else:
        res = (None, x, rstd, weight)
    return y, res


def _rms_norm_bwd(normalized_ndim, eps, memory_efficient, interpret, res, dy):
    y, x, rstd, weight = res
    affine = weight is not None
    x_is_xhat = x is None
    if x_is_xhat:
        y2d, _ = _flatten(y, normalized_ndim)
        yf = y2d.astype(jnp.float32)
        if affine:
            w = _clamp_by_magnitude(weight.reshape(-1).astype(jnp.float32), eps)
            x2d = yf / w  # == xhat, fp32
        else:
            x2d = yf
        xshape = y.shape
    else:
        x2d, _ = _flatten(x, normalized_ndim)
        xshape = x.shape
    dy2d, _ = _flatten(dy, normalized_ndim)
    wf = weight.reshape(-1) if affine else None
    if _use_pallas(x2d.shape[1], interpret):
        dx2d, dw = _rms_bwd_pallas(dy2d, x2d, rstd, wf, affine, x_is_xhat, interpret)
    else:
        dx2d, dw = _rms_bwd_xla(dy2d, x2d, rstd, wf, affine, x_is_xhat)
    dx = dx2d.reshape(xshape)
    dweight = dw.reshape(weight.shape).astype(weight.dtype) if affine else None
    dweight = _psum_partial_param_grad(dweight, dy, weight)
    return dx, dweight


rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)
