"""Fused transformer-block *tail* operations: bias + GeLU, bias + dropout +
residual, and the attention tail with the next sublayer's LayerNorm.

The TPU-native analogue of Apex's fused epilogues — ``csrc/fused_dense_cuda``'s
GEMM+bias+GeLU, ``csrc/fused_layer_norm_cuda``, and Megatron's
``bias_dropout_add`` — as three operations, each computed in float32 inside
and rounded once to the I/O dtype at every tensor it writes:

- :func:`bias_gelu`              ``gelu(x + bias)`` — the MLP
  up-projection epilogue (reference ``fused_dense_cuda``'s
  ``bias_gelu``/``bgradb`` kernel pair). For float32 inputs bitwise
  ``jax.nn.gelu(x + bias, approximate=True)`` on the XLA form.
- :func:`bias_dropout_residual`  ``residual + dropout(x + bias)`` — the
  Megatron ``bias_dropout_add`` fusion. Dropout is counter-hash dropout
  (the ``flash_attention.py`` pattern): the keep mask is a murmur3 hash of
  ``(seed, row, col)``, bit-identical between forward/backward and between
  kernel and XLA form, so no ``[s, b, h]`` mask tensor is ever kept.
- :func:`residual_add_layer_norm` ``sum = residual + dropout(x + bias);
  y = LN(sum)`` — the attention tail. Returns BOTH ``sum`` (the onward
  residual stream) and ``y``.

Every op is a ``custom_vjp`` with two forms of the same arithmetic: a
Pallas forward AND backward kernel over a ``(rows, n)`` view, and an XLA
form over ``[..., n]`` as it lies (backward via ``jax.vjp`` of the forward,
so its gradients are the autodiff of the reference math). Which one a call
lowers to is :func:`apex_tpu.ops.layer_norm._use_pallas`, one gate for
these and the plain norms: **on a TPU the XLA form, by what the chip
said** (PR 34; the table is in ``docs/fused_block.md``). GPT-2 345M on one
v5e, 8 x 1024 tokens a step: 36,389 tokens/s with the three kernels,
43,432 with XLA's own fusions of the same arithmetic (step 225.1 -> 188.6
ms), and each kernel loses on its own against a step whose other two tails
are XLA's: XLA folds a tail into the GEMMs either side of it, a kernel
stands between them as three sweeps and, seeing ``[s, b, n]`` as ``(rows,
n)``, pays a copy each way where 8 rows of bf16 batch do not fill a tile.
The kernels were on by default until then on the strength of
``BENCH_r05.json``'s 42.7% elementwise share, a profile older than the
packed optimizer, the flash layouts and selective recomputation.
``interpret=True`` runs the kernel bodies on the CPU (parity tests, the
benchmark's rehearsal, ``tools/static_audit.py``);
``APEX_TPU_FORCE_PALLAS_LN`` compiles them, for whoever asks the chip
again; :func:`fused_block_available` says which form a compiled call gets.

All public entry points run under an ``apex_tpu.fused_block`` named
scope (analysis rule 6: xplane breakdowns must attribute kernel time),
and the forward kernels carry stable names
(``apex_tpu_bias_gelu_fwd`` etc.) so name-matching remat policies — the
``recompute_granularity="selective_elementwise"`` policy in
``standalone_transformer_lm.py`` — can pin their outputs as saveable
where they are in the trace.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _keep_mask
from .layer_norm import _row_block, _use_pallas

# kernel names pinned by the selective_elementwise remat policy
# (standalone_transformer_lm._FUSED_BLOCK_SAVEABLE_KERNELS) and by the
# scopes-rule red test — rename only with both call sites
BIAS_GELU_FWD = "apex_tpu_bias_gelu_fwd"
BIAS_DROPOUT_RESIDUAL_FWD = "apex_tpu_bias_dropout_residual_fwd"
RESIDUAL_LN_FWD = "apex_tpu_residual_ln_fwd"

_SQRT_2_OVER_PI = 0.7978845608028654  # sqrt(2/pi)
_GELU_C = 0.044715


# ---------------------------------------------------------------------------
# plumbing
# ---------------------------------------------------------------------------

def _flat2d(x: jax.Array) -> Tuple[jax.Array, Tuple[int, ...]]:
    """View ``[..., n]`` as ``(rows, n)``."""
    n = x.shape[-1]
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    return x.reshape(rows, n), x.shape


def _resolve_seed(dropout_p: float, seed) -> jax.Array:
    """int32 scalar seed for the hash counters (required when p > 0;
    the flash_attention seed contract)."""
    if not 0.0 <= dropout_p < 1.0:
        raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
    if dropout_p > 0.0 and seed is None:
        raise ValueError(
            "dropout_p > 0 requires a seed (an int or int32 scalar; "
            "derive one per step, e.g. jax.random.randint)"
        )
    return jnp.asarray(seed if seed is not None else 0, jnp.int32)


def _tile_keep(seed, i, br, n, dropout_p):
    """fp32 {0,1} keep mask for a (br, n) row-block tile at grid step
    ``i`` — hashed on GLOBAL (row, col) so the mask is independent of
    the block decomposition (forward, backward, kernel and fallback all
    regenerate the identical mask from the seed alone)."""
    rowg = i * br + jax.lax.broadcasted_iota(jnp.int32, (br, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (br, n), 1)
    return _keep_mask(seed, jnp.int32(0), rowg, col, dropout_p)


def _keep_as_shaped(seed, shape, dropout_p):
    """The same mask over ``[..., n]`` as it lies: the row counter is the
    row-major index over the leading dims, so no ``(rows, n)`` view (a
    copy where the leading dims do not fill the tiles) is taken."""
    row = jnp.zeros(shape, jnp.int32)
    for d, size in enumerate(shape[:-1]):
        row = row * size + jax.lax.broadcasted_iota(jnp.int32, shape, d)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return _keep_mask(seed, jnp.int32(0), row, col, dropout_p)


def dropout_mask_reference(seed, rows: int, n: int,
                           dropout_p: float) -> jax.Array:
    """The exact (rows, n) keep mask the fused ops use (tests only)."""
    return _tile_keep(jnp.asarray(seed, jnp.int32), jnp.int32(0), rows, n,
                      dropout_p)


def _gelu_tanh_f32(x):
    """tanh-approximate GeLU in fp32 (``jax.nn.gelu(approximate=True)``
    math)."""
    inner = _SQRT_2_OVER_PI * (x + _GELU_C * x * x * x)
    return 0.5 * x * (1.0 + jnp.tanh(inner))


def _dgelu_tanh_f32(x):
    """d/dx of tanh-approximate GeLU, fp32."""
    inner = _SQRT_2_OVER_PI * (x + _GELU_C * x * x * x)
    t = jnp.tanh(inner)
    dinner = _SQRT_2_OVER_PI * (1.0 + 3.0 * _GELU_C * x * x)
    return 0.5 * (1.0 + t) + 0.5 * x * (1.0 - t * t) * dinner


def _kernel_scope():
    """Named scope carried by the pallas_call eqns THEMSELVES: the
    decorator on the public wrappers covers differentiated traces (AD
    inlines the custom_vjp fwd), but a forward-only trace keeps the
    custom_vjp opaque and the inner kernel eqns would audit as
    unscoped (rule 6)."""
    return jax.named_scope("apex_tpu.fused_block")


def _vec_spec(n: int):
    return pl.BlockSpec((1, n), lambda i: (0, 0))


def _row_spec(br: int, n: int):
    return pl.BlockSpec((br, n), lambda i: (i, 0))


def _seed_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# bias_gelu
# ---------------------------------------------------------------------------

def _bias_gelu_fwd_kernel(x_ref, b_ref, y_ref):
    xb = x_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = _gelu_tanh_f32(xb).astype(y_ref.dtype)


def _bias_gelu_bwd_kernel(dy_ref, x_ref, b_ref, dx_ref, db_ref):
    dy = dy_ref[:].astype(jnp.float32)
    xb = x_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    dx = dy * _dgelu_tanh_f32(xb)
    dx_ref[:] = dx.astype(dx_ref.dtype)

    # dbias accumulates into one (1, n) block revisited by every grid
    # step (TPU grid is sequential — the layer_norm dgamma pattern)
    @pl.when(pl.program_id(0) == 0)
    def _init():
        db_ref[:] = jnp.zeros_like(db_ref)

    db_ref[:] += jnp.sum(dx, axis=0, keepdims=True)


def _bias_gelu_fallback(x, bias):
    """The kernel's arithmetic as XLA ops: bias add and GeLU in fp32, one
    rounding to the output dtype (for fp32 inputs bitwise the unfused
    ``jax.nn.gelu(x + bias, approximate=True)``)."""
    xb = x.astype(jnp.float32) + bias.astype(jnp.float32)
    return jax.nn.gelu(xb, approximate=True).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _bias_gelu(x, bias, interpret):
    y, _ = _bias_gelu_fwd(x, bias, interpret)
    return y


def _bias_gelu_fwd(x, bias, interpret):
    if _use_pallas(x.shape[-1], interpret):
        x2, shape = _flat2d(x)
        rows, n = x2.shape
        br = _row_block(rows, n)
        with _kernel_scope():
            y2 = pl.pallas_call(
                _bias_gelu_fwd_kernel,
                name=BIAS_GELU_FWD,
                grid=(rows // br,),
                in_specs=[_row_spec(br, n), _vec_spec(n)],
                out_specs=_row_spec(br, n),
                out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
                interpret=interpret,
            )(x2, bias.reshape(1, n))
        return y2.reshape(shape), (x, bias)
    return _bias_gelu_fallback(x, bias), (x, bias)


def _bias_gelu_bwd(interpret, res, dy):
    x, bias = res
    if _use_pallas(x.shape[-1], interpret):
        x2, shape = _flat2d(x)
        rows, n = x2.shape
        br = _row_block(rows, n)
        dy2, _ = _flat2d(dy)
        with _kernel_scope():
            dx2, db = pl.pallas_call(
                _bias_gelu_bwd_kernel,
                name="apex_tpu_bias_gelu_bwd",
                grid=(rows // br,),
                in_specs=[_row_spec(br, n), _row_spec(br, n), _vec_spec(n)],
                out_specs=[_row_spec(br, n), _vec_spec(n)],
                out_shape=[
                    jax.ShapeDtypeStruct((rows, n), dy.dtype),
                    jax.ShapeDtypeStruct((1, n), jnp.float32),
                ],
                interpret=interpret,
            )(dy2, x2, bias.reshape(1, n))
        return dx2.reshape(shape), db[0].astype(bias.dtype)
    # fallback grads ARE the autodiff of the reference math
    _, vjp = jax.vjp(_bias_gelu_fallback, x, bias)
    return vjp(dy)


_bias_gelu.defvjp(_bias_gelu_fwd, _bias_gelu_bwd)


@jax.named_scope("apex_tpu.fused_block")
def bias_gelu(x: jax.Array, bias: jax.Array, *,
              interpret: bool = False) -> jax.Array:
    """Fused ``gelu(x + bias, approximate=True)`` over the trailing dim.

    The MLP up-projection epilogue (reference ``fused_dense_cuda``
    GEMM+bias+GeLU): call the projection with ``bias=None`` and add the
    bias here, in float32 with one rounding; on a TPU XLA makes it the
    GEMM's own epilogue. ``bias`` is 1-D ``[n]``.
    """
    if bias.ndim != 1 or bias.shape[0] != x.shape[-1]:
        raise ValueError(
            f"bias must be [{x.shape[-1]}], got {bias.shape}")
    return _bias_gelu(x, bias, bool(interpret))


# ---------------------------------------------------------------------------
# bias_dropout_residual
# ---------------------------------------------------------------------------

def _bdr_fwd_kernel(x_ref, b_ref, r_ref, seed_ref, out_ref, *, dropout_p):
    xb = x_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    if dropout_p > 0.0:
        keep = _tile_keep(seed_ref[0], pl.program_id(0),
                          x_ref.shape[0], x_ref.shape[1], dropout_p)
        xb = xb * keep * (1.0 / (1.0 - dropout_p))
    out = r_ref[:].astype(jnp.float32) + xb
    out_ref[:] = out.astype(out_ref.dtype)


def _bdr_bwd_kernel(dy_ref, seed_ref, dx_ref, db_ref, *, dropout_p):
    dy = dy_ref[:].astype(jnp.float32)
    if dropout_p > 0.0:
        keep = _tile_keep(seed_ref[0], pl.program_id(0),
                          dy_ref.shape[0], dy_ref.shape[1], dropout_p)
        dx = dy * keep * (1.0 / (1.0 - dropout_p))
    else:
        dx = dy
    dx_ref[:] = dx.astype(dx_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        db_ref[:] = jnp.zeros_like(db_ref)

    db_ref[:] += jnp.sum(dx, axis=0, keepdims=True)


def _bdr_fallback(x, bias, residual, seed, dropout_p):
    """Identical math as XLA ops: fp32 branch, hash keep mask from the
    same counters, one rounding to the output dtype."""
    xb = x.astype(jnp.float32) + bias.astype(jnp.float32)
    if dropout_p > 0.0:
        keep = _keep_as_shaped(seed, xb.shape, dropout_p)
        xb = xb * keep * (1.0 / (1.0 - dropout_p))
    return (residual.astype(jnp.float32) + xb).astype(residual.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bias_dropout_residual(x, bias, residual, seed, dropout_p, interpret):
    out, _ = _bdr_fwd(x, bias, residual, seed, dropout_p, interpret)
    return out


def _bdr_fwd(x, bias, residual, seed, dropout_p, interpret):
    if _use_pallas(x.shape[-1], interpret):
        x2, shape = _flat2d(x)
        rows, n = x2.shape
        br = _row_block(rows, n)
        r2, _ = _flat2d(residual)
        with _kernel_scope():
            out2 = pl.pallas_call(
                functools.partial(_bdr_fwd_kernel, dropout_p=dropout_p),
                name=BIAS_DROPOUT_RESIDUAL_FWD,
                grid=(rows // br,),
                in_specs=[_row_spec(br, n), _vec_spec(n), _row_spec(br, n),
                          _seed_spec()],
                out_specs=_row_spec(br, n),
                out_shape=jax.ShapeDtypeStruct((rows, n), residual.dtype),
                interpret=interpret,
            )(x2, bias.reshape(1, n), r2, seed.reshape(1))
        # kernel-path residuals: the bwd kernel regenerates the mask from
        # the seed and needs only dy — keeping x/residual alive here
        # would pin ~[s, b, h] per call for nothing (on the no-remat
        # config that is the exact activation memory the fusion saves).
        # 0-d tokens carry the dtypes; shapes come from dy.
        res = (jnp.zeros((), x.dtype), jnp.zeros((), bias.dtype), None,
               seed)
        return out2.reshape(shape), res
    return (_bdr_fallback(x, bias, residual, seed, dropout_p),
            (x, bias, residual, seed))


def _bdr_bwd(dropout_p, interpret, res, dy):
    x, bias, residual, seed = res
    if residual is None:  # pallas branch (static — mirrors _bdr_fwd)
        dy2, shape = _flat2d(dy)
        rows, n = dy2.shape
        br = _row_block(rows, n)
        with _kernel_scope():
            dx2, db = pl.pallas_call(
                functools.partial(_bdr_bwd_kernel, dropout_p=dropout_p),
                name="apex_tpu_bias_dropout_residual_bwd",
                grid=(rows // br,),
                in_specs=[_row_spec(br, n), _seed_spec()],
                out_specs=[_row_spec(br, n), _vec_spec(n)],
                out_shape=[
                    jax.ShapeDtypeStruct((rows, n), x.dtype),
                    jax.ShapeDtypeStruct((1, n), jnp.float32),
                ],
                interpret=interpret,
            )(dy2, seed.reshape(1))
        # dres is dy unchanged: the fwd output carries residual.dtype, so
        # its cotangent already does too
        return dx2.reshape(shape), db[0].astype(bias.dtype), dy, None
    _, vjp = jax.vjp(
        lambda xx, bb, rr: _bdr_fallback(xx, bb, rr, seed, dropout_p),
        x, bias, residual)
    return vjp(dy) + (None,)


_bias_dropout_residual.defvjp(_bdr_fwd, _bdr_bwd)


@jax.named_scope("apex_tpu.fused_block")
def bias_dropout_residual(
    x: jax.Array,
    bias: jax.Array,
    residual: jax.Array,
    *,
    dropout_p: float = 0.0,
    seed=None,
    interpret: bool = False,
) -> jax.Array:
    """Fused ``residual + dropout(x + bias)`` (Megatron's
    ``bias_dropout_add``).

    Dropout is counter-hash dropout: the keep mask is regenerated from
    ``seed`` in forward, backward, kernel and fallback alike — no mask
    tensor is ever materialised, and a fixed seed reproduces the exact
    mask everywhere. With ``dropout_p == 0`` this is a pure
    bias+residual add.
    """
    if bias.ndim != 1 or bias.shape[0] != x.shape[-1]:
        raise ValueError(
            f"bias must be [{x.shape[-1]}], got {bias.shape}")
    if x.shape != residual.shape:
        raise ValueError(
            f"x {x.shape} and residual {residual.shape} must match")
    seed = _resolve_seed(dropout_p, seed)
    return _bias_dropout_residual(x, bias, residual, seed,
                                  float(dropout_p), bool(interpret))


# ---------------------------------------------------------------------------
# residual_add_layer_norm
# ---------------------------------------------------------------------------

def _raln_fwd_kernel(x_ref, b_ref, r_ref, w_ref, lb_ref, seed_ref,
                     sum_ref, y_ref, mu_ref, rstd_ref, *, eps, dropout_p):
    xb = x_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    if dropout_p > 0.0:
        keep = _tile_keep(seed_ref[0], pl.program_id(0),
                          x_ref.shape[0], x_ref.shape[1], dropout_p)
        xb = xb * keep * (1.0 / (1.0 - dropout_p))
    s_full = r_ref[:].astype(jnp.float32) + xb
    sum_ref[:] = s_full.astype(sum_ref.dtype)
    # LN runs on the ROUNDED sum — the onward residual the next layer
    # actually sees — matching the unfused astype(dt) -> LN(f32) chain
    s = sum_ref[:].astype(jnp.float32)
    mu = jnp.mean(s, axis=1, keepdims=True)
    sc = s - mu
    var = jnp.mean(sc * sc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (sc * rstd) * w_ref[:].astype(jnp.float32) \
        + lb_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mu_ref[:] = mu
    rstd_ref[:] = rstd


def _raln_bwd_kernel(dsum_ref, dy_ref, sum_ref, mu_ref, rstd_ref, w_ref,
                     seed_ref, dres_ref, dx_ref, dw_ref, dlb_ref, db_ref,
                     *, dropout_p):
    dy = dy_ref[:].astype(jnp.float32)
    s = sum_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = (s - mu_ref[:]) * rstd
    wdy = dy * w_ref[:].astype(jnp.float32)
    c1 = jnp.mean(xhat * wdy, axis=1, keepdims=True)
    c2 = jnp.mean(wdy, axis=1, keepdims=True)
    dsum = (wdy - xhat * c1 - c2) * rstd \
        + dsum_ref[:].astype(jnp.float32)
    dres_ref[:] = dsum.astype(dres_ref.dtype)
    if dropout_p > 0.0:
        keep = _tile_keep(seed_ref[0], pl.program_id(0),
                          dy_ref.shape[0], dy_ref.shape[1], dropout_p)
        dx = dsum * keep * (1.0 / (1.0 - dropout_p))
    else:
        dx = dsum
    dx_ref[:] = dx.astype(dx_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        dlb_ref[:] = jnp.zeros_like(dlb_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    dw_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    dlb_ref[:] += jnp.sum(dy, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(dx, axis=0, keepdims=True)


def _raln_fallback(x, bias, residual, w, lb, seed, eps, dropout_p):
    """Identical math as XLA ops (the unfused reference chain: branch +
    bias, hash dropout, residual add rounded to the residual dtype, LN
    with fp32 stats on the rounded sum)."""
    s = _bdr_fallback(x, bias, residual, seed, dropout_p)
    sf = s.astype(jnp.float32)
    mu = jnp.mean(sf, axis=-1, keepdims=True)
    sc = sf - mu
    var = jnp.mean(sc * sc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (sc * rstd) * w.astype(jnp.float32) + lb.astype(jnp.float32)
    return s, y.astype(s.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _residual_add_layer_norm(x, bias, residual, w, lb, seed, eps,
                             dropout_p, interpret):
    out, _ = _raln_fwd(x, bias, residual, w, lb, seed, eps, dropout_p,
                       interpret)
    return out


def _raln_fwd(x, bias, residual, w, lb, seed, eps, dropout_p, interpret):
    if _use_pallas(x.shape[-1], interpret):
        x2, shape = _flat2d(x)
        rows, n = x2.shape
        br = _row_block(rows, n)
        stat = pl.BlockSpec((br, 1), lambda i: (i, 0))
        r2, _ = _flat2d(residual)
        with _kernel_scope():
            s2, y2, mu, rstd = pl.pallas_call(
                functools.partial(_raln_fwd_kernel, eps=eps,
                                  dropout_p=dropout_p),
                name=RESIDUAL_LN_FWD,
                grid=(rows // br,),
                in_specs=[_row_spec(br, n), _vec_spec(n), _row_spec(br, n),
                          _vec_spec(n), _vec_spec(n), _seed_spec()],
                out_specs=[_row_spec(br, n), _row_spec(br, n), stat, stat],
                out_shape=[
                    jax.ShapeDtypeStruct((rows, n), residual.dtype),
                    jax.ShapeDtypeStruct((rows, n), residual.dtype),
                    jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                    jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                ],
                interpret=interpret,
            )(x2, bias.reshape(1, n), r2, w.reshape(1, n),
              lb.reshape(1, n), seed.reshape(1))
        s = s2.reshape(shape)
        y = y2.reshape(shape)
        # kernel-path residuals: the saved sum replaces x/residual (the
        # branch choice is static, so the two paths may save different
        # leaves — None marks the unused slots)
        return (s, y), (None, bias, None, w, lb, seed, s, mu, rstd)
    out = _raln_fallback(x, bias, residual, w, lb, seed, eps, dropout_p)
    return out, (x, bias, residual, w, lb, seed, None, None, None)


def _raln_bwd(eps, dropout_p, interpret, res, cts):
    dsum_out, dy = cts
    if res[6] is not None:  # pallas branch (static — mirrors _raln_fwd)
        _, bias, _, w, lb, seed, s, mu, rstd = res
        s2, shape = _flat2d(s)
        rows, n = s2.shape
        br = _row_block(rows, n)
        stat = pl.BlockSpec((br, 1), lambda i: (i, 0))
        dsum2, _ = _flat2d(dsum_out)
        dy2, _ = _flat2d(dy)
        with _kernel_scope():
            dres2, dx2, dw, dlb, db = pl.pallas_call(
                functools.partial(_raln_bwd_kernel, dropout_p=dropout_p),
                name="apex_tpu_residual_ln_bwd",
                grid=(rows // br,),
                in_specs=[_row_spec(br, n), _row_spec(br, n),
                          _row_spec(br, n), stat, stat, _vec_spec(n),
                          _seed_spec()],
                out_specs=[_row_spec(br, n), _row_spec(br, n),
                           _vec_spec(n), _vec_spec(n), _vec_spec(n)],
                out_shape=[
                    jax.ShapeDtypeStruct((rows, n), s.dtype),
                    jax.ShapeDtypeStruct((rows, n), s.dtype),
                    jax.ShapeDtypeStruct((1, n), jnp.float32),
                    jax.ShapeDtypeStruct((1, n), jnp.float32),
                    jax.ShapeDtypeStruct((1, n), jnp.float32),
                ],
                interpret=interpret,
            )(dsum2, dy2, s2, mu, rstd, w.reshape(1, n), seed.reshape(1))
        return (dx2.reshape(shape), db[0].astype(bias.dtype),
                dres2.reshape(shape), dw[0].astype(w.dtype),
                dlb[0].astype(lb.dtype), None)
    x, bias, residual, w, lb, seed, _, _, _ = res
    _, vjp = jax.vjp(
        lambda xx, bb, rr, ww, ll: _raln_fallback(
            xx, bb, rr, ww, ll, seed, eps, dropout_p),
        x, bias, residual, w, lb)
    return vjp((dsum_out, dy)) + (None,)


_residual_add_layer_norm.defvjp(_raln_fwd, _raln_bwd)


@jax.named_scope("apex_tpu.fused_block")
def residual_add_layer_norm(
    x: jax.Array,
    bias: jax.Array,
    residual: jax.Array,
    ln_weight: jax.Array,
    ln_bias: jax.Array,
    *,
    eps: float = 1e-5,
    dropout_p: float = 0.0,
    seed=None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused ``sum = residual + dropout(x + bias); y = LayerNorm(sum)``.

    Returns ``(sum, y)``: ``sum`` is the onward residual stream (stored
    once, in the residual dtype), ``y`` the next sublayer's pre-LN input.
    LN stats are fp32 per row over the ROUNDED
    sum, matching the unfused ``astype(dt) -> layer_norm(f32)`` chain.
    """
    if bias.ndim != 1 or bias.shape[0] != x.shape[-1]:
        raise ValueError(
            f"bias must be [{x.shape[-1]}], got {bias.shape}")
    if x.shape != residual.shape:
        raise ValueError(
            f"x {x.shape} and residual {residual.shape} must match")
    seed = _resolve_seed(dropout_p, seed)
    return _residual_add_layer_norm(
        x, bias, residual, ln_weight.reshape(-1), ln_bias.reshape(-1),
        seed, float(eps), float(dropout_p), bool(interpret))


def fused_block_available(n: int) -> bool:
    """Whether a compiled call would engage the kernels for trailing dim
    ``n`` on this backend (on a TPU: only when forced; the introspection
    hook ``chip_smoke.py``'s kernel inventory reads)."""
    return _use_pallas(n, False)
