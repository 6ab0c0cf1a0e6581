"""fp8 (e4m3) GEMM with TransformerEngine-style delayed scaling.

Reference context: the reference's ``parallel_state`` builds an
amax-reduction group "for fp8 precision conversion"
(``apex/transformer/parallel_state.py:280-292``) — the communicator side
of a TE-style fp8 recipe; the GEMMs themselves live outside apex. Here
both halves are TPU-native: :func:`apex_tpu.transformer.parallel_state.
reduce_amax` is the group all-reduce (pmax over the (data, tensor) axes),
and this module is the fp8 GEMM path for ``fused_dense``.

Delayed scaling (the standard TE recipe): each fp8 tensor carries an
``amax_history`` ring of the last H observed ``max|x|`` values; the
quantization scale for step t is derived from the history BEFORE step t's
amax is recorded, so the scale is available without a pre-pass over the
data. ``scale = FP8_E4M3_MAX / (max(history) * 2**margin)``.

Two backward flavors: :func:`fp8_fused_dense` keeps dgrad/wgrad in the
INPUT precision (the conservative recipe half), while
:func:`fp8_fused_dense_qgrad` quantizes dY to e5m2 with a delayed
gradient scale — the FULL recipe — surfacing the backward-observed
gradient amax as the cotangent of a carrier argument (a pure function
cannot write state from its backward; :func:`record_grad_amax` folds it
in). On chips without native fp8 MXU paths (v5e) XLA upcasts the dot;
the API and numerics are identical, only the speedup is hardware-
dependent — ``docs/fp8_v5e.md`` has the last ratio measured on a chip.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

FP8_E4M3_MAX = 448.0
FP8_E5M2_MAX = 57344.0


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _pmax_nondiff(x, axes):
    """`lax.pmax` as a non-differentiable statistic: amaxes describe the
    data, not the graph, but `pmax` has no JVP rule, so a bare call
    inside a differentiated loss fails at linearization even downstream
    of `stop_gradient`. Forward = pmax; backward = zeros."""
    return jax.lax.pmax(x, axes)


def _pmax_nondiff_fwd(x, axes):
    return jax.lax.pmax(x, axes), None


def _pmax_nondiff_bwd(axes, _, g):
    return (jnp.zeros_like(g),)


_pmax_nondiff.defvjp(_pmax_nondiff_fwd, _pmax_nondiff_bwd)


class Fp8TensorMeta(NamedTuple):
    """Per-tensor delayed-scaling state."""

    amax_history: jax.Array  # [H] fp32, most recent at index 0
    scale: jax.Array  # fp32 scalar: multiply BEFORE the e4m3 cast


class Fp8DenseState(NamedTuple):
    """Delayed-scaling state for one fp8 dense layer: x and w metas, and
    (for the full recipe, :func:`fp8_fused_dense_qgrad`) the e5m2
    gradient meta ``g``."""

    x: Fp8TensorMeta
    w: Fp8TensorMeta
    g: Optional[Fp8TensorMeta] = None


def _init_meta(history_len: int) -> Fp8TensorMeta:
    return Fp8TensorMeta(
        amax_history=jnp.zeros((history_len,), jnp.float32),
        scale=jnp.float32(1.0),
    )


def init_fp8_dense_state(
    history_len: int = 16, with_grad_meta: bool = False
) -> Fp8DenseState:
    return Fp8DenseState(
        x=_init_meta(history_len),
        w=_init_meta(history_len),
        g=_init_meta(history_len) if with_grad_meta else None,
    )


def _quantize(x, scale, fp8_max, dtype):
    """Scale, saturate to the format's range, cast — one implementation
    so the quantization convention cannot diverge between formats."""
    xs = x.astype(jnp.float32) * scale
    return jnp.clip(xs, -fp8_max, fp8_max).astype(dtype)


def quantize_e4m3(x: jax.Array, scale: jax.Array) -> jax.Array:
    """e4m3: the activation/weight format."""
    return _quantize(x, scale, FP8_E4M3_MAX, jnp.float8_e4m3fn)


def quantize_e5m2(x: jax.Array, scale: jax.Array) -> jax.Array:
    """e5m2: the gradient format (TE recipe: wide exponent for the long
    dynamic-range tail of dY)."""
    return _quantize(x, scale, FP8_E5M2_MAX, jnp.float8_e5m2)


def _updated_meta(meta: Fp8TensorMeta, amax_now: jax.Array,
                  margin: float,
                  fp8_max: float = FP8_E4M3_MAX) -> Fp8TensorMeta:
    """Roll the history and derive the NEXT step's scale from it (delayed
    scaling: ``amax_now`` only influences future scales)."""
    hist = jnp.concatenate(
        [jnp.asarray(amax_now, jnp.float32)[None], meta.amax_history[:-1]]
    )
    amax = jnp.max(hist)
    scale = jnp.where(
        amax > 0.0,
        fp8_max / (amax * (2.0 ** margin)),
        jnp.float32(1.0),
    )
    return Fp8TensorMeta(amax_history=hist, scale=scale.astype(jnp.float32))


def _forward_metas(x, weight, state, margin, amax_reduction_axes):
    """Shared forward bookkeeping: observe (and optionally group-reduce)
    the x/w amaxes, return the rolled metas. The amaxes describe the
    data, not the graph — no gradient flows into them."""
    amax_x = jnp.max(jnp.abs(x)).astype(jnp.float32)
    amax_w = jnp.max(jnp.abs(weight)).astype(jnp.float32)
    if amax_reduction_axes is not None:
        axes = tuple(amax_reduction_axes) if isinstance(
            amax_reduction_axes, (tuple, list)) else amax_reduction_axes
        amax_x = _pmax_nondiff(amax_x, axes)
        amax_w = _pmax_nondiff(amax_w, axes)
    amax_x = jax.lax.stop_gradient(amax_x)
    amax_w = jax.lax.stop_gradient(amax_w)
    return (_updated_meta(state.x, amax_x, margin),
            _updated_meta(state.w, amax_w, margin))


@jax.custom_vjp
def _fp8_matmul(x, w, scale_x, scale_w):
    qx = quantize_e4m3(x, scale_x)
    qw = quantize_e4m3(w, scale_w)
    y = jnp.einsum(
        "...i,oi->...o", qx, qw, preferred_element_type=jnp.float32
    )
    return (y / (scale_x * scale_w)).astype(x.dtype)


def _fp8_matmul_fwd(x, w, scale_x, scale_w):
    return _fp8_matmul(x, w, scale_x, scale_w), (x, w)


def _dgrad_wgrad(x, w, dyf):
    """fp32 dgrad/wgrad shared by both backward flavors."""
    dx = jnp.einsum(
        "...o,oi->...i", dyf, w.astype(jnp.float32)
    ).astype(x.dtype)
    dw = jnp.einsum(
        "...o,...i->oi", dyf, x.astype(jnp.float32)
    ).astype(w.dtype)
    return dx, dw


def _fp8_matmul_bwd(res, dy):
    # straight-through: dgrad/wgrad in the input precision (TE's
    # conservative recipe half; _fp8_matmul_qgrad is the e5m2 version)
    x, w = res
    dx, dw = _dgrad_wgrad(x, w, dy.astype(jnp.float32))
    return dx, dw, None, None


_fp8_matmul.defvjp(_fp8_matmul_fwd, _fp8_matmul_bwd)


@jax.custom_vjp
def _fp8_matmul_qgrad(x, w, scale_x, scale_w, scale_g, grad_amax_carrier):
    del scale_g, grad_amax_carrier  # backward-only
    return _fp8_matmul(x, w, scale_x, scale_w)


def _fp8_matmul_qgrad_fwd(x, w, scale_x, scale_w, scale_g,
                          grad_amax_carrier):
    return _fp8_matmul(x, w, scale_x, scale_w), (x, w, scale_g)


def _fp8_matmul_qgrad_bwd(res, dy):
    # FULL TE recipe backward: dY quantized to e5m2 with the delayed
    # gradient scale before dgrad/wgrad. The observed amax(dY) leaves the
    # backward as the COTANGENT of grad_amax_carrier — the functional
    # side-channel for updating the gradient meta (delayed scaling needs
    # backward-time statistics, and a pure function cannot write state).
    x, w, scale_g = res
    amax_g = jnp.max(jnp.abs(dy)).astype(jnp.float32)
    qdy = quantize_e5m2(dy, scale_g)
    dx, dw = _dgrad_wgrad(x, w, qdy.astype(jnp.float32) / scale_g)
    return dx, dw, None, None, None, amax_g


_fp8_matmul_qgrad.defvjp(_fp8_matmul_qgrad_fwd, _fp8_matmul_qgrad_bwd)


def fp8_fused_dense(
    x: jax.Array,
    weight: jax.Array,  # [out, in] (torch Linear layout, like fused_dense)
    bias: Optional[jax.Array],
    state: Fp8DenseState,
    *,
    margin: float = 0.0,
    amax_reduction_axes=None,
):
    """e4m3 GEMM + bias with delayed scaling; returns ``(y, new_state)``.

    Quantizes with the CURRENT state's scales (derived from past history),
    then records this step's amaxes into the returned state. Inside
    ``shard_map``, pass ``amax_reduction_axes`` (or rely on
    ``parallel_state.get_amax_reduction_group()`` via
    ``parallel_state.reduce_amax``) so every rank sharing a tensor derives
    the same scale next step.
    """
    meta_x, meta_w = _forward_metas(x, weight, state, margin,
                                    amax_reduction_axes)
    y = _fp8_matmul(x, weight, state.x.scale, state.w.scale)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y, Fp8DenseState(x=meta_x, w=meta_w, g=state.g)


def fp8_fused_dense_qgrad(
    x: jax.Array,
    weight: jax.Array,
    bias: Optional[jax.Array],
    state: Fp8DenseState,
    grad_amax_carrier: jax.Array,
    *,
    margin: float = 0.0,
    amax_reduction_axes=None,
):
    """The FULL TE recipe: e4m3 forward + e5m2-quantized gradients.

    Like :func:`fp8_fused_dense`, plus the backward quantizes dY to e5m2
    with ``state.g``'s delayed scale. Because the gradient amax is only
    observed during BACKWARD, it cannot be written into the returned
    state by a pure forward — it surfaces as the COTANGENT of
    ``grad_amax_carrier`` (pass a per-layer ``jnp.float32(0.0)`` and
    include it in the differentiated arguments). Thread the returned
    ``new_state`` out as aux so the x/w forward scales keep calibrating:

        def loss(params, carrier):
            y, new_state = fp8_fused_dense_qgrad(x, w, b, state, carrier)
            return objective(y), new_state
        (_, new_state), (grads, amax_g) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, carrier)
        state = record_grad_amax(new_state, amax_g)

    Use one carrier per fp8 layer — cotangents of a shared carrier would
    SUM the amaxes where the recipe wants each layer's own max. The same
    summing applies under ``shard_map`` when the carrier is REPLICATED
    (the transpose psums each rank's cotangent): call this INSIDE the
    shard_map with a rank-varying carrier and fold the amax with
    ``record_grad_amax(..., amax_reduction_axes=group)`` there, rather
    than differentiating a replicated carrier through the shard_map
    boundary.
    """
    if state.g is None:
        raise ValueError(
            "fp8_fused_dense_qgrad needs a gradient meta: "
            "init_fp8_dense_state(with_grad_meta=True)"
        )
    meta_x, meta_w = _forward_metas(x, weight, state, margin,
                                    amax_reduction_axes)
    y = _fp8_matmul_qgrad(
        x, weight, state.x.scale, state.w.scale, state.g.scale,
        grad_amax_carrier,
    )
    if bias is not None:
        y = y + bias.astype(y.dtype)
    # g is updated later via record_grad_amax (backward-time statistic)
    return y, Fp8DenseState(x=meta_x, w=meta_w, g=state.g)


def record_grad_amax(
    state: Fp8DenseState,
    amax: jax.Array,
    *,
    margin: float = 0.0,
    amax_reduction_axes=None,
    fp8_max: float = FP8_E5M2_MAX,
) -> Fp8DenseState:
    """Fold a backward-observed gradient amax (the
    ``grad_amax_carrier`` cotangent) into the delayed-scaling g meta."""
    if state.g is None:
        raise ValueError("state has no gradient meta")
    amax = jnp.asarray(amax, jnp.float32)
    if amax_reduction_axes is not None:
        amax = jax.lax.pmax(amax, amax_reduction_axes)
    return state._replace(
        g=_updated_meta(state.g, amax, margin, fp8_max=fp8_max)
    )
