"""OpenFold fused attention — pair-biased MHA on the flash kernel.

Reference: ``apex/contrib/openfold_triton/mha.py`` — the Triton
``FusedAttenionCoreFunc`` (``:133``, ``AttnTri = ...apply`` ``:397``) takes
``(q, k, v, mask=None, bias=None, inf, is_training)`` with 4-dim
``[b, h, n, d]`` or 5-dim ``[1, b, h, n, d]`` operands, a {0,1} logit mask
applied additively as ``(mask - 1) * inf``, and an additive pair-bias
broadcastable to ``[b, h, n, n]`` (the AlphaFold triangle/row attention
shape); eager fallbacks ``_attention_bias``/``_attention_no_bias``
(``:400-466``); ``CanSchTriMHA`` schedule gate (``:36``) and module-level
``enable``/``disable``/``is_enabled`` toggles (``:20-33``).

Here the core is :func:`apex_tpu.ops.flash_attention.flash_attention` with
its native additive-bias support — same online-softmax tiles, no [n, n]
score tensor, dbias via the tile-wise backward — instead of a separate
Triton kernel family. The {0,1} mask folds into the kernel's key-padding
mask when it is key-only (``[b, 1, 1, K]``-broadcastable); a general mask
folds into the additive bias exactly as the reference does.
"""
from __future__ import annotations

import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_available,
)

_enabled: Optional[bool] = None


def is_enabled() -> Optional[bool]:
    """Mirror of the reference's module toggle (``mha.py:20``)."""
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def can_use_fused_attention(
    in_shape, has_bias: bool = True, training: bool = True,
    interpret: bool = False,
) -> bool:
    """Availability gate, the ``CanSchTriMHA`` analogue (``mha.py:36``):
    the reference checks head-dim ∈ {16,32,64,128} and its schedule table;
    here the flash kernel's own tileability gate decides."""
    del has_bias, training  # the flash kernel handles both uniformly
    n, d = in_shape[-2], in_shape[-1]
    return flash_attention_available(n, n, d, interpret=interpret)


def _drop5(x, what):
    """Strip a validated leading 1 dim from a 5-dim mask/bias operand."""
    if x.ndim == 5:
        if x.shape[0] != 1:
            raise ValueError(
                f"5-dim {what} must have a leading 1 dim, got {x.shape}"
            )
        return x[0]
    return x


_warned_fully_masked = False


def _is_traced(x) -> bool:
    """True for values that are abstract at this point (inside a trace)."""
    return not jax.core.is_concrete(x)


def _maybe_warn_fully_masked(key_mask):
    """One-time heads-up for the kv_mask fast path's edge semantics.

    The reference's ``(mask - 1) * inf`` bias makes a fully-masked row
    softmax to a uniform average over values; the kernel's ``kv_mask``
    input excludes masked keys exactly, so such a row yields zeros. Rows
    with >=1 live key agree to kernel tolerance either way. For traced
    masks (the jit/perf path) the divergence is unknowable at trace
    time, so the unconditional trace-time warning is opt-in via
    ``APEX_TPU_WARN_FULLY_MASKED=1`` (by default it would fire for every
    jitted caller whether or not a fully-masked row can ever occur —
    pure noise). Concrete masks are actually CHECKED, every call until
    one warns: the check is a host sync, but an eager-mode caller is not
    on the perf path, and a silent latch would miss the fully-padded
    batch the warning exists for when it arrives after a clean first
    batch.
    """
    global _warned_fully_masked
    if _warned_fully_masked:
        return
    if _is_traced(key_mask):
        fully_masked_possible = (
            os.environ.get("APEX_TPU_WARN_FULLY_MASKED", "0") == "1")
    else:
        fully_masked_possible = bool(
            jnp.any(~jnp.any(key_mask != 0, axis=-1))
        )
    if fully_masked_possible:
        _warned_fully_masked = True
        warnings.warn(
            "openfold attention_core: key-only masks use the flash "
            "kernel's exact kv_mask path — a row whose keys are ALL "
            "masked returns zeros, where the reference's (mask-1)*inf "
            "bias returns a uniform average over values. If you rely on "
            "the uniform-average behavior for fully-padded rows, fold "
            "the mask into `bias` instead.",
            stacklevel=4,
        )


def _to_bnsd(x):
    """[*, h, n, d] with 4 or 5 dims -> ([b, h, n, d], had_5dim)."""
    if x.ndim == 5:
        if x.shape[0] != 1:
            raise ValueError(
                f"5-dim operands must have a leading 1 dim, got {x.shape}"
            )
        return x[0], True
    if x.ndim != 4:
        raise ValueError(f"expected 4- or 5-dim operand, got {x.shape}")
    return x, False


def attention_core(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    inf: float = 1e9,
    is_training: bool = True,
    *,
    interpret: bool = False,
) -> jax.Array:
    """The ``AttnTri`` / ``FusedAttenionCoreFunc`` analogue.

    ``mask`` is {0,1} (1 = attend), broadcastable to ``[b, h, q, k]`` —
    typically the AlphaFold ``[b, 1, 1, k]`` key mask; ``bias`` is an
    additive logit bias broadcastable to ``[b, h, q, k]``. Differentiable
    in q/k/v/bias (like the reference, which returns dB but no dmask).

    Divergence from the reference for fully-masked rows: a key-only mask
    rides the kernel's ``kv_mask`` input, which excludes masked keys
    exactly — a row whose keys are ALL masked yields zeros. The reference
    instead adds a finite ``(mask - 1) * inf`` penalty, so such a row
    softmaxes to a uniform average over all values. Rows with at least one
    live key agree to kernel tolerance; AlphaFold-style callers that rely
    on the uniform-average behavior for fully-padded rows should pass the
    mask folded into ``bias`` instead.
    """
    del is_training  # dropout-free core, as in the reference kernel
    q, had5 = _to_bnsd(q)
    k, _ = _to_bnsd(k)
    v, _ = _to_bnsd(v)
    b, h, s_q, d = q.shape
    s_k = k.shape[2]

    kv_mask = None
    mask_bias = None
    if mask is not None:
        mask = _drop5(mask, "mask")
        # key-only masks ride the kernel's native padding-mask input;
        # anything else becomes additive logits, as the reference does
        # with (mask - 1) * inf
        if mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1:
            _maybe_warn_fully_masked(mask[:, 0, 0, :])
            kv_mask = jnp.broadcast_to(mask[:, 0, 0, :], (b, s_k))
        else:
            m = mask.astype(jnp.float32)
            while m.ndim < 4:
                m = m[None]
            # only the key dim needs materialising; the kernel broadcasts
            # size-1 batch/head/q dims itself
            if m.shape[-1] != s_k:
                m = jnp.broadcast_to(m, m.shape[:3] + (s_k,))
            # the reference returns no dmask: keep the folded mask out of
            # the autodiff graph so d(add_bias)/d(mask) inf-scaled terms
            # can't leak when a learned bias is also present
            mask_bias = jax.lax.stop_gradient((m - 1.0) * inf)
    add_bias = mask_bias
    if bias is not None:
        bias = _drop5(bias, "bias")
        while bias.ndim < 4:
            bias = bias[None]
        # the kernel itself broadcasts batch/head dims and a size-1 q dim;
        # only a size-1 KEY dim needs materialising
        if bias.shape[-1] != s_k:
            bias = jnp.broadcast_to(bias, bias.shape[:3] + (s_k,))
        if add_bias is None:
            add_bias = bias
        else:
            add_bias = add_bias + bias.astype(jnp.float32)

    o = flash_attention(
        q, k, v, bias=add_bias, kv_mask=kv_mask,
        # only a user-supplied bias carries gradients (the reference
        # returns dB but no dmask); a folded mask alone skips the O(s^2)
        # dbias emission in the backward
        bias_grad=bias is not None,
        interpret=interpret,
    )
    return o[None] if had5 else o


# reference alias (``AttnTri = FusedAttenionCoreFunc.apply``, mha.py:397)
AttnTri = attention_core


def attention_reference(
    q, k, v, mask=None, bias=None, inf: float = 1e9
) -> jax.Array:
    """Eager math (``_attention_bias``/``_attention_no_bias``,
    ``mha.py:400-466``) for tests: softmax(q@k.T/sqrt(d) + (mask-1)*inf
    [+ bias]) @ v."""
    q, had5 = _to_bnsd(q)
    k, _ = _to_bnsd(k)
    v, _ = _to_bnsd(v)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    a = jnp.einsum(
        "bhqd,bhkd->bhqk", q * scale, k, preferred_element_type=jnp.float32
    )
    if mask is not None:
        mask = _drop5(mask, "mask")
        a = a + (mask.astype(jnp.float32) - 1.0) * inf
    if bias is not None:
        bias = _drop5(bias, "bias")
        a = a + bias.astype(jnp.float32)
    a = jax.nn.softmax(a, axis=-1)
    o = jnp.einsum(
        "bhqk,bhkd->bhqd", a.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    return o[None] if had5 else o
