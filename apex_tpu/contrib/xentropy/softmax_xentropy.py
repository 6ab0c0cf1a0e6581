"""Fused softmax cross entropy with label smoothing, and the chunked LM-head
cross entropy.

Reference: ``apex/contrib/xentropy/softmax_xentropy.py:6-30`` over
``csrc/xentropy/xentropy_kernel.cu`` (718 LoC). The kernel's exact loss
(``xentropy_kernel.cu:428-429``)::

    loss = smoothing * (logsumexp(x) - mean(x)) + (1-smoothing) * (logsumexp(x) - x[label])

i.e. cross entropy against the mixture target ``(1-s)*onehot + s/K``.
Positions with ``label == padding_idx`` contribute zero loss and zero
gradient (the reference masks both fwd and bwd).

The CUDA kernel exists to (a) fuse max/sum-exp/gather into one pass and
(b) save only ``max_log_sum_exp`` for backward instead of the softmax
probabilities (in-place bwd). Under XLA, (a) is one fusion already, and (b)
is exactly what a ``jax.checkpoint`` of this function provides — the saved
residual is the logits; probabilities are never materialised in fp32 unless
the scheduler chooses to. ``half_to_float`` upcasts the returned losses (the
kernel always produces fp32 losses; the flag controls the saved softmax
dtype, moot here).

The LM head carries the kernel's idea across the head GEMM: both chunked
functions scan over row chunks and never hold the ``[N, V]`` logits.
``lm_head_cross_entropy`` returns per-row losses and replays each chunk's
head GEMM in backward. ``lm_head_cross_entropy_sum`` returns a weighted sum
of them, with the weights given in forward: the chunk's logits, their
logsumexp and the labels are all the gradient needs besides the scalar
cotangent, so the forward chunk loop computes ``d(hidden)`` and
``d(head_weight)`` too and backward only scales them. No GEMM is replayed
and nothing ``[N, V]`` is stored. Float16 keeps the replaying backward:
a gradient formed before the loss scale reaches it lies under float16's
range.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu._vma import pvary_union_like


@jax.named_scope("apex_tpu.cross_entropy")
def softmax_cross_entropy_loss(
    logits: jax.Array,
    labels: jax.Array,
    smoothing: float = 0.0,
    padding_idx: int = 0,
    half_to_float: bool = False,
) -> jax.Array:
    """Per-example smoothed CE; ``(N, K)`` logits + ``(N,)`` int labels ->
    ``(N,)`` fp32 losses, zeroed where ``labels == padding_idx``."""
    del half_to_float  # losses are always fp32 (kernel parity)
    x = logits.astype(jnp.float32)
    n, k = x.shape
    lse = jax.nn.logsumexp(x, axis=-1)
    picked = jnp.take_along_axis(x, labels[:, None], axis=-1)[:, 0]
    loss = smoothing * (lse - jnp.mean(x, axis=-1)) + (1.0 - smoothing) * (
        lse - picked
    )
    return jnp.where(labels == padding_idx, 0.0, loss)


class SoftmaxCrossEntropyLoss:
    """``.apply`` parity shim for the reference autograd-Function spelling
    (``SoftmaxCrossEntropyLoss.apply(logits, labels, ...)``)."""

    @staticmethod
    def apply(logits, labels, smoothing=0.0, padding_idx=0, half_to_float=False):
        return softmax_cross_entropy_loss(
            logits, labels, smoothing, padding_idx, half_to_float
        )


def _chunks(n: int, chunk_size: int) -> int:
    if n % chunk_size:
        raise ValueError(f"N ({n}) must be divisible by chunk_size ({chunk_size})")
    return n // chunk_size


def _has_float32_range(dtype) -> bool:
    return jnp.finfo(dtype).minexp <= jnp.finfo(jnp.float32).minexp


def _chunk_head(hrow, w, lrow):
    """One chunk of rows through the head: float32 logits ``[c, V]``, their
    logsumexp and the gold logit, each ``[c]``."""
    logits = jnp.einsum(
        "ch,vh->cv", hrow, w.astype(hrow.dtype),
        preferred_element_type=jnp.float32,
    )
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lrow[:, None], axis=-1)[:, 0]
    return logits, lse, gold


@jax.named_scope("apex_tpu.cross_entropy")
def lm_head_cross_entropy(
    hidden: jax.Array,  # [N, h] pre-head activations (any float dtype)
    head_weight: jax.Array,  # [V, h] (tied-embedding layout)
    labels: jax.Array,  # [N] int
    *,
    chunk_size: int = 2048,
) -> jax.Array:
    """Chunk-fused LM-head GEMM + cross entropy: per-row losses WITHOUT
    materialising the full ``[N, V]`` logits tensor.

    The head projection is where LM training's biggest single tensor lives
    (``[b*s, vocab]`` fp32 — 1.6 GB for GPT-2 at batch 8/seq 1024): this
    scans over row chunks, computes each chunk's logits, reduces them to
    ``logsumexp - gold`` immediately, and rematerialises the chunk in
    backward (``jax.checkpoint``), so peak memory holds ONE ``[chunk, V]``
    block. The loop-level analogue of the reference xentropy kernel's
    save-only-``max_log_sum_exp`` trick (``xentropy_kernel.cu``), applied
    across the head GEMM as well. A caller that reduces the losses to one
    weighted sum takes :func:`lm_head_cross_entropy_sum`, which replays
    nothing.

    Gradients: d(hidden) per chunk and d(head_weight) summed across chunks
    by the scan transpose. ``N`` must be divisible by ``chunk_size`` (pick
    any divisor; it only changes peak memory).
    """
    n, h = hidden.shape
    nc = _chunks(n, chunk_size)
    hc = hidden.reshape(nc, chunk_size, h)
    lc = labels.reshape(nc, chunk_size)

    @jax.checkpoint
    def chunk_loss(w, xs):
        hrow, lrow = xs
        _, lse, gold = _chunk_head(hrow, w, lrow)
        return lse - gold

    def body(carry, xs):
        return carry, chunk_loss(head_weight, xs)

    # a rolled scan: unrolled, several [chunk, V] fp32 logit blocks go live
    # at once, which read ~6 ms/step slower on the v5e (345M, round 5)
    _, losses = jax.lax.scan(body, None, (hc, lc))
    return losses.reshape(n)


@jax.named_scope("apex_tpu.cross_entropy")
def lm_head_cross_entropy_sum(
    hidden: jax.Array,  # [N, h] pre-head activations (any float dtype)
    head_weight: jax.Array,  # [V, h] (tied-embedding layout)
    labels: jax.Array,  # [N] int
    weights: jax.Array,  # [N] float row weights
    *,
    chunk_size: int = 2048,
) -> jax.Array:
    """``sum_i weights[i] * loss_i`` over the rows, as a float32 scalar, with
    ``loss_i`` the cross entropy of row ``i`` through the head
    (:func:`lm_head_cross_entropy`'s per-row loss).

    The gradient is computed in the forward chunk loop: per chunk,
    ``d = (softmax(logits) - onehot) * weights`` in float32,
    ``d(hidden) = d @ W`` kept per chunk in the activations' dtype and
    ``d(W) += d^T @ hidden`` summed in the head weight's dtype. Backward
    scales what forward kept by the cotangent and runs no GEMM; the
    residuals are ``d(hidden)`` ``[N, h]``, ``d(W)`` and the per-row losses
    (``weights``' cotangent). Where the cotangent is a power of two, as a
    loss scale is, the gradients round as the replaying backward's do.
    That needs float32's exponent range: with 1/n weights ``d`` lies far
    below float16's smallest normal until the loss scale reaches it, so
    where the rows or the head are float16 the replaying per-row loss is
    summed instead. A call that is not differentiated runs the losses
    alone. ``N`` must be divisible by ``chunk_size``.
    """
    _chunks(hidden.shape[0], chunk_size)
    weights = weights.astype(jnp.float32)
    if not all(_has_float32_range(a.dtype) for a in (hidden, head_weight)):
        return jnp.sum(weights * lm_head_cross_entropy(
            hidden, head_weight, labels, chunk_size=chunk_size))
    args = (hidden, head_weight, labels, weights)
    # under shard_map every operand varies where any does, so a replicated
    # head weight's gradient is summed over the shards by the pvary's
    # transpose, outside the rule
    return _ce_sum(*(pvary_union_like(a, args) for a in args), chunk_size)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ce_sum(hidden, head_weight, labels, weights, chunk_size):
    return jnp.sum(weights * lm_head_cross_entropy(
        hidden, head_weight, labels, chunk_size=chunk_size))


def _ce_sum_fwd(hidden, head_weight, labels, weights, chunk_size):
    n, h = hidden.shape
    nc = n // chunk_size
    dtype = hidden.dtype
    w = head_weight.astype(dtype)

    def body(dw, xs):
        hrow, lrow, wrow = xs
        logits, lse, gold = _chunk_head(hrow, w, lrow)
        # onehot as a broadcast iota-compare (fuses into the exp pass; a
        # scatter here forces an extra full [chunk, V] memory pass)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
                  == lrow[:, None])
        d = (jnp.exp(logits - lse[:, None]) - onehot) * wrow[:, None]
        dh = jnp.einsum("cv,vh->ch", d, w,
                        preferred_element_type=jnp.float32).astype(dtype)
        # summed in the head weight's dtype, as the replaying backward's
        # scan sums it: a float32 carry is another [V, h] float32 buffer
        # live beside the forward's activations
        dw = (dw + jnp.einsum("cv,ch->vh", d, hrow,
                              preferred_element_type=jnp.float32)
              ).astype(dw.dtype)
        return dw, (lse - gold, dh)

    xs = (hidden.reshape(nc, chunk_size, h), labels.reshape(nc, chunk_size),
          weights.reshape(nc, chunk_size))
    dw0 = pvary_union_like(jnp.zeros(head_weight.shape, head_weight.dtype),
                           (head_weight,))
    # a rolled scan, as lm_head_cross_entropy's
    dw, (losses, dh) = jax.lax.scan(body, dw0, xs)
    losses = losses.reshape(n)
    return jnp.sum(weights * losses), (dh.reshape(n, h), dw, losses)


def _ce_sum_bwd(chunk_size, res, g):
    dh, dw, losses = res
    return ((g * dh).astype(dh.dtype), (g * dw).astype(dw.dtype), None,
            g * losses)


_ce_sum.defvjp(_ce_sum_fwd, _ce_sum_bwd)
