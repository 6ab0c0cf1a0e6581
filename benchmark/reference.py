"""The plain reference: the published architecture in straightforward
``jax.numpy``, float32, matmuls at ``highest`` precision, no kernels, no
cache, no batching tricks. It imports nothing of ``apex_tpu``.

- :func:`logits` / :func:`loss_sum`: pre-LN transformer (GPT-2 medium as
  Megatron-LM trains it; BERT with ``causal=False``), learned positions,
  tanh-GeLU, tied output head, mean cross-entropy over every position.
- :func:`train_steps`: a few optimizer steps (Adam as Kingma & Ba with
  bias correction; LAMB as You et al. with NVIDIA's global-norm clip),
  gradients accumulated over blocks of rows so that float32 at full width
  fits the chip.

``quant`` switches on the control: the four projection matmuls of every
layer computed with both operands rounded to float8 (e4m3, one scale per
tensor) — the precision below the bf16 the configurations state.
``rows_used`` plants the faults a training cell can have.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round_f8(x):
    """Round to e4m3 under one scale for the tensor; the gradient passes
    straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / F8_MAX
    q = (x / scale).astype(F8).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _proj(x, w, quant: bool):
    """``x @ w.T`` for ``w`` stored ``[out, in]``."""
    if quant:
        x, w = _round_f8(x), _round_f8(w)
    return jnp.einsum("...i,oi->...o", x, w, precision=HIGHEST)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _layer(x, lp, *, heads: int, causal: bool, eps: float, quant: bool):
    b, s, h = x.shape
    d = h // heads
    y = _layer_norm(x, lp["input_ln_w"], lp["input_ln_b"], eps)
    qkv = _proj(y, lp["qkv_w"], quant) + lp["qkv_b"]
    # Megatron's layout: per head, [q | k | v]
    q, k, v = jnp.split(qkv.reshape(b, s, heads, 3 * d), 3, axis=-1)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k,
                        precision=HIGHEST) / (d ** 0.5)
    if causal:
        qi = jnp.arange(s)[:, None]
        ki = jnp.arange(s)[None, :]
        scores = jnp.where(ki > qi, -jnp.inf, scores)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bnqk,bknd->bqnd", probs, v,
                     precision=HIGHEST).reshape(b, s, h)
    x = x + _proj(ctx, lp["proj_w"], quant) + lp["proj_b"]
    y = _layer_norm(x, lp["post_ln_w"], lp["post_ln_b"], eps)
    y = _gelu(_proj(y, lp["fc1_w"], quant) + lp["fc1_b"])
    return x + _proj(y, lp["fc2_w"], quant) + lp["fc2_b"]


def hidden_states(params, tokens, *, heads: int, causal: bool,
                  eps: float = 1e-5, quant: bool = False):
    s = tokens.shape[1]
    emb = params["embedding"]
    x = emb["word"][tokens] + emb["position"][:s][None]
    layer = jax.checkpoint(functools.partial(
        _layer, heads=heads, causal=causal, eps=eps, quant=quant))

    def body(x, lp):
        return layer(x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _layer_norm(x, params["final_ln_w"], params["final_ln_b"], eps)


def logits(params, tokens, **kw):
    """``[batch, seq, vocab]`` float32."""
    return jnp.einsum("bsh,vh->bsv", hidden_states(params, tokens, **kw),
                      params["embedding"]["word"], precision=HIGHEST)


def loss_sum(params, tokens, labels, **kw):
    """Sum (not mean) of the cross-entropy over every position of the
    rows given, so that blocks of rows add up."""
    lg = logits(params, tokens, **kw)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


def f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def _loss_and_grad(params, tokens, labels, *, block_rows: int,
                   row_sharding=None, **kw):
    """Mean loss and its gradient over all rows, block by block. With
    ``row_sharding`` a block's rows are spread over the chips (the
    parameters stand on each): the same sum, sooner."""
    n_rows = tokens.shape[0]
    vg = jax.jit(jax.value_and_grad(functools.partial(loss_sum, **kw)))
    put = (jnp.asarray if row_sharding is None
           else functools.partial(jax.device_put, device=row_sharding))
    total, grads = 0.0, None
    for r0 in range(0, n_rows, block_rows):
        l, g = vg(params, put(tokens[r0:r0 + block_rows]),
                  put(labels[r0:r0 + block_rows]))
        total = total + l
        grads = g if grads is None else _tree_add(grads, g)
    n = n_rows * tokens.shape[1]
    return total / n, _tree_scale(grads, 1.0 / n)


_tree_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
_tree_scale = jax.jit(lambda a, s: jax.tree_util.tree_map(
    lambda x: x * s, a))


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"))
def _adam(params, grads, m, v, t, *, lr, b1, b2, eps):
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def leaf(p, g, m, v):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        return p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps), m, v

    out = jax.tree_util.tree_map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@functools.partial(jax.jit, static_argnames=(
    "lr", "b1", "b2", "eps", "wd", "max_grad_norm"))
def _lamb(params, grads, m, v, t, *, lr, b1, b2, eps, wd, max_grad_norm):
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                         for g in jax.tree_util.tree_leaves(grads)))
    clip = jnp.maximum(gnorm / max_grad_norm, 1.0)

    def leaf(p, g, m, v):
        g = g / clip
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        u = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p
        wn, un = jnp.sqrt(jnp.sum(p * p)), jnp.sqrt(jnp.sum(u * u))
        ratio = jnp.where((wn > 0) & (un > 0), wn / un, 1.0)
        return p - lr * ratio * u, m, v

    out = jax.tree_util.tree_map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def tensor_norms(tree, heads: int):
    """Norms by published tensor. The program stacks the layers' tensors
    and fuses q, k and v: a stacked leaf gives one norm a layer, and the
    fused qkv weight and bias give one each for q, k and v (Megatron's
    layout: per head, [q | k | v]). So a key's bias, whose gradient is
    nought under softmax, is a tensor of its own."""
    def norms(path, x):
        x = x.astype(jnp.float32)
        name = jax.tree_util.keystr(path)
        if "'layers'" not in name:
            return jnp.sqrt(jnp.sum(x * x))
        if "qkv" in name:
            x = x.reshape(x.shape[0], heads, 3, -1)
            return jnp.sqrt(jnp.sum(x * x, axis=(1, 3)))       # [L, 3]
        return jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1) ** 2, axis=1))

    return jax.tree_util.tree_map_with_path(norms, tree)


def tensor_diff_norms(a, b, heads: int):
    return tensor_norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b),
        heads)


_tensor_norms = jax.jit(tensor_norms, static_argnums=1)
_tensor_diff_norms = jax.jit(tensor_diff_norms, static_argnums=2)


def by_tensor(tree) -> Dict[str, float]:
    """Flatten the norms of :func:`tensor_norms` to ``{name: norm}``."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out: Dict[str, float] = {}
    for path, x in flat:
        name = jax.tree_util.keystr(path)
        x = np.asarray(x)
        for idx in np.ndindex(x.shape):
            out[name + "".join(f"[{i}]" for i in idx)] = float(x[idx])
    return out


def train_steps(params0, batches: Sequence, *, heads: int, causal: bool,
                optimizer: Dict[str, Any], ln_eps: float = 1e-5,
                block_rows: int = 2, quant: bool = False, rows_used: Optional[slice] = None,
                devices: Optional[Sequence] = None) -> Dict[str, Any]:
    """Follow ``len(batches)`` steps from ``params0`` (float32).

    Returns each step's loss, the per-leaf norm of the first gradient as
    the optimizer's first moment holds it after one step, and the per-leaf
    norm of the parameters' change over all the steps.

    Faults, for the control runs: ``rows_used`` trains on that slice of
    every batch only and takes the mean over it: half of the batch left
    out, or with one chip's rows, the exchange between chips left out.
    ``devices`` (more than one) spreads each block's rows over them.
    """
    kind = optimizer["kind"]
    hyper = {k: v for k, v in optimizer.items() if k != "kind"}
    row_sharding = None
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.asarray(devices), ("rows",))
        row_sharding = NamedSharding(mesh, P("rows"))
        params0 = jax.device_put(params0, NamedSharding(mesh, P()))
        block_rows = block_rows * len(devices)
    params = params0
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    m, v = zeros(params), zeros(params)
    losses, grad1 = [], None
    for i, (tokens, labels) in enumerate(batches):
        if rows_used is not None:
            tokens, labels = tokens[rows_used], labels[rows_used]
        loss, grads = _loss_and_grad(
            params, np.asarray(tokens), np.asarray(labels),
            block_rows=block_rows, row_sharding=row_sharding, heads=heads,
            causal=causal, eps=ln_eps, quant=quant)
        step = _adam if kind == "adam" else _lamb
        params, m, v = step(params, grads, m, v, jnp.float32(i + 1), **hyper)
        del grads
        losses.append(float(loss))
        if i == 0:
            grad1 = by_tensor(_tensor_norms(m, heads))
    return {"losses": losses, "grad1_norms": grad1,
            "change_norms": by_tensor(
                _tensor_diff_norms(params, params0, heads))}
