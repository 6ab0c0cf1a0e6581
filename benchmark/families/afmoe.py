"""The ``afmoe`` family: Arcee's Trinity block (``model_type: afmoe``), read
from the published ``config.json``'s own keys, as ONE RANK of an
expert-parallel deployment holds it.

The block (``modeling_afmoe.py``; what ``config.json`` does not state is
listed under the configuration file's ``assumed``): ``h = E[tokens] *
sqrt(hidden)``; every layer ``h += norm(attn(norm(h)))``, ``h += norm(mlp(
norm(h)))`` with four gain-only RMSNorms; a final RMSNorm and an untied
head. Attention is bias-free with grouped K/V heads, RMSNorm over each head
of q and k, rotary positions on ``sliding_attention`` layers only (which
also see only keys ``0 <= i - j < sliding_window``; ``full_attention``
layers carry no positions), and a sigmoid gate on the context. The first
``num_dense_layers`` MLPs are SiLU-gated and dense; the others route: ``s =
sigmoid(W_r x)`` over all ``published.num_experts`` experts, the
``num_experts_per_tok`` largest are selected, ``w_e = route_scale * s_e /
sum of the selected s``, and ``y = shared(x) + sum over the selected
experts HELD HERE of w_e E_e(x)``.

The share (``deployment``): this rank holds experts ``0 .. num_experts - 1``
of every expert layer (``num_experts`` in the file is the count held; the
router keeps its published width) and vocabulary rows ``0 .. vocab_size -
1``. What the absent experts would add is left out, in the program and in
the reference alike, and the partial result goes on to the next layer.
Departure from the published model: the expert bias, which the published
training moves outside the gradient at ``load_balance_coeff``, is held at
zero and is no parameter here.

Everything above the recipes is plain ``jax.numpy`` in float32 at
``highest`` and imports nothing of ``apex_tpu``. The reference applies every
expert held to every token and weights it by ``w_e`` or zero (no sort, no
grouped product); it routes for itself. At the cell's 8,192 positions a
row's score tensor would not fit, so attention goes by blocks of queries
under ``jax.checkpoint``, the MLPs and the head by blocks of tokens, and a
layer takes the rows of a batch one after another: still a masked softmax
and plain matmuls. Its optimizer loop is its own (``reference.train_steps`` keeps the
state of two steps alive, which 705 M parameters in float32 do not allow on
16 GB): the same Adam, its buffers donated, the moments on the host while
the gradient is computed.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference, weights
from benchmark.reference import HIGHEST, proj, round_f8

REDUCIBLE = {
    "num_hidden_layers": "layers kept: the leading dense layer once, then "
                         "one whole period of layer_types",
    "num_dense_layers": "leading dense layers kept",
    "num_experts": "routed experts held here (the router keeps "
                   "published.num_experts outputs)",
    "vocab_size": "vocabulary rows held here (embedding and head; ids are "
                  "drawn from the slice)",
}
QUERY_BLOCK = 256       # queries a block of the reference's attention holds
TOKEN_BLOCK = 2048      # tokens a block of its MLPs and head holds


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------
def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The block's sizes as run. ``layer_types`` is the kept layers' (the
    file keeps the published list whole and names the layers kept of it
    under ``kept_layers``); ``experts`` the count held, ``router`` the
    router's width."""
    layers = int(config["num_hidden_layers"])
    types = [config["layer_types"][i] for i in config["kept_layers"]]
    if len(types) != layers:
        raise ValueError(f"kept_layers names {len(types)} layers of "
                         f"num_hidden_layers {layers}")
    return {
        "layers": layers, "dense_layers": int(config["num_dense_layers"]),
        "layer_types": types, "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "ffn": int(config["intermediate_size"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "shared_experts": int(config["num_shared_experts"]),
        "experts": int(config["num_experts"]),
        "router": int(config.get("published", {}).get(
            "num_experts", config["num_experts"])),
        "per_token": int(config["num_experts_per_tok"]),
        "window": int(config["sliding_window"]),
        "vocab": int(config["vocab_size"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "route_scale": float(config["route_scale"]),
        "route_norm": bool(config["route_norm"]),
    }


def _is_expert_layer(d, i: int) -> bool:
    return i >= d["dense_layers"]


def init_from_key(config, key, dtype):
    """The parameter tree of the program's ``layer_kinds`` stack (``params[
    "layers"]`` a list of one dict a layer; linears ``[out, in]``, the held
    experts' matrices ``[held, in, out]``): normal(0, 0.02) for every
    matrix, unit gains (``assumed.initialisation``)."""
    d = sizes(config)
    h, n, nkv, hd = d["hidden"], d["heads"], d["kv_heads"], d["head_dim"]
    keys = iter(jax.random.split(key, 16 * d["layers"] + 4))

    def w(*shape):
        x = jax.random.normal(next(keys), shape, jnp.float32) * 0.02
        if dtype == jnp.bfloat16:
            # round where the compiler cannot take the rounding out again:
            # the harness subtracts this start from the float32 masters
            # inside one program, and a float32 -> bf16 -> float32 pair that
            # XLA elides leaves the rounding error (4e-5 on 0.02) in the
            # change it reads
            x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return x.astype(dtype)

    ones = lambda size: jnp.ones((size,), dtype)
    layers = []
    for i in range(d["layers"]):
        lp = {"input_ln_w": ones(h), "post_attn_ln_w": ones(h),
              "post_ln_w": ones(h), "post_mlp_ln_w": ones(h),
              "q_w": w(n * hd, h), "k_w": w(nkv * hd, h),
              "v_w": w(nkv * hd, h), "attn_gate_w": w(n * hd, h),
              "proj_w": w(h, n * hd),
              "q_norm_w": ones(hd), "k_norm_w": ones(hd)}
        if _is_expert_layer(d, i):
            f, fs = d["expert_ffn"], d["expert_ffn"] * d["shared_experts"]
            lp.update(router_w=w(d["router"], h),
                      experts_gate_w=w(d["experts"], h, f),
                      experts_up_w=w(d["experts"], h, f),
                      experts_down_w=w(d["experts"], f, h))
            if fs:
                lp.update(shared_gate_w=w(fs, h), shared_up_w=w(fs, h),
                          shared_down_w=w(h, fs))
        else:
            lp.update(gate_w=w(d["ffn"], h), up_w=w(d["ffn"], h),
                      down_w=w(h, d["ffn"]))
        layers.append(lp)
    return {"embedding": {"word": w(d["vocab"], h)}, "layers": layers,
            "final_ln_w": ones(h), "lm_head": w(d["vocab"], h)}


def tensor_norms(config, tree):
    """Norms by published tensor: every leaf one norm, and each held
    expert's three matrices tensors of their own (the checkpoint names
    ``experts.<e>.gate_proj`` / ``up_proj`` / ``down_proj``)."""
    def norms(path, x):
        x = x.astype(jnp.float32)
        if "experts_" in jax.tree_util.keystr(path):
            return jnp.sqrt(jnp.sum(x * x, axis=(1, 2)))
        return jnp.sqrt(jnp.sum(x * x))

    return jax.tree_util.tree_map_with_path(norms, tree)


# ---------------------------------------------------------------------------
# the plain reference's block
# ---------------------------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, theta):
    """Rotary positions over all of the last dimension of ``[..., s, n,
    d]``, rotate-half convention."""
    s, _, dim = x.shape[-3:]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _by_blocks(fn: Callable, x, block: int):
    """``fn`` over blocks of ``block`` leading rows of ``x`` (a tree of
    arrays with one leading size), each block under ``jax.checkpoint``;
    the results concatenated."""
    n = jax.tree_util.tree_leaves(x)[0].shape[0]
    block = min(block, n)
    if n % block:
        raise ValueError(f"{n} rows do not divide into blocks of {block}")
    split = jax.tree_util.tree_map(
        lambda a: a.reshape(n // block, block, *a.shape[1:]), x)
    out = jax.lax.map(jax.checkpoint(fn), split)
    return jax.tree_util.tree_map(
        lambda a: a.reshape(n, *a.shape[2:]), out)


def _softmax_attention(q, k, v, d, sliding: bool):
    """One row's masked softmax attention, by blocks of queries: ``q [s,
    kv_heads, group, hd]`` (query head n is K/V head n // group), ``k``,
    ``v [s, kv_heads, hd]``."""
    s, hd = q.shape[0], q.shape[-1]
    ki = jnp.arange(s)[None, None, None, :]

    def block(args):
        qb, qi = args                           # [bq, kv, group, hd], [bq]
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k,
                            precision=HIGHEST) / (hd ** 0.5)
        qi = qi[None, None, :, None]
        masked = ki > qi
        if sliding:
            masked = masked | (qi - ki >= d["window"])
        probs = jax.nn.softmax(jnp.where(masked, -jnp.inf, scores), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, v, precision=HIGHEST)

    return _by_blocks(block, (q, jnp.arange(s)), QUERY_BLOCK)


def _attention(x, lp, d, sliding: bool, quant: bool):
    """One row: ``x [s, hidden]``."""
    s = x.shape[0]
    n, nkv, hd = d["heads"], d["kv_heads"], d["head_dim"]
    q = proj(x, lp["q_w"], quant).reshape(s, n, hd)
    k = proj(x, lp["k_w"], quant).reshape(s, nkv, hd)
    v = proj(x, lp["v_w"], quant).reshape(s, nkv, hd)
    gate = proj(x, lp["attn_gate_w"], quant)
    q, k = _rms(q, lp["q_norm_w"], d["eps"]), _rms(k, lp["k_norm_w"], d["eps"])
    if sliding:
        q, k = _rotate(q, d["theta"]), _rotate(k, d["theta"])
    ctx = _softmax_attention(q.reshape(s, nkv, n // nkv, hd), k, v, d,
                             sliding)
    return proj(ctx.reshape(s, n * hd) * jax.nn.sigmoid(gate), lp["proj_w"],
                quant)


def _gated(x, gate_w, up_w, down_w, quant):
    return proj(jax.nn.silu(proj(x, gate_w, quant)) * proj(x, up_w, quant),
                down_w, quant)


def _matmul(x, w, quant):
    """``x @ w`` for an expert's ``[in, out]`` matrix."""
    if quant:
        x, w = round_f8(x), round_f8(w)
    return jnp.einsum("ti,io->to", x, w, precision=HIGHEST)


def route(x, router_w, d):
    """``(selected [tokens, k], weights [tokens, k])``: sigmoid scores over
    every expert of the router, the k largest (the expert bias is zero),
    normalised over all k selected, held or not."""
    scores = jax.nn.sigmoid(jnp.einsum("th,eh->te", x, router_w,
                                       precision=HIGHEST))
    picked, selected = jax.lax.top_k(scores, d["per_token"])
    if d["route_norm"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return selected, picked * d["route_scale"]


def _experts(x, lp, d, quant: bool):
    """``shared(x) + sum over the selected experts held here of w_e
    E_e(x)``: every held expert over every token, weighted by ``w_e`` or
    zero."""
    selected, w = route(x, lp["router_w"], d)

    def one(y, expert):
        e, gate_w, up_w, down_w = expert
        w_e = jnp.sum(jnp.where(selected == e, w, 0.0), axis=-1)
        out = _matmul(jax.nn.silu(_matmul(x, gate_w, quant))
                      * _matmul(x, up_w, quant), down_w, quant)
        return y + w_e[:, None] * out, None

    y = jnp.zeros_like(x)
    if "shared_gate_w" in lp:
        y = _gated(x, lp["shared_gate_w"], lp["shared_up_w"],
                   lp["shared_down_w"], quant)
    y, _ = jax.lax.scan(
        jax.checkpoint(one), y,
        (jnp.arange(d["experts"]), lp["experts_gate_w"],
         lp["experts_up_w"], lp["experts_down_w"]))
    return y


def _row_layer(x, lp, d, sliding: bool, quant: bool):
    """One row through one layer: ``x [s, hidden]``."""
    eps = d["eps"]
    a = _attention(_rms(x, lp["input_ln_w"], eps), lp, d, sliding, quant)
    x = x + _rms(a, lp["post_attn_ln_w"], eps)
    y = _rms(x, lp["post_ln_w"], eps)
    if "router_w" in lp:
        m = _by_blocks(lambda t: _experts(t, lp, d, quant), y, TOKEN_BLOCK)
    else:
        m = _by_blocks(lambda t: _gated(t, lp["gate_w"], lp["up_w"],
                                        lp["down_w"], quant), y, TOKEN_BLOCK)
    return x + _rms(m, lp["post_mlp_ln_w"], eps)


def _layer(x, lp, d, sliding: bool, quant: bool):
    """One layer over ``x [rows, s, hidden]``, one row after another, each
    under ``jax.checkpoint`` (a row's projections at 8,192 positions are
    134 MB apiece in float32)."""
    return jax.lax.map(jax.checkpoint(functools.partial(
        _row_layer, lp=lp, d=d, sliding=sliding, quant=quant)), x)


def loss_sum(params, tokens, labels, *, d, quant: bool = False):
    """Sum (not mean) of the cross-entropy over every position of the
    rows given; every layer under ``jax.checkpoint``."""
    x = params["embedding"]["word"][tokens] * (d["hidden"] ** 0.5)
    for lp, kind in zip(params["layers"], d["layer_types"]):
        x = jax.checkpoint(functools.partial(
            _layer, d=d, sliding=kind == "sliding_attention",
            quant=quant))(x, lp)
    x = _rms(x, params["final_ln_w"], d["eps"])

    def head(args):
        xb, lab = args
        lg = jnp.einsum("th,vh->tv", xb, params["lm_head"], precision=HIGHEST)
        return (jax.nn.logsumexp(lg, axis=-1)
                - jnp.take_along_axis(lg, lab[:, None], axis=-1)[:, 0])

    return jnp.sum(_by_blocks(
        head, (x.reshape(-1, d["hidden"]), labels.reshape(-1)), TOKEN_BLOCK))


# ---------------------------------------------------------------------------
# the reference's steps: reference.train_steps's Adam, buffers donated
# ---------------------------------------------------------------------------
def _adam_step(params, grads, m, v, t, scale, *, lr, b1, b2, eps):
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def leaf(p, g, m, v):
        g = g * scale
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        return p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps), m, v

    out = jax.tree_util.tree_map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@functools.cache
def _adam():
    """The jitted step; parameters, gradient and moments are donated where
    the backend can reuse them (the CPU cannot, and says so)."""
    donate = () if jax.default_backend() == "cpu" else (0, 1, 2, 3)
    return jax.jit(_adam_step, static_argnames=("lr", "b1", "b2", "eps"),
                   donate_argnums=donate)


def _to_host(tree):
    """The tree as numpy arrays, its device buffers released."""
    host = jax.device_get(tree)
    for x in jax.tree_util.tree_leaves(tree):
        x.delete()
    return host


def reference_steps(config, params0, batches, *, block_rows: int,
                    devices=None, quant: bool = False, rows_used=None):
    """What ``reference.train_steps`` returns, for this block: each step's
    loss, the first gradient's and the three-step change's norms by
    tensor. ``params0`` (float32) is consumed. One chip: ``devices``
    spreads nothing.

    Memory: 705 M parameters are 2.8 GB a float32 copy, and the gradient
    program needs 4.9 GB beside the parameters, so only the parameters
    stay on the device while it runs: both moments, and the start (kept in
    ``train.weights_dtype``, which holds it exactly), wait on the host."""
    optimizer = config["train"]["optimizer"]
    if optimizer["kind"] != "adam":
        raise ValueError(f"afmoe's reference steps under adam, not "
                         f"{optimizer['kind']!r}")
    hyper = {k: v for k, v in optimizer.items() if k != "kind"}
    d = sizes(config)
    start = _to_host(jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.dtype(config["train"]["weights_dtype"])), t))(
            params0))
    norms = jax.jit(functools.partial(tensor_norms, config))
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    vg = jax.jit(jax.value_and_grad(
        functools.partial(loss_sum, d=d, quant=quant)))
    params, moments = params0, None
    del params0
    losses, grad1 = [], None
    for i, (tokens, labels) in enumerate(batches):
        if rows_used is not None:
            tokens, labels = tokens[rows_used], labels[rows_used]
        tokens, labels = np.asarray(tokens), np.asarray(labels)
        loss, grads = vg(params, jnp.asarray(tokens), jnp.asarray(labels))
        m, v = ((zeros(grads), zeros(grads)) if moments is None
                else jax.device_put(moments))
        params, m, v = _adam()(params, grads, m, v, jnp.float32(i + 1),
                               jnp.float32(1.0 / tokens.size), **hyper)
        del grads
        losses.append(float(loss) / tokens.size)
        if i == 0:
            grad1 = reference.by_tensor(norms(m))
        moments = _to_host((m, v)) if i + 1 < len(batches) else None
        del m, v
    change = jax.jit(lambda a, b: tensor_norms(
        config, reference.diff(a, b)))(params, jax.device_put(start))
    return {"losses": losses, "grad1_norms": grad1,
            "change_norms": reference.by_tensor(change)}


# ---------------------------------------------------------------------------
# the work one step needs, from shapes
# ---------------------------------------------------------------------------
def attention_pairs(seq: int, window) -> float:
    """Score pairs one causal row of ``seq`` positions needs: ``j <= i``,
    and with a window ``i - j < window``."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * window


def _layer_pairs(d, seq: int):
    return [attention_pairs(seq, d["window"] if kind == "sliding_attention"
                            else None) for kind in d["layer_types"]]


def expected_assignments(d, tokens: int) -> float:
    """Assignments the experts held here expect from ``tokens`` tokens a
    layer: each of a token's ``per_token`` choices falls here with
    probability held / router."""
    return tokens * d["per_token"] * d["experts"] / d["router"]


def train_flops_per_step(config, batch: int, seq: int) -> float:
    """Matmul FLOPs of one forward + backward step (backward = 2 x forward)
    as the mathematics needs them: grouped-query projections and the gate,
    the score pairs each kind of layer needs, the routed experts at the
    expected number of assignments, the shared expert, the router, the
    dense MLP and the sliced head."""
    d = sizes(config)
    h, qd = d["hidden"], d["heads"] * d["head_dim"]
    kvd = d["kv_heads"] * d["head_dim"]
    tokens = batch * seq
    total = 0.0
    for i, pairs in enumerate(_layer_pairs(d, seq)):
        total += 2.0 * tokens * h * (3 * qd + 2 * kvd)      # q, gate, o; k, v
        total += 2 * 2.0 * batch * pairs * qd                # q k^T and p v
        if _is_expert_layer(d, i):
            total += 2.0 * tokens * h * d["router"]
            total += 3 * 2.0 * tokens * h * (
                d["expert_ffn"] * d["shared_experts"])
            total += 3 * 2.0 * expected_assignments(d, tokens) * h * (
                d["expert_ffn"])
        else:
            total += 3 * 2.0 * tokens * h * d["ffn"]
    total += 2.0 * tokens * h * d["vocab"]
    return 3.0 * total


def kernel_work(config, batch: int, seq: int, bytes_per_el: int = 2):
    """``{kernel: (flops, bytes)}`` of one step on one chip.

    ``flash_attention``: forward two matmuls and backward four over the
    pairs each layer's band holds; bytes: q, o (forward) and q, o, do, dq
    (backward) at the query heads, k, v and k, v, dk, dv at the K/V heads.

    ``grouped_matmul``: the three products of every expert layer, each
    forward, by its input and by its weight, over the EXPECTED number of
    assignments (``tokens x per_token x held / router``: the run's own
    routing leaves it by well under 1%, 131,072 draws at 1/8 in the cell);
    bytes: each product reads its rows and the held experts' weights once
    and writes its result (a recomputed forward earns nothing)."""
    d = sizes(config)
    pairs = sum(_layer_pairs(d, seq))
    qd, kvd = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    flash_flops = 6.0 * 2.0 * batch * pairs * qd
    flash_bytes = float(d["layers"] * batch * seq * 6 * (qd + kvd)
                        * bytes_per_el)
    expert_layers = sum(_is_expert_layer(d, i) for i in range(d["layers"]))
    rows = expected_assignments(d, batch * seq)
    h, f = d["hidden"], d["expert_ffn"]
    weight = d["experts"] * h * f
    one_product = 3 * 2.0 * rows * h * f                 # fwd, dlhs, drhs
    # fwd: lhs + weights + out; dlhs: dout + weights + dlhs; drhs: lhs +
    # dout + the weights' gradient
    one_bytes = (3 * weight + 3 * rows * (h + f)) * bytes_per_el
    return {
        "flash_attention": (flash_flops, flash_bytes),
        "grouped_matmul": (expert_layers * 3 * one_product,
                           float(expert_layers * 3 * one_bytes)),
    }


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------
def program_config(config: Dict[str, Any], **kw):
    """The program's ``GPTConfig`` for a configuration file: the block by
    the model's own shape, bf16 compute, no dropout."""
    from apex_tpu.transformer.testing import GPTConfig, LayerKind

    d = sizes(config)
    kinds = tuple(
        LayerKind(window=d["window"] if kind == "sliding_attention" else None,
                  rotary=kind == "sliding_attention",
                  experts=_is_expert_layer(d, i))
        for i, kind in enumerate(d["layer_types"]))
    return GPTConfig(
        num_layers=d["layers"], hidden_size=d["hidden"],
        num_attention_heads=d["heads"], num_kv_heads=d["kv_heads"],
        head_dim=d["head_dim"], ffn_hidden_size=d["ffn"],
        vocab_size=d["vocab"], layernorm_epsilon=d["eps"],
        hidden_dropout=0.0, attention_dropout=0.0,
        compute_dtype=jnp.bfloat16, layer_kinds=kinds, norm="rmsnorm",
        sandwich_norm=True, qk_norm=True, attention_gate=True,
        gated_mlp=True, linear_bias=False, learned_positions=False,
        rope_theta=d["theta"], embedding_scale=d["hidden"] ** 0.5,
        untied_head=True, num_experts=d["router"],
        experts_held=(0, d["experts"]), experts_per_token=d["per_token"],
        expert_ffn_size=d["expert_ffn"],
        shared_expert_ffn_size=d["expert_ffn"] * d["shared_experts"],
        router_score=config["score_func"], route_norm=d["route_norm"],
        route_scale=d["route_scale"], **kw)


def amp_o2_fused_adam(config, mix, seed, devices, interpret):
    """amp O2 -> ``scaled_value_and_grad`` -> ``FusedAdam(packed=True)
    .step(found_inf=)`` -> ``update_scale``, the ``gpt`` family's recipe of
    this name over this block: ``gpt_loss`` with the ``GPTConfig`` the
    configuration file gives. Every layer is recomputed whole in the
    backward pass (``recompute_granularity="full"``): 16,384 tokens of
    saved projections do not fit beside 705 M parameters' state."""
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.testing import gpt_loss
    from benchmark.train_cell import TrainProgram

    cfg = program_config(
        config, recompute_granularity="full",
        use_flash_attention=True if interpret else None)
    hyper = config["train"]["optimizer"]
    params = weights.init_params(init_from_key, config, seed, jnp.bfloat16)
    opt = FusedAdam(lr=hyper["lr"], betas=(hyper["b1"], hyper["b2"]),
                    eps=hyper["eps"], packed=True, packed_interpret=interpret)
    params, opt, amp_state = amp.initialize(params, opt, opt_level="O2")
    scaler = amp_state.scaler(0)
    grad_fn = amp.scaled_value_and_grad(
        lambda p, tokens, labels: gpt_loss(cfg, p, tokens, labels), scaler)

    def train_step(params, opt_state, sstate, tokens, labels):
        loss, grads, sstate = grad_fn(sstate, params, tokens, labels)
        params, opt_state = opt.step(grads, opt_state, params,
                                     found_inf=sstate.found_inf)
        return params, opt_state, scaler.update_scale(sstate), loss

    opt_state = jax.jit(opt.init)(params)
    spec = opt_state.spec
    return TrainProgram(
        train_step, (params, opt_state, amp_state.scaler_state(0)),
        moments=lambda st: spec.unpack(st[1].exp_avg, cast=False),
        masters=lambda st: spec.unpack(st[1].master_params, cast=False),
        opt_step=lambda st: st[1].step,
        init0=lambda key: init_from_key(config, key, jnp.bfloat16),
        tensor_norms=functools.partial(tensor_norms, config))


RECIPES: Dict[str, Callable] = {"amp_o2_fused_adam": amp_o2_fused_adam}
