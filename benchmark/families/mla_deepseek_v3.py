"""The ``mla_deepseek_v3`` family: latent attention (MLA) over a mixture of
experts (``model_type: deepseek_v3``; Moonlight-16B-A3B), read from the
published ``config.json``'s own keys, as ONE RANK of an expert-parallel
deployment holds it. (The file is not ``deepseek_v3.py``: an accepted test
looks for ``'bert.py', 'gpt.py'`` side by side in the sorted list of family
files, which a name between them would part.)

The block (``modeling_deepseek.py`` of the source repository; what
``config.json`` does not state is listed under the configuration file's
``assumed``): ``h = E[tokens]``; every layer ``h += attn(rms(h))``, ``h +=
mlp(rms(h))`` with gain-only RMSNorms; a final RMSNorm and an untied head.
Attention is bias-free: ``q = W_q x`` is ``qk_nope_head_dim +
qk_rope_head_dim`` a head; ``(c, k_r) = W_dkv x`` is ``kv_lora_rank`` and
ONE rotary key of ``qk_rope_head_dim`` for all heads; ``(k_n, v)`` a head
``= W_ukv rms(c)``; ``q_r`` and ``k_r`` are rotated over their own lanes,
pairs ``(2i, 2i + 1)`` by the angle ``pos * theta^(-2i / rope)`` (the
source permutes pairs to halves and rotates halves: the same scores);
``k = (k_n, k_r)``; scores ``q k^T / sqrt(nope + rope)``, causal, softmax;
the context is ``v_head_dim`` a head. The first ``first_k_dense_replace``
MLPs are SiLU-gated and dense; the others route as ``afmoe`` does at one
group (``topk_method: noaux_tc`` with ``n_group = topk_group = 1``): ``s =
sigmoid(W_r x)`` over all ``published.n_routed_experts``, the
``num_experts_per_tok`` largest selected, ``w_e = routed_scaling_factor *
s_e / sum of the selected s``, ``y = shared(x) + sum over the selected
experts HELD HERE of w_e E_e(x)``, the ``n_shared_experts`` shared experts
one gated MLP of their summed width.

The share (``deployment``) is ``afmoe``'s: this rank holds experts ``0 ..
n_routed_experts - 1`` of every expert layer (the file's count is the
count held; the router keeps its published width) and vocabulary rows ``0
.. vocab_size - 1``; what the absent experts would add is left out, in the
program and in the reference alike. The expert bias is held at zero and is
no parameter (a departure, as there).

Everything above the recipe is plain ``jax.numpy`` in float32 at
``highest`` and imports nothing of ``apex_tpu``; what the two expert
families share (RMSNorm, the gated MLP, routing, the held experts over every
token, blocks of rows under ``jax.checkpoint``, the donating Adam) is
``afmoe.py``'s, imported.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference, weights
from benchmark.families import afmoe
from benchmark.families.afmoe import (  # noqa: F401  (route: for callers)
    QUERY_BLOCK, TOKEN_BLOCK, _by_blocks, _experts, _gated, _rms,
    attention_pairs, expected_assignments, route, tensor_norms)
from benchmark.reference import HIGHEST, proj

REDUCIBLE = {
    "num_hidden_layers": "layers kept: the leading dense layer, then "
                         "expert layers (moe_layer_freq 1: a period is one)",
    "n_routed_experts": "routed experts held here (the router keeps "
                        "published.n_routed_experts outputs)",
    "vocab_size": "vocabulary rows held here (embedding and head; ids are "
                  "drawn from the slice)",
}


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------
def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The block's sizes as run; ``experts`` the count held, ``router`` the
    router's width, ``shared_ffn`` the shared experts' summed width."""
    layers = int(config["num_hidden_layers"])
    if len(config["kept_layers"]) != layers:
        raise ValueError(f"kept_layers names {len(config['kept_layers'])} "
                         f"layers of num_hidden_layers {layers}")
    for key, only in (("q_lora_rank", None), ("n_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("scoring_func", "sigmoid"), ("rope_scaling", None)):
        if config.get(key) != only:
            raise ValueError(f"{key} = {config.get(key)!r}: this family "
                             f"builds {only!r} only")
    return {
        "layers": layers,
        "dense_layers": int(config["first_k_dense_replace"]),
        "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "latent": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "value": int(config["v_head_dim"]),
        "ffn": int(config["intermediate_size"]),
        "expert_ffn": int(config["moe_intermediate_size"]),
        "shared_ffn": int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
        "experts": int(config["n_routed_experts"]),
        "router": int(config.get("published", {}).get(
            "n_routed_experts", config["n_routed_experts"])),
        "per_token": int(config["num_experts_per_tok"]),
        "vocab": int(config["vocab_size"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "route_norm": bool(config["norm_topk_prob"]),
    }


def _is_expert_layer(d, i: int) -> bool:
    return i >= d["dense_layers"]


def init_from_key(config, key, dtype):
    """The parameter tree of the program's ``layer_kinds`` stack with
    ``latent_kv`` (linears ``[out, in]`` in the published tensors' row
    order: ``q_w`` ``[head, (nope, rope)]``, ``kv_down_w`` ``[(latent,
    rope)]``, ``kv_up_w`` ``[head, (nope, value)]``; the held experts'
    matrices ``[held, in, out]``): normal(0, 0.02) for every matrix, unit
    gains (``assumed.initialisation``). A bf16 start is rounded with
    ``reduce_precision`` (``afmoe.init_from_key`` says why)."""
    d = sizes(config)
    h, n = d["hidden"], d["heads"]
    keys = iter(jax.random.split(key, 16 * d["layers"] + 4))

    def w(*shape):
        x = jax.random.normal(next(keys), shape, jnp.float32) * 0.02
        if dtype == jnp.bfloat16:
            x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return x.astype(dtype)

    ones = lambda size: jnp.ones((size,), dtype)
    layers = []
    for i in range(d["layers"]):
        lp = {"input_ln_w": ones(h), "post_ln_w": ones(h),
              "q_w": w(n * (d["nope"] + d["rope"]), h),
              "kv_down_w": w(d["latent"] + d["rope"], h),
              "kv_norm_w": ones(d["latent"]),
              "kv_up_w": w(n * (d["nope"] + d["value"]), d["latent"]),
              "proj_w": w(h, n * d["value"])}
        if _is_expert_layer(d, i):
            f, fs = d["expert_ffn"], d["shared_ffn"]
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


# ---------------------------------------------------------------------------
# the plain reference's block
# ---------------------------------------------------------------------------
def _rotate_pairs(x, theta):
    """Rotary positions over the last dimension of ``[s, heads, rope]``:
    the pair ``(2i, 2i + 1)`` by the angle ``pos * theta^(-2i / rope)``."""
    s, _, dim = x.shape
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    # [s, 1, dim / 2]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _softmax_attention(q, k, v):
    """One row's causal softmax attention, by blocks of queries: ``q``, ``k
    [s, heads, nope + rope]``, ``v [s, heads, value]``."""
    s, width = q.shape[0], q.shape[-1]
    ki = jnp.arange(s)[None, None, :]

    def block(args):
        qb, qi = args                               # [bq, heads, width], [bq]
        scores = jnp.einsum("qnd,tnd->nqt", qb, k,
                            precision=HIGHEST) / (width ** 0.5)
        probs = jax.nn.softmax(
            jnp.where(ki > qi[None, :, None], -jnp.inf, scores), axis=-1)
        return jnp.einsum("nqt,tnd->qnd", probs, v, precision=HIGHEST)

    return _by_blocks(block, (q, jnp.arange(s)), QUERY_BLOCK)


def attention(x, lp, d, quant: bool = False):
    """One row of latent attention: ``x [s, hidden]``."""
    s, n = x.shape[0], d["heads"]
    nope, rope, latent = d["nope"], d["rope"], d["latent"]
    q = proj(x, lp["q_w"], quant).reshape(s, n, nope + rope)
    ckv = proj(x, lp["kv_down_w"], quant)
    c = _rms(ckv[:, :latent], lp["kv_norm_w"], d["eps"])
    kv = proj(c, lp["kv_up_w"], quant).reshape(s, n, nope + d["value"])
    q_r = _rotate_pairs(q[..., nope:], d["theta"])
    k_r = _rotate_pairs(ckv[:, None, latent:], d["theta"])      # one head
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (s, n, rope))], axis=-1)
    ctx = _softmax_attention(q, k, kv[..., nope:])
    return proj(ctx.reshape(s, n * d["value"]), lp["proj_w"], quant)


def _row_layer(x, lp, d, quant: bool):
    """One row through one layer: ``x [s, hidden]``."""
    x = x + attention(_rms(x, lp["input_ln_w"], d["eps"]), lp, d, quant)
    y = _rms(x, lp["post_ln_w"], d["eps"])
    if "router_w" in lp:
        return x + _by_blocks(lambda t: _experts(t, lp, d, quant), y,
                              TOKEN_BLOCK)
    return x + _by_blocks(lambda t: _gated(
        t, lp["gate_w"], lp["up_w"], lp["down_w"], quant), y, TOKEN_BLOCK)


def loss_sum(params, tokens, labels, *, d, quant: bool = False):
    """Sum (not mean) of the cross-entropy over every position of the rows
    given; every layer under ``jax.checkpoint``, a layer's rows one after
    another."""
    x = params["embedding"]["word"][tokens]
    for lp in params["layers"]:
        x = jax.checkpoint(lambda x, lp: jax.lax.map(jax.checkpoint(
            functools.partial(_row_layer, lp=lp, d=d, quant=quant)), x))(
                x, lp)
    x = _rms(x, params["final_ln_w"], d["eps"])

    def head(args):
        xb, lab = args
        lg = jnp.einsum("th,vh->tv", xb, params["lm_head"], precision=HIGHEST)
        return (jax.nn.logsumexp(lg, axis=-1)
                - jnp.take_along_axis(lg, lab[:, None], axis=-1)[:, 0])

    return jnp.sum(_by_blocks(
        head, (x.reshape(-1, d["hidden"]), labels.reshape(-1)), TOKEN_BLOCK))


def reference_steps(config, params0, batches, *, block_rows: int,
                    devices=None, quant: bool = False, rows_used=None):
    """What ``reference.train_steps`` returns, for this block, by
    ``afmoe.reference_steps``'s loop (its donating Adam; only the
    parameters stay on the device while a gradient is computed: 669 M
    parameters are 2.7 GB a float32 copy). ``params0`` is consumed."""
    optimizer = config["train"]["optimizer"]
    if optimizer["kind"] != "adam":
        raise ValueError(f"mla_deepseek_v3's reference steps under adam, not "
                         f"{optimizer['kind']!r}")
    hyper = {k: v for k, v in optimizer.items() if k != "kind"}
    d = sizes(config)
    start = afmoe._to_host(jax.jit(lambda t: jax.tree_util.tree_map(
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
        params, m, v = afmoe._adam()(
            params, grads, m, v, jnp.float32(i + 1),
            jnp.float32(1.0 / tokens.size), **hyper)
        del grads
        losses.append(float(loss) / tokens.size)
        if i == 0:
            grad1 = reference.by_tensor(norms(m))
        moments = afmoe._to_host((m, v)) if i + 1 < len(batches) else None
        del m, v
    change = jax.jit(lambda a, b: tensor_norms(
        config, reference.diff(a, b)))(params, jax.device_put(start))
    return {"losses": losses, "grad1_norms": grad1,
            "change_norms": reference.by_tensor(change)}


# ---------------------------------------------------------------------------
# the work one step needs, from shapes
# ---------------------------------------------------------------------------
def _expert_layers(d) -> int:
    return sum(_is_expert_layer(d, i) for i in range(d["layers"]))


def train_flops_per_step(config, batch: int, seq: int) -> float:
    """Matmul FLOPs of one forward + backward step (backward = 2 x forward)
    as the mathematics needs them: the projections at their own widths (q,
    the down- and up-projection of the latent, o), the causal score pairs
    at ``nope + rope`` and the values at ``value``, the routed experts at
    the expected number of assignments, the shared experts, the router, the
    dense MLP and the sliced head."""
    d = sizes(config)
    h, n, tokens = d["hidden"], d["heads"], batch * seq
    qk, dv = d["nope"] + d["rope"], d["value"]
    attention_weights = (h * (n * qk + d["latent"] + d["rope"])
                         + d["latent"] * n * (d["nope"] + dv) + n * dv * h)
    per_layer = (2.0 * tokens * attention_weights
                 + 2.0 * batch * attention_pairs(seq, None) * n * (qk + dv))
    dense = 3 * 2.0 * tokens * h * d["ffn"]
    expert = (2.0 * tokens * h * d["router"]
              + 3 * 2.0 * tokens * h * d["shared_ffn"]
              + 3 * 2.0 * expected_assignments(d, tokens) * h
              * d["expert_ffn"])
    experts = _expert_layers(d)
    total = (d["layers"] * per_layer + (d["layers"] - experts) * dense
             + experts * expert + 2.0 * tokens * h * d["vocab"])
    return 3.0 * total


def kernel_work(config, batch: int, seq: int, bytes_per_el: int = 2):
    """``{kernel: (flops, bytes)}`` of one step on one chip, by
    ``afmoe.kernel_work``'s conventions.

    ``flash_attention``: forward two products (``q k^T`` over ``nope +
    rope``, ``p v`` over ``value``), backward four (``dv`` and ``dp`` over
    ``value``, ``dq`` and ``dk`` over ``nope + rope``), over every causal
    pair of every head; a recomputed score earns nothing. Bytes, each
    tensor at its own width: q, k, v, o forward; q, k, v, o, do, dq, dk, dv
    backward, and of k the part every head shares (the rotary key) once.

    ``grouped_matmul``: as ``afmoe``'s, at this width and count."""
    d = sizes(config)
    n, qk, dv = d["heads"], d["nope"] + d["rope"], d["value"]
    pairs = d["layers"] * attention_pairs(seq, None)
    flash_flops = 2.0 * batch * pairs * n * (3 * qk + 3 * dv)
    k_width = n * d["nope"] + d["rope"]
    flash_bytes = float(d["layers"] * batch * seq * 3
                        * (n * qk + k_width + 2 * n * dv) * bytes_per_el)
    rows = expected_assignments(d, batch * seq)
    h, f = d["hidden"], d["expert_ffn"]
    one_product = 3 * 2.0 * rows * h * f                 # fwd, dlhs, drhs
    one_bytes = (3 * d["experts"] * h * f + 3 * rows * (h + f)) * bytes_per_el
    experts = _expert_layers(d)
    return {
        "flash_attention": (flash_flops, flash_bytes),
        "grouped_matmul": (experts * 3 * one_product,
                           float(experts * 3 * one_bytes)),
    }


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------
def program_config(config: Dict[str, Any], **kw):
    """The program's ``GPTConfig`` for a configuration file: the block by
    the model's own shape with latent attention, bf16 compute, no dropout.
    A program without latent attention (this benchmark laid over an older
    checkout) cannot run the configuration: it exits at once."""
    from apex_tpu.transformer import testing

    if not hasattr(testing, "LatentKV"):
        raise SystemExit("this checkout's GPTConfig has no latent attention "
                         "(latent_kv): it cannot run a deepseek_v3 "
                         "configuration")
    d = sizes(config)
    kinds = tuple(
        testing.LayerKind(window=None, rotary=True,
                          experts=_is_expert_layer(d, i))
        for i in range(d["layers"]))
    return testing.GPTConfig(
        num_layers=d["layers"], hidden_size=d["hidden"],
        num_attention_heads=d["heads"], ffn_hidden_size=d["ffn"],
        vocab_size=d["vocab"], layernorm_epsilon=d["eps"],
        hidden_dropout=0.0, attention_dropout=0.0,
        compute_dtype=jnp.bfloat16, layer_kinds=kinds, norm="rmsnorm",
        latent_kv=testing.LatentKV(d["latent"], d["nope"], d["rope"],
                                   d["value"]),
        gated_mlp=True, linear_bias=False, learned_positions=False,
        rope_theta=d["theta"], untied_head=True, num_experts=d["router"],
        experts_held=(0, d["experts"]), experts_per_token=d["per_token"],
        expert_ffn_size=d["expert_ffn"],
        shared_expert_ffn_size=d["shared_ffn"],
        router_score=config["scoring_func"], route_norm=d["route_norm"],
        route_scale=d["route_scale"], **kw)


def amp_o2_fused_adam(config, mix, seed, devices, interpret):
    """``afmoe.amp_o2_fused_adam`` over this block: amp O2 ->
    ``scaled_value_and_grad`` -> ``FusedAdam(packed=True).step(found_inf=)``
    -> ``update_scale`` round ``gpt_loss`` with this family's ``GPTConfig``,
    every layer recomputed in the backward pass (``"full"``: 16,384 tokens
    of saved projections do not fit beside 669 M parameters' state)."""
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
