"""Standalone Megatron-style transformer LM, TPU-native.

Reference: ``apex/transformer/testing/standalone_transformer_lm.py`` (1574
LoC) — the in-repo Megatron-LM clone used by the transformer test suite and
GPT/BERT scaling harnesses: ``ParallelMLP`` (``:89``), ``ParallelAttention``
(``:210``), ``ParallelTransformerLayer``, ``ParallelTransformer``,
embeddings, and ``post_language_model_processing`` heads.

TPU-native design: the model is a pure function over an explicit parameter
pytree in the Megatron ``[s, b, h]`` layout, built from the
``tensor_parallel`` functional cores. Two execution modes share one code
path:

- ``axis_name=None`` — dense single-device math (weights global);
- ``axis_name="tensor"`` — inside ``shard_map``; weights are the local TP
  shards and the collectives come from ``tensor_parallel.mappings``.

Layer weights are *stacked* ``[L, ...]`` and the layer loop is a
``lax.scan`` (one compiled layer body regardless of depth — the XLA
equivalent of Megatron reusing one CUDA graph per layer), with optional
rematerialisation. Pipeline stages slice the layer stack; the partition
specs for every weight are exported for pjit/shard_map wiring.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import parallel_state
from ..enums import AttnMaskType
from ..functional.fused_rope import _apply_rope
from ..functional.fused_softmax import FusedScaleMaskSoftmax
from ..tensor_parallel import (
    column_parallel_linear,
    row_parallel_linear,
    vocab_parallel_cross_entropy,
    vocab_parallel_embedding,
)
from ..tensor_parallel import mappings
from ...ops.layer_norm import layer_norm as fused_layer_norm
from ...ops.layer_norm import rms_norm
from ...ops.flash_attention import (
    FLASH_RESIDUAL_NAMES,
    flash_attention,
    flash_attention_available,
    flash_attention_qkv,
    flash_attention_sbhd,
    heads_per_block,
    mha_reference,
)
from ...ops.fused_block import (
    BIAS_DROPOUT_RESIDUAL_FWD,
    BIAS_GELU_FWD,
    RESIDUAL_LN_FWD,
    bias_dropout_residual,
    bias_gelu,
    residual_add_layer_norm,
)
from ...telemetry import numerics as _numerics

Pytree = Any


class LayerKind(NamedTuple):
    """What one layer of a mixed stack is (``GPTConfig.layer_kinds``)."""

    window: Optional[int] = None    # attention sees keys 0 <= i - j < window
    rotary: bool = False            # rotary positions on q and k
    experts: bool = False           # expert MLP (else the dense one)


class LatentKV(NamedTuple):
    """The shape of latent attention (``GPTConfig.latent_kv``): keys and
    values come from one compressed vector a token."""

    rank: int           # width of the compressed K/V vector (its own RMSNorm)
    nope_dim: int       # a head's q/k lanes that carry no position
    rope_dim: int       # its rotary lanes: ONE such key head serves all heads
    value_dim: int      # a head's value (and context) width


@dataclasses.dataclass
class GPTConfig:
    """Model shape config (the relevant subset of the reference's
    ``testing/arguments.py`` Megatron flag surface)."""

    num_layers: int = 4
    hidden_size: int = 64
    num_attention_heads: int = 4
    vocab_size: int = 512
    max_position_embeddings: int = 128
    ffn_hidden_size: Optional[int] = None  # default 4h
    layernorm_epsilon: float = 1e-5
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32  # bf16 for mixed precision
    tensor_model_parallel_size: int = 1
    sequence_parallel: bool = False
    apply_query_key_layer_scaling: bool = True
    attn_mask_type: AttnMaskType = AttnMaskType.causal
    # None | "full" | "selective" | "selective_elementwise" — see
    # transformer_block. "full" keeps a layer's input and, where the layer
    # runs the flash kernel, that kernel's output and row statistics (the
    # replay in backward holds no forward kernel); everything else is
    # recomputed. "selective_elementwise" additionally pins the
    # fused-block tail kernel outputs as saveable where those kernels are
    # in the trace (interpreted: tests, the rehearsal); a program compiled
    # for a TPU holds XLA's form of the tails, and the mode keeps what
    # "selective" keeps (docs/fused_block.md has the decision table).
    recompute_granularity: Optional[str] = None
    # Layer-scan unroll factor. 1 = one compiled layer body (fast compile,
    # the default for tests/virtual meshes); -1 = fully unrolled whatever
    # num_layers is (the single-chip perf configuration: removes the
    # per-layer dynamic-slice/update machinery — ~40 ms/step on the 345M
    # bench — at the cost of longer compiles). Intermediate values trade
    # between.
    layer_unroll: int = 1
    # None = auto (Pallas flash attention when available & applicable);
    # True forces it (errors if inapplicable); False forces the XLA path.
    use_flash_attention: Optional[bool] = None
    # Fused transformer-block tail (ops/fused_block.py): the projection
    # GEMMs run bias-free and each tail is one operation computed in
    # float32 and rounded once — bias+GeLU on the MLP up-projection,
    # bias+dropout+residual on the MLP output, bias+dropout+residual+LN
    # on the attention output. What an operation lowers to is the
    # library's choice (ops/layer_norm._use_pallas): on a TPU, XLA's own
    # fusions into the neighbouring GEMMs, which the chip read faster
    # than the Pallas kernels. Hidden dropout then uses counter-hash
    # dropout (seeded from the step key) instead of bernoulli-from-key —
    # same rate, different (deterministic) stream.
    # fused_block_interpret runs the kernel bodies under the Pallas
    # interpreter (CPU parity tests, the benchmark's rehearsal).
    fused_block: bool = False
    fused_block_interpret: bool = False
    # Context parallelism (long context): name of a mesh axis the SEQUENCE
    # is sharded over end-to-end — attention runs as ring attention over
    # that axis (apex_tpu.transformer.context_parallel). Composable with
    # the TP axis; mutually exclusive with sequence_parallel (Megatron SP
    # gathers the full sequence inside the block). zigzag selects the
    # load-balanced layout (rank r holds global chunks (r, 2cp-1-r);
    # zigzag_indices builds the permutation).
    context_parallel_axis: Optional[str] = None
    context_parallel_zigzag: bool = False
    # fp8 (e4m3 fwd + e5m2 grads, TE-style delayed scaling) on the four
    # projection GEMMs per layer (qkv / proj / fc1 / fc2). Thread
    # ``init_gpt_fp8_states(cfg)`` through ``gpt_loss(...,
    # fp8_states=..., fp8_carriers=...)``; amaxes are group-reduced over
    # ``fp8_amax_reduction_axes`` (the reference amax-reduction group
    # over (data, tensor), ``apex/transformer/parallel_state.py:280``).
    fp8: bool = False
    fp8_amax_reduction_axes: Optional[Tuple[str, ...]] = None
    # BERT extras
    add_binary_head: bool = False
    # --- the block by the model's own shape -------------------------------
    # ``layer_kinds`` (one LayerKind a layer) makes the stack a list of
    # layers of different kinds (window or full attention, rotary or no
    # positions, dense or expert MLP) run one after another, where the
    # default is one kind of layer scanned over stacked parameters. The
    # fields below describe that block's shape and are read on that path
    # only; ``init_gpt_params`` lays the parameters out to match.
    layer_kinds: Optional[Tuple[LayerKind, ...]] = None
    num_kv_heads: Optional[int] = None      # K/V heads; None: as many as query heads
    head_dim: Optional[int] = None          # None: hidden_size / heads
    norm: str = "layernorm"                 # | "rmsnorm" (gain only)
    sandwich_norm: bool = False             # a norm after each branch too
    qk_norm: bool = False                   # RMSNorm over each head of q and k
    attention_gate: bool = False            # out = Wo (ctx * sigmoid(Wg x))
    # latent attention (attention_by_kind): q is nope_dim + rope_dim a head,
    # k and v are up-projected from a normed latent of ``rank``, the rotary
    # key is shared by all heads; head_dim plays no part
    latent_kv: Optional[LatentKV] = None
    gated_mlp: bool = False                 # down(silu(gate x) * up x)
    linear_bias: bool = True                # False: bias-free linears
    learned_positions: bool = True          # False: no position table
    rope_theta: float = 10000.0
    embedding_scale: Optional[float] = None  # h = E[tokens] * scale
    untied_head: bool = False               # a head matrix of its own
    # experts (layers whose kind says so): the router is num_experts wide,
    # this rank holds experts_held = (first, count) of them
    num_experts: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    experts_per_token: int = 0
    expert_ffn_size: int = 0
    shared_expert_ffn_size: int = 0         # 0: no shared expert
    router_score: str = "sigmoid"           # | "softmax"
    route_norm: bool = True
    route_scale: float = 1.0

    def __post_init__(self):
        shaped = {
            "num_kv_heads": None, "head_dim": None, "norm": "layernorm",
            "sandwich_norm": False, "qk_norm": False,
            "attention_gate": False, "latent_kv": None, "gated_mlp": False,
            "linear_bias": True, "learned_positions": True,
            "embedding_scale": None, "untied_head": False, "num_experts": 0}
        if self.layer_kinds is None:
            changed = [k for k, v in shaped.items() if getattr(self, k) != v]
            if changed:
                raise ValueError(
                    f"{changed} describe the block that layer_kinds runs; "
                    "the scanned stack of one kind of layer runs the "
                    "LayerNorm + GeLU block only: set layer_kinds")
            return
        self.layer_kinds = tuple(LayerKind(*k) for k in self.layer_kinds)
        if len(self.layer_kinds) != self.num_layers:
            raise ValueError(
                f"layer_kinds names {len(self.layer_kinds)} layers of "
                f"num_layers {self.num_layers}")
        for what, on in (
                ("tensor parallelism (tensor_model_parallel_size > 1): the "
                 "grouped K/V heads, the gate and the experts have no "
                 "partition rule yet", self.tensor_model_parallel_size > 1),
                ("sequence_parallel: it is the tensor-parallel block's",
                 self.sequence_parallel),
                ("context_parallel_axis: ring attention has no window and "
                 "no grouped K/V heads", self.context_parallel_axis is not None),
                ("fp8: the delayed-scaling state is laid out for the four "
                 "GEMMs of the LayerNorm + GeLU block", self.fp8),
                ("fused_block: its tail kernels are written for bias + "
                 "LayerNorm + GeLU", self.fused_block),
                ("add_binary_head: BERT's", self.add_binary_head)):
            if on:
                raise ValueError(f"layer_kinds does not support {what}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.num_attention_heads % self.kv_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not divide into "
                f"{self.kv_heads} K/V heads")
        if self.latent_kv is not None:
            self.latent_kv = LatentKV(*self.latent_kv)
            for what, on in (
                    ("a window: the two-width flash path is not built with "
                     "one", any(k.window for k in self.layer_kinds)),
                    ("grouped K/V heads (num_kv_heads): every head has keys "
                     "and values of its own from the latent",
                     self.kv_heads != self.num_attention_heads),
                    ("head_dim: a head is nope_dim + rope_dim wide",
                     self.head_dim is not None),
                    ("qk_norm: the latent has a norm of its own",
                     self.qk_norm),
                    ("attention_gate: not built on this branch",
                     self.attention_gate),
                    ("linear_bias: its projections are bias-free",
                     self.linear_bias)):
                if on:
                    raise ValueError(f"latent_kv does not support {what}")
            if min(self.latent_kv) < 1 or self.latent_kv.rope_dim % 2:
                raise ValueError(
                    f"latent_kv {tuple(self.latent_kv)}: every width at "
                    "least 1 and an even rope_dim")
        if any(k.experts for k in self.layer_kinds):
            first, count = self.experts_held or (0, 0)
            if not (self.num_experts > 0 and count > 0 and first >= 0
                    and first + count <= self.num_experts
                    and 0 < self.experts_per_token <= self.num_experts
                    and self.expert_ffn_size > 0 and self.gated_mlp):
                raise ValueError(
                    "expert layers need num_experts, experts_held = (first, "
                    "count) inside it, experts_per_token, expert_ffn_size "
                    "and gated_mlp (the experts are gated MLPs)")

    @property
    def ffn_size(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def kv_channels(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_attention_heads


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_gpt_params(cfg: GPTConfig, key: jax.Array) -> Pytree:
    """Global (unsharded) parameter pytree.

    Init scheme mirrors Megatron (reference ``standalone_transformer_lm.py``
    init helpers): normal(0, 0.02) for weights, scaled by
    ``1/sqrt(2*num_layers)`` for output projections, zeros for biases, ones
    for LN weights.
    """
    if cfg.layer_kinds is not None:
        return _init_params_by_kind(cfg, key)
    h, L, v = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    ffn = cfg.ffn_size
    k = jax.random.split(key, 8)
    std = 0.02
    out_std = std / (2.0 * L) ** 0.5
    dt = cfg.params_dtype

    def n(kk, shape, s=std):
        return (jax.random.normal(kk, shape) * s).astype(dt)

    kl = jax.random.split(k[7], 6)
    params = {
        "embedding": {
            "word": n(k[0], (v, h)),
            "position": n(k[1], (cfg.max_position_embeddings, h)),
        },
        "layers": {
            "input_ln_w": jnp.ones((L, h), dt),
            "input_ln_b": jnp.zeros((L, h), dt),
            "qkv_w": n(kl[0], (L, 3 * h, h)),
            "qkv_b": jnp.zeros((L, 3 * h), dt),
            "proj_w": n(kl[1], (L, h, h), out_std),
            "proj_b": jnp.zeros((L, h), dt),
            "post_ln_w": jnp.ones((L, h), dt),
            "post_ln_b": jnp.zeros((L, h), dt),
            "fc1_w": n(kl[2], (L, ffn, h)),
            "fc1_b": jnp.zeros((L, ffn), dt),
            "fc2_w": n(kl[3], (L, h, ffn), out_std),
            "fc2_b": jnp.zeros((L, h), dt),
        },
        "final_ln_w": jnp.ones((h,), dt),
        "final_ln_b": jnp.zeros((h,), dt),
    }
    if cfg.add_binary_head:
        params["binary_head"] = {
            "pooler_w": n(k[2], (h, h)),
            "pooler_b": jnp.zeros((h,), dt),
            "head_w": n(k[3], (2, h)),
            "head_b": jnp.zeros((2,), dt),
        }
    return params


def _init_params_by_kind(cfg: GPTConfig, key: jax.Array) -> Pytree:
    """The parameters of a ``layer_kinds`` stack: ``params["layers"]`` is a
    list of one dict a layer (a layer's leaves depend on its kind, so they
    do not stack). Linears are ``[out, in]``; the held experts' matrices
    are ``[count, in, out]``, the layout the grouped product reads. Every
    matrix normal(0, 0.02), unit gains, zero biases."""
    h, v, dt = cfg.hidden_size, cfg.vocab_size, cfg.params_dtype
    n, nkv, d = cfg.num_attention_heads, cfg.kv_heads, cfg.kv_channels
    keys = iter(jax.random.split(key, 16 * cfg.num_layers + 8))

    def w(*shape):
        return (jax.random.normal(next(keys), shape) * 0.02).astype(dt)

    def norm_gains(*names):
        out = {f"{name}_w": jnp.ones((h,), dt) for name in names}
        if cfg.norm == "layernorm":
            out.update({f"{name}_b": jnp.zeros((h,), dt) for name in names})
        return out

    def linear(name, out_dim, in_dim):
        out = {f"{name}_w": w(out_dim, in_dim)}
        if cfg.linear_bias:
            out[f"{name}_b"] = jnp.zeros((out_dim,), dt)
        return out

    def dense_mlp(ffn):
        if cfg.gated_mlp:
            return {**linear("gate", ffn, h), **linear("up", ffn, h),
                    **linear("down", h, ffn)}
        return {**linear("fc1", ffn, h), **linear("fc2", h, ffn)}

    layers = []
    for kind in cfg.layer_kinds:
        lp = norm_gains("input_ln", "post_ln")
        if cfg.sandwich_norm:
            lp.update(norm_gains("post_attn_ln", "post_mlp_ln"))
        if cfg.latent_kv is not None:
            lat = cfg.latent_kv
            # the published tensors' row order: q [head, (nope, rope)],
            # kv_down [(latent, rope)], kv_up [head, (nope, value)]
            lp.update(linear("q", n * (lat.nope_dim + lat.rope_dim), h))
            lp.update(linear("kv_down", lat.rank + lat.rope_dim, h))
            lp["kv_norm_w"] = jnp.ones((lat.rank,), dt)
            lp.update(linear("kv_up", n * (lat.nope_dim + lat.value_dim),
                             lat.rank))
            lp.update(linear("proj", h, n * lat.value_dim))
        else:
            lp.update(linear("q", n * d, h))
            lp.update(linear("k", nkv * d, h))
            lp.update(linear("v", nkv * d, h))
            lp.update(linear("proj", h, n * d))
        if cfg.attention_gate:
            lp.update(linear("attn_gate", n * d, h))
        if cfg.qk_norm:
            lp["q_norm_w"] = jnp.ones((d,), dt)
            lp["k_norm_w"] = jnp.ones((d,), dt)
        if kind.experts:
            count, f = cfg.experts_held[1], cfg.expert_ffn_size
            lp["router_w"] = w(cfg.num_experts, h)
            lp["experts_gate_w"] = w(count, h, f)
            lp["experts_up_w"] = w(count, h, f)
            lp["experts_down_w"] = w(count, f, h)
            if cfg.shared_expert_ffn_size:
                fs = cfg.shared_expert_ffn_size
                lp["shared_gate_w"], lp["shared_up_w"] = w(fs, h), w(fs, h)
                lp["shared_down_w"] = w(h, fs)
        else:
            lp.update(dense_mlp(cfg.ffn_size))
        layers.append(lp)
    params = {"embedding": {"word": w(v, h)}, "layers": layers,
              **norm_gains("final_ln")}
    if cfg.learned_positions:
        params["embedding"]["position"] = w(cfg.max_position_embeddings, h)
    if cfg.untied_head:
        params["lm_head"] = w(v, h)
    return params


def gpt_partition_specs(cfg: GPTConfig) -> Pytree:
    """PartitionSpec per parameter for the TP mesh axis (Megatron sharding:
    column weights row-sharded, row weights column-sharded, vocab sharded,
    LN replicated)."""
    t = parallel_state.TENSOR_AXIS
    specs = {
        "embedding": {"word": P(t, None), "position": P()},
        "layers": {
            "input_ln_w": P(), "input_ln_b": P(),
            "qkv_w": P(None, t, None), "qkv_b": P(None, t),
            "proj_w": P(None, None, t), "proj_b": P(),
            "post_ln_w": P(), "post_ln_b": P(),
            "fc1_w": P(None, t, None), "fc1_b": P(None, t),
            "fc2_w": P(None, None, t), "fc2_b": P(),
        },
        "final_ln_w": P(), "final_ln_b": P(),
    }
    if cfg.add_binary_head:
        specs["binary_head"] = {
            "pooler_w": P(), "pooler_b": P(), "head_w": P(), "head_b": P(),
        }
    return specs


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def _dropout(x, rate, key, deterministic):
    if deterministic or rate == 0.0 or key is None:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0).astype(x.dtype)


FP8_GEMM_NAMES = ("qkv", "proj", "fc1", "fc2")
#: Steps of amax history the delayed scaling keeps for each GEMM.
FP8_AMAX_HISTORY_LEN = 16


def init_gpt_fp8_states(cfg: GPTConfig):
    """Per-layer delayed-scaling state for the four projection GEMMs:
    ``{name: Fp8DenseState with [L, ...] leaves}``. Thread through
    ``gpt_loss(..., fp8_states=...)``; the returned states carry the
    rolled x/w histories, and the gradient amaxes come back as the
    ``fp8_carriers`` cotangent (fold with :func:`record_gpt_grad_amaxes`)."""
    from apex_tpu.fused_dense import init_fp8_dense_state

    one = init_fp8_dense_state(FP8_AMAX_HISTORY_LEN, with_grad_meta=True)
    stack = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape).copy(),
        one,
    )
    return {name: stack for name in FP8_GEMM_NAMES}


def init_gpt_fp8_carriers(cfg: GPTConfig):
    """Zero per-layer gradient-amax carriers, ``{name: [L]}`` — pass as a
    DIFFERENTIATED argument; its cotangent is the per-layer amax(dY)."""
    return {
        name: jnp.zeros((cfg.num_layers,), jnp.float32)
        for name in FP8_GEMM_NAMES
    }


def record_gpt_grad_amaxes(cfg: GPTConfig, fp8_states, carrier_grads):
    """Fold the backward-observed gradient amaxes (the carriers'
    cotangent) into each layer's g meta, group-reduced over the amax
    axes (call inside the same shard_map as the loss)."""
    from apex_tpu.fused_dense import record_grad_amax

    out = {}
    for name in FP8_GEMM_NAMES:
        amax = carrier_grads[name]
        if cfg.fp8_amax_reduction_axes is not None:
            amax = jax.lax.pmax(amax, cfg.fp8_amax_reduction_axes)
        out[name] = jax.vmap(record_grad_amax)(fp8_states[name], amax)
    return out


def _fp8_dense(cfg, fp8, name, x, w, b):
    """Single-device fp8 projection: e4m3 GEMM + bias; returns
    ``(y, {name: new_state})``."""
    from apex_tpu.fused_dense import fp8_fused_dense_qgrad

    state, carrier = fp8[name]
    y, new_state = fp8_fused_dense_qgrad(
        x, w, None, state, carrier,
        amax_reduction_axes=cfg.fp8_amax_reduction_axes,
    )
    y = y.astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y, new_state


def _use_flash(cfg: GPTConfig, s: int, hn: int, compatible: bool) -> bool:
    """Whether ``parallel_attention`` takes the flash kernels. They replace
    the materialised ``[b, np, sq, sk]`` scores when applicable: no traced
    per-layer scaling, and a mask expressible as causal or key-padding
    (``[b, 1, 1, sk]``-broadcast): ``compatible``. Attention dropout runs
    IN-KERNEL (hash counters, the reference fmha's Philox analogue) so
    dropout > 0 does not re-materialise ``[s, s]`` probabilities. In
    causal mode any provided mask is ignored on every path — parity with
    the reference's upper-triangular kernel, which takes no mask."""
    if cfg.use_flash_attention is None:
        return compatible and flash_attention_available(s, s, hn)
    if not cfg.use_flash_attention:
        return False
    if not compatible:
        raise ValueError(
            "use_flash_attention=True but the configuration is not "
            "flash-compatible (traced qk scaling or a non-causal/"
            "non-padding mask)"
        )
    # the TPU-tileability rule of flash_attention_available, checked on
    # every backend so a forced-on config fails loudly in CPU tests rather
    # than at TPU compile time
    from ...ops.flash_attention import require_kernel_tileable

    require_kernel_tileable(s, hn, "use_flash_attention=True")
    return True


def _flash_batch_major(cfg: GPTConfig, lp, hidden, flash_kw, fuse_tail):
    """The flash path of ``parallel_attention`` on one device, batch-major
    inside (see there): ``hidden [s, b, h]`` in, the projected context
    ``[s, b, h]`` out."""
    s, b, h = hidden.shape
    n, hn = cfg.num_attention_heads, cfg.kv_channels
    dt = hidden.dtype
    # qkv_w keeps Megatron's row order (the checkpoint's), [head, (q, k, v),
    # hn]; the GEMM takes its rows as [(q, k, v), head, hn], so that q, k
    # and v of a pair of heads are each 128 whole lanes of what it writes
    w = jnp.swapaxes(lp["qkv_w"].astype(dt).reshape(n, 3, hn, h), 0, 1)
    bias = jnp.swapaxes(lp["qkv_b"].astype(dt).reshape(n, 3, hn), 0, 1)
    qkv = (jnp.einsum("bsh,oh->bso", jnp.swapaxes(hidden, 0, 1),
                      w.reshape(3 * n * hn, h))
           + bias.reshape(3 * n * hn))
    ctx = flash_attention_qkv(
        qkv.reshape(b, s, 3, n, hn), **flash_kw).astype(dt)
    out = jnp.einsum("bso,ho->sbh", ctx.reshape(b, s, n * hn),
                     lp["proj_w"].astype(dt))
    if not fuse_tail:
        out = out + lp["proj_b"].astype(dt)
    return out


@jax.named_scope("apex_tpu.attention")
def parallel_attention(
    cfg: GPTConfig,
    lp: Dict[str, jax.Array],
    hidden: jax.Array,  # [s, b, h]
    attention_mask: Optional[jax.Array],
    axis_name: Optional[str],
    dropout_key: Optional[jax.Array],
    deterministic: bool,
    layer_number: Optional[jax.Array] = None,
    fp8=None,  # {name: (Fp8DenseState, carrier)} for qkv/proj
    fuse_tail: bool = False,
):
    """Self-attention (reference ``ParallelAttention``
    ``standalone_transformer_lm.py:210-400``): column-parallel fused QKV,
    head-parallel scaled-masked softmax, row-parallel output projection.

    Who moves data. On the flash path without tensor parallelism or fp8,
    where the heads cut into 128-lane blocks (``heads_per_block``: pairs
    of 64, heads of 128), the block runs batch-major inside
    (``_flash_batch_major``): ``hidden`` is swapped to ``[b, s, h]``, ONE
    GEMM writes ``[b, s, (q, k, v), head, hn]`` (the rows of ``qkv_w``,
    kept in Megatron's ``[head, (q, k, v), hn]`` order, are reordered in
    the weight, not in the activation), the flash kernels read q, k and v
    as three views of that array and the output projection reads the
    context where the kernels wrote it (``flash_attention_qkv``), and its
    result is swapped back to ``[s, b, h]``. XLA moves: the two swaps of
    ``[s, b, h]`` (rows moved whole, fused into the norm before and the
    tail after where it can) and, in backward, ``dq``, ``dk``, ``dv`` put
    side by side as the GEMM's output gradient; no transpose or split of
    q, k, v or the context. Elsewhere the fused projection's ``[s, b,
    heads, 3 x hn]`` is split and the kernels are reached through
    ``flash_attention_sbhd``, which swaps and, where heads do not pair,
    transposes (ring attention and the XLA scores take their own layouts
    from the split).

    ``fuse_tail=True`` returns the projection WITHOUT ``proj_b`` — the
    caller fuses the bias into the block tail (fused_block path)."""
    s, b, _ = hidden.shape
    tp = cfg.tensor_model_parallel_size if axis_name is not None else 1
    np_local = cfg.num_attention_heads // tp
    hn = cfg.kv_channels

    # fp16 query-key layer scaling (reference coeff trick): divide scores
    # by the 1-based layer number before any fp16 cast and multiply back
    # inside the fp32 softmax, so deep-layer fp16 scores cannot overflow
    qk_scaling = (
        cfg.apply_query_key_layer_scaling
        and cfg.compute_dtype == jnp.float16
        and layer_number is not None
    )
    causal = cfg.attn_mask_type == AttnMaskType.causal
    kv_mask = None
    mask_ok = causal
    if (
        not causal
        and attention_mask is not None
        and attention_mask.ndim == 4
        and attention_mask.shape[1] == 1
        and attention_mask.shape[2] == 1
    ):
        kv_mask = attention_mask[:, 0, 0, :] == 0  # True = attend
        mask_ok = True
    attn_dropout_p = (
        0.0 if deterministic or dropout_key is None
        else float(cfg.attention_dropout)
    )
    flash_kw = dict(causal=causal, kv_mask=kv_mask, scale=1.0 / (hn ** 0.5))
    if attn_dropout_p > 0.0:
        # int32 seed derived from the step's dropout key: the kernel
        # regenerates the identical mask in backward from this counter
        flash_kw.update(
            dropout_p=attn_dropout_p,
            dropout_seed=jax.random.randint(
                dropout_key, (), -(2 ** 31), 2 ** 31 - 1, jnp.int32))

    if (fp8 is None and axis_name is None
            and cfg.context_parallel_axis is None
            and heads_per_block(np_local, hn)
            and _use_flash(cfg, s, hn, not qk_scaling and mask_ok)):
        return _flash_batch_major(cfg, lp, hidden, flash_kw, fuse_tail)

    new_fp8 = {}
    if fp8 is not None and axis_name is not None:
        st, car = fp8["qkv"]
        qkv, _, new_fp8["qkv"] = column_parallel_linear(
            hidden, lp["qkv_w"].astype(hidden.dtype),
            lp["qkv_b"].astype(hidden.dtype), axis_name=axis_name,
            gather_output=False,
            sequence_parallel_enabled=cfg.sequence_parallel,
            fp8_state=st, fp8_grad_carrier=car,
            fp8_amax_reduction_axes=cfg.fp8_amax_reduction_axes,
        )
    elif fp8 is not None:
        qkv, new_fp8["qkv"] = _fp8_dense(
            cfg, fp8, "qkv", hidden, lp["qkv_w"].astype(hidden.dtype),
            lp["qkv_b"])
    elif axis_name is not None:
        qkv, _, _ = column_parallel_linear(
            hidden, lp["qkv_w"].astype(hidden.dtype),
            lp["qkv_b"].astype(hidden.dtype), axis_name=axis_name,
            gather_output=False,
            sequence_parallel_enabled=cfg.sequence_parallel,
        )
    else:
        qkv = (jnp.einsum("sbh,oh->sbo", hidden, lp["qkv_w"].astype(hidden.dtype))
               + lp["qkv_b"].astype(hidden.dtype))

    # under sequence parallelism the column-parallel QKV gathered the
    # scattered [s/tp] input back to the full sequence length
    s = qkv.shape[0]
    qkv = qkv.reshape(s, b, np_local, 3 * hn)
    q, kk, vv = jnp.split(qkv, 3, axis=-1)  # [s, b, np, hn]

    # --- context-parallel path (ring attention over the cp axis) --------
    if cfg.context_parallel_axis is not None:
        from apex_tpu.transformer.context_parallel import ring_attention

        if cfg.attn_mask_type != AttnMaskType.causal:
            raise ValueError(
                "context parallelism supports causal attention only"
            )
        if cfg.sequence_parallel:
            raise ValueError(
                "context_parallel_axis and sequence_parallel are mutually "
                "exclusive (Megatron SP gathers the full sequence inside "
                "the block; CP keeps it sharded end-to-end)"
            )
        if qk_scaling:
            raise ValueError(
                "context parallelism needs a static softmax scale; disable "
                "apply_query_key_layer_scaling (fp16 layer scaling)"
            )
        if cfg.attention_dropout > 0.0 and not deterministic \
                and dropout_key is not None:
            raise ValueError(
                "attention dropout is not supported on the ring-attention "
                "path; set attention_dropout=0 (hidden dropout still works)"
            )
        if cfg.use_flash_attention is False:
            raise ValueError(
                "use_flash_attention=False cannot be honored under "
                "context parallelism: ring attention runs the flash chunk "
                "kernels internally"
            )
        # same loud every-backend gate as the forced-flash path: the ring
        # path compiles the Pallas chunk kernels on TPU. Zigzag runs them
        # on HALF chunks, so the local length must split into two tileable
        # halves.
        from ...ops.flash_attention import require_kernel_tileable

        if cfg.context_parallel_zigzag:
            if s % 16 != 0:
                raise ValueError(
                    f"zigzag context parallelism needs local seq {s} % 16 "
                    "== 0 (the kernels run on tileable half-chunks)"
                )
            require_kernel_tileable(s // 2, hn, "context parallelism")
        else:
            require_kernel_tileable(s, hn, "context parallelism")
        qb = jnp.transpose(q, (1, 2, 0, 3))   # [s,b,np,hn] -> [b,np,s,hn]
        kb = jnp.transpose(kk, (1, 2, 0, 3))
        vb = jnp.transpose(vv, (1, 2, 0, 3))
        ctx = ring_attention(
            qb, kb, vb, axis_name=cfg.context_parallel_axis, causal=True,
            zigzag=cfg.context_parallel_zigzag,
            scale=1.0 / (hn ** 0.5),
        ).astype(hidden.dtype)
        ctx = jnp.transpose(ctx, (2, 0, 1, 3)).reshape(s, b, np_local * hn)
        return _attn_out_proj(cfg, lp, ctx, axis_name, fp8, new_fp8,
                              fuse_tail)

    # --- flash attention path (Pallas, O(s) memory) ---------------------
    if _use_flash(cfg, s, hn, not qk_scaling and mask_ok):
        ctx = flash_attention_sbhd(q, kk, vv, **flash_kw).astype(hidden.dtype)
        ctx = ctx.reshape(s, b, np_local * hn)
    else:
        norm_factor = hn ** 0.5
        coeff = None
        if qk_scaling:
            coeff = jnp.maximum(layer_number.astype(jnp.float32), 1.0)
            norm_factor = norm_factor * coeff
            # traced scale: inline fp32 softmax (the Pallas kernel needs a
            # static scale; fp16+layer-scaling takes the XLA path)
            scores = jnp.einsum(
                "sbnh,tbnh->bnst", q, kk,
                preferred_element_type=jnp.float32
            ) / norm_factor
            x = scores * coeff
            if causal:
                qi = jax.lax.broadcasted_iota(jnp.int32, x.shape[-2:], 0)
                ki = jax.lax.broadcasted_iota(jnp.int32, x.shape[-2:], 1)
                x = jnp.where(ki > qi, -10000.0, x)
            elif attention_mask is not None:
                x = jnp.where(attention_mask != 0, -10000.0, x)
            probs = jax.nn.softmax(x, axis=-1).astype(cfg.compute_dtype)
        else:
            softmax = FusedScaleMaskSoftmax(
                input_in_fp16=(cfg.compute_dtype == jnp.float16),
                input_in_bf16=(cfg.compute_dtype == jnp.bfloat16),
                attn_mask_type=cfg.attn_mask_type,
                mask_func=None,
                softmax_in_fp32=True,
                scale=None,
            )
            # scores come off the MXU in compute dtype directly (the
            # accumulator is fp32 internally and rounds ONCE at the
            # output) — the old preferred_element_type=fp32 einsum
            # followed by a compute-dtype truncation was a pure
            # f32->bf16->f32 round-trip into the fp32 fused softmax
            # (the analysis.dtype_flow 'double_cast' finding): mantissa
            # already lost, two convert sweeps paid. Keeping scores in
            # compute dtype also keeps the [b, np, sq, sk] probs
            # residual (the largest attention activation on this path)
            # at compute-dtype width, matching the dispatcher's
            # input_in_* flags.
            scores = jnp.einsum("sbnh,tbnh->bnst", q, kk) / norm_factor
            probs = softmax(
                scores,
                None if causal else attention_mask,
            )

        if dropout_key is not None:
            dropout_key, sub = jax.random.split(dropout_key)
            probs = _dropout(probs, cfg.attention_dropout, sub, deterministic)

        ctx = jnp.einsum(
            "bnst,tbnh->sbnh", probs.astype(vv.dtype), vv,
            preferred_element_type=jnp.float32,
        ).astype(hidden.dtype)
        ctx = ctx.reshape(s, b, np_local * hn)

    return _attn_out_proj(cfg, lp, ctx, axis_name, fp8, new_fp8,
                          fuse_tail)


def _attn_out_proj(cfg, lp, ctx, axis_name, fp8=None, new_fp8=None,
                   fuse_tail=False):
    """Row-parallel (or dense) attention output projection, shared by the
    flash/XLA and ring-attention context-parallel paths. With fp8 active,
    returns ``(out, new_fp8)`` carrying the rolled qkv/proj states.
    ``fuse_tail`` omits ``proj_b`` (fused into the block tail by the
    caller — bias rides the single fused sweep, not the GEMM epilogue)."""
    bias = None if fuse_tail else lp["proj_b"]
    if fp8 is not None and axis_name is not None:
        st, car = fp8["proj"]
        out, _, new_fp8["proj"] = row_parallel_linear(
            ctx, lp["proj_w"].astype(ctx.dtype),
            None if bias is None else bias.astype(ctx.dtype),
            axis_name=axis_name,
            input_is_parallel=True,
            sequence_parallel_enabled=cfg.sequence_parallel,
            fp8_state=st, fp8_grad_carrier=car,
            fp8_amax_reduction_axes=cfg.fp8_amax_reduction_axes,
        )
        return out, new_fp8
    if fp8 is not None:
        out, new_fp8["proj"] = _fp8_dense(
            cfg, fp8, "proj", ctx, lp["proj_w"].astype(ctx.dtype),
            bias)
        return out, new_fp8
    if axis_name is not None:
        out, _, _ = row_parallel_linear(
            ctx, lp["proj_w"].astype(ctx.dtype),
            None if bias is None else bias.astype(ctx.dtype),
            axis_name=axis_name,
            input_is_parallel=True,
            sequence_parallel_enabled=cfg.sequence_parallel,
        )
    else:
        out = jnp.einsum("sbo,ho->sbh", ctx, lp["proj_w"].astype(ctx.dtype))
        if bias is not None:
            out = out + bias.astype(ctx.dtype)
    return out


@jax.named_scope("apex_tpu.mlp")
def parallel_mlp(
    cfg: GPTConfig,
    lp: Dict[str, jax.Array],
    hidden: jax.Array,
    axis_name: Optional[str],
    fp8=None,  # {name: (Fp8DenseState, carrier)} for fc1/fc2
    fuse_tail: bool = False,
):
    """Reference ``ParallelMLP`` (``standalone_transformer_lm.py:89-130``):
    column-parallel h→4h, fused bias-GeLU, row-parallel 4h→h. With fp8
    active, returns ``(out, new_fp8)``.

    ``fuse_tail=True`` is the fused-block MLP: fc1 runs bias-free and the
    bias+GeLU epilogue is :func:`apex_tpu.ops.bias_gelu` (float32 inside,
    one rounding — the ``fused_dense_cuda`` GEMM+bias+GeLU shape; on a TPU
    XLA fuses it into the GEMMs either side); fc2 also runs bias-free and
    the caller fuses ``fc2_b`` into the block-tail bias+dropout+residual.
    """

    def act(inter):
        if fuse_tail:
            return bias_gelu(inter, lp["fc1_b"].astype(inter.dtype),
                             interpret=cfg.fused_block_interpret)
        return jax.nn.gelu(inter, approximate=True)

    fc1_b = None if fuse_tail else lp["fc1_b"]
    fc2_b = None if fuse_tail else lp["fc2_b"]
    new_fp8 = {}
    if fp8 is not None and axis_name is not None:
        st1, car1 = fp8["fc1"]
        inter, _, new_fp8["fc1"] = column_parallel_linear(
            hidden, lp["fc1_w"].astype(hidden.dtype),
            None if fc1_b is None else fc1_b.astype(hidden.dtype),
            axis_name=axis_name,
            gather_output=False,
            sequence_parallel_enabled=cfg.sequence_parallel,
            fp8_state=st1, fp8_grad_carrier=car1,
            fp8_amax_reduction_axes=cfg.fp8_amax_reduction_axes,
        )
        inter = act(inter)
        st2, car2 = fp8["fc2"]
        out, _, new_fp8["fc2"] = row_parallel_linear(
            inter, lp["fc2_w"].astype(inter.dtype),
            None if fc2_b is None else fc2_b.astype(inter.dtype),
            axis_name=axis_name,
            input_is_parallel=True,
            sequence_parallel_enabled=cfg.sequence_parallel,
            fp8_state=st2, fp8_grad_carrier=car2,
            fp8_amax_reduction_axes=cfg.fp8_amax_reduction_axes,
        )
        return out, new_fp8
    if fp8 is not None:
        inter, new_fp8["fc1"] = _fp8_dense(
            cfg, fp8, "fc1", hidden, lp["fc1_w"].astype(hidden.dtype),
            fc1_b)
        inter = act(inter)
        out, new_fp8["fc2"] = _fp8_dense(
            cfg, fp8, "fc2", inter, lp["fc2_w"].astype(inter.dtype),
            fc2_b)
        return out, new_fp8
    if axis_name is not None:
        inter, _, _ = column_parallel_linear(
            hidden, lp["fc1_w"].astype(hidden.dtype),
            None if fc1_b is None else fc1_b.astype(hidden.dtype),
            axis_name=axis_name,
            gather_output=False,
            sequence_parallel_enabled=cfg.sequence_parallel,
        )
        inter = act(inter)
        out, _, _ = row_parallel_linear(
            inter, lp["fc2_w"].astype(inter.dtype),
            None if fc2_b is None else fc2_b.astype(inter.dtype),
            axis_name=axis_name,
            input_is_parallel=True,
            sequence_parallel_enabled=cfg.sequence_parallel,
        )
        return out
    inter = jnp.einsum("sbh,oh->sbo", hidden, lp["fc1_w"].astype(hidden.dtype))
    if fc1_b is not None:
        inter = inter + fc1_b.astype(hidden.dtype)
    inter = act(inter)
    out = jnp.einsum("sbo,ho->sbh", inter, lp["fc2_w"].astype(hidden.dtype))
    if fc2_b is not None:
        out = out + fc2_b.astype(hidden.dtype)
    return out


def _norm(cfg: GPTConfig, lp, name: str, x32: jax.Array) -> jax.Array:
    """The configured norm of a float32 ``[..., h]``, in float32."""
    if cfg.norm == "rmsnorm":
        return rms_norm(x32, lp[f"{name}_w"].astype(jnp.float32), 1,
                        cfg.layernorm_epsilon)
    return fused_layer_norm(
        x32, lp[f"{name}_w"].astype(jnp.float32),
        lp[f"{name}_b"].astype(jnp.float32), eps=cfg.layernorm_epsilon)


def _linear(lp, name: str, x: jax.Array, spec: str, shape=None):
    """``einsum(spec, x, W)`` with ``W = lp[name_w]`` stored ``[out, in]``
    (seen as ``shape`` where the heads are split off), plus the bias where
    the block has one."""
    w = lp[f"{name}_w"].astype(x.dtype)
    y = jnp.einsum(spec, x, w if shape is None else w.reshape(shape))
    b = lp.get(f"{name}_b")
    if b is None:
        return y
    b = b.astype(x.dtype)
    if spec.endswith("->bnsd"):         # heads off a projection: [n, d]
        b = b.reshape(shape[:2])[None, :, None, :]
    return y + b


def _rotary(x: jax.Array, theta: float, first: int = 0) -> jax.Array:
    """Rotary positions ``0..s`` over the lanes of ``[b, n, s, d]`` from
    ``first`` on (rotate-half convention; the lanes before pass through),
    in float32, as one pass over ``x`` (``fused_rope._apply_rope``)."""
    s, d = x.shape[-2], x.shape[-1] - first
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]   # [s, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    return _apply_rope(x, cos, sin, first)


def _pairs_to_halves(w: jax.Array, first: int) -> jax.Array:
    """The rows of ``w [..., rows, in]`` from ``first`` on, published as
    rotary pairs ``(2i, 2i + 1)``, put as halves (every even row, then
    every odd one): rotating halves then gives the scores that rotating
    pairs gives, and the weight moves, never an activation."""
    rope = w[..., first:, :]
    half = rope.shape[-2] // 2
    rope = jnp.swapaxes(
        rope.reshape(*rope.shape[:-2], half, 2, rope.shape[-1]), -3, -2)
    return jnp.concatenate(
        [w[..., :first, :], rope.reshape(*w.shape[:-2], 2 * half,
                                         w.shape[-1])], axis=-2)


def _latent_qkv(cfg: GPTConfig, kind: LayerKind, lp, x: jax.Array):
    """q ``[b, n, s, nope + rope]``, k of the same shape and v ``[b, n, s,
    value]`` of latent attention (``GPTConfig.latent_kv``), bias-free: ``q
    = W_q x``; ``(c, k_r) = W_dkv x``; ``(k_n, v)`` a head ``= W_ukv
    rms(c)``; ``q_r`` and the ONE ``k_r`` rotated over their ``rope``
    lanes; ``k = (k_n, k_r)``, the rotary key broadcast to every head (its
    gradient is XLA's sum over the heads of ``dk``'s last lanes)."""
    n, dt = cfg.num_attention_heads, x.dtype
    rank, nope, rope, dv = cfg.latent_kv
    h = x.shape[-1]
    q_w = _pairs_to_halves(lp["q_w"].astype(dt).reshape(n, nope + rope, h),
                           nope)
    q = jnp.einsum("sbh,ndh->bnsd", x, q_w)
    with jax.named_scope("apex_tpu.mla_latent"):
        ckv = jnp.einsum("sbh,ch->bsc", x,
                         _pairs_to_halves(lp["kv_down_w"].astype(dt), rank))
        c = rms_norm(ckv[..., :rank].astype(jnp.float32),
                     lp["kv_norm_w"].astype(jnp.float32), 1,
                     cfg.layernorm_epsilon).astype(dt)
        up_w = lp["kv_up_w"].astype(dt).reshape(n, nope + dv, rank)
        k_n = jnp.einsum("bsc,ndc->bnsd", c, up_w[:, :nope])
        v = jnp.einsum("bsc,ndc->bnsd", c, up_w[:, nope:])
    k_r = ckv[:, None, :, rank:]                        # [b, 1, s, rope]
    if kind.rotary:
        with jax.named_scope("apex_tpu.mla_rope"):
            q = _rotary(q, cfg.rope_theta, nope)
            k_r = _rotary(k_r, cfg.rope_theta)
    with jax.named_scope("apex_tpu.mla_latent"):
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r, k_n.shape[:-1] + (rope,))], axis=-1)
    return q, k, v


@jax.named_scope("apex_tpu.attention")
def attention_by_kind(cfg: GPTConfig, kind: LayerKind, lp, x: jax.Array):
    """Causal self-attention of a ``layer_kinds`` layer over ``x [s, b,
    h]``: separate q / k / v projections with ``kv_heads`` K/V heads,
    optionally RMSNorm over each head of q and k, rotary positions, a
    window, and a sigmoid gate on the context before the output
    projection; or, with ``cfg.latent_kv``, latent attention
    (:func:`_latent_qkv`: the value and the context have a width of their
    own). Heads come off the projections head-major, ``[b, n, s,
    d]`` (``einsum "sbh,ndh->bnsd"``), the layout the banded flash kernels
    read (a window, grouped K/V heads or two widths; ``flash_attention``):
    XLA moves the data, a transpose behind each projection GEMM and one
    before the output projection. The dense kernels' batch-major layout
    (``parallel_attention``) is not taken here."""
    s, b, h = x.shape
    n, nkv, d = cfg.num_attention_heads, cfg.kv_heads, cfg.kv_channels
    if cfg.latent_kv is not None:
        q, k, v = _latent_qkv(cfg, kind, lp, x)
        d = q.shape[-1]
    else:
        q = _linear(lp, "q", x, "sbh,ndh->bnsd", (n, d, h))
        k = _linear(lp, "k", x, "sbh,ndh->bnsd", (nkv, d, h))
        v = _linear(lp, "v", x, "sbh,ndh->bnsd", (nkv, d, h))
        if cfg.qk_norm:
            q = rms_norm(q.astype(jnp.float32),
                         lp["q_norm_w"].astype(jnp.float32), 1,
                         cfg.layernorm_epsilon).astype(x.dtype)
            k = rms_norm(k.astype(jnp.float32),
                         lp["k_norm_w"].astype(jnp.float32), 1,
                         cfg.layernorm_epsilon).astype(x.dtype)
        if kind.rotary:
            q, k = _rotary(q, cfg.rope_theta), _rotary(k, cfg.rope_theta)
    scale = 1.0 / (d ** 0.5)
    use_flash = cfg.use_flash_attention
    if use_flash is None:
        use_flash = flash_attention_available(s, s, d)
    if use_flash:
        from ...ops.flash_attention import require_kernel_tileable

        require_kernel_tileable(s, d, "flash attention by layer kind")
        ctx = flash_attention(q, k, v, causal=True, window=kind.window,
                              scale=scale)
    else:
        ctx = mha_reference(q, k, v, causal=True, window=kind.window,
                            scale=scale)
    ctx = ctx.astype(x.dtype)
    if cfg.attention_gate:
        gate = _linear(lp, "attn_gate", x, "sbh,ndh->bnsd", (n, d, h))
        ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(x.dtype)
    return _linear(lp, "proj", ctx, "bnsd,hnd->sbh", (h, n, v.shape[-1]))


@jax.named_scope("apex_tpu.mlp")
def mlp_by_kind(cfg: GPTConfig, kind: LayerKind, lp, x: jax.Array,
                x32: jax.Array):
    """The dense MLP (gated or fc1-GeLU-fc2) or, on an expert layer, this
    rank's part of the expert layer (``transformer/moe.py``; the router
    reads the float32 ``x32``). Returns ``(y, moe_stats or None)``."""
    if kind.experts:
        from .. import moe

        s, b, h = x.shape
        y, stats = moe.expert_mlp(
            x.reshape(s * b, h), x32.reshape(s * b, h), lp,
            num_experts=cfg.num_experts, held=cfg.experts_held,
            per_token=cfg.experts_per_token, score=cfg.router_score,
            route_norm=cfg.route_norm, route_scale=cfg.route_scale,
            interpret=jax.default_backend() != "tpu")
        return y.reshape(s, b, h), stats
    if cfg.gated_mlp:
        g = _linear(lp, "gate", x, "sbh,fh->sbf")
        u = _linear(lp, "up", x, "sbh,fh->sbf")
        return _linear(lp, "down", jax.nn.silu(g) * u, "sbf,hf->sbh"), None
    inter = jax.nn.gelu(_linear(lp, "fc1", x, "sbh,fh->sbf"),
                        approximate=True)
    return _linear(lp, "fc2", inter, "sbf,hf->sbh"), None


def layer_by_kind(cfg: GPTConfig, kind: LayerKind, lp, hidden: jax.Array):
    """One pre-norm layer of a ``layer_kinds`` stack: ``h += [norm](attn(
    norm(h)))``, ``h += [norm](mlp(norm(h)))``; the bracketed norms with
    ``sandwich_norm``. Norms run in float32. No dropout on this path.
    Returns ``(hidden, moe_stats or None)``."""
    with jax.named_scope("apex_tpu.transformer_layer"):
        dt = hidden.dtype
        f32 = jnp.float32
        attn = attention_by_kind(
            cfg, kind, lp, _norm(cfg, lp, "input_ln", hidden.astype(f32))
            .astype(dt))
        if cfg.sandwich_norm:
            attn = _norm(cfg, lp, "post_attn_ln", attn.astype(f32))
        hidden = (hidden.astype(f32) + attn.astype(f32)).astype(dt)
        pre = _norm(cfg, lp, "post_ln", hidden.astype(f32))
        out, stats = mlp_by_kind(cfg, kind, lp, pre.astype(dt), pre)
        if cfg.sandwich_norm:
            out = _norm(cfg, lp, "post_mlp_ln", out.astype(f32))
        hidden = (hidden.astype(f32) + out.astype(f32)).astype(dt)
    return hidden, stats


def transformer_layer(
    cfg: GPTConfig,
    lp: Dict[str, jax.Array],
    hidden: jax.Array,
    attention_mask: Optional[jax.Array],
    axis_name: Optional[str],
    dropout_key: Optional[jax.Array],
    deterministic: bool,
    layer_number: Optional[jax.Array] = None,
    fp8_l=None,  # {name: (Fp8DenseState, carrier)}, this layer's slice
):
    """Pre-LN transformer layer (reference ``ParallelTransformerLayer``).
    With ``fp8_l`` set, returns ``(hidden, new_fp8_l)``.

    The whole layer runs under the ``apex_tpu.transformer_layer`` named
    scope, and the attention/MLP branch outputs carry opt-in activation-
    watch taps keyed by that scope (``telemetry.numerics.tap`` — identity
    unless a ``numerics.activation_watch`` context is active at trace
    time; under a differentiated layer scan the taps fire on
    forward-only runs, the same restriction as the pipeline tick hooks).

    With ``cfg.fused_block`` the two sublayer tails run as the
    ``ops/fused_block.py`` operations: the attention tail is
    ``residual_add_layer_norm`` (proj bias + hidden dropout + residual
    add + the MLP's pre-LN, one sweep), the MLP tail is
    ``bias_dropout_residual``; the taps then observe the bias-free
    branch outputs (same tap keys, the bias moves into the fused sweep).
    """
    with jax.named_scope("apex_tpu.transformer_layer"):
        dt = hidden.dtype
        k1 = k2 = k3 = None
        if dropout_key is not None:
            k1, k2, k3 = jax.random.split(dropout_key, 3)

        ln1 = fused_layer_norm(
            hidden.astype(jnp.float32), lp["input_ln_w"].astype(jnp.float32),
            lp["input_ln_b"].astype(jnp.float32), eps=cfg.layernorm_epsilon,
        ).astype(dt)
        attn = parallel_attention(
            cfg, lp, ln1, attention_mask, axis_name, k1, deterministic,
            layer_number, fp8=fp8_l, fuse_tail=cfg.fused_block,
        )
        new_fp8 = {}
        if fp8_l is not None:
            attn, attn_fp8 = attn
            new_fp8.update(attn_fp8)
        attn = _numerics.tap(
            "apex_tpu.transformer_layer/attn", attn, layer=layer_number)

        if cfg.fused_block:
            p = (0.0 if deterministic or k3 is None
                 else float(cfg.hidden_dropout))
            hidden, ln2 = residual_add_layer_norm(
                attn, lp["proj_b"].astype(dt), hidden,
                lp["post_ln_w"], lp["post_ln_b"],
                eps=cfg.layernorm_epsilon, dropout_p=p,
                seed=_hash_dropout_seed(k3, p),
                interpret=cfg.fused_block_interpret,
            )
        else:
            hidden = (hidden + _dropout(attn, cfg.hidden_dropout, k3,
                                        deterministic)).astype(dt)
            ln2 = fused_layer_norm(
                hidden.astype(jnp.float32),
                lp["post_ln_w"].astype(jnp.float32),
                lp["post_ln_b"].astype(jnp.float32),
                eps=cfg.layernorm_epsilon,
            ).astype(dt)
        mlp_out = parallel_mlp(cfg, lp, ln2, axis_name, fp8=fp8_l,
                               fuse_tail=cfg.fused_block)
        if fp8_l is not None:
            mlp_out, mlp_fp8 = mlp_out
            new_fp8.update(mlp_fp8)
        mlp_out = _numerics.tap(
            "apex_tpu.transformer_layer/mlp", mlp_out, layer=layer_number)
        if cfg.fused_block:
            p = (0.0 if deterministic or k2 is None
                 else float(cfg.hidden_dropout))
            out = bias_dropout_residual(
                mlp_out, lp["fc2_b"].astype(dt), hidden,
                dropout_p=p, seed=_hash_dropout_seed(k2, p),
                interpret=cfg.fused_block_interpret,
            )
        else:
            out = (hidden + _dropout(mlp_out, cfg.hidden_dropout, k2,
                                     deterministic)).astype(dt)
    if fp8_l is not None:
        return out, new_fp8
    return out


def _hash_dropout_seed(key, p: float):
    """int32 seed for the fused tails' counter-hash dropout, derived from
    the step's dropout key (the flash-attention in-kernel dropout seed
    contract). None when dropout is off."""
    if p <= 0.0 or key is None:
        return None
    return jax.random.randint(key, (), -(2 ** 31), 2 ** 31 - 1, jnp.int32)


# pallas kernels whose forward outputs 'selective' recompute stores: the
# flash bwd kernel re-derives score tiles from its saved (o, lse), so
# replaying the fwd kernel in backward is pure waste (~17 MB/layer saved
# buys back one full fwd flash pass per layer at the 345M bench shape);
# the O(s) norm outputs skip the LN replay. These policies keep the
# pallas_call's raw outputs; 'full' keeps the same two flash outputs by
# name instead (FLASH_RESIDUAL_NAMES: lse lane-dense, [b, n, s], never the
# kernel's [b, n, s, 1]) and no GEMM output. Deliberately NOT a blanket
# pallas_call match: the non-flash path's fused-softmax kernel emits the
# [b, n, s, s] probability tensor — the exact activation selective
# recompute exists to avoid storing.
_SELECTIVE_SAVEABLE_KERNELS = frozenset({
    "apex_tpu_flash_fwd", "apex_tpu_layer_norm_fwd", "apex_tpu_rms_norm_fwd",
})


def _selective_policy(prim, *args, **kwargs):
    """Megatron 'selective' recompute, flash-aware: save weight-GEMM
    outputs plus the allowlisted O(s)-output pallas kernels above."""
    return _policy_with_saveable_kernels(
        prim, _SELECTIVE_SAVEABLE_KERNELS, *args, **kwargs)


def _policy_with_saveable_kernels(prim, kernels, *args, **kwargs):
    if getattr(prim, "name", "") == "pallas_call":
        return kwargs["name"] in kernels
    return jax.checkpoint_policies.dots_with_no_batch_dims_saveable(
        prim, *args, **kwargs
    )


# the fused-block tail kernels' forward outputs are 'selective_elementwise'
# saveable on top of the selective set, where those kernels are in the
# trace (interpreted). A program compiled for a TPU holds none (the gate
# hands the tails to XLA, which replays them inside the backward GEMMs),
# and this policy then keeps exactly what 'selective' keeps.
_FUSED_BLOCK_SAVEABLE_KERNELS = frozenset({
    BIAS_GELU_FWD, BIAS_DROPOUT_RESIDUAL_FWD, RESIDUAL_LN_FWD,
})


def _selective_elementwise_policy(prim, *args, **kwargs):
    """The fused-block remat policy: matmul/attention/norm outputs plus
    the fused tail-kernel outputs are saved; only unfused elementwise
    remains to replay. Pairs with ``GPTConfig.fused_block`` (without the
    fused kernels in the trace, as on a TPU, it is exactly 'selective')."""
    return _policy_with_saveable_kernels(
        prim, _SELECTIVE_SAVEABLE_KERNELS | _FUSED_BLOCK_SAVEABLE_KERNELS,
        *args, **kwargs)


# 'full': the flash forward's two named outputs and nothing else; a layer
# without the flash kernel holds no such name and keeps its input alone.
# ONE policy object for every layer: jax caches a remat's partial
# evaluation by the policy's identity, and a fresh closure a layer had
# every layer's kernels traced and lowered anew (2.5 s of cell 4's set-up).
_FULL_POLICY = jax.checkpoint_policies.save_only_these_names(
    *FLASH_RESIDUAL_NAMES)


def _remat(cfg: GPTConfig, fn):
    """``fn`` under the configured recompute granularity."""
    if cfg.recompute_granularity == "full":
        return jax.checkpoint(fn, policy=_FULL_POLICY)
    if cfg.recompute_granularity == "selective":
        return jax.checkpoint(fn, policy=_selective_policy)
    if cfg.recompute_granularity == "selective_elementwise":
        return jax.checkpoint(fn, policy=_selective_elementwise_policy)
    if cfg.recompute_granularity is not None:
        raise ValueError(
            f"unknown recompute_granularity "
            f"{cfg.recompute_granularity!r}: use None, 'full', 'selective' "
            f"or 'selective_elementwise'"
        )
    return fn


def _block_by_kind(cfg: GPTConfig, layers, hidden, moe_stats: bool):
    """The ``layer_kinds`` stack: each layer its own trace, under
    ``apex_tpu.layer_stack`` like the scan. The expert layers' counters add
    up over the layers (the load ratio takes the largest)."""
    if len(layers) != len(cfg.layer_kinds):
        raise ValueError(
            f"{len(layers)} layers of parameters for layer_kinds of "
            f"{len(cfg.layer_kinds)}")
    total = None
    with jax.named_scope("apex_tpu.layer_stack"):
        for kind, lp in zip(cfg.layer_kinds, layers):
            hidden, stats = _remat(
                cfg, functools.partial(layer_by_kind, cfg, kind))(lp, hidden)
            if stats is not None:
                # every counter adds up; the load ratio takes the largest
                total = stats if total is None else {
                    name: (jnp.maximum
                           if name == "max_over_mean_load" else jnp.add)(
                               total[name], stats[name])
                    for name in stats}
    return (hidden, total) if moe_stats else hidden


def transformer_block(
    cfg: GPTConfig,
    layer_params: Dict[str, jax.Array],  # stacked [L, ...]
    hidden: jax.Array,
    attention_mask: Optional[jax.Array],
    axis_name: Optional[str] = None,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
    fp8_states=None,  # {name: Fp8DenseState [L, ...]}
    fp8_carriers=None,  # {name: [L]}
    moe_stats: bool = False,
):
    """Scan the stacked layers (reference ``ParallelTransformer`` loop).

    With ``cfg.layer_kinds`` the layers differ in kind and in their
    parameters, which a scan over one stacked tree cannot run:
    ``layer_params`` is then a list of one dict a layer, run one after
    another (:func:`_block_by_kind`), each under the same recompute policy;
    ``moe_stats=True`` returns ``(hidden, stats)`` with the expert layers'
    counters.

    ``recompute_granularity="full"`` rematerialises each layer in backward —
    the reference's ``--recompute-granularity full`` activation
    checkpointing (``tensor_parallel/random.py:237``) — but for the flash
    forward kernel: beside the layer's input its output ``o`` and row
    statistics ``lse`` (float32 ``[b, n, s]``) are kept, the two the
    backward kernels read, so the replay runs projections, norms and MLP
    and no attention kernel (without the flash kernel, the input alone);
    ``"selective"``
    keeps matmul outputs and replays only the cheap elementwise/softmax work
    (the reference's ``--recompute-granularity selective``);
    ``"selective_elementwise"`` additionally keeps the fused-block tail
    kernel outputs where those kernels are in the trace (interpreted); on a
    TPU, where ``cfg.fused_block``'s tails are XLA's, it is ``"selective"``.

    With ``fp8_states``/``fp8_carriers`` the per-layer state slices ride
    the scan's xs and the rolled states come back as ys: returns
    ``(hidden, new_fp8_states)``.
    """
    if cfg.layer_kinds is not None:
        return _block_by_kind(cfg, layer_params, hidden, moe_stats)
    L = layer_params["qkv_w"].shape[0]
    with_fp8 = fp8_states is not None

    def body(carry, xs):
        h, key = carry
        if with_fp8:
            lp, layer_number, fp8_sl, fp8_cl = xs
            fp8_l = {
                name: (fp8_sl[name], fp8_cl[name])
                for name in FP8_GEMM_NAMES
            }
        else:
            lp, layer_number = xs
            fp8_l = None
        sub = None
        if key is not None:
            key, sub = jax.random.split(key)
        h = transformer_layer(
            cfg, lp, h, attention_mask, axis_name, sub, deterministic,
            layer_number, fp8_l=fp8_l,
        )
        if with_fp8:
            h, new_fp8_l = h
            return (h, key), new_fp8_l
        return (h, key), None

    body = _remat(cfg, body)

    unroll = int(cfg.layer_unroll)
    if unroll == -1:
        unroll = L  # "full", tracking num_layers
    elif unroll < 1:
        raise ValueError(
            f"layer_unroll must be >= 1 or the sentinel -1 (full), got "
            f"{cfg.layer_unroll}"
        )
    xs = (layer_params, jnp.arange(1, L + 1))
    if with_fp8:
        xs = xs + (fp8_states, fp8_carriers)
    # the scan's own work is the per-layer slices of the stacked
    # parameters and the stacking of their gradients in backward
    with jax.named_scope("apex_tpu.layer_stack"):
        (hidden, _), ys = jax.lax.scan(
            body, (hidden, dropout_key), xs, length=L,
            unroll=max(1, min(unroll, L)),
        )
    if with_fp8:
        return hidden, ys
    return hidden


# --------------------------------------------------------------------------
# GPT
# --------------------------------------------------------------------------

@jax.named_scope("apex_tpu.embed")
def gpt_embed(
    cfg: GPTConfig,
    params: Pytree,
    tokens: jax.Array,  # [b, s]
    position_ids: Optional[jax.Array] = None,
    axis_name: Optional[str] = None,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> jax.Array:
    """Word + position embeddings → [s, b, h] (reference ``Embedding``)."""
    if position_ids is None and cfg.learned_positions:
        position_ids = jnp.broadcast_to(
            _local_position_ids(cfg, tokens.shape[1]), tokens.shape
        )
    if axis_name is not None:
        word = vocab_parallel_embedding(
            tokens, params["embedding"]["word"], axis_name=axis_name
        )
    else:
        word = jnp.take(params["embedding"]["word"], tokens, axis=0)
    if cfg.embedding_scale is not None:
        word = word.astype(jnp.float32) * cfg.embedding_scale
    if cfg.learned_positions:
        word = word + jnp.take(
            params["embedding"]["position"], position_ids, axis=0)
    emb = word.astype(cfg.compute_dtype)
    emb = jnp.transpose(emb, (1, 0, 2))  # [b,s,h] -> [s,b,h]
    if axis_name is not None and cfg.sequence_parallel:
        # enter the sequence-parallel region: each TP rank keeps its s/tp
        # slice (reference Megatron embedding path,
        # ``tensor_parallel/layers.py`` SP wiring + ``mappings.py:213``);
        # dropout below then acts on the local slice
        emb = mappings.scatter_to_sequence_parallel_region(emb, axis_name)
    return _dropout(emb, cfg.hidden_dropout, dropout_key, deterministic)


def _local_position_ids(cfg: GPTConfig, s_loc: int) -> jax.Array:
    """[s_loc] GLOBAL position ids of this rank's tokens. Without context
    parallelism that is just arange; under CP the shard's global offset
    (contiguous: rank*s_loc; zigzag: rank's two chunks r and 2cp-1-r)."""
    cp_size = (1 if cfg.context_parallel_axis is None
               else jax.lax.axis_size(cfg.context_parallel_axis))
    if cp_size * s_loc > cfg.max_position_embeddings:
        # jnp.take would clamp out-of-range ids silently — late tokens
        # would all share the table's last row (on EVERY path, not just CP)
        raise ValueError(
            f"global sequence {cp_size}*{s_loc}={cp_size * s_loc} exceeds "
            f"max_position_embeddings={cfg.max_position_embeddings}"
        )
    if cfg.context_parallel_axis is None:
        return jnp.arange(s_loc)
    r = jax.lax.axis_index(cfg.context_parallel_axis)
    if cfg.context_parallel_zigzag:
        if s_loc % 2 != 0:
            raise ValueError(
                "zigzag needs an even local sequence length, got "
                f"{s_loc} tokens per rank"
            )
        cp = jax.lax.axis_size(cfg.context_parallel_axis)
        h = s_loc // 2
        return jnp.concatenate([
            r * h + jnp.arange(h),
            (2 * cp - 1 - r) * h + jnp.arange(h),
        ])
    return r * s_loc + jnp.arange(s_loc)


def gpt_hidden(
    cfg: GPTConfig,
    params: Pytree,
    tokens: jax.Array,  # [b, s]
    axis_name: Optional[str] = None,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
    fp8_states=None,
    fp8_carriers=None,
    moe_stats: bool = False,
):
    """GPT trunk → pre-head hidden states [s, b, h] (embeddings, layer
    stack, final LN, SP gather) — everything of ``gpt_forward`` except the
    LM-head projection. With ``fp8_states`` the projection GEMMs run the
    e4m3/e5m2 recipe and ``(hidden, new_fp8_states)`` is returned; with
    ``moe_stats`` (a ``layer_kinds`` stack with expert layers) ``(hidden,
    stats)``."""
    if bool(cfg.fp8) != (fp8_states is not None):
        raise ValueError(
            "GPTConfig.fp8 and the fp8_states argument must agree: the "
            "flag declares the recipe, the state carries it — pass "
            "init_gpt_fp8_states(cfg) (+ carriers) when cfg.fp8, and "
            "don't pass states to a non-fp8 config. (The flag alone "
            "cannot run fp8: delayed scaling is stateful.)"
        )
    k_embed = k_block = None
    if dropout_key is not None:
        if axis_name is not None and cfg.sequence_parallel:
            # per-rank RNG fork for dropout on sequence-scattered
            # activations (the reference's model-parallel RNG tracker
            # fork, ``tensor_parallel/random.py`` seed+2718+tp_rank)
            dropout_key = jax.random.fold_in(
                dropout_key, jax.lax.axis_index(axis_name)
            )
        if cfg.context_parallel_axis is not None:
            # each cp rank holds different tokens: fork hidden-dropout too
            dropout_key = jax.random.fold_in(
                dropout_key, jax.lax.axis_index(cfg.context_parallel_axis)
            )
        k_embed, k_block = jax.random.split(dropout_key)
    hidden = gpt_embed(
        cfg, params, tokens, None, axis_name, k_embed, deterministic
    )
    new_fp8 = stats = None
    hidden = transformer_block(
        cfg, params["layers"], hidden, None, axis_name, k_block,
        deterministic, fp8_states=fp8_states, fp8_carriers=fp8_carriers,
        moe_stats=moe_stats,
    )
    if fp8_states is not None:
        hidden, new_fp8 = hidden
    elif moe_stats:
        hidden, stats = hidden
    hidden = _final_layer_norm(cfg, params, hidden)
    if axis_name is not None and cfg.sequence_parallel:
        # leave the SP region before the LM head: all-gather the sequence
        # (backward reduce-scatters the partial d(hidden) — the SP linear
        # pairing, reference ``layers.py:311-437``)
        hidden = mappings.gather_from_sequence_parallel_region(
            hidden, axis_name
        )
    if fp8_states is not None:
        return hidden, new_fp8
    if moe_stats:
        return hidden, stats
    return hidden


def gpt_forward(
    cfg: GPTConfig,
    params: Pytree,
    tokens: jax.Array,  # [b, s]
    axis_name: Optional[str] = None,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
    fp8_states=None,
    fp8_carriers=None,
):
    """Full GPT forward → vocab(-parallel) logits [b, s, v(/tp)]
    (reference ``GPTModel.forward`` + ``post_language_model_processing``).
    With ``fp8_states``: returns ``(logits, new_fp8_states)``."""
    hidden = gpt_hidden(
        cfg, params, tokens, axis_name, dropout_key, deterministic,
        fp8_states=fp8_states, fp8_carriers=fp8_carriers,
    )
    new_fp8 = None
    if fp8_states is not None:
        hidden, new_fp8 = hidden
    with jax.named_scope("apex_tpu.lm_head"):
        logits = _lm_head(cfg, params, hidden, axis_name)
        logits = jnp.transpose(logits, (1, 0, 2))  # [b, s, v(/tp)]
    if fp8_states is not None:
        return logits, new_fp8
    return logits


@jax.named_scope("apex_tpu.lm_head")
def _final_layer_norm(cfg, params, hidden):
    """The LN before the output head; on the device timeline it counts to
    the head (``apex_tpu.lm_head``), not to a layer."""
    if cfg.norm == "rmsnorm":
        return _norm(cfg, params, "final_ln", hidden.astype(jnp.float32)
                     ).astype(cfg.compute_dtype)
    return fused_layer_norm(
        hidden.astype(jnp.float32),
        params["final_ln_w"].astype(jnp.float32),
        params["final_ln_b"].astype(jnp.float32),
        eps=cfg.layernorm_epsilon,
    ).astype(cfg.compute_dtype)


def _head_weight(cfg, params):
    """The output head's ``[vocab, hidden]`` matrix: the embedding table,
    or the head's own where ``cfg.untied_head``."""
    return params["lm_head"] if cfg.untied_head else params["embedding"]["word"]


def _lm_head(cfg, params, hidden, axis_name):
    """Output head (tied to the embedding unless ``cfg.untied_head``): a
    column-parallel GEMM over the
    vocab-sharded table (reference ``parallel_lm_logits``) — the
    copy-to-region makes backward all-reduce the partial d(hidden)."""
    if axis_name is not None:
        hidden = mappings.copy_to_tensor_model_parallel_region(
            hidden, axis_name
        )
    return jnp.einsum(
        "sbh,vh->sbv", hidden,
        _head_weight(cfg, params).astype(cfg.compute_dtype),
        preferred_element_type=jnp.float32,
    )


def _mean_weights(loss_mask, shape, cp_axis):
    """The masked mean over losses of ``shape`` as weights of their sum:
    ``1 / n`` without a mask, else ``m / sum(m)``, the sum taken across
    ``cp_axis`` where the sequence is sharded over it."""
    if loss_mask is None and cp_axis is None:
        return jnp.full(shape, 1.0 / math.prod(shape), jnp.float32)
    m = (jnp.ones(shape, jnp.float32) if loss_mask is None
         else loss_mask.astype(jnp.float32))
    den = jnp.sum(m)
    if cp_axis is not None:
        den = jax.lax.psum(den, cp_axis)
    return m / jnp.maximum(den, 1.0)


def gpt_loss(
    cfg: GPTConfig,
    params: Pytree,
    tokens: jax.Array,  # [b, s]
    labels: jax.Array,  # [b, s]
    loss_mask: Optional[jax.Array] = None,
    axis_name: Optional[str] = None,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
    fp8_states=None,
    fp8_carriers=None,
    moe_stats: bool = False,
):
    """Masked mean LM loss (reference GPT ``loss_func``).

    ``moe_stats=True`` (single device, a ``layer_kinds`` stack with expert
    layers) returns ``(loss, stats)``: assignments routed to the experts
    held here, the largest expert's load over the mean, assignments that
    found no row (``telemetry.accumulate(..., moe_stats=stats)``).

    Single-device path: the head GEMM and the CE are chunk-fused
    (``contrib.xentropy.lm_head_cross_entropy_sum``, the mean handed in as
    row weights) so the ``[b*s, vocab]`` fp32 logits tensor is never fully
    materialised and the head's gradient is computed in the forward chunk
    loop; TP path: vocab-parallel CE over the sharded logits.

    With ``fp8_states``/``fp8_carriers`` (see :func:`init_gpt_fp8_states`)
    the layer projections run the fp8 recipe and ``(loss,
    new_fp8_states)`` is returned — differentiate w.r.t. the carriers and
    fold their cotangent with :func:`record_gpt_grad_amaxes`.
    """
    new_fp8 = None
    cp = cfg.context_parallel_axis
    if axis_name is not None:
        logits = gpt_forward(
            cfg, params, tokens, axis_name, dropout_key, deterministic,
            fp8_states=fp8_states, fp8_carriers=fp8_carriers,
        )
        if fp8_states is not None:
            logits, new_fp8 = logits
        with jax.named_scope("apex_tpu.cross_entropy"):
            losses = vocab_parallel_cross_entropy(
                logits, labels, 0.0, axis_name)
            loss = jnp.sum(losses * _mean_weights(loss_mask, losses.shape, cp))
    else:
        from apex_tpu.contrib.xentropy import lm_head_cross_entropy_sum

        hidden = gpt_hidden(
            cfg, params, tokens, axis_name, dropout_key, deterministic,
            fp8_states=fp8_states, fp8_carriers=fp8_carriers,
            moe_stats=moe_stats,
        )
        if fp8_states is not None:
            hidden, new_fp8 = hidden
        elif moe_stats:
            hidden, stats = hidden
        with jax.named_scope("apex_tpu.cross_entropy"):
            s, b, h = hidden.shape
            n = s * b
            # largest divisor of n that is <= 2048: keeps the chunked-CE
            # memory guarantee for any batch/seq (falling back to n would
            # materialise exactly the [n, vocab] block this path exists to
            # avoid)
            chunk = 1
            for cand in range(min(2048, n), 0, -1):
                if n % cand == 0:
                    chunk = cand
                    break
            loss = lm_head_cross_entropy_sum(
                hidden.reshape(n, h),
                _head_weight(cfg, params),
                jnp.transpose(labels, (1, 0)).reshape(n),  # [s, b] rows
                jnp.transpose(_mean_weights(loss_mask, (b, s), cp)).reshape(n),
                chunk_size=chunk,
            )
    if cp is not None:
        # the shards' sums over the sequence-sharded rows
        with jax.named_scope("apex_tpu.cross_entropy"):
            loss = jax.lax.psum(loss, cp)
    if fp8_states is not None:
        return loss, new_fp8
    if moe_stats:
        return loss, stats
    return loss


# --------------------------------------------------------------------------
# BERT
# --------------------------------------------------------------------------

def bert_forward(
    cfg: GPTConfig,
    params: Pytree,
    tokens: jax.Array,  # [b, s]
    padding_mask: Optional[jax.Array] = None,  # [b, s] 1 = real token
    axis_name: Optional[str] = None,
    dropout_key: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """BERT-style bidirectional encoder (reference ``standalone_bert.py``):
    padding-mask attention, MLM logits via the tied embedding head, optional
    binary (NSP) head over the pooled first token."""
    b, s = tokens.shape
    if padding_mask is None:
        padding_mask = jnp.ones((b, s), jnp.int32)
    # [b, 1, 1, sk] nonzero = masked out — kept in key-padding form so the
    # flash path can consume it directly; the XLA/Pallas softmax paths
    # broadcast it over sq
    attn_mask = (padding_mask[:, None, None, :] == 0).astype(jnp.int8)

    cfg_pad = dataclasses.replace(cfg, attn_mask_type=AttnMaskType.padding)
    k_embed = k_block = None
    if dropout_key is not None:
        if axis_name is not None and cfg.sequence_parallel:
            dropout_key = jax.random.fold_in(
                dropout_key, jax.lax.axis_index(axis_name)
            )
        k_embed, k_block = jax.random.split(dropout_key)
    hidden = gpt_embed(
        cfg_pad, params, tokens, None, axis_name, k_embed, deterministic
    )
    hidden = transformer_block(
        cfg_pad, params["layers"], hidden, attn_mask, axis_name, k_block,
        deterministic,
    )
    hidden = _final_layer_norm(cfg, params, hidden)
    if axis_name is not None and cfg.sequence_parallel:
        hidden = mappings.gather_from_sequence_parallel_region(
            hidden, axis_name
        )

    with jax.named_scope("apex_tpu.lm_head"):
        lm_logits = _lm_head(cfg, params, hidden, axis_name)
        lm_logits = jnp.transpose(lm_logits, (1, 0, 2))

    binary_logits = None
    if cfg.add_binary_head and "binary_head" in params:
        bh = params["binary_head"]
        pooled = jnp.tanh(
            hidden[0] @ bh["pooler_w"].astype(hidden.dtype)
            + bh["pooler_b"].astype(hidden.dtype)
        )  # first token, [b, h]
        binary_logits = (
            pooled @ bh["head_w"].T.astype(pooled.dtype)
            + bh["head_b"].astype(pooled.dtype)
        )
    return lm_logits, binary_logits
