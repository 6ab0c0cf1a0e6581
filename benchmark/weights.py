"""Weights, made by the benchmark from ``--seed``.

The benchmark, not the program, makes the weights: one jitted call on the
device, in the layout the program's entry points take (the pytree of
``init_gpt_params``), by the published initialisation of Megatron-LM's
GPT-2/BERT (normal(0, 0.02), output projections scaled by 1/sqrt(2 L),
zero biases, unit layer-norm gains). The reference calls the same function
with the same seed, so neither side takes anything the other has made.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def key_from_seed(seed: int, stream: int = 0):
    """A PRNG key from any whole number up to past 2**31 (``PRNGKey`` takes
    32 signed bits): low 31 bits, then the rest folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def _init(key, layers: int, hidden: int, ffn: int, vocab: int,
          positions: int, dtype) -> Dict[str, Any]:
    k = jax.random.split(key, 6)
    std = 0.02
    out_std = std / (2.0 * layers) ** 0.5

    def n(kk, shape, s=std):
        return (jax.random.normal(kk, shape, jnp.float32) * s).astype(dtype)

    L, h = layers, hidden
    return {
        "embedding": {"word": n(k[0], (vocab, h)),
                      "position": n(k[1], (positions, h))},
        "layers": {
            "input_ln_w": jnp.ones((L, h), dtype),
            "input_ln_b": jnp.zeros((L, h), dtype),
            "qkv_w": n(k[2], (L, 3 * h, h)),
            "qkv_b": jnp.zeros((L, 3 * h), dtype),
            "proj_w": n(k[3], (L, h, h), out_std),
            "proj_b": jnp.zeros((L, h), dtype),
            "post_ln_w": jnp.ones((L, h), dtype),
            "post_ln_b": jnp.zeros((L, h), dtype),
            "fc1_w": n(k[4], (L, ffn, h)),
            "fc1_b": jnp.zeros((L, ffn), dtype),
            "fc2_w": n(k[5], (L, h, ffn), out_std),
            "fc2_b": jnp.zeros((L, h), dtype),
        },
        "final_ln_w": jnp.ones((h,), dtype),
        "final_ln_b": jnp.zeros((h,), dtype),
    }


def init_from_key(config: Dict[str, Any], key, dtype):
    """:func:`init_params` for use inside a jitted function."""
    dims = model_dims(config)
    return _init(key, dims["layers"], dims["hidden"], dims["ffn"],
                 dims["vocab"], dims["positions"], dtype)


def init_params(config: Dict[str, Any], seed: int, dtype=jnp.float32,
                sharding=None):
    """The parameter pytree for ``config`` (a configuration file's
    contents) from ``seed``, as one compiled program."""
    fn = jax.jit(lambda key: init_from_key(config, key, dtype),
                 out_shardings=sharding)
    return fn(key_from_seed(seed))


def model_dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes the harness needs, under one set of names, whichever
    family's ``config.json`` keys the configuration file keeps."""
    def first(*names):
        for name in names:
            if name in config:
                return int(config[name])
        raise KeyError(f"configuration has none of {names}")

    hidden = first("n_embd", "hidden_size")
    heads = first("n_head", "num_attention_heads")
    return {
        "layers": first("n_layer", "num_hidden_layers"),
        "hidden": hidden,
        "heads": heads,
        "head_dim": hidden // heads,
        "ffn": (int(config["intermediate_size"])
                if "intermediate_size" in config
                else int(config["assumed"].get("n_inner") or 4 * hidden)),
        "vocab": int(config["assumed"]["padded_vocab_size"]),
        "vocab_published": first("vocab_size"),
        "positions": first("n_positions", "max_position_embeddings"),
    }


def layer_norm_eps(config: Dict[str, Any]) -> float:
    """The layer norms' epsilon: the published key, or the one the
    configuration file states under ``assumed`` where it trains with
    another than its source's."""
    for where in (config, config["assumed"]):
        for name in ("layer_norm_epsilon", "layer_norm_eps"):
            if name in where:
                return float(where[name])
    raise KeyError("configuration states no layer-norm epsilon")
