"""The expert layer (``transformer/moe.py``) and the block by the model's own
shape (``GPTConfig.layer_kinds``), at small sizes on seeded random weights,
against the ``afmoe`` family's plain reference.

- the whole model: loss and first gradient in float32 equal the reference's;
- the experts selected equal the reference's, token for token, in float32;
- the shares add up: the routed parts of all shares plus the shared expert
  once equal the uncut reference's layer;
- dropless: nothing is dropped under any routing, the counters say so;
- combinations the new fields do not support raise at construction;
- the new scopes stand in the compiled step's text.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from apex_tpu import telemetry  # noqa: E402
from apex_tpu.transformer import moe  # noqa: E402
from apex_tpu.transformer.testing import GPTConfig, LayerKind, gpt_loss  # noqa: E402
from apex_tpu.transformer.testing.standalone_transformer_lm import (  # noqa: E402
    init_gpt_params,
)
from benchmark import manifest as mf  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark import traffic, weights  # noqa: E402

CELL = "trinity-mini.train-1chip"


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal model: its configuration file at the family's tiny
    sizes, the family, the sizes, float32 weights and one batch."""
    cell = mf.Cell(mf.load_manifest(), CELL)
    harness.rehearsal_cell(cell)
    family = cell.family
    d = family.sizes(cell.config)
    params = weights.init_params(family.init_from_key, cell.config, 7,
                                 jnp.float32)
    tokens, labels = traffic.train_batch(7, 0, 2, 64, d["vocab"], "next")
    return cell.config, family, d, params, jnp.asarray(tokens), jnp.asarray(
        labels)


def _f32(family, config, **kw):
    return dataclasses.replace(
        family.program_config(config, use_flash_attention=True, **kw),
        compute_dtype=jnp.float32)


def test_loss_and_first_gradient_equal_the_reference_in_float32(tiny):
    config, family, d, params, tokens, labels = tiny
    cfg = _f32(family, config, recompute_granularity="full")
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: gpt_loss(cfg, p, tokens, labels))(params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: family.loss_sum(p, tokens, labels, d=d) / tokens.size)(
            params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) / scale < 2e-5, (
            jax.tree_util.keystr(path))


def test_the_experts_selected_equal_the_reference_s_token_for_token(tiny):
    _, family, d, params, _, _ = tiny
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (256, d["hidden"]))
    selected, w = moe.route(x, lp["router_w"], per_token=d["per_token"],
                            route_scale=d["route_scale"])
    ref_selected, ref_w = family.route(x, lp["router_w"], d)
    np.testing.assert_array_equal(selected, ref_selected)
    np.testing.assert_allclose(w, ref_w, rtol=1e-6)
    assert selected.shape == (256, d["per_token"])
    assert int(selected.max()) >= d["experts"]      # routed over ALL experts


def _layer(x, lp, held, d, shared):
    lp = dict(lp)
    if not shared:
        for k in ("shared_gate_w", "shared_up_w", "shared_down_w"):
            lp.pop(k)
    return moe.expert_mlp(
        x, x, lp, num_experts=d["router"], held=held,
        per_token=d["per_token"], route_scale=d["route_scale"],
        interpret=True)


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """4 shares of 2 of 8 experts: the routed parts of all shares plus the
    shared expert once equal the reference's layer holding all 8."""
    _, family, d, params, _, _ = tiny
    h, f, router = d["hidden"], d["expert_ffn"], d["router"]
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    whole = dict(params["layers"][1])
    whole["experts_gate_w"] = jax.random.normal(ks[0], (router, h, f)) * 0.1
    whole["experts_up_w"] = jax.random.normal(ks[1], (router, h, f)) * 0.1
    whole["experts_down_w"] = jax.random.normal(ks[2], (router, f, h)) * 0.1
    x = jax.random.normal(ks[3], (128, h))
    with jax.default_matmul_precision("highest"):
        uncut = family._experts(x, whole, {**d, "experts": router}, False)
        total = moe.gated_mlp(x, whole["shared_gate_w"], whole["shared_up_w"],
                              whole["shared_down_w"])
        routed = 0.0
        for first in range(0, router, 2):
            share = {k: (v[first:first + 2] if k.startswith("experts_")
                         else v) for k, v in whole.items()}
            y, stats = _layer(x, share, (first, 2), d, shared=False)
            total = total + y
            routed += float(stats["routed"])
            assert float(stats["dropped"]) == 0
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    assert routed == 128 * d["per_token"]       # every assignment, once


@pytest.mark.parametrize("skew", ["uniform", "all_on_the_held",
                                  "none_on_the_held", "one_expert"])
def test_nothing_is_dropped_whatever_the_routing(tiny, skew):
    """The dropless guarantee: under any imbalance every assignment to an
    expert held here finds its row (``dropped`` is 0) and the layer equals
    the reference's."""
    _, family, d, params, _, _ = tiny
    lp = dict(params["layers"][1])
    x = jax.random.normal(jax.random.PRNGKey(2), (128, d["hidden"]))
    bias = {"uniform": jnp.zeros((d["router"],)),
            "all_on_the_held": jnp.arange(d["router"]) < d["per_token"],
            "none_on_the_held": jnp.arange(d["router"]) >= d["experts"],
            "one_expert": jnp.arange(d["router"]) == 1}[skew]
    # move the router so that the scores themselves are skewed (the
    # reference holds the expert bias at zero)
    lp["router_w"] = lp["router_w"] + 10.0 * bias.astype(jnp.float32)[
        :, None] * jnp.sign(jnp.mean(x, axis=0))[None, :]
    with jax.default_matmul_precision("highest"):
        y, stats = _layer(x, lp, (0, d["experts"]), d, shared=True)
        ref = family._experts(x, lp, d, False)
    np.testing.assert_allclose(y, ref, atol=2e-5)
    assert float(stats["dropped"]) == 0
    selected, _ = family.route(x, lp["router_w"], d)
    assert float(stats["routed"]) == int(jnp.sum(selected < d["experts"]))
    rows = moe.buffer_rows(128, d["per_token"], d["experts"])
    assert rows >= 128 * min(d["per_token"], d["experts"])


def test_the_counters_reach_the_device_resident_telemetry(tiny):
    config, family, d, params, tokens, labels = tiny
    cfg = _f32(family, config)
    metrics = telemetry.init_metrics()

    @jax.jit
    def step(metrics, params):
        loss, stats = gpt_loss(cfg, params, tokens, labels, moe_stats=True)
        return telemetry.accumulate(metrics, loss=loss, moe_stats=stats)

    metrics = step(step(metrics, params), params)
    out = telemetry.summarize(metrics)
    expert_layers = d["layers"] - d["dense_layers"]
    assert 0 < float(out["moe_routed"]) <= (
        expert_layers * tokens.size * d["per_token"])
    assert float(out["moe_max_load"]) >= 1.0
    assert int(out["moe_dropped"]) == 0


def test_the_new_scopes_stand_in_the_compiled_step_s_text(tiny):
    config, family, d, params, tokens, labels = tiny
    cfg = _f32(family, config, recompute_granularity="full")
    text = jax.jit(jax.grad(lambda p: gpt_loss(cfg, p, tokens, labels))
                   ).lower(params).compile().as_text()
    new = ("apex_tpu.moe_router", "apex_tpu.moe_dispatch",
           "apex_tpu.moe_experts", "apex_tpu.moe_shared")
    for scope in new:
        assert scope in telemetry.LAYER_SCOPES
        assert f"apex_tpu.mlp/{scope}" in text, scope
    for scope in ("apex_tpu.attention", "apex_tpu.layer_stack",
                  "apex_tpu.transformer_layer", "apex_tpu.lm_head",
                  "apex_tpu.embed", "apex_tpu.cross_entropy"):
        assert scope in text, scope


KINDS = (LayerKind(8, True, False), LayerKind(None, False, True))
BY_KIND = dict(
    num_layers=2, hidden_size=32, num_attention_heads=4, vocab_size=64,
    hidden_dropout=0.0, attention_dropout=0.0, layer_kinds=KINDS,
    num_kv_heads=2, head_dim=8, norm="rmsnorm", gated_mlp=True,
    linear_bias=False, learned_positions=False, num_experts=4,
    experts_held=(0, 2), experts_per_token=2, expert_ffn_size=16)


@pytest.mark.parametrize("field, value, reason", [
    ("tensor_model_parallel_size", 2, "no partition rule"),
    ("sequence_parallel", True, "tensor-parallel block"),
    ("context_parallel_axis", "cp", "ring attention has no window"),
    ("fp8", True, "delayed-scaling state"),
    ("fused_block", True, "LayerNorm \\+ GeLU"),
    ("add_binary_head", True, "BERT"),
])
def test_unsupported_combinations_raise_with_the_reason(field, value, reason):
    GPTConfig(**BY_KIND)                                 # the base is sound
    with pytest.raises(ValueError, match=reason):
        GPTConfig(**{**BY_KIND, field: value})


@pytest.mark.parametrize("change, reason", [
    ({"layer_kinds": None}, "set layer_kinds"),
    ({"layer_kinds": KINDS[:1]}, "names 1 layers"),
    ({"num_kv_heads": 3}, "do not divide"),
    ({"experts_held": (3, 2)}, "expert layers need"),
    ({"gated_mlp": False}, "expert layers need"),
    ({"norm": "batchnorm"}, "unknown norm"),
])
def test_a_shape_that_does_not_hold_together_raises(change, reason):
    with pytest.raises(ValueError, match=reason):
        GPTConfig(**{**BY_KIND, **change})


def test_every_new_field_has_a_path_of_its_own():
    """Bias, LayerNorm, fc1-GeLU-fc2, learned positions and a tied head on
    the ``layer_kinds`` path: the fields are independent of one another."""
    cfg = GPTConfig(**{**BY_KIND, "layer_kinds": (LayerKind(8, True, False),
                                                  LayerKind(None, False, False)),
                       "norm": "layernorm", "gated_mlp": False,
                       "linear_bias": True, "learned_positions": True,
                       "num_experts": 0, "experts_held": None,
                       "experts_per_token": 0, "expert_ffn_size": 0,
                       "sandwich_norm": True, "qk_norm": True,
                       "attention_gate": True, "embedding_scale": 2.0})
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    assert "position" in params["embedding"] and "lm_head" not in params
    assert {"q_b", "fc1_w", "fc2_b", "input_ln_b", "attn_gate_w",
            "q_norm_w", "post_mlp_ln_w"} <= set(params["layers"][0])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    loss, grads = jax.value_and_grad(
        lambda p: gpt_loss(cfg, p, tokens, jnp.roll(tokens, -1, 1)))(params)
    assert np.isfinite(float(loss))
    assert all(np.all(np.isfinite(np.asarray(g)))
               for g in jax.tree_util.tree_leaves(grads))
    # and the program's own initialiser lays an expert stack out as the
    # family's does
    expert = init_gpt_params(GPTConfig(**BY_KIND), jax.random.PRNGKey(0))
    assert expert["layers"][1]["experts_gate_w"].shape == (2, 32, 16)
    assert expert["layers"][1]["router_w"].shape == (4, 32)
