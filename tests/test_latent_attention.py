"""Latent attention (``GPTConfig.latent_kv``) on the training path, at small
sizes on seeded random weights:

- the flash kernels with a value width that is not the query/key width
  equal ``mha_reference`` in value and in ``dq``, ``dk``, ``dv`` and in the
  shared rotary key's gradient, under ``jit`` + ``"full"`` recomputation,
  in float32 and bfloat16;
- the latent branch of ``attention_by_kind`` equals the ``mla_deepseek_v3``
  family's plain attention, and the whole model its ``loss_sum`` in loss
  and in every tensor's gradient;
- a layer under ``"full"`` keeps ``o`` and ``lse`` and its backward holds
  no flash forward kernel;
- what the branch does not build raises at construction, with the reason;
- the expert layer's counters read right for 6 of 64 with 8 held;
- the two scopes stand in the compiled step's text.
"""
import collections
import dataclasses
import functools
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jax._src.ad_checkpoint import saved_residuals  # noqa: E402

from apex_tpu import telemetry  # noqa: E402
from apex_tpu.analysis import kernel_inventory  # noqa: E402
from apex_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention,
    flash_attention_bshd,
    mha_reference,
)
from apex_tpu.transformer import moe  # noqa: E402
from apex_tpu.transformer.testing import (  # noqa: E402
    GPTConfig,
    LatentKV,
    LayerKind,
    gpt_loss,
)
from apex_tpu.transformer.testing import standalone_transformer_lm as lm  # noqa: E402
from apex_tpu.transformer.testing.standalone_transformer_lm import (  # noqa: E402
    init_gpt_params,
)
from benchmark import manifest as mf  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark import traffic, weights  # noqa: E402

CELL = "moonlight-16b-a3b.train-1chip"
FLASH_FWD, FLASH_BWD = "apex_tpu_flash_fwd", ("apex_tpu_flash_bwd_dq",
                                              "apex_tpu_flash_bwd_dkv")


# ---------------------------------------------------------------------------
# the kernels at two widths
# ---------------------------------------------------------------------------
B, N, S = 2, 3, 64
NOPE, ROPE, VALUE = 32, 16, 24          # q/k 48 wide, v 24


def _operands(dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    draw = lambda k, *shape: jax.random.normal(k, shape, jnp.float32).astype(
        dtype)
    return (draw(ks[0], B, N, S, NOPE + ROPE), draw(ks[1], B, N, S, NOPE),
            draw(ks[2], B, 1, S, ROPE), draw(ks[3], B, N, S, VALUE),
            draw(ks[4], B, N, S, VALUE))


def _attend(attention, q, k_n, k_r, v):
    """Attention with ONE rotary key for all heads, broadcast into the
    key: the gradient of ``k_r`` is the sum over the heads."""
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r, k_n.shape[:-1] + (ROPE,))], axis=-1)
    return attention(q, k, v)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
def test_two_width_kernels_equal_the_reference_in_value_and_every_gradient(
        dtype, tol):
    """Several q and k tiles a head (blocks of 16 at 64 positions), under
    ``jit`` and the ``"full"`` policy, which keeps the named ``(o, lse)``."""
    q, k_n, k_r, v, do = _operands(dtype)
    scale = (NOPE + ROPE) ** -0.5
    kernels = functools.partial(flash_attention, causal=True, scale=scale,
                                block_q=16, block_k=16, interpret=True)
    plain = functools.partial(mha_reference, causal=True, scale=scale)

    def run(attention, remat):
        fn = functools.partial(_attend, attention)
        if remat:
            fn = jax.checkpoint(fn, policy=lm._FULL_POLICY)
        out, vjp = jax.vjp(fn, q, k_n, k_r, v)
        return (out,) + vjp(do)

    got = jax.jit(functools.partial(run, kernels, True))()
    want = run(plain, False)
    assert got[0].shape == (B, N, S, VALUE) and got[0].dtype == dtype
    for name, a, b in zip(("o", "dq", "dk_n", "dk_r", "dv"), got, want):
        assert a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), (
            name)
    assert got[3].shape == (B, 1, S, ROPE)       # one key's gradient


def test_two_widths_keep_grouped_heads_and_a_window_and_the_other_layouts():
    """The widths are read off the blocks: grouped K/V heads and a window
    still work beside them, and the batch-major entry point falls back to
    the head-major kernels."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 4, 32, 48))
    k = jax.random.normal(ks[1], (1, 2, 32, 48))
    v = jax.random.normal(ks[2], (1, 2, 32, 16))
    kw = dict(causal=True, window=8)
    f = lambda fn, **more: jax.grad(  # noqa: E731
        lambda q, k, v: jnp.sum(fn(q, k, v, **kw, **more) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(f(flash_attention, block_q=8, block_k=8, interpret=True),
                    f(mha_reference)):
        np.testing.assert_allclose(a, b, atol=2e-5)
    o = flash_attention_bshd(*(jnp.swapaxes(x, 1, 2) for x in (q, k, v)),
                             interpret=True, **kw)
    np.testing.assert_allclose(jnp.swapaxes(o, 1, 2),
                               mha_reference(q, k, v, **kw), atol=2e-5)


@pytest.mark.parametrize("what, kw, reason", [
    ("not causal", dict(causal=False), "need causal self-attention"),
    ("a key mask", dict(causal=True, kv_mask=jnp.ones((2, 64))),
     "take no kv_mask"),
    ("dropout", dict(causal=True, dropout_p=0.1, dropout_seed=1),
     "take no kv_mask, bias or dropout"),
])
def test_what_the_two_width_path_does_not_take_raises(what, kw, reason):
    q, k_n, k_r, v, _ = _operands(jnp.float32)
    with pytest.raises(ValueError, match=reason):
        _attend(functools.partial(flash_attention, interpret=True, **kw),
                q, k_n, k_r, v)


def test_a_key_of_another_width_than_the_query_raises():
    q, k_n, _, v, _ = _operands(jnp.float32)
    with pytest.raises(ValueError, match="scores contract over one width"):
        flash_attention(q, k_n, v, causal=True, interpret=True)


# ---------------------------------------------------------------------------
# the branch and the model against the family's plain reference
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    """The rehearsal model: the configuration file at the family's tiny
    sizes, the family, its sizes, float32 weights and one batch."""
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
    return dataclasses.replace(family.program_config(config, **kw),
                               compute_dtype=jnp.float32)


def test_the_rehearsal_has_three_widths(tiny):
    _, _, d, _, _, _ = tiny
    assert len({d["nope"] + d["rope"], d["value"], d["latent"]}) == 3
    assert d["experts"] < d["router"] and d["per_token"] < d["experts"]
    assert d["shared_ffn"] == 2 * d["expert_ffn"]


@pytest.mark.parametrize("flash", [True, False])
def test_the_latent_branch_equals_the_family_s_plain_attention(tiny, flash):
    config, family, d, params, _, _ = tiny
    cfg = _f32(family, config, use_flash_attention=flash)
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 2, d["hidden"]))
    with jax.default_matmul_precision("highest"):
        got = lm.attention_by_kind(cfg, cfg.layer_kinds[1], lp, x)
    want = jnp.stack([family.attention(x[:, r], lp, d) for r in range(2)],
                     axis=1)
    assert got.shape == want.shape == (64, 2, d["hidden"])
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the rotary lanes matter at this size: without them the result differs
    still = dataclasses.replace(cfg, layer_kinds=tuple(
        k._replace(rotary=False) for k in cfg.layer_kinds))
    with jax.default_matmul_precision("highest"):
        unrotated = lm.attention_by_kind(still, still.layer_kinds[1], lp, x)
    assert float(jnp.max(jnp.abs(unrotated - want))) > 1e-4


def test_loss_and_every_tensor_s_gradient_equal_the_reference_in_float32(
        tiny):
    config, family, d, params, tokens, labels = tiny
    cfg = _f32(family, config, use_flash_attention=True,
               recompute_granularity="full")
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: gpt_loss(cfg, p, tokens, labels)))(params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: family.loss_sum(p, tokens, labels, d=d) / tokens.size)(
            params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert {"q_w", "kv_down_w", "kv_norm_w", "kv_up_w", "proj_w"} <= set(
        params["layers"][0])
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) / scale < 2e-5, (
            jax.tree_util.keystr(path))


def test_the_program_s_own_initialiser_lays_the_leaves_out_as_the_family_s(
        tiny):
    config, family, _, params, _, _ = tiny
    own = jax.eval_shape(lambda: init_gpt_params(
        family.program_config(config), jax.random.PRNGKey(0)))
    shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)  # noqa: E731
    assert shapes(own) == shapes(params)


# ---------------------------------------------------------------------------
# "full" recomputation on this path
# ---------------------------------------------------------------------------
LATENT = dict(
    num_layers=2, hidden_size=32, num_attention_heads=4, vocab_size=64,
    hidden_dropout=0.0, attention_dropout=0.0,
    layer_kinds=(LayerKind(None, True, False),) * 2, norm="rmsnorm",
    latent_kv=LatentKV(24, 16, 8, 32), gated_mlp=True, linear_bias=False,
    learned_positions=False, untied_head=True, use_flash_attention=True)


def _case(**kw):
    cfg = GPTConfig(**{**LATENT, **kw})
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    return cfg, params, lambda p: gpt_loss(cfg, p, tokens,
                                           jnp.roll(tokens, -1, 1))


def _flash_calls(recompute):
    _, params, loss = _case(recompute_granularity=recompute)
    return collections.Counter(
        k.name for k in kernel_inventory(jax.value_and_grad(loss), params))


def test_the_backward_of_a_layer_under_full_holds_no_flash_forward_kernel():
    """One forward kernel a layer in ``value_and_grad``, as without
    recomputation; a replay of the whole layer would make it two."""
    full, none = _flash_calls("full"), _flash_calls(None)
    assert full[FLASH_FWD] == none[FLASH_FWD] == 2
    assert [full[n] for n in FLASH_BWD] == [none[n] for n in FLASH_BWD]
    assert full[FLASH_BWD[1]] == 2
    with mock.patch.object(lm, "_FULL_POLICY", None):
        assert _flash_calls("full")[FLASH_FWD] == 4


def test_a_layer_under_full_keeps_its_input_and_o_and_lse_at_their_widths():
    cfg, params, _ = _case(recompute_granularity="full")
    hidden = jnp.ones((16, 2, cfg.hidden_size), cfg.compute_dtype)
    fn = lm._remat(cfg, lambda lp, h: lm.layer_by_kind(
        cfg, cfg.layer_kinds[0], lp, h)[0].sum())
    saved = [(a.shape, why) for a, why in saved_residuals(
        fn, params["layers"][0], hidden)
        if "the argument lp" not in why and "a constant" not in why]
    # o is value_dim wide, not nope_dim + rope_dim
    assert sorted(shape for shape, _ in saved) == sorted([
        (16, 2, 32), (2, 4, 16, 32), (2, 4, 16)])
    assert any("'apex_tpu_flash_lse'" in why for _, why in saved)


def test_full_recompute_gives_the_gradients_of_no_recomputation():
    grads = []
    for recompute in ("full", None):
        _, params, loss = _case(recompute_granularity=recompute)
        grads.append(jax.jit(jax.value_and_grad(loss))(params))
    for a, b in zip(*map(jax.tree_util.tree_leaves, grads)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# what is not built raises at construction
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("change, reason", [
    ({"layer_kinds": (LayerKind(8, True, False),) * 2}, "a window"),
    ({"num_kv_heads": 2}, "grouped K/V heads"),
    ({"qk_norm": True}, "qk_norm"),
    ({"attention_gate": True}, "attention_gate"),
    ({"head_dim": 16}, "head_dim"),
    ({"linear_bias": True}, "bias-free"),
    ({"latent_kv": (24, 16, 7, 32)}, "even rope_dim"),
    ({"layer_kinds": None}, "set layer_kinds"),
    ({"tensor_model_parallel_size": 2}, "no partition rule"),
    ({"sequence_parallel": True}, "tensor-parallel block"),
    ({"context_parallel_axis": "cp"}, "ring attention"),
])
def test_what_the_latent_branch_does_not_build_raises_with_the_reason(
        change, reason):
    assert GPTConfig(**LATENT).latent_kv == (24, 16, 8, 32)
    with pytest.raises(ValueError, match=reason):
        GPTConfig(**{**LATENT, **change})


# ---------------------------------------------------------------------------
# the expert layer's counters at this routing: 6 of 64 with 8 held
# ---------------------------------------------------------------------------
def test_the_counters_read_right_for_six_of_64_with_eight_held():
    tokens, h, f = 256, 32, 128
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    lp = {"router_w": jax.random.normal(ks[0], (64, h)),
          "experts_gate_w": jax.random.normal(ks[1], (8, h, f)) * 0.1,
          "experts_up_w": jax.random.normal(ks[2], (8, h, f)) * 0.1,
          "experts_down_w": jax.random.normal(ks[3], (8, f, h)) * 0.1}
    x = jax.random.normal(ks[4], (tokens, h))
    y, stats = moe.expert_mlp(x, x, lp, num_experts=64, held=(0, 8),
                              per_token=6, route_scale=2.446, interpret=True)
    selected, _ = moe.route(x, lp["router_w"], per_token=6,
                            route_scale=2.446)
    here = np.asarray(selected) < 8
    loads = np.bincount(np.asarray(selected)[here], minlength=8)
    assert float(stats["routed"]) == here.sum() > 0
    assert float(stats["dropped"]) == 0
    assert float(stats["buffer_rows"]) == moe.buffer_rows(tokens, 6, 8) == (
        tokens * 6)
    np.testing.assert_allclose(stats["max_over_mean_load"],
                               loads.max() * 8 / here.sum(), rtol=1e-6)
    tile = moe.row_tile(tokens * 6)
    assert float(stats["rows_walked"]) == -(-here.sum() // tile) * tile
    # and they reach the device-resident telemetry
    metrics = telemetry.accumulate(telemetry.init_metrics(),
                                   loss=jnp.float32(1.0), moe_stats=stats)
    out = telemetry.summarize(metrics)
    assert float(out["moe_routed"]) == here.sum()
    assert int(out["moe_dropped"]) == 0
    assert moe.buffer_rows(16384, 6, 8) == 98304       # the cell's


# ---------------------------------------------------------------------------
# the two scopes
# ---------------------------------------------------------------------------
def test_the_two_scopes_stand_in_the_compiled_step_s_text(tiny):
    config, family, _, params, tokens, labels = tiny
    cfg = _f32(family, config, recompute_granularity="full")
    text = jax.jit(jax.grad(lambda p: gpt_loss(cfg, p, tokens, labels))
                   ).lower(params).compile().as_text()
    for scope in ("apex_tpu.mla_latent", "apex_tpu.mla_rope"):
        assert scope in telemetry.LAYER_SCOPES
        assert f"apex_tpu.attention/{scope}" in text, scope
    # nested: the time is attention's, as the expert scopes' is the MLP's
    assert telemetry.scope_of(
        "jit(f)/apex_tpu.transformer_layer/apex_tpu.attention/"
        "apex_tpu.mla_latent/dot_general")[0] == "apex_tpu.attention"
