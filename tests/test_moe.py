"""The expert layer (``transformer/moe.py``) and the block by the model's own
shape (``GPTConfig.layer_kinds``), at small sizes on seeded random weights,
against the ``afmoe`` family's plain reference.

- the whole model: loss and first gradient in float32 equal the reference's;
- the experts selected equal the reference's, token for token, in float32;
- the shares add up: the routed parts of all shares plus the shared expert
  once equal the uncut reference's layer;
- dropless: nothing is dropped under any routing, the counters say so;
- the row kernels (``ops/moe_rows.py``) equal the XLA formulation in value
  and in every gradient, under any routing, whatever stands past the routed
  rows, and say how much of the buffer they walked;
- combinations the new fields do not support raise at construction;
- the new scopes stand in the compiled step's text.
"""
import ast
import collections
import dataclasses
import functools
import math
import os
import pathlib
import re
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jax._src.ad_checkpoint import saved_residuals  # noqa: E402

from apex_tpu import telemetry  # noqa: E402
from apex_tpu.analysis import kernel_inventory, walk  # noqa: E402
from apex_tpu.ops import moe_rows  # noqa: E402
from apex_tpu.ops.grouped_matmul import grouped_matmul, row_tile  # noqa: E402
from apex_tpu.transformer import moe  # noqa: E402
from apex_tpu.transformer.testing import GPTConfig, LayerKind, gpt_loss  # noqa: E402
from apex_tpu.transformer.testing import standalone_transformer_lm as lm  # noqa: E402
from apex_tpu.transformer.testing.standalone_transformer_lm import (  # noqa: E402
    init_gpt_params,
)
from benchmark import manifest as mf  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark import traffic, weights  # noqa: E402

CELL = "trinity-mini.train-1chip"
FLASH_FWD, FLASH_BWD = "apex_tpu_flash_fwd", ("apex_tpu_flash_bwd_dq",
                                              "apex_tpu_flash_bwd_dkv")


@functools.lru_cache(maxsize=None)
def _tiny(cell_name):
    """A cell's rehearsal model: its configuration file at the family's
    tiny sizes, the family, the sizes, float32 weights and one batch."""
    cell = mf.Cell(mf.load_manifest(), cell_name)
    harness.rehearsal_cell(cell)
    family = cell.family
    d = family.sizes(cell.config)
    params = weights.init_params(family.init_from_key, cell.config, 7,
                                 jnp.float32)
    tokens, labels = traffic.train_batch(7, 0, 2, 64, d["vocab"], "next")
    return cell.config, family, d, params, jnp.asarray(tokens), jnp.asarray(
        labels)


@pytest.fixture(scope="module")
def tiny():
    return _tiny(CELL)


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


@pytest.mark.parametrize("cell_name", [CELL, "moonlight-16b-a3b.train-1chip"])
def test_the_shares_add_up_to_the_uncut_layer(cell_name):
    """4 shares of 2 of 8 experts: the routed parts of all shares plus the
    shared expert once equal the reference's layer holding all 8. (The
    second cell's two shared experts are one MLP of twice the width,
    counted once.)"""
    _, family, d, params, _, _ = _tiny(cell_name)
    assert d["router"] == 8 and d["experts"] < d["router"]
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


SKEWS = ["uniform", "all_on_the_held", "none_on_the_held", "one_expert"]
# 96 tokens x 4 choices on 4 held experts of 16: a buffer of three row tiles
# of 128, of which none, some or all are in use
ROUTED = dict(tokens=96, k=4, experts=16, count=4, hidden=128, ffn=128)


def _routing(skew, seed=0):
    """``(selected, weights)`` drawn so that the held experts ``[0, count)``
    get a share, every assignment, none, or one expert's worth."""
    t, k, e, count = (ROUTED[n] for n in ("tokens", "k", "experts", "count"))
    scores = jax.random.uniform(jax.random.PRNGKey(seed), (t, e))
    held = jnp.arange(e) < count
    scores = scores + {"uniform": 0.0 * held, "all_on_the_held": 2.0 * held,
                       "none_on_the_held": -2.0 * held,
                       "one_expert": -2.0 * held + 4.0 * (jnp.arange(e) == 1)
                       }[skew]
    _, selected = jax.lax.top_k(scores, k)
    weights = jax.random.uniform(jax.random.PRNGKey(seed + 1), (t, k)) + 0.5
    return selected.astype(jnp.int32), weights


def _plan(skew):
    selected, weights = _routing(skew)
    rows = moe.buffer_rows(ROUTED["tokens"], ROUTED["k"], ROUTED["count"])
    return moe.plan(selected, weights, (0, ROUTED["count"]), rows), weights


@jax.custom_vjp
def _poison(rows, past):
    """NaN on the rows past the sum, on the way forward and on the way
    back: what a buffer nobody wrote may hold."""
    return jnp.where(past, jnp.nan, rows)


_poison.defvjp(lambda rows, past: (_poison(rows, past), past),
               lambda past, d: (jnp.where(past, jnp.nan, d), None))


def _moved(x, weights, mats, p, *, kernels, poison=False):
    """dispatch -> two products -> activation -> product -> combine, as
    ``expert_mlp`` strings them; ``kernels=False`` is the XLA formulation
    (what runs off the TPU without the interpreter)."""
    past = ~moe._in_use(p)[:, None]
    dirty = (lambda rows: _poison(rows, past)) if poison else (lambda r: r)
    gmm = lambda a, b: dirty(grouped_matmul(  # noqa: E731
        a, b, p.group_sizes, interpret=True))
    for_gate, for_up = moe.dispatch(x, p, 2, kernels)
    gate, up = gmm(dirty(for_gate), mats[0]), gmm(dirty(for_up), mats[1])
    if kernels:
        act = moe_rows.gated_act(gate, up, moe._tiles(p), True)
    else:
        act = jax.nn.silu(gate) * up
    return moe.combine(gmm(dirty(act), mats[2]), weights, p, kernels)


def _operands(dtype):
    t, h, f, count = (ROUTED[n] for n in ("tokens", "hidden", "ffn", "count"))
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (t, h)).astype(dtype)
    mats = tuple((jax.random.normal(k, shape) / 8).astype(dtype)
                 for k, shape in zip(ks[1:4], ((count, h, f), (count, h, f),
                                               (count, f, h))))
    cot = jax.random.normal(ks[4], (t, h))
    return x, mats, cot


def _value_and_grads(p, weights, dtype, **kw):
    x, mats, cot = _operands(dtype)

    @jax.jit
    def run(x, weights, mats):
        def loss(x, weights, mats):
            y = jax.checkpoint(functools.partial(_moved, p=p, **kw))(
                x, weights, mats)
            return jnp.sum(y.astype(jnp.float32) * cot), y
        (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)(x, weights, mats)
        return y, grads

    with jax.default_matmul_precision("highest"):
        return jax.tree_util.tree_map(
            lambda a: np.asarray(a, np.float32), run(x, weights, mats))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("skew", SKEWS)
def test_the_row_kernels_equal_the_xla_formulation(skew, dtype):
    """Value, ``d x``, ``d weights`` and the gradients that pass through
    ``d rows`` (the three matrices'), jitted under full recomputation: zero
    rows in use and a full buffer are both legal. float32 to the order of
    the sums, bfloat16 to a unit in the last place of the largest element
    (the activation rounds once where XLA's rounds by operation)."""
    p, weights = _plan(skew)
    got = _value_and_grads(p, weights, jnp.dtype(dtype), kernels=True)
    want = _value_and_grads(p, weights, jnp.dtype(dtype), kernels=False)
    in_use = {"all_on_the_held": p.token_of_row.shape[0],
              "none_on_the_held": 0, "one_expert": ROUTED["tokens"]}
    assert skew not in in_use or int(jnp.sum(p.group_sizes)) == in_use[skew]
    rounding = 2e-6 if dtype == "float32" else 2.0 ** -6
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(
            g, w, atol=rounding * max(float(np.max(np.abs(w))), 1e-3))


@pytest.mark.parametrize("skew", ["uniform", "one_expert",
                                  "none_on_the_held"])
def test_rows_past_the_sum_may_hold_anything_in_every_buffer(skew):
    """NaN past the routed rows in every buffer between ``dispatch`` and
    ``combine``, and in every gradient handed back through them: the loss
    side and all gradients are finite and equal the clean run's (the last
    tile in use holds such rows too: the kernels work on whole tiles)."""
    p, weights = _plan(skew)
    used = int(jnp.sum(p.group_sizes))
    assert used % row_tile(p.token_of_row.shape[0]) or skew != "uniform"
    clean = _value_and_grads(p, weights, jnp.float32, kernels=True)
    dirty = _value_and_grads(p, weights, jnp.float32, kernels=True,
                             poison=True)
    for d, c in zip(jax.tree_util.tree_leaves(dirty),
                    jax.tree_util.tree_leaves(clean)):
        assert np.all(np.isfinite(d))
        np.testing.assert_array_equal(d, c)


def _plan_by_argsort(selected, held, rows):
    """PR 28's ``plan``, kept as the oracle of the order."""
    first, count = held
    tokens, k = selected.shape
    n = tokens * k
    local = selected - first
    is_held = (local >= 0) & (local < count)
    key = jnp.where(is_held, local, count).reshape(n)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    row_of = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    order = jnp.concatenate([order, jnp.zeros((max(rows - n, 0),),
                                              jnp.int32)])[:rows]
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None], axis=0)
    return order // k, order % k, row_of.reshape(tokens, k), is_held, sizes


@pytest.mark.parametrize("count", [4, 2])
@pytest.mark.parametrize("skew", SKEWS)
def test_the_plan_s_rows_in_use_are_the_argsort_s(skew, count):
    """One sort now carries the weights: the rows in use come out element
    for element as the ``argsort`` gave them, each with its assignment's
    weight (``count`` 2 < k: a buffer shorter than the assignments)."""
    selected, weights = _routing(skew)
    held = (1, count)
    rows = moe.buffer_rows(ROUTED["tokens"], ROUTED["k"], count)
    p = moe.plan(selected, weights, held, rows)
    tok, choice, row_of, is_held, sizes = _plan_by_argsort(
        selected, held, rows)
    used = int(jnp.sum(sizes))
    np.testing.assert_array_equal(p.group_sizes, sizes)
    np.testing.assert_array_equal(p.held, is_held)
    np.testing.assert_array_equal(p.token_of_row[:used], tok[:used])
    np.testing.assert_array_equal(p.choice_of_row[:used], choice[:used])
    np.testing.assert_array_equal(jnp.where(is_held, p.row_of, -1),
                                  jnp.where(is_held, row_of, -1))
    np.testing.assert_array_equal(
        p.weight_of_row[:used], weights[tok[:used], choice[:used]])
    np.testing.assert_array_equal(jnp.sort(p.order),
                                  jnp.arange(selected.size))
    # and a sort by the order takes a row's number back to its assignment
    back = moe._by_assignment(jnp.arange(rows, dtype=jnp.float32), p)
    np.testing.assert_array_equal(jnp.where(is_held, back, -1),
                                  jnp.where(is_held, row_of, -1))


@pytest.mark.parametrize("skew, share", [("uniform", 4 / 32),
                                         ("all_on_the_held", 1.0)])
def test_the_share_of_the_buffer_walked_reaches_the_telemetry(skew, share):
    """``rows_walked`` is the sweeps' common bound, whole row tiles: about
    ``count / num_experts`` of the buffer at an even load, all of it when
    every choice falls on the experts held (1.0 at an even load would mean
    the bound is not applied)."""
    tokens, k, experts, count, h, f = 800, 4, 32, 4, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    x = jax.random.normal(ks[0], (tokens, h))
    lp = {"router_w": jax.random.normal(ks[1], (experts, h)) * 0.1,
          "experts_gate_w": jax.random.normal(ks[2], (count, h, f)) * 0.1,
          "experts_up_w": jax.random.normal(ks[3], (count, h, f)) * 0.1,
          "experts_down_w": jax.random.normal(ks[4], (count, f, h)) * 0.1}
    if skew == "all_on_the_held":
        lp["expert_bias"] = 10.0 * (jnp.arange(experts) < count)

    @jax.jit
    def step(metrics):
        _, stats = moe.expert_mlp(x, x, lp, num_experts=experts,
                                  held=(0, count), per_token=k,
                                  interpret=True)
        return telemetry.accumulate(metrics, moe_stats=stats), stats

    metrics, stats = step(step(telemetry.init_metrics())[0])
    rows = moe.buffer_rows(tokens, k, count)
    tile = row_tile(rows)
    assert rows // tile == 25 and float(stats["buffer_rows"]) == rows
    walked = -(-int(stats["routed"]) // tile) * tile
    assert float(stats["rows_walked"]) == walked
    out = telemetry.summarize(metrics)
    assert float(out["moe_rows_walked"]) == walked       # the window's mean
    np.testing.assert_allclose(out["moe_walked_share"], walked / rows)
    assert abs(float(out["moe_walked_share"]) - share) <= 1.5 * tile / rows
    records = []
    jax.jit(lambda m: telemetry.drain(m, records.append))(metrics)
    jax.effects_barrier()
    np.testing.assert_allclose(records[0]["moe_walked_share"], walked / rows)


# ---------------------------------------------------------------------------
# the chip compiler's word (compile only: a described v5e, no chip)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kernel", ["gather", "gather_scaled_dotted", "add",
                                    "add_two", "act"])
def test_v5e_compiles_the_row_kernels_at_the_cell_s_shapes(one_chip, kernel):
    """``trinity-mini.train-1chip``: 131,072 rows of 2048 (1024 between the
    products) for 16,384 tokens, 16 groups, bfloat16."""
    rows, tokens, h, f, groups = 131072, 16384, 2048, 1024, 16
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    buf, tok, sizes = S((rows, h), bf), S((rows,), i32), S((groups,), i32)
    fn, shapes, names = {
        "gather": (lambda x, t, n: moe_rows.gather_rows(
            x, t, n, out_dtype=bf), (S((tokens, h), bf), tok, S((), i32)),
            (moe_rows.BY_ROW, moe_rows.GATHER)),
        "gather_scaled_dotted": (lambda x, t, n, w, o: moe_rows.gather_rows(
            x, t, n, out_dtype=bf, scale=w, dot_with=o),
            (S((tokens, h), bf), tok, S((), i32), S((rows,), f32), buf),
            (moe_rows.BY_ROW, moe_rows.GATHER)),
        "add": (lambda a, t, s, w: moe_rows.add_rows(
            (a,), t, s, tokens, scale=w), (buf, tok, sizes, S((rows,), f32)),
            (moe_rows.ADD,)),
        "add_two": (lambda a, b, t, s: moe_rows.add_rows(
            (a, b), t, s, tokens), (buf, buf, tok, sizes), (moe_rows.ADD,)),
        "act": (lambda g, u, n: jax.value_and_grad(
            lambda g, u: jnp.sum(moe_rows.gated_act(g, u, n, False)),
            argnums=(0, 1))(g, u),
            (S((rows, f), bf), S((rows, f), bf), S((), i32)),
            (moe_rows.ACT_FWD, moe_rows.ACT_BWD)),
    }[kernel]
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(fn).lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    text = compiled.as_text()
    for name in names:
        assert name in text and "tpu_custom_call" in text, name
    # no temporary the size of a [rows, hidden] buffer (512 MiB): the token
    # side's float32 rows ([16384, 1, 2048]: 128 MiB) and a weight a row
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * rows * h


def test_v5e_keeps_o_and_a_lane_dense_lse_of_a_layer_at_the_cell_s_shape(
        one_chip):
    """``trinity-mini.train-1chip``: one sliding layer's attention (window
    2048, 32 query heads on 4 K/V heads of 128, 2 x 8192 tokens, bfloat16)
    under the recipe's ``"full"``: the compiled forward and backward hold
    one forward kernel, and what the forward hands to the backward beside
    parameters and input is ``o`` and a ``[b, n, s]`` ``lse``, 0.14 GB: not
    the kernel's ``f32[2,32,8192,1]`` (one valid lane of 128: 268 MB)."""
    cell = mf.Cell(mf.load_manifest(), CELL)
    cfg = cell.family.program_config(cell.config,
                                     recompute_granularity="full")
    kind = cfg.layer_kinds[1]
    assert kind.window == 2048 and cfg.kv_heads == 4
    bf = jnp.bfloat16
    lp = {name: jax.ShapeDtypeStruct(x.shape, bf, sharding=one_chip)
          for name, x in jax.eval_shape(
              lambda: init_gpt_params(cfg, jax.random.PRNGKey(0))
          )["layers"][1].items()
          if name.split("_")[0] in ("q", "k", "v", "proj", "attn")}
    x = jax.ShapeDtypeStruct((8192, 2, cfg.hidden_size), bf,
                             sharding=one_chip)
    layer = lm._remat(cfg, functools.partial(lm.attention_by_kind, cfg, kind))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            step = jax.jit(jax.grad(
                lambda lp, x: layer(lp, x).astype(jnp.float32).sum(),
                argnums=(0, 1))).lower(lp, x).compile()
            forward = jax.jit(
                lambda lp, x: jax.vjp(layer, lp, x)).lower(lp, x).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    calls = collections.Counter(re.findall(
        r"^\s*%?(apex_tpu_flash_\w+?)[.\d]* = ", step.as_text(), re.M))
    assert calls == {FLASH_FWD: 1, FLASH_BWD[0]: 1, FLASH_BWD[1]: 1}
    handed = [(o.shape, o.dtype) for o in jax.tree_util.tree_leaves(
        forward.out_info)]
    assert ((2, 32, 8192, 128), bf) in handed
    assert ((2, 32, 8192), jnp.float32) in handed
    assert not [shape for shape, _ in handed if shape == (2, 32, 8192, 1)]
    given = sum(math.prod(a.shape) * a.dtype.itemsize
                for a in [*lp.values(), x])
    kept = sum(math.prod(shape) * dt.itemsize
               for shape, dt in handed) - given - x.size * 2   # the output
    assert 0.134e9 < kept < 0.14e9, kept


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


EVERY_FIELD = {**BY_KIND, "layer_kinds": (LayerKind(8, True, False),
                                          LayerKind(None, False, False)),
               "norm": "layernorm", "gated_mlp": False, "linear_bias": True,
               "learned_positions": True, "num_experts": 0,
               "experts_held": None, "experts_per_token": 0,
               "expert_ffn_size": 0, "sandwich_norm": True, "qk_norm": True,
               "attention_gate": True, "embedding_scale": 2.0}


def test_every_new_field_has_a_path_of_its_own():
    """Bias, LayerNorm, fc1-GeLU-fc2, learned positions and a tied head on
    the ``layer_kinds`` path, all at once: the fields are independent of one
    another (each one's own parameter: ``OWN_PARAMETER`` below)."""
    cfg = GPTConfig(**EVERY_FIELD)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
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


# ---------------------------------------------------------------------------
# recompute_granularity="full" keeps what the flash forward kernel wrote
# ---------------------------------------------------------------------------
#: One sliding and one full layer on grouped K/V heads (``BY_KIND``), and
#: the scanned block; with the bodies each traces forward (the scan: one).
FULL_BLOCKS = {
    "by_kind": (BY_KIND, 2),
    "scanned": (dict(num_layers=2, hidden_size=32, num_attention_heads=4,
                     vocab_size=64, max_position_embeddings=16,
                     hidden_dropout=0.0, attention_dropout=0.0), 1),
}


def _full_case(block, **kw):
    """``(cfg, params, loss)`` of a small model on the flash kernels."""
    cfg = GPTConfig(**{**FULL_BLOCKS[block][0], "use_flash_attention": True,
                       **kw})
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    return cfg, params, lambda p: gpt_loss(cfg, p, tokens,
                                           jnp.roll(tokens, -1, 1))


def _flash_calls(block, recompute):
    _, params, loss = _full_case(block, recompute_granularity=recompute)
    return collections.Counter(
        k.name for k in kernel_inventory(jax.value_and_grad(loss), params))


def _loss_and_grads(block, recompute):
    _, params, loss = _full_case(block, recompute_granularity=recompute)
    return jax.jit(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("against", ["none", "whole_replay"])
@pytest.mark.parametrize("block", list(FULL_BLOCKS))
def test_full_recompute_gives_the_same_loss_and_gradients_bit_for_bit(
        block, against):
    """The kept ``o`` and ``lse`` are the values a replay would write: equal
    to the replay of the whole layer (no name kept) and to no recomputation.
    The scan's body alone compiles to other fusions once it is replayed at
    all, with or without the names: there the last bit against ``None``."""
    full = _loss_and_grads(block, "full")
    if against == "none":
        other = _loss_and_grads(block, None)
    else:
        with mock.patch.object(lm, "_FULL_POLICY", None):
            other = _loss_and_grads(block, "full")
    exact = (block, against) != ("scanned", "none")
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(full),
                            jax.tree_util.tree_leaves(other)):
        if exact:
            np.testing.assert_array_equal(a, b, jax.tree_util.keystr(path))
        else:
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-7,
                                       err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("block", list(FULL_BLOCKS))
def test_full_recompute_runs_the_flash_forward_once_a_layer(block):
    """``value_and_grad`` holds one forward kernel a layer body, as without
    recomputation (a replay would make it two), and the backward kernels
    it held before."""
    bodies = FULL_BLOCKS[block][1]
    full, none = _flash_calls(block, "full"), _flash_calls(block, None)
    assert full[FLASH_FWD] == none[FLASH_FWD] == bodies
    # (one key block, the scanned model here: dq rides in the dkv kernel)
    assert [full[name] for name in FLASH_BWD] == [
        none[name] for name in FLASH_BWD]
    assert full[FLASH_BWD[1]] == bodies
    # the other policies keep the kernel's raw outputs and do not replay it
    # either: the names are identities to them
    assert _flash_calls(block, "selective")[FLASH_FWD] == bodies
    with mock.patch.object(lm, "_FULL_POLICY", None):
        assert _flash_calls(block, "full")[FLASH_FWD] == 2 * bodies


def test_every_layer_under_full_shares_one_policy_object():
    """jax caches a remat's partial evaluation by the policy's identity: a
    fresh ``save_only_these_names`` closure a layer had every layer's
    kernels traced and lowered anew (60 kernel bodies in cell 4's step for
    the parent's 41, 2.5 s of ``setup_s`` on the chip's host, PR 32)."""
    _, params, loss = _full_case("by_kind", recompute_granularity="full")
    policies = [eqn.params["policy"]
                for eqn, _ in walk(jax.make_jaxpr(loss)(params).jaxpr)
                if eqn.primitive.name == "remat2"
                and eqn.params["policy"] is not None]   # (the loss's: None)
    assert len(policies) == 2 and policies[0] is policies[1]


def _replaying_sum(hidden, head_weight, labels, weights, *, chunk_size):
    """The weighted sum over the per-row chunked CE, whose backward replays
    each chunk's head GEMM."""
    from apex_tpu.contrib.xentropy import lm_head_cross_entropy

    return jnp.sum(weights * lm_head_cross_entropy(
        hidden, head_weight, labels, chunk_size=chunk_size))


@pytest.mark.parametrize("block", list(FULL_BLOCKS))
def test_the_loss_computes_the_head_s_gradient_in_its_forward_loop(block):
    """Through either block, ``gpt_loss``'s backward holds no product with
    the head weight, where the replaying CE's holds two (the replay and
    d(hidden)); the loss and every gradient equal the replaying CE's at
    float32."""
    cfg, params, loss = _full_case(block)
    head = lm._head_weight(cfg, params).shape

    def head_gemms_in_backward():
        _, vjp_fn = jax.vjp(loss, params)
        return sum(
            eqn.primitive.name == "dot_general"
            and any(tuple(x.aval.shape) == head for x in eqn.invars)
            for eqn, _ in walk(jax.make_jaxpr(vjp_fn)(jnp.float32(1)).jaxpr))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(loss))(params)
        assert head_gemms_in_backward() == 0
        with mock.patch("apex_tpu.contrib.xentropy.lm_head_cross_entropy_sum",
                        _replaying_sum):
            want = jax.jit(jax.value_and_grad(loss))(params)
            assert head_gemms_in_backward() == 2
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


def _saved_by_a_layer(block, **kw):
    """What one layer under ``"full"`` hands to its backward pass, beyond
    its parameters: ``[(shape, dtype, where from)]``."""
    cfg, params, _ = _full_case(block, recompute_granularity="full", **kw)
    hidden = jnp.ones((16, 2, cfg.hidden_size), cfg.compute_dtype)
    if block == "by_kind":
        lp, kind = params["layers"][0], cfg.layer_kinds[0]
        layer = lambda lp, h: lm.layer_by_kind(cfg, kind, lp, h)[0]  # noqa: E731
    else:
        lp = jax.tree_util.tree_map(lambda x: x[0], params["layers"])
        layer = lambda lp, h: lm.transformer_layer(  # noqa: E731
            cfg, lp, h, None, None, None, True, 1)
    fn = lm._remat(cfg, lambda lp, h: layer(lp, h).sum())
    return [(a.shape, a.dtype, why) for a, why in saved_residuals(
        fn, lp, hidden) if "the argument lp" not in why
        and "a constant" not in why]


@pytest.mark.parametrize("block", list(FULL_BLOCKS))
def test_a_layer_under_full_keeps_its_input_o_and_a_rank_3_lse(block):
    """And no GEMM output: ``o`` as ``[b, n, s, d]``, ``lse`` float32
    ``[b, n, s]`` (never the banded kernel's ``[b, n, s, 1]``)."""
    cfg = _full_case(block)[0]
    b, n, s, d = 2, cfg.num_attention_heads, 16, cfg.kv_channels
    saved = _saved_by_a_layer(block)
    assert sorted((shape, str(dt)) for shape, dt, _ in saved) == sorted([
        ((s, b, cfg.hidden_size), "float32"), ((b, n, s, d), "float32"),
        ((b, n, s), "float32")])
    assert any("'apex_tpu_flash_lse'" in why for _, _, why in saved)


@pytest.mark.parametrize("block", list(FULL_BLOCKS))
def test_without_the_flash_kernel_full_keeps_the_input_alone(block):
    saved = _saved_by_a_layer(block, use_flash_attention=False)
    assert [(shape, "argument h" in why) for shape, _, why in saved] == [
        ((16, 2, 32), True)]


# ---------------------------------------------------------------------------
# no option without a caller
# ---------------------------------------------------------------------------
REPO = pathlib.Path(__file__).resolve().parent.parent

#: Fields that no caller outside ``tests/`` sets away from their default,
#: each with what keeps it: ROADMAP D5's list in executable form. A field
#: leaves this list when a cell, an example or a tool sets it, or when it
#: goes.
TESTS_ONLY = {
    "add_binary_head": "BERT's next-sentence head (test_standalone_models); "
                       "the bert cell does not build it (PERF.md §4)",
}

#: Under ``EVERY_FIELD``, the parameter that shows a field took a path of
#: its own: where it stands in the tree, and whether it is there.
OWN_PARAMETER = {
    "linear_bias": (("layers", 0, "q_b"), True),
    "gated_mlp": (("layers", 0, "fc1_w"), True),
    "norm": (("layers", 0, "input_ln_b"), True),
    "attention_gate": (("layers", 0, "attn_gate_w"), True),
    "qk_norm": (("layers", 0, "q_norm_w"), True),
    "sandwich_norm": (("layers", 0, "post_mlp_ln_w"), True),
    "learned_positions": (("embedding", "position"), True),
    "untied_head": (("lm_head",), False),
}

#: What builds a ``GPTConfig`` or a ``LayerKind`` outside ``tests/``.
_BUILDERS = {"GPTConfig", "LayerKind", "program_config", "gpt_config",
             "replace"}
_FIELDS = ([(GPTConfig, f.name, f.default)
            for f in dataclasses.fields(GPTConfig)]
           + [(LayerKind, n, LayerKind._field_defaults[n])
              for n in LayerKind._fields])


@functools.lru_cache(maxsize=None)
def _set_by_callers():
    """``{field: {file, ...}}``: keyword arguments that a builder call in
    ``benchmark/families``, ``chip_smoke.py``, ``__graft_entry__.py``,
    ``examples/``, ``tools/`` or ``apex_tpu/`` gives a value that is not
    (as far as a literal shows) the default."""
    defaults = {name: default for _, name, default in _FIELDS}
    files = [*REPO.glob("benchmark/families/*.py"), REPO / "chip_smoke.py",
             REPO / "__graft_entry__.py", *REPO.glob("examples/**/*.py"),
             *REPO.glob("tools/*.py"), *REPO.glob("apex_tpu/**/*.py")]
    found = {}
    for path in files:
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.Call):
                continue
            name = getattr(node.func, "attr", getattr(node.func, "id", None))
            if name not in _BUILDERS:
                continue
            for kw in node.keywords:
                if kw.arg not in defaults:
                    continue
                try:
                    if ast.literal_eval(kw.value) == defaults[kw.arg]:
                        continue
                except ValueError:
                    pass                        # an expression: a choice
                found.setdefault(kw.arg, set()).add(
                    str(path.relative_to(REPO)))
    return found


@functools.lru_cache(maxsize=None)
def _package_text():
    return "\n".join(p.read_text() for p in REPO.glob("apex_tpu/**/*.py"))


@functools.lru_cache(maxsize=None)
def _every_field_params():
    return init_gpt_params(GPTConfig(**EVERY_FIELD), jax.random.PRNGKey(0))


@pytest.mark.parametrize("owner, field", [
    pytest.param(owner, name, id=f"{owner.__name__}.{name}")
    for owner, name, _ in _FIELDS])
def test_no_option_without_a_caller(owner, field):
    """Every field of the model's configuration is read by the package
    (an attribute access: its declaration is none) and is set away from its
    default by a caller outside ``tests/``, or stands in ``TESTS_ONLY`` with
    its reason. Where ``EVERY_FIELD`` turns a field, its own parameter is
    there."""
    assert re.search(r"\.%s\b" % field, _package_text()), (
        f"{owner.__name__}.{field} is declared and never read")
    callers = _set_by_callers().get(field, set())
    if owner is GPTConfig and field in TESTS_ONLY:
        assert not callers, (
            f"{field} has a caller now ({sorted(callers)}): take it out of "
            "TESTS_ONLY")
    else:
        assert callers, (
            f"no caller outside tests/ sets {owner.__name__}.{field}: give "
            "it one, put it in TESTS_ONLY with the reason, or delete it")
    if field in OWN_PARAMETER:
        (*where, key), present = OWN_PARAMETER[field]
        tree = functools.reduce(lambda t, k: t[k], where,
                                _every_field_params())
        assert (key in tree) is present
