"""The ``trinity-mini`` configuration and its cell: the literal widths of
the published ``config.json``, the cut and the deployment the file states,
the family's work counts against a count by hand, and the cell's CPU
rehearsal: the sound run reads ``correct``; the float8 control and every
planted fault do not."""
import io
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import control, manifest as mf  # noqa: E402
from benchmark import run as harness  # noqa: E402

M = mf.load_manifest()
CELL = "trinity-mini.train-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the source's config.json, key for key (numbers, strings and flags; its
# layer_types is three sliding layers and a full one, eight times over)
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
CUT = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 16,
       "vocab_size": 25024}


def _config():
    entry = {c["name"]: c for c in M["configs"]}["trinity-mini"]
    return entry, mf._json(os.path.join(mf.ROOT, entry["file"]))


def test_every_width_is_the_published_one_and_only_the_four_keys_are_cut():
    entry, config = _config()
    assert sorted(entry["reduced"]) == sorted(CUT) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    for key, published in PUBLISHED.items():
        if key in CUT:
            assert config[key] == CUT[key], key
            assert config["published"][key] == published, key
        else:
            assert config[key] == published, key
    assert config["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 8
    # no width among the keys cut
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CUT)


def test_the_file_equals_the_catalog_row_but_for_the_cut():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = {r["name"]: r for r in rows}["Trinity-Mini"]
    entry, config = _config()
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CUT:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_cut_is_one_rank_of_eight_with_a_whole_period_kept():
    _, config = _config()
    d = mf.family("afmoe").sizes(config)
    dep = config["deployment"]
    assert dep["chips_sharing_each_layer"] == 8 and dep["this_rank"] == 0
    assert d["router"] == 128 and d["per_token"] == 8
    assert d["experts"] * 8 == d["router"]
    assert d["vocab"] * 8 == config["published"]["vocab_size"]
    # the leading dense layer once, then a whole period in published order
    assert config["kept_layers"] == [0, 4, 5, 6, 7]
    assert d["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert d["dense_layers"] == 1 and d["layers"] == 5
    # the guide's floors
    assert d["layers"] - d["dense_layers"] >= 4 and d["experts"] >= 8
    for key in ("origin", "attention", "expert_mlp", "expert_bias",
                "optimizer", "initialisation"):
        assert config["assumed"][key]


def test_the_parameters_are_what_the_arithmetic_says():
    import jax
    import jax.numpy as jnp

    _, config = _config()
    family = mf.family("afmoe")
    tree = jax.eval_shape(
        lambda k: family.init_from_key(config, k, jnp.bfloat16),
        jax.random.PRNGKey(0))
    attention = 2048 * (4096 + 512 + 512 + 4096 + 4096) + 2 * 128
    norms = 4 * 2048
    dense = attention + norms + 3 * 2048 * 6144
    expert = attention + norms + 128 * 2048 + 17 * 3 * 2048 * 1024
    want = dense + 4 * expert + 2 * 25024 * 2048 + 2048
    got = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert got == want == 705_473_792
    # every held expert's three matrices are tensors of their own
    norms_of = jax.eval_shape(
        lambda t: family.tensor_norms(config, t), tree)
    assert norms_of["layers"][1]["experts_gate_w"].shape == (16,)
    assert norms_of["layers"][0]["gate_w"].shape == ()


def test_the_bf16_start_is_rounded_where_the_compiler_cannot_undo_it():
    """The harness reads the three-step change as float32 masters minus
    this start, regenerated inside the same program: the rounding to bf16
    has to be an operation XLA may not elide (it may elide a convert
    pair), or its error (4e-5 on a weight of 0.02) is read as change."""
    import jax
    import jax.numpy as jnp

    cell = mf.Cell(M, CELL)
    harness.rehearsal_cell(cell)
    family = cell.family
    text = str(jax.make_jaxpr(lambda k: family.init_from_key(
        cell.config, k, jnp.bfloat16))(jax.random.PRNGKey(0)))
    assert "reduce_precision" in text
    assert "reduce_precision" not in str(jax.make_jaxpr(
        lambda k: family.init_from_key(cell.config, k, jnp.float32))(
            jax.random.PRNGKey(0)))


def test_the_work_a_step_needs_against_a_count_by_hand():
    _, config = _config()
    family = mf.family("afmoe")
    b, s = 2, 8192
    assert family.attention_pairs(s, 2048) == 14_681_088
    assert family.attention_pairs(s, None) == 33_558_528
    assert family.attention_pairs(1024, 2048) == 1024 * 1025 / 2
    tokens = b * s
    rows = tokens * 8 * 16 / 128                    # expected assignments
    assert rows == 16384
    pairs = 4 * 14_681_088 + 33_558_528
    attn = 5 * 2 * tokens * 2048 * (3 * 4096 + 2 * 512) + 4 * b * pairs * 4096
    mlp = (6 * tokens * 2048 * 6144
           + 4 * (2 * tokens * 2048 * 128 + 6 * tokens * 2048 * 1024
                  + 6 * rows * 2048 * 1024))
    head = 2 * tokens * 2048 * 25024
    flops = family.train_flops_per_step(config, b, s)
    assert flops == pytest.approx(3 * (attn + mlp + head), rel=1e-12)
    assert 2.1e9 < flops / tokens < 2.3e9           # 2.2 GFLOP a token
    work = family.kernel_work(config, b, s)
    assert set(work) == {"flash_attention", "grouped_matmul"}
    assert work["flash_attention"][0] == 12 * b * pairs * 4096
    assert work["flash_attention"][1] == 5 * tokens * 6 * (4096 + 512) * 2
    gf, gb = work["grouped_matmul"]
    assert gf == 4 * 3 * 3 * 2 * rows * 2048 * 1024
    assert gb == 4 * 3 * 2 * (3 * 16 * 2048 * 1024 + 3 * rows * 3072)


# ---------------------------------------------------------------------------
# the cell's rehearsal
# ---------------------------------------------------------------------------
def test_the_cell_reports_what_the_gpt_cell_reports_and_its_own():
    cell, gpt = mf.Cell(M, CELL), mf.Cell(M, "gpt2-345m.train-1chip")
    mine = {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in gpt.per_layer} <= mine
    assert not any(name.startswith("ddp.") for name in mine)
    new = {"model.moe_router_ms", "model.moe_dispatch_ms",
           "model.moe_experts_ms", "kernels.grouped_matmul_ms",
           "kernels.grouped_matmul_roofline"}
    assert new <= mine
    for m in M["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
    assert cell.chips == 1 and cell.kind == "train"
    assert (cell.mix["batch"], cell.mix["seq"]) == (2, 8192)
    assert cell.limits["max"] and cell.limits["min"]["window_losses_finite"]


def test_the_rehearsal_keeps_every_kind_of_layer():
    cell = mf.Cell(M, CELL)
    harness.rehearsal_cell(cell)
    d = cell.family.sizes(cell.config)
    assert d["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert d["dense_layers"] == 1 and d["experts"] < d["router"]
    assert d["kv_heads"] < d["heads"] and d["per_token"] < d["experts"]
    assert d["window"] < cell.mix["seq"]


def test_sound_rehearsal_is_correct_and_names_the_platform(capsys):
    cell = mf.Cell(M, CELL)
    out = io.StringIO()
    rc = harness.run_cell(cell, 2 ** 31 + 78, 1.0, False, rehearse=True,
                          out=out)
    err = capsys.readouterr().err
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    lines = [ln for ln in err.splitlines() if ln.startswith("[bench")]
    assert lines and all(ln.startswith("[bench cpu]") for ln in lines)
    assert line["phases"]["compiled_in_window"] == 0


def test_control_and_planted_faults_fail_the_rehearsal_s_limits():
    cell = mf.Cell(M, CELL)
    harness.rehearsal_cell(cell)
    fails = control.verdicts(cell, control.train_readings(cell, seed=21))
    assert set(fails) == {"control_float8", "half_batch", "state_unchanged"}
    assert all(fails.values()), fails
    assert "change_worst_leaf_gap" in fails["state_unchanged"]


def test_the_reference_imports_nothing_of_the_program():
    code = (
        "import sys, numpy as np\n"
        "from benchmark import manifest as mf, run as harness, train_cell\n"
        f"cell = mf.Cell(mf.load_manifest(), {CELL!r})\n"
        "harness.rehearsal_cell(cell)\n"
        "batch = np.zeros((2, 32), np.int32)\n"
        "ref = train_cell.reference_steps(cell.config, 3, [(batch, batch)],"
        " family=cell.family)\n"
        "assert len(ref['losses']) == 1 and ref['grad1_norms']\n"
        "print([m for m in sys.modules if m.split('.')[0] == 'apex_tpu'])\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=mf.ROOT, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": mf.ROOT})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"


def test_the_parent_s_harness_refuses_an_unknown_cell_at_once():
    """What the parent commit says of this cell: no such workload, before
    any backend is touched."""
    with pytest.raises(KeyError, match="no workload"):
        mf.Cell({**M, "workloads": [w for w in M["workloads"]
                                    if w["name"] != CELL]}, CELL)


# ---------------------------------------------------------------------------
# the cell's new kernels compiled for the described v5e at the cell's shapes
# (no chip attached: the compiler raises what the chip's would)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.mark.parametrize("window", [2048, None])
def test_banded_flash_kernels_compile_for_v5e_at_the_cell_s_shape(
        one_chip, no_cache, window):
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("apex_tpu.ops.flash_attention")
    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 4, 8192, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa._flash_band(q, k, v, 128 ** -0.5, window, 1024,
                                      1024, False).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    for name in ("apex_tpu_flash_fwd", "apex_tpu_flash_bwd_dq",
                 "apex_tpu_flash_bwd_dkv"):
        assert name in text


def test_grouped_matmul_kernels_compile_for_v5e_at_the_cell_s_shape(
        one_chip, no_cache):
    import importlib

    import jax
    import jax.numpy as jnp

    gm = importlib.import_module("apex_tpu.ops.grouped_matmul")
    rows = importlib.import_module("apex_tpu.transformer.moe").buffer_rows(
        16384, 8, 16)
    assert rows == 131072
    lhs = jax.ShapeDtypeStruct((rows, 2048), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((16, 2048, 1024), jnp.bfloat16,
                               sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)

    def loss(lhs, rhs, sizes):
        return jnp.sum(gm._grouped(lhs, rhs, sizes, False).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        lhs, rhs, sizes).compile().as_text()
    for name in (gm.FWD, gm.DLHS, gm.DRHS):
        assert name in text
