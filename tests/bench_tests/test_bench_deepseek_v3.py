"""The ``moonlight-16b-a3b`` configuration and its cell: the catalog row's
``config`` key for key but for the cut, the deployment the file states, the
parameter arithmetic, the family's work counts against a count by hand, the
cell's CPU rehearsal (the sound run reads ``correct``; the float8 control
and every planted fault do not), and the cell's kernels compiled for the
described v5e at the cell's shapes."""
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
CELL = "moonlight-16b-a3b.train-1chip"
SIBLING = "trinity-mini.train-1chip"
# the family file is not named after the model_type alone: an accepted test
# (test_bench_manifest.py) looks for "bert.py", "gpt.py" side by side in
# the sorted list of family files, and deepseek_v3.py would part them
FAMILY = "mla_deepseek_v3"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the source's config.json as the catalog holds it, key for key
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
CUT = {"num_hidden_layers": 6, "n_routed_experts": 8, "vocab_size": 20480}


def _config():
    entry = {c["name"]: c for c in M["configs"]}["moonlight-16b-a3b"]
    return entry, mf._json(os.path.join(mf.ROOT, entry["file"]))


def test_every_width_is_the_published_one_and_only_the_three_keys_are_cut():
    entry, config = _config()
    assert sorted(entry["reduced"]) == sorted(CUT) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert config["family"] == FAMILY
    for key, published in PUBLISHED.items():
        if key in CUT:
            assert config[key] == CUT[key], key
            assert config["published"][key] == published, key
        else:
            assert config[key] == published, key
    # no width among the keys cut, nor what the contract calls one
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in CUT)
    assert "num_experts_per_tok" not in CUT
    assert set(CUT) == set(mf.family(FAMILY).REDUCIBLE)


def test_every_line_this_pr_writes_into_the_manifest_has_the_drivers_form():
    # manifest.check holds a cell's `why` to 200 characters and says nothing
    # of a configuration's: the driver refused this PR's first at 218
    entry, _ = _config()
    cell = {w["name"]: w for w in M["workloads"]}[CELL]
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable(), text
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        entry["name"], "train-s8192", 1)
    assert M["configs"][-1] is entry and M["workloads"][-1] is cell


def test_the_file_equals_the_catalog_row_but_for_the_cut():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures here")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = {r["name"]: r for r in rows}["Moonlight-16B-A3B"]
    entry, config = _config()
    assert entry["source"] == row["source_url"]
    assert row["config"] == PUBLISHED
    for key, value in row["config"].items():
        if key in CUT:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_cut_is_one_rank_of_eight_with_five_expert_layers_kept():
    _, config = _config()
    d = mf.family(FAMILY).sizes(config)
    dep = config["deployment"]
    assert dep["chips_sharing_each_layer"] == 8 and dep["this_rank"] == 0
    assert d["router"] == 64 and d["per_token"] == 6
    assert d["experts"] * 8 == d["router"]
    assert d["vocab"] * 8 == config["published"]["vocab_size"]
    assert config["kept_layers"] == [0, 1, 2, 3, 4, 5]
    assert d["dense_layers"] == 1 and d["layers"] == 6
    assert (d["nope"], d["rope"], d["value"], d["latent"]) == (
        128, 64, 128, 512)
    assert d["shared_ffn"] == 2816 and d["expert_ffn"] == 1408
    # the guide's floors
    assert d["layers"] - d["dense_layers"] >= 4 and d["experts"] >= 8
    for key in ("origin", "attention", "rotary_order", "expert_mlp",
                "expert_bias", "optimizer", "initialisation"):
        assert config["assumed"][key]
    assert "Muon" in config["assumed"]["optimizer"]
    assert config["train"]["optimizer"] == {
        "kind": "adam", "lr": 1e-05, "b1": 0.9, "b2": 0.999, "eps": 1e-08}


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("moe_layer_freq", 2), ("scoring_func", "softmax"),
    ("rope_scaling", {"type": "yarn", "factor": 40})])
def test_the_family_refuses_what_it_does_not_build(key, value):
    _, config = _config()
    with pytest.raises(ValueError, match=key):
        mf.family(FAMILY).sizes({**config, key: value})


def test_the_parameters_are_what_the_arithmetic_says():
    import jax
    import jax.numpy as jnp

    _, config = _config()
    family = mf.family(FAMILY)
    tree = jax.eval_shape(
        lambda k: family.init_from_key(config, k, jnp.bfloat16),
        jax.random.PRNGKey(0))
    q, down, up, o = 2048 * 3072, 2048 * 576, 512 * 4096, 2048 * 2048
    attention = q + down + 512 + up + o
    assert (q, down, up, o, attention) == (
        6_291_456, 1_179_648, 2_097_152, 4_194_304, 13_763_072)
    norms = 2 * 2048
    dense = attention + norms + 3 * 2048 * 11264
    expert = (attention + norms + 64 * 2048 + 3 * 2048 * 2816
              + 8 * 3 * 2048 * 1408)
    assert (dense, expert) == (82_973_184, 100_405_760)
    want = dense + 5 * expert + 2 * 20480 * 2048 + 2048
    got = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert got == want == 668_890_112
    assert 9.36e9 < 14 * got < 9.37e9                # of the chip's 16 GB
    # whole, an expert layer does not fit twice; six kept do not leave room
    assert attention + norms + 64 * 2048 + 3 * 2048 * 2816 + (
        64 * 3 * 2048 * 1408) == 584_847_872    # 8.2 GB
    assert want + expert == 769_295_872
    # every held expert's three matrices are tensors of their own
    norms_of = jax.eval_shape(
        lambda t: family.tensor_norms(config, t), tree)
    assert norms_of["layers"][1]["experts_gate_w"].shape == (8,)
    assert norms_of["layers"][0]["gate_w"].shape == ()
    assert norms_of["layers"][1]["kv_up_w"].shape == ()


def test_the_work_a_step_needs_against_a_count_by_hand():
    _, config = _config()
    family = mf.family(FAMILY)
    b, s = 2, 8192
    tokens = b * s
    pairs = 33_558_528                                  # s (s + 1) / 2
    assert family.attention_pairs(s, None) == pairs
    rows = tokens * 6 * 8 / 64                          # expected assignments
    assert rows == 12288
    proj = 2048 * (3072 + 576) + 512 * 4096 + 2048 * 2048
    attn = 6 * (2 * tokens * proj + 2 * b * pairs * 16 * (192 + 128))
    mlp = (6 * tokens * 2048 * 11264
           + 5 * (2 * tokens * 2048 * 64 + 6 * tokens * 2048 * 2816
                  + 6 * rows * 2048 * 1408))
    head = 2 * tokens * 2048 * 20480
    flops = family.train_flops_per_step(config, b, s)
    assert flops == pytest.approx(3 * (attn + mlp + head), rel=1e-12)
    assert 2.5e9 < flops / tokens < 2.8e9
    work = family.kernel_work(config, b, s)
    assert set(work) == {"flash_attention", "grouped_matmul"}
    # per pair and head 2 x (320 + 640): two products forward, four backward
    assert work["flash_attention"][0] == 6 * b * pairs * 16 * 2 * (320 + 640)
    # q, k, v, o forward; q, k, v, o, do, dq, dk, dv backward: q-like 3 x
    # 16 x 192, k 3 x (16 x 128 + 64), v-like 6 x 16 x 128
    assert work["flash_attention"][1] == 6 * tokens * 2 * 3 * (
        16 * 192 + 16 * 128 + 64 + 2 * 16 * 128)
    gf, gb = work["grouped_matmul"]
    assert gf == 5 * 3 * 3 * 2 * rows * 2048 * 1408
    assert gb == 5 * 3 * 2 * (3 * 8 * 2048 * 1408 + 3 * rows * (2048 + 1408))


# ---------------------------------------------------------------------------
# the cell's rehearsal
# ---------------------------------------------------------------------------
def test_the_cell_reports_what_the_gpt_cell_reports_and_its_two_own():
    """And, of what the sibling expert cell reports beyond that, nothing
    yet: an accepted test (``test_bench_afmoe.py``) holds the three expert
    scopes' and the two grouped-matmul metrics' ``workloads`` to that one
    cell, and this PR may edit no file the benchmark has (``PERF.md`` §7
    asks the next ``benchmark`` PR for it)."""
    cell = mf.Cell(M, CELL)
    sibling, gpt = mf.Cell(M, SIBLING), mf.Cell(M, "gpt2-345m.train-1chip")
    mine = {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in gpt.per_layer} <= mine
    assert not any(name.startswith("ddp.") for name in mine)
    new = {"model.mla_latent_ms", "model.mla_rope_ms"}
    assert mine - {m["name"] for m in gpt.per_layer} == new
    assert {m["name"] for m in sibling.per_layer} - mine == {
        "model.moe_router_ms", "model.moe_dispatch_ms",
        "model.moe_experts_ms", "kernels.grouped_matmul_ms",
        "kernels.grouped_matmul_roofline"}
    assert {"kernels.flash_attention_ms", "kernels.flash_attention_roofline",
            "model.mfu", "device.hbm_program_gb"} <= mine
    for m in M["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
            assert (m["source"], m["layer"], m["moves"]) == (
                "program_span", "model", "train_tokens_per_s")
    assert [m["name"] for m in cell.end_to_end] == [
        m["name"] for m in sibling.end_to_end]
    assert cell.chips == 1 and cell.kind == "train"
    assert cell.traffic_name == sibling.traffic_name == "train-s8192"
    assert (cell.mix["batch"], cell.mix["seq"]) == (2, 8192)
    assert cell.limits["max"] and cell.limits["min"]["window_losses_finite"]
    assert not mf.check(M)


@pytest.mark.parametrize("metric, scope", [
    ("model.mla_latent_ms", "apex_tpu.mla_latent"),
    ("model.mla_rope_ms", "apex_tpu.mla_rope")])
def test_a_new_reader_reads_its_scope_and_is_silent_without_it(metric, scope):
    """The parent's step names no such scope: the reader returns nothing
    and does not raise."""
    from benchmark import scope_reduce as sr

    read = mf.reader(metric)
    assert read({"trace": None, "traced_units": 0}) is None
    table = {"inside": {scope: 12.5, "apex_tpu.attention": 300.0}}
    old = {"inside": {"apex_tpu.attention": 300.0}}
    for cell, t, want in (("with", table, 12.5), ("without", old, None)):
        sr._TABLES[cell] = t
        try:
            run = {"cell": cell, "traced_units": 3,
                   "trace": type("T", (), {"device_ops": [1]})()}
            assert read(run) == want
        finally:
            del sr._TABLES[cell]


def test_sound_rehearsal_is_correct_and_names_the_platform(capsys):
    cell = mf.Cell(M, CELL)
    out = io.StringIO()
    rc = harness.run_cell(cell, 2 ** 31 + 79, 1.0, False, rehearse=True,
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
    fails = control.verdicts(cell, control.train_readings(cell, seed=22))
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


def test_a_program_without_latent_attention_exits_at_once(monkeypatch):
    """What the parent commit says of this cell under this benchmark: its
    ``GPTConfig`` has no ``latent_kv``, and the recipe exits before any
    weight is made or any step compiled."""
    from apex_tpu.transformer import testing

    _, config = _config()
    monkeypatch.delattr(testing, "LatentKV")
    with pytest.raises(SystemExit, match="no latent attention"):
        mf.family(FAMILY).program_config(config)


# ---------------------------------------------------------------------------
# the cell's kernels compiled for the described v5e at the cell's shapes
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


def test_two_width_flash_kernels_compile_for_v5e_at_the_cell_s_shape(
        one_chip, no_cache):
    """``[2, 16, 8192]`` x 192 (q, k) / 128 (v), bfloat16: each operand at
    its own width, nothing padded in HBM."""
    import importlib

    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("apex_tpu.ops.flash_attention")
    qk = jax.ShapeDtypeStruct((2, 16, 8192, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 16, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa._flash_band(q, k, v, 192 ** -0.5, None, 1024, 1024,
                                      False).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        qk, qk, v).compile()
    text = compiled.as_text()
    for name in ("apex_tpu_flash_fwd", "apex_tpu_flash_bwd_dq",
                 "apex_tpu_flash_bwd_dkv"):
        assert name in text
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes == 2 * 16 * 8192 * (192 + 192 + 128) * 2
    # dq, dk at 192, dv at 128 (and the float32 loss)
    assert ma.output_size_in_bytes - ma.argument_size_in_bytes < 4096


def test_grouped_matmul_kernels_compile_for_v5e_at_the_cell_s_shape(
        one_chip, no_cache):
    """98,304 buffer rows (16,384 x 6) of 2048, 8 groups, width 1408."""
    import importlib

    import jax
    import jax.numpy as jnp

    gm = importlib.import_module("apex_tpu.ops.grouped_matmul")
    rows = importlib.import_module("apex_tpu.transformer.moe").buffer_rows(
        16384, 6, 8)
    assert rows == 98304
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(lhs, rhs, sizes):
        return jnp.sum(gm._grouped(lhs, rhs, sizes, False).astype(jnp.float32))

    for lhs, rhs in ((S(rows, 2048), S(8, 2048, 1408)),     # gate, up
                     (S(rows, 1408), S(8, 1408, 2048))):    # down
        text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
            lhs, rhs, sizes).compile().as_text()
        for name in (gm.FWD, gm.DLHS, gm.DRHS):
            assert name in text


def test_the_activation_kernels_compile_for_v5e_at_width_1408(
        one_chip, no_cache):
    import importlib

    import jax
    import jax.numpy as jnp

    moe_rows = importlib.import_module("apex_tpu.ops.moe_rows")
    buf = jax.ShapeDtypeStruct((98304, 1408), jnp.bfloat16, sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda g, u, n: jax.value_and_grad(
        lambda g, u: jnp.sum(moe_rows.gated_act(g, u, n, False)
                             .astype(jnp.float32)),
        argnums=(0, 1))(g, u)).lower(buf, buf, n).compile().as_text()
    for name in (moe_rows.ACT_FWD, moe_rows.ACT_BWD):
        assert name in text
