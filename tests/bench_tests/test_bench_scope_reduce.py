"""Device time by named scope (``benchmark/scope_reduce.py``): the rules on
a small hand-written HLO text and event list with known answers; the scopes
the readers name, found in the compiled rehearsal-size step of every cell;
and the readers on runs with nothing to read."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import manifest as mf  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark import scope_reduce as sr  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

M = mf.load_manifest()
NEW = [m["name"] for m in M["per_layer"] if m["source"] == "program_span"]

STEP = "jit(train_step)"
IN_LAYER = "apex_tpu.layer_stack/checkpoint/apex_tpu.transformer_layer"
HLO = f'''
HloModule jit_train_step

%fused_computation.1 (p0: bf16[8,8], p1: bf16[8,8]) -> bf16[8,8] {{
  %p0 = bf16[8,8]{{1,0}} parameter(0)
  %p1 = bf16[8,8]{{1,0}} parameter(1)
  %tanh.1 = bf16[8,8]{{1,0}} tanh(%p0), metadata={{op_name="{STEP}/jvp()/{IN_LAYER}/apex_tpu.mlp/tanh"}}
  ROOT %dot.1 = bf16[8,8]{{1,0}} dot(%tanh.1, %p1), metadata={{op_name="{STEP}/jvp()/{IN_LAYER}/apex_tpu.mlp/dot_general"}}
}}

%fused_computation.6 (p.6: bf16[8,8]) -> (bf16[8,8], bf16[8]) {{
  %p.6 = bf16[8,8]{{1,0}} parameter(0)
  %bitcast.6 = bf16[64]{{0}} bitcast(%p.6)
  ROOT %dynamic-update-slice.6 = (bf16[8,8]{{1,0}}, bf16[8]{{0}}) dynamic-update-slice(%bitcast.6, %p.6), metadata={{op_name="{STEP}/apex_tpu.optimizer_step/cond/branch_1_fun/apex_tpu.unpack/slice"}}
}}

ENTRY %main.9 (a: bf16[8,8], w: bf16[8,8]) -> bf16[8,8] {{
  %a = bf16[8,8]{{1,0}} parameter(0), metadata={{op_name="tokens"}}
  %w = bf16[8,8]{{1,0}} parameter(1), metadata={{op_name="params"}}
  %slice.2 = bf16[8,8]{{1,0}} slice(%w), slice={{[0:8], [0:8]}}, metadata={{op_name="{STEP}/jvp(apex_tpu.layer_stack)/slice"}}
  %copy.3 = bf16[8,8]{{0,1}} copy(%slice.2)
  %fusion.4 = bf16[8,8]{{1,0}} fusion(%a, %copy.3), kind=kOutput, calls=%fused_computation.1
  %apex_tpu_flash_fwd.5 = bf16[8,8]{{1,0}} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp()/{IN_LAYER}/apex_tpu.attention/apex_tpu.flash_attention/apex_tpu_flash_fwd"}}
  %fusion.6 = bf16[8,8]{{1,0}} fusion(%apex_tpu_flash_fwd.5), kind=kLoop, calls=%fc.2, metadata={{op_name="{STEP}/jvp()/{IN_LAYER}/apex_tpu.fused_block/add"}}
  %fusion.7 = bf16[8,8]{{1,0}} fusion(%fusion.6), kind=kLoop, calls=%fc.3, metadata={{op_name="{STEP}/transpose(jvp())/{IN_LAYER}/checkpoint/rematted_computation/apex_tpu.transformer_layer/apex_tpu.mlp/tanh"}}
  %fusion.8 = bf16[8,8]{{1,0}} fusion(%fusion.7), kind=kLoop, calls=%fc.4, metadata={{op_name="{STEP}/transpose(jvp(apex_tpu.cross_entropy))/apex_tpu.cross_entropy/while/body/mul"}}
  %copy.9 = bf16[8,8]{{0,1}} copy(%fusion.8)
  %fusion.10 = bf16[8,8]{{1,0}} fusion(%copy.9), kind=kLoop, calls=%fc.5, metadata={{op_name="{STEP}/apex_tpu.optimizer_step/cond/branch_1_fun/apex_tpu.pack/concatenate"}}
  %copy.11 = bf16[8,8]{{0,1}} copy(%fusion.10)
  %bitcast_dynamic-update-slice_fusion.12 = bf16[8,8]{{1,0}} fusion(%copy.11), kind=kLoop, calls=%fused_computation.6, metadata={{op_name="{STEP}/apex_tpu.optimizer_step/cond/branch_1_fun/apex_tpu.unpack/slice"}}
  %copy.13 = bf16[8,8]{{0,1}} copy(%a)
  %multiply.14 = bf16[8,8]{{1,0}} multiply(%copy.13, %copy.13), metadata={{op_name="{STEP}/mul"}}
  ROOT %psum.15 = bf16[8,8]{{1,0}} all-reduce(%multiply.14), metadata={{op_name="{STEP}/apex_tpu.sync_gradients/apex_tpu.grad_bucket/0/psum"}}
}}
'''


def _events():
    """One step of 200 ns and a second one of the same: ``(name, start,
    duration)`` with a ``while`` around the loss."""
    one = [("slice.2", 0, 4), ("copy.3", 4, 6), ("fusion.4", 10, 40),
           ("apex_tpu_flash_fwd.5", 50, 20), ("fusion.6", 70, 10),
           ("fusion.7", 80, 16), ("%while.99 = (...) while(...)", 96, 24),
           ("fusion.8", 100, 14), ("copy.9", 120, 10), ("fusion.10", 130, 10),
           ("copy.11", 140, 8),
           ("bitcast_dynamic-update-slice_fusion.12", 148, 12),
           ("copy.13", 160, 5), ("multiply.14", 165, 5),
           ("psum.15", 170, 30)]
    return [(n, float(s + off), float(d)) for off in (0, 200)
            for n, s, d in one]


def _scopes():
    """The text's scopes, with the loss's ``while`` among them."""
    return sr.scopes_of_text(
        HLO + f'  %while.99 = () while(), metadata={{op_name="{STEP}/'
        'transpose(jvp(apex_tpu.cross_entropy))/while"}\n')


def _table():
    return sr.reduce(_events(), _scopes(), (0.0, 400.0), 2)


def test_layer_and_phase_of_a_path():
    lp = sr.layer_and_phase
    assert lp(f"{STEP}/jvp()/{IN_LAYER}/apex_tpu.mlp/tanh") == (sr.MLP, "fwd")
    assert lp(f"{STEP}/transpose(jvp())/{IN_LAYER}/apex_tpu.attention/"
              "apex_tpu.flash_attention/apex_tpu_flash_bwd_dkv") == (
                  sr.ATTENTION, "bwd")
    assert lp(f"{STEP}/transpose(jvp())/{IN_LAYER}/checkpoint/"
              "rematted_computation/apex_tpu.transformer_layer/"
              "apex_tpu.fused_block/add") == (sr.LAYER, "recompute")
    assert lp(f"{STEP}/jvp(apex_tpu.layer_stack)/slice") == (
        sr.STACK, "fwd")
    assert lp(f"{STEP}/transpose(jvp(apex_tpu.embed))/scatter-add") == (
        sr.EMBED, "bwd")
    # the outermost layer owns what nests in it
    assert lp(f"{STEP}/apex_tpu.optimizer_step/cond/apex_tpu.unpack/slice"
              ) == (sr.OPTIMIZER, "fwd")
    assert lp(f"{STEP}/apex_tpu.sync_gradients/apex_tpu.grad_bucket/0/psum"
              ) == (sr.SYNC, "fwd")
    assert lp(f"{STEP}/mul") == (None, "fwd")
    assert lp("") == (None, "fwd")


def test_a_fusion_takes_its_root_s_path_and_a_kernel_its_layer():
    s = sr.scopes_of_text(HLO)
    assert s["fusion.4"].layer == sr.MLP and s["fusion.4"].how == "direct"
    assert s["fusion.4"].path.endswith("dot_general")      # the root's
    assert s["apex_tpu_flash_fwd.5"].layer == sr.ATTENTION
    assert s["fusion.6"].layer == sr.LAYER     # a tail, under no child
    assert (s["fusion.7"].layer, s["fusion.7"].phase) == (
        sr.MLP, "recompute")


def test_an_unscoped_copy_takes_its_consumer_else_its_producer():
    s = sr.scopes_of_text(HLO)
    # produced under the layer stack, consumed by the MLP: the consumer's
    assert (s["copy.3"].layer, s["copy.3"].how) == (sr.MLP, "neighbour")
    assert s["copy.9"].layer == sr.OPTIMIZER        # consumer
    # its consumer names no layer: its producer's... which is an argument
    assert (s["copy.13"].layer, s["copy.13"].how) == (sr.UNSCOPED, "none")
    assert s["multiply.14"].layer == sr.UNSCOPED
    only_producer = HLO.replace("multiply(%copy.13, %copy.13)",
                                "multiply(%w, %w)").replace(
        "copy(%a)", "copy(%fusion.6)")
    assert sr.scopes_of_text(only_producer)["copy.13"].layer == sr.LAYER


def test_which_operations_only_move_data_is_read_from_the_text():
    s = sr.scopes_of_text(HLO)
    for name in ("slice.2", "copy.3", "copy.9", "copy.13",
                 "bitcast_dynamic-update-slice_fusion.12"):
        assert s[name].moves, name
    for name in ("fusion.4", "apex_tpu_flash_fwd.5", "fusion.6",
                 "multiply.14", "psum.15"):
        assert not s[name].moves, name
    # the name does not decide: a weight-gradient GEMM that writes into a
    # stacked buffer is named after its bitcast and its update alone
    renamed = HLO.replace("%fusion.4 ", "%bitcast_dynamic-update-slice_"
                          "fusion.4 ")
    assert not sr.scopes_of_text(renamed)[
        "bitcast_dynamic-update-slice_fusion.4"].moves
    # an opcode stands after the shape, which for a tuple holds spaces
    assert sr._opcode("(bf16[8,8]{1,0}, bf16[8]{0}) dynamic-update-slice("
                      "%a, %b)") == "dynamic-update-slice"
    assert sr._opcode("bf16[8,8]{1,0} fusion(%a), kind=kLoop") == "fusion"


def test_the_table_per_step_by_layer_phase_and_kind():
    t = _table()
    rows = t["rows"]
    ms = 1e-6                                    # ns per step -> ms
    assert rows[(sr.STACK, "fwd", "relayout")] == pytest.approx(4 * ms)
    # the MLP's fusion and the copy that feeds it
    assert rows[(sr.MLP, "fwd", "compute")] == pytest.approx(40 * ms)
    assert rows[(sr.MLP, "fwd", "relayout")] == pytest.approx(6 * ms)
    assert rows[(sr.MLP, "recompute", "compute")] == pytest.approx(16 * ms)
    assert rows[(sr.ATTENTION, "fwd", "compute")] == pytest.approx(20 * ms)
    # the while keeps what its body leaves: 24 - 14
    assert rows[(sr.LOSS, "bwd", "compute")] == pytest.approx(24 * ms)
    assert rows[(sr.OPTIMIZER, "fwd", "relayout")] == pytest.approx(
        (10 + 8 + 12) * ms)
    assert rows[(sr.OPTIMIZER, "fwd", "compute")] == pytest.approx(10 * ms)
    assert rows[(sr.SYNC, "fwd", "compute")] == pytest.approx(30 * ms)
    assert t["busy_ms"] == pytest.approx(200 * ms)
    assert sum(rows.values()) == pytest.approx(t["busy_ms"])
    assert t["how"]["neighbour"] == pytest.approx((6 + 10 + 8) * ms)
    assert t["how"]["none"] == pytest.approx(10 * ms)
    assert t["unscoped_ops"] == {"copy": pytest.approx(5 * ms),
                                 "multiply": pytest.approx(5 * ms)}
    # which operations moved the data, by layer and phase
    assert t["relayout_ops"][f"{sr.OPTIMIZER}|fwd|copy"] == pytest.approx(
        18 * ms)
    assert t["relayout_ops"][
        f"{sr.OPTIMIZER}|fwd|bitcast_dynamic-update-slice_fusion"
    ] == pytest.approx(12 * ms)


def test_totals_the_readers_take():
    t = _table()
    ms = 1e-6
    assert sr.total(t, layers=(sr.MLP,)) == pytest.approx(62 * ms)
    assert sr.total(t, layers=sr.MODEL, phase="bwd") == pytest.approx(
        24 * ms)
    assert sr.total(t, phase="recompute") == pytest.approx(16 * ms)
    assert sr.total(t, layers=sr.MODEL, kind="relayout") == pytest.approx(
        10 * ms)
    assert sr.total(t, layers=(sr.SCALER,)) is None
    # pack and unpack wherever they stand: here inside the optimizer,
    # each with the copy that feeds it
    assert sr.inside(t, (sr.PACK, sr.UNPACK)) == pytest.approx(
        (10 + 10 + 8 + 12) * ms)
    assert sr.inside(t, (sr.SCALER,)) is None


def test_events_outside_the_window_are_cut_and_steps_divide():
    one_step = sr.reduce(_events(), _scopes(), (0.0, 200.0), 1)
    assert one_step["rows"][(sr.MLP, "fwd", "compute")] == pytest.approx(
        40e-6)
    assert one_step["busy_ms"] == pytest.approx(200e-6)
    head = sr.reduce(_events(), _scopes(), (0.0, 30.0), 1)
    assert head["rows"][(sr.MLP, "fwd", "compute")] == pytest.approx(20e-6)
    assert head["busy_ms"] == pytest.approx(30e-6)


def test_an_event_the_text_does_not_hold_gives_nothing():
    events = _events() + [("fusion.777", 400.0, 50.0)]
    assert sr.reduce(events, _scopes(), (0.0, 450.0), 2) is None
    # the loss's while alone, 10 of 200 ns a step, is already too much
    assert sr.reduce(_events(), sr.scopes_of_text(HLO), (0.0, 400.0),
                     2) is None
    assert sr.reduce([], _scopes(), (0.0, 1.0), 1) is None


# ---------------------------------------------------------------------------
# the readers with nothing to read
# ---------------------------------------------------------------------------
def test_the_manifest_has_the_eleven_scope_metrics_at_its_end():
    assert NEW == [m["name"] for m in M["per_layer"]][-11:]
    assert len(NEW) == 11 and mf.check(M) == []


@pytest.mark.parametrize("metric", NEW)
@pytest.mark.parametrize("run", [
    {"trace": None, "traced_units": 3},
    {"trace": tr.Trace({0: [("fusion.1", 0.0, 1.0)]}, []),
     "traced_units": 0},
    {"trace": tr.Trace({}, []), "traced_units": 3}])
def test_a_reader_without_a_trace_or_steps_reads_nothing_and_builds_nothing(
        metric, run, monkeypatch):
    def no_rebuild(*a, **k):
        raise AssertionError("a reader rebuilt the step with nothing "
                             "to read")
    monkeypatch.setattr(sr, "step_text", no_rebuild)
    assert mf.reader(metric)({"cell": "gpt2-345m.train-1chip",
                              "platform": "cpu", **run}) is None


# ---------------------------------------------------------------------------
# the scopes the readers name, in the compiled step of every cell
# ---------------------------------------------------------------------------
_RUNS = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")
BOTH_WAYS = (sr.EMBED, sr.STACK, sr.LAYER, sr.ATTENTION, sr.MLP, sr.HEAD,
             sr.LOSS)
ONE_WAY = {
    "gpt2-345m.train-1chip": (sr.OPTIMIZER, sr.SCALER),
    "bert-large.train-1chip": (sr.OPTIMIZER,),
    "gpt2-345m.train-dp4": (sr.OPTIMIZER, sr.SCALER, sr.SYNC),
}


@pytest.fixture(scope="module", params=sorted(ONE_WAY))
def compiled_cell(request):
    cell = mf.Cell(M, request.param)
    harness.rehearsal_cell(cell)
    text = sr.step_text(cell, jax.devices()[:cell.chips], interpret=True)
    return request.param, text


def test_every_scope_a_reader_names_is_in_the_compiled_step(compiled_cell):
    name, text = compiled_cell
    assert text is not None
    scopes = sr.scopes_of_text(text)
    seen = {(s.layer, s.phase) for s in scopes.values()}
    for layer in BOTH_WAYS:
        assert (layer, "fwd") in seen and (layer, "bwd") in seen, layer
    for layer in ONE_WAY[name]:
        assert (layer, "fwd") in seen, layer
    assert any(phase == "recompute" for _, phase in seen)
    if name != "bert-large.train-1chip":        # its LAMB packs nothing
        paths = " ".join(s.path for s in scopes.values())
        assert sr.UNPACK in paths and sr.PACK in paths


def test_nearly_every_operation_of_the_step_stands_under_a_layer(
        compiled_cell):
    _, text = compiled_cell
    scopes = sr.scopes_of_text(text)
    ran = own = 0
    for line in text.splitlines():
        m = sr._INSTRUCTION.match(line)
        if (m is None or "op_name=" not in line
                or sr._opcode(line[m.end():]) in _RUNS):
            continue
        ran += 1
        own += scopes[m.group(2)].layer != sr.UNSCOPED
    assert ran > 500 and own >= 0.95 * ran, (own, ran)
