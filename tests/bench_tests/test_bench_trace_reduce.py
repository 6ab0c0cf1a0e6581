"""The reduction from a trace to numbers: busy/idle union, kernel sums,
gap attribution. On hand-made events with known answers, and on a small
recorded trace (a slice of a traced run of ``gpt2-345m.train-1chip`` on a
v5e, ``data/trace_sample.json``) against a second way of computing the
same thing."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace_reduce as tr  # noqa: E402

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "trace_sample.json")


def _hand():
    ops = [("fusion.1", 0.0, 10.0), ("fusion.2", 5.0, 10.0),    # overlap
           ("apex_tpu_flash_fwd", 20.0, 5.0),
           ("%copy.7 = bf16[8,1024]", 40.0, 10.0),
           ("apex_tpu_flash_bwd_dkv.3", 50.0, 20.0)]
    spans = [("bench.trace_window", 0.0, 100.0),
             ("bench.dispatch", 14.0, 5.0), ("bench.loss_fetch", 24.0, 15.0),
             ("bench.feed", 70.0, 4.0)]
    return tr.Trace({0: ops, 1: [("fusion.1", 0.0, 50.0)]}, spans)


def test_names_lose_their_numbers_and_their_hlo_text():
    assert tr.op_name("fusion.123") == "fusion"
    assert tr.op_name("%copy.7 = bf16[8,1024]") == "copy"
    assert tr.op_name("apex_tpu_flash_bwd_dkv.3") == "apex_tpu_flash_bwd_dkv"
    assert tr.op_name("bitcast_dynamic-update-slice_fusion.2.1") == \
        "bitcast_dynamic-update-slice_fusion"


def test_busy_is_the_union_averaged_over_devices():
    busy, window = tr.busy_and_window(_hand())
    # device 0: [0,15] + [20,25] + [40,70] = 50 ns; device 1: 50 ns
    assert busy == pytest.approx(50e-9) and window == pytest.approx(100e-9)


def test_kernel_sums_by_prefix_on_one_device():
    t = _hand()
    assert tr.kernel_seconds(t, "apex_tpu_flash") == pytest.approx(25e-9)
    assert tr.kernel_seconds(t, "apex_tpu_packed_") is None
    # two fusions overlap by 5 ns: the overlap is counted once
    assert tr.op_seconds(t)["fusion"] == pytest.approx(15e-9)
    assert tr.top_ops(t, 2)[0][0] in ("fusion", "apex_tpu_flash_bwd_dkv")


def test_a_loop_s_own_time_is_what_its_body_leaves():
    t = tr.Trace({0: [("%while.3 = (...) while(...)", 0.0, 100.0),
                      ("apex_tpu_packed_adam.1", 10.0, 30.0),
                      ("copy.2", 50.0, 10.0), ("fusion.9", 120.0, 5.0)]}, [])
    ops = tr.op_seconds(t)
    assert ops["while"] == pytest.approx(60e-9)
    assert ops["apex_tpu_packed_adam"] == pytest.approx(30e-9)
    assert sum(ops.values()) == pytest.approx(105e-9)   # = busy
    assert tr.busy_and_window(t)[0] == pytest.approx(105e-9)


def test_per_step_numbers_divide_by_the_whole_steps_in_the_window():
    mods = [("jit_train_step(1)", -50.0, 100.0),     # began before it
            ("jit_train_step(1)", 60.0, 100.0), ("jit_read(2)", 161.0, 2.0),
            ("jit_train_step(1)", 170.0, 100.0),
            ("jit_train_step(1)", 170.0, 100.0),     # listed twice
            ("jit_train_step(1)", 271.0, 3.0),       # a sliver of the same
            ("jit_train_step(1)", 280.0, 100.0)]     # ends after it
    ops = [("fusion.1", float(t), 5.0) for t in range(0, 300, 10)]
    t = tr.Trace({0: ops}, [("bench.trace_window", 0.0, 300.0),
                            ("bench.feed", 5.0, 1.0)], {0: mods})
    cut, n = tr.whole_steps(t)
    assert n == 2 and tr.window_of(cut) == (60.0, 270.0)
    assert tr.busy_and_window(cut)[1] == pytest.approx(210e-9)
    assert ("bench.feed", 5.0, 1.0) in cut.spans
    assert tr.whole_steps(tr.Trace({0: ops}, t.spans)) == (
        tr.Trace({0: ops}, t.spans), 0)


def test_gaps_go_to_the_span_that_covers_most_of_them():
    gaps = dict(tr.idle_gaps(_hand()))
    # [15,20]: dispatch covers 4 of 5. [25,40]: loss_fetch covers 14.
    # [70,100]: feed covers 4 of 30, nothing else does.
    assert gaps["bench.dispatch"] == pytest.approx(5e-9)
    assert gaps["bench.loss_fetch"] == pytest.approx(15e-9)
    assert gaps["bench.feed"] == pytest.approx(30e-9)


def test_a_gap_under_no_span_is_named_so():
    t = tr.Trace({0: [("a", 0.0, 1.0), ("b", 9.0, 1.0)]}, [])
    assert tr.idle_gaps(t) == [["_no_span_", pytest.approx(8e-9)]]
    assert tr.busy_and_window(tr.Trace({}, [])) is None


def test_events_are_clipped_to_the_traced_window():
    t = tr.Trace({0: [("a", -5.0, 10.0), ("b", 95.0, 10.0)]},
                 [("bench.trace_window", 0.0, 100.0)])
    busy, window = tr.busy_and_window(t)
    assert busy == pytest.approx(10e-9) and window == pytest.approx(100e-9)


# ---------------------------------------------------------------------------
# the recorded trace
# ---------------------------------------------------------------------------
def _recorded():
    with open(SAMPLE) as f:
        raw = json.load(f)
    return tr.Trace({int(k): [tuple(e) for e in v]
                     for k, v in raw["device_ops"].items()},
                    [tuple(s) for s in raw["spans"]])


def _busy_by_sweep(events, lo, hi):
    """A second way: sweep over the sorted edges counting open events."""
    edges = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort(key=lambda e: (e[0], -e[1]))
    busy, depth, since = 0.0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_trace_busy_idle_and_kernels():
    t = _recorded()
    lo, hi = tr.window_of(t)
    busy, window = tr.busy_and_window(t)
    assert window == pytest.approx((hi - lo) * 1e-9)
    want = _busy_by_sweep(t.device_ops[0], lo, hi) * 1e-9
    assert busy == pytest.approx(want, rel=1e-9)
    assert 0.5 < busy / window <= 1.0          # a train step keeps it busy
    ops = tr.op_seconds(t)
    # the kernels are found under their stable names
    assert any(k.startswith("apex_tpu_flash_") for k in ops)
    flash = tr.kernel_seconds(t, "apex_tpu_flash_")
    by_hand = sum(d for n, s, d in t.device_ops[0]
                  if tr.op_name(n).startswith("apex_tpu_flash_")
                  and s >= lo and s + d <= hi) * 1e-9
    assert flash == pytest.approx(by_hand, rel=1e-3)
    gaps = tr.idle_gaps(t)
    assert sum(v for _, v in gaps) == pytest.approx(window - busy, rel=1e-6)
