"""Smoke tests for tools/compare_bench.py — tier-1-safe (pure JSON, no
jax): per-leg regression detection plus schema-drift protection against
the real archived bench captures, so a bench.py output change that
breaks the extractor fails CI here rather than silently in the driver.
"""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from tools.compare_bench import (  # noqa: E402
    compare,
    compare_trajectory,
    extract_legs,
    load_bench,
    main,
)

REPO = Path(__file__).resolve().parent.parent


def _bench(tokens=30000.0, bert=12000.0, gbps=600.0):
    return {
        "metric": "gpt2_345m_1chip_bf16_train_throughput",
        "value": tokens,
        "unit": "tokens/sec",
        "true_mfu": 0.33,
        "bert_large_lamb": {"tokens_per_sec": bert},
        "packed_optimizer": {"gbps_achieved": gbps, "vs_pytree": 1.4},
        "telemetry_overhead": {"overhead_pct": 0.3},
    }


def test_extract_legs_orients_lower_is_better():
    legs = extract_legs(_bench())
    assert legs["gpt_tokens_per_sec"] == 30000.0
    # lower-is-better legs are negated so "higher is better" is uniform
    assert legs["telemetry_overhead_pct"] == -0.3


def test_compare_flags_regression_and_improvement():
    base = _bench()
    new = _bench(tokens=20000.0, bert=13000.0)  # gpt -33%, bert +8%
    rep = compare(base, new, threshold=0.05)
    regressed = {r["leg"] for r in rep["regressions"]}
    improved = {r["leg"] for r in rep["improvements"]}
    assert "gpt_tokens_per_sec" in regressed
    assert "bert_tokens_per_sec" in improved
    assert "packed_opt_gbps" in rep["unchanged"]
    # a higher overhead_pct is a REGRESSION even though the number rose,
    # and the report shows the ORIGINAL signed values, not magnitudes
    lucky = _bench()
    lucky["telemetry_overhead"]["overhead_pct"] = -0.5
    worse_overhead = _bench()
    worse_overhead["telemetry_overhead"]["overhead_pct"] = 5.0
    rep2 = compare(lucky, worse_overhead, threshold=0.05)
    (entry,) = [r for r in rep2["regressions"]
                if r["leg"] == "telemetry_overhead_pct"]
    assert entry["base"] == -0.5 and entry["new"] == 5.0
    assert entry["delta_abs"] == pytest.approx(5.5)


def test_overhead_pct_uses_absolute_tolerance():
    """A near-zero percentage metric must not turn sub-point noise into
    a regression via the relative threshold (-0.3 -> +0.4 is noise)."""
    lucky, noisy = _bench(), _bench()
    lucky["telemetry_overhead"]["overhead_pct"] = -0.3
    noisy["telemetry_overhead"]["overhead_pct"] = 0.4
    rep = compare(lucky, noisy, threshold=0.05)
    assert "telemetry_overhead_pct" in rep["unchanged"]


def test_compare_within_threshold_is_unchanged():
    rep = compare(_bench(tokens=10000.0), _bench(tokens=10300.0),
                  threshold=0.05)
    assert not rep["regressions"] and not rep["improvements"]
    assert "gpt_tokens_per_sec" in rep["unchanged"]


def test_compare_reports_schema_drift():
    base, new = _bench(), _bench()
    del new["bert_large_lamb"]  # a leg vanishing must be visible
    rep = compare(base, new)
    assert "bert_tokens_per_sec" in rep["only_in_base"]


def test_load_bench_handles_raw_capture_and_garbage(tmp_path):
    raw = tmp_path / "raw.json"
    raw.write_text(json.dumps(_bench()))
    assert load_bench(str(raw))["value"] == 30000.0

    cap = tmp_path / "cap.json"
    cap.write_text(json.dumps(
        {"n": 3, "rc": 0, "tail": "noise\n" + json.dumps(_bench()),
         "parsed": None}))
    assert load_bench(str(cap))["value"] == 30000.0

    trunc = tmp_path / "trunc.json"
    trunc.write_text(json.dumps(
        {"n": 5, "rc": 0, "tail": 'gbps": 1.0}', "parsed": None}))
    assert load_bench(str(trunc)) is None


def test_trajectory_over_driver_captures(tmp_path):
    """A trajectory over captures in the driver's format (the JSON line
    at the end of a noisy ``tail``, ``parsed`` null) — the schema-drift
    canary: bench.py's output and the extractor evolve together."""
    paths = []
    for i, tokens in enumerate((27600.0, 33700.0, 40700.0, 46200.0)):
        path = tmp_path / f"capture_{i}.json"
        path.write_text(json.dumps(
            {"n": i, "rc": 0, "parsed": None,
             "tail": "a warning line\n" + json.dumps(_bench(tokens=tokens))}))
        paths.append(str(path))
        assert extract_legs(load_bench(paths[-1]))[
            "gpt_tokens_per_sec"] == tokens
    rep = compare_trajectory(paths, threshold=0.05)
    assert len(rep["steps"]) == 3
    for step in rep["steps"]:
        assert "regressions" in step and "only_in_new" in step


def test_cli_exit_codes(tmp_path, capsys):
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_bench()))
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_bench(tokens=31000.0)))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(_bench(tokens=9000.0)))

    assert main([str(base), str(good)]) == 0
    capsys.readouterr()  # drop the first report
    assert main([str(base), str(bad)]) == 1
    report = json.loads(capsys.readouterr().out)
    assert report["regressions"][0]["leg"] == "gpt_tokens_per_sec"
    # custom threshold: a 10% drop passes at --threshold 0.2
    mid = tmp_path / "mid.json"
    mid.write_text(json.dumps(_bench(tokens=27000.0)))
    assert main([str(base), str(mid), "--threshold", "0.2"]) == 0


def test_cli_trajectory_all_unparseable_fails_loudly(tmp_path, capsys):
    """Schema drift truncating EVERY capture must not exit 0 — an empty
    comparison is a failure of the gate, not a pass."""
    paths = []
    for i in range(3):
        p = tmp_path / f"t{i}.json"
        p.write_text(json.dumps({"n": i, "rc": 0, "tail": "}", "parsed": None}))
        paths.append(str(p))
    assert main(paths + ["--trajectory"]) == 2
    report = json.loads(capsys.readouterr().out)
    assert len(report["skipped_unparseable"]) == 3


# ---------------------------------------------------------------------------
# op-breakdown category diffing (ISSUE-9)
# ---------------------------------------------------------------------------

def _bench_with_categories(elementwise, data_movement, matmul):
    b = _bench()
    other = 100.0 - elementwise - data_movement - matmul
    b["op_breakdown"] = {
        "source": "xplane",
        "categories": {
            "fusion(elementwise)": {"ms_per_step": 1.0, "pct": elementwise},
            "data-movement": {"ms_per_step": 1.0, "pct": data_movement},
            "matmul/conv": {"ms_per_step": 1.0, "pct": matmul},
            "attention-kernel": {"ms_per_step": 1.0, "pct": other},
        },
    }
    return b


def test_category_regression_flagged_over_2pp():
    base = _bench_with_categories(20.0, 10.0, 40.0)
    new = _bench_with_categories(25.0, 10.0, 35.0)  # elementwise +5pp
    rep = compare(base, new)
    regressed = {r["leg"] for r in rep["regressions"]}
    assert "op_category:fusion(elementwise)" in regressed
    (entry,) = [r for r in rep["regressions"]
                if r["leg"] == "op_category:fusion(elementwise)"]
    assert entry["delta_pp"] == 5.0
    # the full shift table rides the report
    shifts = {s["category"]: s["delta_pp"]
              for s in rep["op_categories"]["shift"]}
    assert shifts["matmul/conv"] == -5.0


def test_compute_category_growth_not_flagged():
    # winning back elementwise time NECESSARILY grows the matmul share —
    # that is the point of the fused tails, not a regression
    base = _bench_with_categories(42.0, 18.0, 12.0)
    new = _bench_with_categories(25.0, 10.0, 37.0)  # the ISSUE-9 target
    rep = compare(base, new)
    assert not [r for r in rep["regressions"]
                if r["leg"].startswith("op_category:")]


def test_category_shift_within_threshold_not_flagged():
    base = _bench_with_categories(20.0, 10.0, 40.0)
    new = _bench_with_categories(21.5, 10.0, 38.5)  # +1.5pp < 2pp
    rep = compare(base, new)
    assert not [r for r in rep["regressions"]
                if r["leg"].startswith("op_category:")]


def test_missing_breakdown_skips_category_diff():
    rep = compare(_bench(), _bench_with_categories(20.0, 10.0, 40.0))
    assert rep["op_categories"] is None
    # cost_analysis captures (CPU) publish empty categories — also skipped
    empty = _bench()
    empty["op_breakdown"] = {"source": "cost_analysis", "categories": {}}
    rep2 = compare(empty, empty)
    assert rep2["op_categories"] is None


def test_category_appearing_counts_as_shift():
    base = _bench_with_categories(20.0, 10.0, 40.0)
    new = _bench_with_categories(20.0, 10.0, 40.0)
    new["op_breakdown"]["categories"]["fusion(unattributed)"] = {
        "ms_per_step": 2.0, "pct": 6.0}
    rep = compare(base, new)
    regressed = {r["leg"] for r in rep["regressions"]}
    assert "op_category:fusion(unattributed)" in regressed


def _bench_with_grad_lifecycle(speedup=1.9, bytes_ratio=0.95,
                               steps_per_sec=50.0):
    b = _bench()
    b["grad_lifecycle"] = {
        "per_leaf": {"steps_per_sec": steps_per_sec / speedup},
        "flat": {"steps_per_sec": steps_per_sec},
        "speedup": speedup,
        "bytes_ratio": bytes_ratio,
        "flops_ratio": 1.1,
    }
    return b


def test_grad_lifecycle_legs_extract_and_gate():
    """ISSUE-14: the flat-vs-per-leaf A/B is a first-class gated leg —
    speedup and flat steps/s regress like throughput, and bytes_ratio
    regresses when it RISES back toward parity (lower is better)."""
    legs = extract_legs(_bench_with_grad_lifecycle())
    assert legs["grad_lifecycle_speedup"] == 1.9
    assert legs["grad_lifecycle_bytes_ratio"] == -0.95  # lower-is-better
    assert legs["grad_lifecycle_steps_per_sec"] == 50.0

    base = _bench_with_grad_lifecycle()
    worse = _bench_with_grad_lifecycle(speedup=1.2, bytes_ratio=1.05,
                                       steps_per_sec=40.0)
    rep = compare(base, worse, threshold=0.05)
    regressed = {r["leg"] for r in rep["regressions"]}
    assert {"grad_lifecycle_speedup", "grad_lifecycle_bytes_ratio",
            "grad_lifecycle_steps_per_sec"} <= regressed
    # improvement direction: bytes_ratio FALLING is an improvement
    better = _bench_with_grad_lifecycle(bytes_ratio=0.80)
    rep2 = compare(base, better, threshold=0.05)
    improved = {r["leg"] for r in rep2["improvements"]}
    assert "grad_lifecycle_bytes_ratio" in improved


def test_grad_lifecycle_smoke_artifact_carries_gated_legs():
    """The committed CPU smoke artifact records the acceptance numbers
    the gates act on: bytes_ratio < 1.0 and speedup > 1 with equal
    final_loss on both legs (the bit-identity witness)."""
    art = json.loads(
        (REPO / "bench_artifacts/grad_lifecycle_cpu_smoke.json")
        .read_text())
    leg = art["grad_lifecycle"]
    assert leg["bytes_ratio"] < 1.0
    assert leg["speedup"] > 1.0
    assert leg["flat"]["final_loss"] == leg["per_leaf"]["final_loss"]
    assert leg["n_buckets"] >= 2 and leg["world"] >= 2


# ---------------------------------------------------------------------------
# static comm budgets (ISSUE-19): count pins + bytes growth gate
# ---------------------------------------------------------------------------
def _comm(psum_count=3, psum_bytes=1040, gather_bytes=2048):
    return {"psum": {"count": psum_count, "bytes": psum_bytes,
                     "axes": ["tensor"]},
            "all_gather": {"count": 2, "bytes": gather_bytes,
                           "axes": ["tensor"]}}


def _bench_with_comm(**kw):
    b = _bench()
    b["serving_tp"] = {"comm_volume": {"decode": _comm(**kw)}}
    return b


def test_comm_count_change_is_exact_pin_both_directions():
    base = _bench_with_comm()
    grew = _bench_with_comm(psum_count=4)
    rep = compare(base, grew, threshold=0.05)
    (entry,) = [r for r in rep["regressions"]
                if r["leg"].startswith("comm_count:")]
    assert entry["leg"] == "comm_count:serving_tp.decode/psum"
    assert entry["base"] == 3 and entry["new"] == 4
    # a VANISHED collective regresses too (lost reduction != perf win)
    shrank = _bench_with_comm(psum_count=2)
    rep2 = compare(base, shrank, threshold=0.05)
    assert any(r["leg"] == "comm_count:serving_tp.decode/psum"
               for r in rep2["regressions"])


def test_comm_new_collective_family_is_flagged():
    base = _bench_with_comm()
    new = _bench_with_comm()
    new["serving_tp"]["comm_volume"]["decode"]["ppermute"] = {
        "count": 1, "bytes": 64, "axes": ["tensor"]}
    rep = compare(base, new, threshold=0.05)
    assert any(r["leg"] == "comm_count:serving_tp.decode/ppermute"
               and r["base"] == 0 and r["new"] == 1
               for r in rep["regressions"])


def test_comm_bytes_growth_gated_at_threshold():
    base = _bench_with_comm()
    fat = _bench_with_comm(gather_bytes=4096)  # +100% at equal count
    rep = compare(base, fat, threshold=0.05)
    (entry,) = [r for r in rep["regressions"]
                if r["leg"].startswith("comm_bytes:")]
    assert entry["leg"] == "comm_bytes:serving_tp.decode/all_gather"
    assert entry["delta_pct"] == 100.0
    # within the threshold: unchanged
    ok = compare(base, _bench_with_comm(gather_bytes=2080),
                 threshold=0.05)
    assert not any(r["leg"].startswith("comm_")
                   for r in ok["regressions"])


def test_comm_absent_in_either_capture_is_not_a_regression():
    """Captures predating the comm model (or a program dropped from the
    bench matrix) compare on the legs they share, like audit blocks."""
    rep = compare(_bench(), _bench_with_comm(), threshold=0.05)
    assert rep["comm"] is None
    assert not any(r["leg"].startswith("comm_")
                   for r in rep["regressions"])
    rep2 = compare(_bench_with_comm(), _bench(), threshold=0.05)
    assert rep2["comm"] is None


def test_comm_gpt_headline_rides_audit_block():
    base = _bench()
    base["audit"] = {"ok": True, "error": 0, "warning": 0, "codes": [],
                     "comm_volume": {"psum": {"count": 4, "bytes": 100,
                                              "axes": ["data"]}}}
    new = json.loads(json.dumps(base))
    new["audit"]["comm_volume"]["psum"]["count"] = 5
    rep = compare(base, new, threshold=0.05)
    assert rep["comm"]["programs"] == ["gpt_headline"]
    assert any(r["leg"] == "comm_count:gpt_headline/psum"
               for r in rep["regressions"])
