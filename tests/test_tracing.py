"""ISSUE-17 observability: end-to-end request tracing, exact-sum latency
attribution, and the crash flight recorder.

Covers the satellites around the tracing tentpole:

- TaggedRecorder close() ownership — two tagged views over ONE shared
  JSONL stream, one replica's teardown must not close the file out from
  under the other (``owns_sink=False`` default);
- the unified cross-sink record schema — every persisting sink stamps
  ``t_wall`` through the same :func:`stamp_wall` choke point;
- ``read_jsonl`` post-mortem hardening — a torn FINAL line (writer
  SIGKILLed mid-write) is tolerated and counted, a mid-file tear still
  raises;
- the span-causality property — a chaos fleet (replica kill, forced
  preemption via fail_allocs, prefix eviction) under VirtualClock
  yields rooted span trees, monotone timestamps, exactly one terminal
  span per offered request, and TTFT attribution terms that sum to the
  measured TTFT within 1%;
- CI wiring — tools/trace_report.py CHECKS run tier-1 and its CLI exit
  codes hold.
"""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

from apex_tpu import telemetry  # noqa: E402
from apex_tpu.telemetry import (  # noqa: E402
    JsonlRecorder,
    RingBufferRecorder,
    TaggedRecorder,
    read_jsonl,
)
from apex_tpu.telemetry.spans import ATTR_TERMS  # noqa: E402

import trace_report  # noqa: E402  (tools/)


# ---------------------------------------------------------------------------
# satellite 1: TaggedRecorder close() ownership
# ---------------------------------------------------------------------------
class TestTaggedRecorderOwnership:
    def test_shared_sink_survives_one_tagger_close(self, tmp_path):
        """The fleet topology: two replicas' TaggedRecorders over ONE
        JsonlRecorder. Tearing one replica down (close) must not close
        the shared stream — the survivor keeps recording."""
        path = tmp_path / "shared.jsonl"
        shared = JsonlRecorder(path, only_logging_process=False)
        a = TaggedRecorder(shared, replica_id=0)
        b = TaggedRecorder(shared, replica_id=1)
        a.record({"event": "x"})
        a.close()  # replica 0 dies
        b.record({"event": "y"})  # survivor must still reach the file
        shared.close()
        recs = read_jsonl(path)
        assert [(r["event"], r["replica_id"]) for r in recs] == [
            ("x", 0), ("y", 1)]

    def test_default_does_not_own_sink(self):
        assert TaggedRecorder(RingBufferRecorder()).owns_sink is False

    def test_owns_sink_true_cascades_close(self, tmp_path):
        path = tmp_path / "private.jsonl"
        private = JsonlRecorder(path, only_logging_process=False)
        t = TaggedRecorder(private, host=3, owns_sink=True)
        t.record({"event": "x"})
        t.close()
        t.record({"event": "after"})  # dropped: underlying file closed
        assert [r["event"] for r in read_jsonl(path)] == ["x"]


# ---------------------------------------------------------------------------
# satellite 2: unified t_wall stamping across sinks
# ---------------------------------------------------------------------------
class TestCrossSinkSchema:
    def test_every_persisting_sink_stamps_t_wall(self, tmp_path):
        """Schema canary: a record written through ANY persisting sink
        (JSONL file, in-memory ring, tagged view over either) carries
        ``t_wall`` — so ring-sourced flight-recorder dumps line up with
        the live JSONL stream on the same axis."""
        path = tmp_path / "t.jsonl"
        jsonl = JsonlRecorder(path, only_logging_process=False)
        jsonl.record({"event": "a"})
        jsonl.close()
        ring = RingBufferRecorder()
        ring.record({"event": "b"})
        tagged_ring = RingBufferRecorder()
        TaggedRecorder(tagged_ring, pod="p").record({"event": "c"})
        stamped = [read_jsonl(path)[0], ring.records[0],
                   tagged_ring.records[0]]
        for rec in stamped:
            assert rec["t_wall"] > 0, rec

    def test_existing_t_wall_wins(self):
        ring = RingBufferRecorder()
        ring.record({"event": "x", "t_wall": 123.25})
        assert ring.records[0]["t_wall"] == 123.25


# ---------------------------------------------------------------------------
# satellite 3: read_jsonl torn-tail tolerance
# ---------------------------------------------------------------------------
class TestReadJsonlTornTail:
    def test_torn_final_line_tolerated_and_counted(self, tmp_path):
        p = tmp_path / "torn.jsonl"
        good = [{"event": "span", "i": i} for i in range(3)]
        with open(p, "w") as f:
            for r in good:
                f.write(json.dumps(r) + "\n")
            f.write('{"event": "span", "i": 3, "tru')  # SIGKILL mid-write
        stats = {}
        recs = read_jsonl(p, stats=stats)
        assert recs == good
        assert stats["torn_lines"] == 1

    def test_clean_file_counts_zero_torn(self, tmp_path):
        p = tmp_path / "clean.jsonl"
        p.write_text('{"event": "a"}\n{"event": "b"}\n')
        stats = {}
        assert len(read_jsonl(p, stats=stats)) == 2
        assert stats["torn_lines"] == 0

    def test_mid_file_tear_still_raises(self, tmp_path):
        """Append-only format: corruption anywhere BEFORE the final
        line means the file is not what we wrote — that must raise, not
        be papered over."""
        p = tmp_path / "corrupt.jsonl"
        p.write_text('{"event": "a"}\n{"ev GARBAGE\n{"event": "b"}\n')
        with pytest.raises(json.JSONDecodeError):
            read_jsonl(p)


# ---------------------------------------------------------------------------
# satellite 4: span-causality property under chaos
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def chaos_trace():
    """One deterministic chaos fleet run: replica 0 killed mid-flight,
    forced preemption (alloc failures), prefix eviction — all under
    VirtualClock so every timestamp is a deterministic function of the
    instrumented code's own clock reads."""
    from serving_check import _tiny_cfg, _tiny_params

    from apex_tpu.resilience.chaos import ServingChaos
    from apex_tpu.serving import Request
    from apex_tpu.serving.fleet import ReplicaFleet
    from apex_tpu.serving.robustness import VirtualClock

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)
    sink = telemetry.RingBufferRecorder(capacity=100000)
    chaos = ServingChaos()
    chaos.kill_replica_at(0, 2)
    chaos.evict_prefix_cache(2)
    chaos.fail_allocs(3)
    fleet = ReplicaFleet(cfg, params, n_replicas=2, sink=sink,
                         clock=VirtualClock(dt=0.01), chaos=chaos,
                         n_slots=2, num_pages=64)
    shared = [1, 2, 3, 4]
    reqs = [Request(rid=i, prompt=shared[: 2 + (i % 2)] + [5 + i],
                    max_new_tokens=4, arrival_step=i % 3)
            for i in range(8)]
    fleet.generate(reqs, max_steps=500)
    return list(sink.records), reqs, fleet


class TestSpanCausalityUnderChaos:
    def test_chaos_actually_fired(self, chaos_trace):
        _, _, fleet = chaos_trace
        assert fleet.replica_deaths >= 1

    def test_span_trees_are_rooted_and_monotone(self, chaos_trace):
        records, _, _ = chaos_trace
        traces = trace_report.build_traces(records)
        assert trace_report.validate(traces) == []

    def test_exactly_one_terminal_span_per_offered_request(
            self, chaos_trace):
        records, reqs, _ = chaos_trace
        traces = trace_report.build_traces(records)
        for r in reqs:
            assert r.trace is not None, f"rid={r.rid} never traced"
            spans = traces.get(r.trace.trace_id)
            assert spans, f"rid={r.rid}: no spans for {r.trace.trace_id}"
            terminals = [s for s in spans if s.get("terminal")]
            assert len(terminals) == 1, (r.rid, terminals)

    def test_children_start_within_parent_window(self, chaos_trace):
        records, _, _ = chaos_trace
        traces = trace_report.build_traces(records)
        for tid, spans in traces.items():
            if not tid.startswith("req-"):
                continue
            by_id = {s["span_id"]: s for s in spans}
            for s in spans:
                pid = s.get("parent_id")
                if pid is None:
                    continue
                assert s["t_start"] >= by_id[pid]["t_start"] - 1e-9, (
                    tid, s)

    def test_ttft_terms_sum_to_measured_ttft(self, chaos_trace):
        _, reqs, _ = chaos_trace
        checked = 0
        for r in reqs:
            if r.t_first_token is None or r.attr_ttft is None:
                continue
            measured = r.t_first_token - r.t_arrival
            if measured <= 0:
                continue
            total = sum(r.attr_ttft.values())
            assert abs(total - measured) / measured <= 0.01, (
                r.rid, total, measured, r.attr_ttft)
            checked += 1
        assert checked >= 1

    def test_e2e_terms_sum_to_measured_e2e(self, chaos_trace):
        _, reqs, _ = chaos_trace
        checked = 0
        for r in reqs:
            if r.attr is None or r.t_done is None or r.t_arrival is None:
                continue
            measured = r.t_done - r.t_arrival
            if measured <= 0:
                continue
            total = sum(r.attr.values())
            assert abs(total - measured) / measured <= 0.01, (
                r.rid, total, measured, r.attr)
            checked += 1
        assert checked >= 1

    def test_replica_death_dumps_black_box(self, chaos_trace):
        records, _, _ = chaos_trace
        boxes = [r for r in records if r.get("event") == "blackbox"]
        assert boxes and boxes[0]["reason"] == "replica_down"
        replayed = [r for r in records if r.get("blackbox_replay")]
        assert replayed, "black box should replay the dead engine's ring"

    def test_fleet_summary_carries_attribution(self, chaos_trace):
        _, _, fleet = chaos_trace
        att = fleet.last_stats["attribution"]
        assert tuple(att["terms"]) == ATTR_TERMS
        assert att["ttft_sum_rel_err_max"] <= 0.01
        assert set(att["ttft_ms"]) == set(ATTR_TERMS)


# ---------------------------------------------------------------------------
# satellite 6a: tools/trace_report.py tier-1 wiring
# ---------------------------------------------------------------------------
class TestTraceReportCLI:
    @pytest.mark.parametrize("check", sorted(trace_report.CHECKS))
    def test_each_check_passes(self, check):
        res = trace_report.CHECKS[check]()
        assert res["ok"], res

    def test_cli_self_exit_zero(self, capsys):
        rc = trace_report.main(
            ["--self", "--check", "detects_broken_causality", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0 and out["ok"]

    def test_cli_failure_exit_one(self, monkeypatch):
        monkeypatch.setitem(trace_report.CHECKS, "seeded_fail",
                            lambda: {"ok": False})
        assert trace_report.main(["--self", "--check", "seeded_fail"]) == 1

    def test_cli_infra_error_exit_two(self, monkeypatch):
        def boom():
            raise RuntimeError("infra")

        monkeypatch.setitem(trace_report.CHECKS, "seeded_boom", boom)
        assert trace_report.main(["--self", "--check", "seeded_boom"]) == 2

    def test_report_exits_nonzero_on_broken_stream(self, tmp_path):
        """The CI contract: a span stream with an orphan parent is a
        broken trace — the report run must fail, not shrug."""
        p = tmp_path / "broken.jsonl"
        spans = [
            {"event": "span", "name": "request", "trace_id": "req-0",
             "span_id": 1, "parent_id": None, "t_start": 0.0,
             "t_end": 1.0, "terminal": True},
            {"event": "span", "name": "orphan", "trace_id": "req-0",
             "span_id": 2, "parent_id": 999, "t_start": 0.2,
             "t_end": 0.4},
        ]
        with open(p, "w") as f:
            for s in spans:
                f.write(json.dumps(s) + "\n")
        assert trace_report.main([str(p)]) == 1
        del spans[1]["parent_id"]
        spans[1]["t_end"] = 0.3
        with open(p, "w") as f:
            for s in spans:
                f.write(json.dumps(s) + "\n")
        assert trace_report.main([str(p)]) == 0
