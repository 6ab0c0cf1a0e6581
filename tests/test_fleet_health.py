"""Fleet health plane: streaming metrics aggregation, SLO error budgets
with burn-rate alerts, and alert-driven auto-response (ISSUE-18).

Coverage map (the acceptance surface):

- LogBucketHistogram: every quantile within the documented ``alpha``
  relative error of the exact nearest-rank quantile, agreement with
  `telemetry.percentiles` on smooth streams, byte-identical
  order-independent merges, alpha-mismatch refusal;
- MetricsAggregator: event routing (request_end/serving_step/reject)
  into counters/gauges/histograms, label plumbing — TaggedRecorder
  stream labels merged under per-request labels (record keys win);
- SLOTracker: the multi-window multi-burn-rate state machine —
  pending(for_count) -> firing exactly once per episode -> resolved
  only after clear_after clean evaluations (hysteresis, no flapping),
  and a second episode fires again;
- determinism: two identical VirtualClock fleet runs produce
  byte-identical aggregator snapshots and alert timelines;
- auto-response on a REAL fleet: a firing attainment alert arms
  DegradationPolicy on every live replica and relaxes it on resolve; a
  firing availability alert restarts the dead replica; a page-severity
  alert mid-rolling-update aborts the wave;
- chaos property test: replica kill + overload burst under
  VirtualClock — every alert episode fires exactly once, alert and
  response events reconcile with the aggregator's own counters, fleet
  invariants stay clean;
- CI wiring: tools/fleet_status.py --self checks pass (parametrized),
  CLI exit codes (0 healthy / 1 firing / 2 unreadable).
"""
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.resilience import ServingChaos
from apex_tpu.serving import (
    AdmissionConfig,
    ReplicaFleet,
    Request,
    VirtualClock,
    is_terminal,
)
from apex_tpu.telemetry import (
    SLO,
    HealthMonitor,
    LogBucketHistogram,
    MetricsAggregator,
    RingBufferRecorder,
    SLOTracker,
    TaggedRecorder,
    default_serving_slos,
    percentiles,
)
from apex_tpu.transformer.testing import GPTConfig, init_gpt_params

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from tools import fleet_status  # noqa: E402



def _tiny_cfg(dtype=jnp.float32):
    return GPTConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=64,
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype=jnp.float32, compute_dtype=dtype)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    params["embedding"]["position"] = params["embedding"]["position"] * 40.0
    return cfg, params


def _toks(rng, n, vocab=128):
    return [int(t) for t in rng.integers(0, vocab, size=n)]


def _attainment_src(agg):
    return (agg.counter_total("slo_good_total"),
            agg.counter_total("slo_bad_total"))


def _availability_src(agg):
    ups = agg.gauge_values("replica_up")
    if not ups:
        return None
    return sum(1.0 for v in ups.values() if v > 0) / len(ups)


def _mk_attainment_tracker(objective=0.5, fast=4.0, slow=8.0,
                           fast_burn=1.5, slow_burn=1.2, **kw):
    """A bench/test-scale attainment SLO: windows a handful of virtual
    seconds, burns reachable against a fat (1 - objective) budget."""
    return SLOTracker(
        SLO(name="slo_attainment", objective=objective, kind="ratio",
            fast_window_s=fast, fast_burn=fast_burn,
            slow_window_s=slow, slow_burn=slow_burn, **kw),
        _attainment_src)


# ---------------------------------------------------------------------------
# LogBucketHistogram: documented error + exact order-independent merges
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alpha", [0.05, 0.01])
def test_histogram_quantiles_within_documented_error(alpha):
    rng = np.random.default_rng(7)
    vals = np.exp(rng.normal(3.0, 1.0, size=5000))
    h = LogBucketHistogram(alpha=alpha)
    for v in vals:
        h.add(float(v))
    srt = np.sort(vals)
    for q in (0.5, 0.9, 0.99):
        exact = float(srt[max(0, math.ceil(q * len(vals)) - 1)])
        got = h.quantile(q)
        assert got is not None
        assert abs(got - exact) / exact <= alpha + 1e-9, (q, got, exact)
    # and the interpolating percentiles() convention agrees on a smooth
    # stream to within the same order of error (1.5x allowance: the two
    # conventions straddle adjacent order statistics)
    ref = percentiles(list(map(float, vals)))
    for p in (50, 90, 99):
        got = h.quantile(p / 100.0)
        assert abs(got - ref[f"p{p}"]) / ref[f"p{p}"] <= 1.5 * alpha


def test_histogram_merges_are_exact_and_order_independent():
    rng = np.random.default_rng(11)
    streams = [np.exp(rng.normal(2.0, 0.7, size=300)),
               rng.uniform(0.5, 4.0, size=200),
               np.concatenate([rng.normal(10.0, 0.1, size=150),
                               rng.normal(400.0, 5.0, size=150)])]
    parts = []
    for s in streams:
        h = LogBucketHistogram(alpha=0.05)
        for v in s:
            h.add(float(v))
        parts.append(h)
    fwd = LogBucketHistogram(alpha=0.05)
    for p in parts:
        fwd.merge(p)
    rev = LogBucketHistogram(alpha=0.05)
    for p in reversed(parts):
        rev.merge(p)
    assert (json.dumps(fwd.snapshot(), sort_keys=True)
            == json.dumps(rev.snapshot(), sort_keys=True))
    # merged counts are exact: identical buckets to one sketch that saw
    # the concatenated stream (counts are integers — no approximation)
    one = LogBucketHistogram(alpha=0.05)
    for s in streams:
        for v in s:
            one.add(float(v))
    assert one.buckets == fwd.buckets
    assert one.count == fwd.count == sum(p.count for p in parts)
    assert one.min == fwd.min and one.max == fwd.max
    # mixed-resolution merges would silently void the error bound
    with pytest.raises(ValueError):
        fwd.merge(LogBucketHistogram(alpha=0.01))


def test_histogram_merged_classmethod_does_not_mutate_inputs():
    a, b = LogBucketHistogram(), LogBucketHistogram()
    for v in (1.0, 2.0, 3.0):
        a.add(v)
    b.add(10.0)
    snap_a, snap_b = a.snapshot(), b.snapshot()
    ab = LogBucketHistogram.merged(a, b)
    ba = LogBucketHistogram.merged(b, a)
    assert ab.snapshot() == ba.snapshot()
    assert ab.count == 4
    assert a.snapshot() == snap_a and b.snapshot() == snap_b


# ---------------------------------------------------------------------------
# MetricsAggregator: routing + the label plumbing satellite
# ---------------------------------------------------------------------------


def test_aggregator_routes_events_and_labels_with_precedence():
    agg = MetricsAggregator()
    # stream-level labels (the multi-tenant hook) ride a TaggedRecorder
    tagged = TaggedRecorder(agg, replica_id=0, labels={"tenant": "a"})
    tagged.record({"event": "serving_step", "step": 1, "queue_depth": 3,
                   "occupancy": 0.5, "free_pages": 7, "active": 2})
    tagged.record({"event": "request_end", "rid": 1, "status": "completed",
                   "slo_ok": True, "generated": 8, "ttft_ms": 12.0,
                   "latency_ms": 30.0})
    # per-request labels win over the stream's on collision
    tagged.record({"event": "request_end", "rid": 2, "status": "completed",
                   "slo_ok": False, "generated": 4, "latency_ms": 90.0,
                   "labels": {"tenant": "b"}})
    tagged.record({"event": "request_end", "rid": 3, "status": "rejected",
                   "slo_ok": True})
    tagged.record({"event": "reject", "code": "QUEUE_FULL"})

    assert agg.counter_total("slo_good_total") == 1.0
    # budget burns on violation AND on never-completing
    assert agg.counter_total("slo_bad_total") == 2.0
    assert agg.counter_total("goodput_tokens_total") == 8.0
    assert agg.counter_total("generated_tokens_total") == 12.0

    keys = set(agg.counters["requests_total"])
    assert (("replica_id", "0"), ("status", "completed"),
            ("tenant", "a")) in keys
    assert (("replica_id", "0"), ("status", "completed"),
            ("tenant", "b")) in keys
    rej = agg.counters["serving_rejects_total"]
    assert (("code", "QUEUE_FULL"), ("replica_id", "0"),
            ("tenant", "a")) in rej

    step_key = (("replica_id", "0"), ("tenant", "a"))
    assert agg.gauges["serving_queue_depth"][step_key] == 3.0
    assert agg.gauges["replica_up"][step_key] == 1.0

    lat = agg.hist_merged("latency_ms")
    assert lat is not None and lat.count == 2
    assert agg.hist_merged("ttft_ms").count == 1


def test_aggregator_bounds_series_cardinality():
    agg = MetricsAggregator(max_series=4)
    for i in range(10):
        agg.record({"event": "request_end", "status": "completed",
                    "slo_ok": True, "labels": {"tenant": str(i)}})
    assert len(agg.counters["slo_good_total"]) == 4
    assert agg.dropped_series > 0  # counted, never silently folded


# ---------------------------------------------------------------------------
# SLOTracker: burn-rate alerting state machine
# ---------------------------------------------------------------------------


def test_slo_state_machine_fires_once_per_episode_with_hysteresis():
    tr = _mk_attainment_tracker(
        objective=0.9, fast=4.0, slow=16.0, fast_burn=4.0, slow_burn=2.0,
        for_count=2, clear_after=3)
    agg = MetricsAggregator()
    t = 0.0

    def feed(counter, n, evals=1):
        nonlocal t
        out = []
        for _ in range(evals):
            t += 1.0
            agg.inc(counter, (), n)
            out.append(tr.evaluate(agg, t)["state"])
        return out

    assert set(feed("slo_good_total", 4, evals=20)) == {"ok"}
    collapse = feed("slo_bad_total", 4, evals=10)
    # for_count=2: one tripped evaluation is PENDING, not yet FIRING
    assert "pending" in collapse and "firing" in collapse
    assert collapse.index("pending") < collapse.index("firing")
    # one episode == one firing transition, no flapping while it burns
    assert tr.fired_count == 1
    assert all(s == "firing" for s in collapse[collapse.index("firing"):])

    recovery = feed("slo_good_total", 4, evals=30)
    assert "resolved" in recovery
    assert tr.resolved_count >= 1
    r = recovery.index("resolved")
    # hysteresis: at least clear_after firing evaluations precede the
    # resolve (burns must stay below resolve_frac for 3 in a row)
    assert all(s == "firing" for s in recovery[:max(1, r - 3)][:3])
    assert all(s == "ok" for s in recovery[r + 1:])

    # a SECOND collapse is a new episode: it fires again
    feed("slo_bad_total", 4, evals=10)
    assert tr.fired_count == 2
    firing_entries = [e for e in tr.timeline if e["state"] == "firing"]
    assert len(firing_entries) == tr.fired_count


def test_slo_multi_window_confirmation_blocks_single_blip():
    """One bad boundary cannot page: the fast window spikes but the slow
    window stays below confirm_frac of the page threshold."""
    tr = _mk_attainment_tracker(
        objective=0.9, fast=2.0, slow=60.0, fast_burn=4.0, slow_burn=2.0,
        confirm_frac=0.25)
    agg = MetricsAggregator()
    t = 0.0
    for _ in range(50):
        t += 1.0
        agg.inc("slo_good_total", (), 4)
        tr.evaluate(agg, t)
    t += 1.0
    agg.inc("slo_bad_total", (), 4)  # a single all-bad boundary
    rec = tr.evaluate(agg, t)
    # fast window is 100% bad (burn 10 >= 4) but the long window holds
    # 200 goods: 4/204 / 0.1 = 0.2 < 4 * 0.25 — no page
    assert rec["burn_fast"] >= 4.0
    assert rec["state"] == "ok", rec


def test_error_budget_accounting():
    tr = _mk_attainment_tracker(objective=0.9)
    agg = MetricsAggregator()
    agg.inc("slo_good_total", (), 90)
    agg.inc("slo_bad_total", (), 10)
    tr.evaluate(agg, 1.0)
    # 10% bad on a 10% budget: exactly spent
    assert tr.budget.attainment == pytest.approx(0.9)
    assert tr.budget.consumed == pytest.approx(1.0)
    assert tr.budget.remaining == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# determinism: byte-identical VirtualClock runs (tentpole acceptance)
# ---------------------------------------------------------------------------


def _run_guarded_fleet(tiny_model):
    cfg, params = tiny_model
    rng = np.random.default_rng(17)
    clock = VirtualClock(dt=1.0)
    health = HealthMonitor(slos=[_mk_attainment_tracker()])
    ring = RingBufferRecorder(capacity=4096)
    fleet = ReplicaFleet(
        cfg, params, n_replicas=2, clock=clock, sink=ring, n_slots=1,
        num_pages=16, max_prompt_len=32, health=health,
        admission=AdmissionConfig(max_queue=6, high_watermark=0.75,
                                  low_watermark=0.25))
    reqs = []
    for i in range(10):
        # half the trace blows an impossible budget -> bad slo events
        reqs.append(Request(
            prompt=_toks(rng, 4), max_new_tokens=3, arrival_step=2 * i,
            latency_budget_ms=0.5 if i % 2 else None))
    fleet.generate(reqs, max_steps=600)
    return fleet, health


def test_virtual_clock_runs_byte_identical(tiny_model):
    f1, h1 = _run_guarded_fleet(tiny_model)
    f2, h2 = _run_guarded_fleet(tiny_model)
    # streaming aggregates: byte-identical serialized snapshots
    assert h1.aggregator.snapshot_json() == h2.aggregator.snapshot_json()
    # alert timelines: identical transition sequences at identical
    # virtual clock values
    t1 = h1.manager.tracker("slo_attainment")
    t2 = h2.manager.tracker("slo_attainment")
    assert t1.timeline == t2.timeline
    assert (json.dumps(h1.snapshot(), sort_keys=True)
            == json.dumps(h2.snapshot(), sort_keys=True))
    # the signal actually flowed: budget events were observed
    assert t1.budget.total > 0
    assert f1.last_stats["slo_attainment"] == f2.last_stats["slo_attainment"]


# ---------------------------------------------------------------------------
# auto-response against a REAL fleet (not fakes)
# ---------------------------------------------------------------------------


def test_responder_arms_and_relaxes_degradation(tiny_model):
    cfg, params = tiny_model
    rng = np.random.default_rng(23)
    clock = VirtualClock(dt=1.0)
    tracker = _mk_attainment_tracker(clear_after=2)
    health = HealthMonitor(slos=[tracker])
    ring = RingBufferRecorder(capacity=4096)
    fleet = ReplicaFleet(
        cfg, params, n_replicas=2, clock=clock, sink=ring, n_slots=1,
        num_pages=16, max_prompt_len=32, health=health,
        admission=AdmissionConfig(max_queue=8))
    bad = [Request(prompt=_toks(rng, 4), max_new_tokens=2,
                   arrival_step=i, latency_budget_ms=0.5)
           for i in range(8)]
    fleet.generate(bad, max_steps=400)
    resp = health.fleet_responder
    armed = [a for a in resp.actions if a["action"] == "arm_degradation"]
    # every live replica's admission controller got the policy
    assert {a["replica_id"] for a in armed} == {0, 1}
    assert resp.armed
    for rep in fleet.replicas:
        assert rep.engine.admission.degradation is resp.degradation

    # recovery traffic: the alert resolves and the original (None)
    # policy is restored — the operator's config, not a guess
    good = [Request(prompt=_toks(rng, 4), max_new_tokens=2,
                    arrival_step=2 * i) for i in range(14)]
    fleet.generate(good, max_steps=800)
    assert any(a["action"] == "relax_degradation" for a in resp.actions)
    assert not resp.armed
    for rep in fleet.replicas:
        assert rep.engine.admission.degradation is None
    # actions landed as structured response events in the shared stream
    acts = {e.get("action") for e in ring.events("response")}
    assert {"arm_degradation", "relax_degradation"} <= acts


def test_responder_restarts_dead_replica(tiny_model):
    cfg, params = tiny_model
    rng = np.random.default_rng(29)
    clock = VirtualClock(dt=1.0)
    # small windows so the availability ticket fires within the trace
    health = HealthMonitor(slos=default_serving_slos(
        fast_window_s=4.0, slow_window_s=8.0))
    ring = RingBufferRecorder(capacity=4096)
    chaos = ServingChaos().kill_replica_at(1, 3)
    fleet = ReplicaFleet(
        cfg, params, n_replicas=2, chaos=chaos, clock=clock, sink=ring,
        n_slots=1, num_pages=16, max_prompt_len=32, health=health)
    reqs = [Request(prompt=_toks(rng, 4), max_new_tokens=3,
                    arrival_step=2 * i) for i in range(12)]
    fleet.generate(reqs, max_steps=600)
    restarts = [a for a in health.fleet_responder.actions
                if a["action"] == "restart_replica"]
    assert restarts and restarts[0]["replica_id"] == 1
    assert fleet.replicas[1].live  # the actuator actually ran
    assert any(e for e in ring.events("replica_restart"))
    # the firing episode is on the availability SLO
    avail = health.manager.tracker("replica_available")
    assert avail.fired_count >= 1


def test_responder_aborts_rolling_update_on_page(tiny_model):
    cfg, params = tiny_model
    rng = np.random.default_rng(31)
    clock = VirtualClock(dt=1.0)
    tracker = _mk_attainment_tracker()  # all-bad burn 2 >= 1.5: page
    health = HealthMonitor(slos=[tracker])
    ring = RingBufferRecorder(capacity=4096)
    fleet = ReplicaFleet(
        cfg, params, n_replicas=2, clock=clock, sink=ring, n_slots=1,
        num_pages=16, max_prompt_len=32, health=health,
        admission=AdmissionConfig(max_queue=8))
    # long-running work keeps the drain wave in flight while the burst
    # of impossible-budget requests burns the error budget
    keep = [Request(prompt=_toks(rng, 4), max_new_tokens=20,
                    arrival_step=0) for _ in range(2)]
    bad = [Request(prompt=_toks(rng, 4), max_new_tokens=2,
                   arrival_step=1 + i, latency_budget_ms=0.5)
           for i in range(8)]
    new_params = jax.tree_util.tree_map(lambda x: x + 0.0, params)
    fleet.schedule_rolling_update(new_params)
    fleet.generate(keep + bad, max_steps=600)
    acts = [a["action"] for a in health.fleet_responder.actions]
    assert "abort_rolling_update" in acts
    assert fleet._swap_plan is None
    assert ring.events("rolling_update_aborted")
    # the firing record that drove the abort carried page severity
    fire = [e for e in tracker.timeline if e["state"] == "firing"]
    assert fire and fire[0]["severity"] == "page"


# ---------------------------------------------------------------------------
# chaos property test (satellite f)
# ---------------------------------------------------------------------------


def test_chaos_alert_episodes_fire_once_and_reconcile(tiny_model):
    cfg, params = tiny_model
    rng = np.random.default_rng(37)
    clock = VirtualClock(dt=1.0)
    trackers = [
        _mk_attainment_tracker(),
        SLOTracker(
            SLO(name="replica_available", objective=0.5, kind="threshold",
                target=0.99, higher_is_better=True, fast_window_s=4.0,
                fast_burn=1.5, slow_window_s=8.0, slow_burn=1.2),
            _availability_src),
    ]
    health = HealthMonitor(slos=trackers)
    ring = RingBufferRecorder(capacity=8192)
    chaos = ServingChaos().kill_replica_at(1, 6)
    fleet = ReplicaFleet(
        cfg, params, n_replicas=2, chaos=chaos, clock=clock, sink=ring,
        n_slots=1, num_pages=16, max_prompt_len=32, health=health,
        admission=AdmissionConfig(max_queue=6, high_watermark=0.75,
                                  low_watermark=0.25))
    reqs = []
    for i in range(16):
        # overload burst with tight budgets after a short healthy head
        tight = i >= 4
        reqs.append(Request(
            prompt=_toks(rng, 4), max_new_tokens=3,
            arrival_step=(3 * i if i < 4 else 12 + (i - 4)),
            latency_budget_ms=2000.0 if tight else None))
    fleet.generate(reqs, max_steps=800)
    fleet.check_invariants()
    assert all(is_terminal(r.status) for r in reqs)

    agg = health.aggregator
    transitions = sum(len(t.timeline) for t in trackers)
    for t in trackers:
        fires = [e for e in t.timeline if e["state"] == "firing"]
        # each episode fires exactly once: firing count equals distinct
        # firing transitions, and no two consecutive transitions both
        # enter FIRING (the state machine must leave it in between)
        assert len(fires) == t.fired_count
        states = [e["state"] for e in t.timeline]
        assert all(not (a == b == "firing")
                   for a, b in zip(states, states[1:]))
    # alert/response events rode the fleet fan-in, so the aggregator
    # counted the health plane's own activity as metrics
    assert agg.counter_total("alerts_total") == transitions
    assert (agg.counter_total("alert_responses_total")
            == len(health.fleet_responder.actions))
    # the availability episode restarted the dead replica
    if health.manager.tracker("replica_available").fired_count:
        assert fleet.replicas[1].live


# ---------------------------------------------------------------------------
# CI wiring: fleet_status CLI (satellite e)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(fleet_status.CHECKS))
def test_fleet_status_self_checks(name):
    res = fleet_status.CHECKS[name]()
    assert res["ok"], res


def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def test_fleet_status_cli_exit_codes(tmp_path, capsys):
    healthy = [{"event": "request_end", "rid": i, "status": "completed",
                "slo_ok": True, "generated": 4, "replica_id": i % 2,
                "latency_ms": 25.0, "t_wall": float(i)}
               for i in range(40)]
    p = tmp_path / "healthy.jsonl"
    _write_jsonl(p, healthy)
    assert fleet_status.main([str(p)]) == 0
    capsys.readouterr()

    burning = [{"event": "request_end", "rid": i, "status": "timed_out",
                "slo_ok": False, "replica_id": 0, "t_wall": float(i)}
               for i in range(48)]
    p2 = tmp_path / "burning.jsonl"
    _write_jsonl(p2, burning)
    assert fleet_status.main([str(p2)]) == 1
    capsys.readouterr()

    assert fleet_status.main([str(tmp_path / "missing.jsonl")]) == 2
    capsys.readouterr()

    # machine formats parse/expose
    assert fleet_status.main([str(p), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert "slos" in out and "replicas" in out
    assert fleet_status.main([str(p), "--prom"]) == 0
    prom = capsys.readouterr().out
    assert "# TYPE requests_total counter" in prom
    assert "latency_ms_count" in prom
