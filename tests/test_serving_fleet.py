"""Fleet-scale serving: deadline-aware multi-replica router, drain/join
weight swaps, replica-kill migration (apex_tpu.serving.fleet).

Coverage map (the ISSUE-11 acceptance surface):

- routing: requests spread by feasibility x load across replicas, the
  loaded replica is skipped, every completion token-identical to the
  dense greedy reference;
- read-only costing: `AdmissionController.probe` / `ServingEngine.probe`
  leave the hysteresis latch, rejection counters, and request state
  untouched (the router must not act through admission side effects);
- fleet-level refusal: when no replica is feasible the request is
  finalized REJECTED with the typed NO_FEASIBLE_REPLICA reason naming
  each replica's own refusal code;
- THE migration proof: 3 CPU-faked replicas, one killed mid-storm by
  `ServingChaos.kill_replica_at` — every in-flight request of the dead
  replica completes token-identically to an undisturbed run
  (requests_lost == 0), riding the replay carrier through the
  survivors' admission control with original deadlines intact;
- drain/join: a rolling weight update drains each replica, swaps
  weights via `cast_params_for_inference`, rejoins — zero dropped
  requests, and post-update requests decode per the NEW weights;
- replica_id tagging: every engine-side request_end/hang/serving_step
  event in the shared sink carries its replica (TaggedRecorder), and
  the fleet summary carries the per-replica breakdown;
- CI wiring: serving_check fleet legs pass.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.resilience import ChaosError, RetryPolicy, ServingChaos
from apex_tpu.serving import (
    AdmissionConfig,
    AdmissionController,
    DegradationPolicy,
    RejectionCode,
    RejectionError,
    ReplicaFleet,
    ReplicaState,
    Request,
    RequestStatus,
    SchedulerError,
    ServingEngine,
    VirtualClock,
    is_terminal,
    reference_decode,
)
from apex_tpu.telemetry import RingBufferRecorder, TaggedRecorder

from apex_tpu.transformer.testing import GPTConfig, init_gpt_params


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
    # position-sensitive continuations (see test_serving.py)
    params["embedding"]["position"] = params["embedding"]["position"] * 40.0
    return cfg, params


def _toks(rng, n, vocab=128):
    return [int(t) for t in rng.integers(0, vocab, size=n)]


# ---------------------------------------------------------------------------
# read-only probing (satellite: the router must not mutate)
# ---------------------------------------------------------------------------

def test_admission_probe_is_read_only():
    """probe() returns the verdict check() would, without flipping the
    hysteresis latch, counting rejections, or moving high-water marks —
    and estimated_step_time_s is the documented read-only EWMA view."""
    ctl = AdmissionController(
        AdmissionConfig(max_queue=8, high_watermark=0.5,
                        low_watermark=0.25, step_time_init_s=0.010),
        n_slots=2)
    assert ctl.estimated_step_time_s == pytest.approx(0.010)
    ctl.observe_step(0.010)
    assert ctl.estimated_step_time_s == ctl.est_step_s
    req = Request(prompt=[1, 2], max_new_tokens=4)
    # depth at the high watermark: probe says BACKPRESSURE...
    r = ctl.probe(req, queue_depth=4, queued_tokens=24)
    assert r is not None and r.code is RejectionCode.BACKPRESSURE
    # ...but nothing latched or counted
    assert not ctl.backpressure
    assert ctl.rejected == 0 and ctl.max_queue_seen == 0
    # feasible probe agrees with check
    assert ctl.probe(req, queue_depth=0, queued_tokens=0) is None
    # deadline-infeasible probe carries the same typed reason
    doomed = Request(prompt=list(range(8)), max_new_tokens=8,
                     latency_budget_ms=10.0)
    r = ctl.probe(doomed, queue_depth=0, queued_tokens=0)
    assert r is not None and r.code is RejectionCode.DEADLINE_INFEASIBLE
    assert ctl.rejected == 0
    # check() on the same inputs DOES latch and count
    r = ctl.check(req, queue_depth=4, queued_tokens=24)
    assert r is not None and r.code is RejectionCode.BACKPRESSURE
    assert ctl.backpressure and ctl.rejected == 1
    # with the latch ON, probe mirrors the latched state above low
    assert ctl.probe(req, queue_depth=3,
                     queued_tokens=18).code is RejectionCode.BACKPRESSURE
    # ...and the would-release state back at low, still without mutating
    assert ctl.probe(req, queue_depth=2, queued_tokens=12) is None
    assert ctl.backpressure  # latch untouched by the probe


def test_engine_probe_is_read_only_and_costs_load(tiny_model):
    cfg, params = tiny_model
    rng = np.random.default_rng(3)
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                        max_prompt_len=16,
                        admission=AdmissionConfig(max_queue=8))
    req = Request(prompt=_toks(rng, 6), max_new_tokens=4)
    reason, steps0 = eng.probe(req)
    assert reason is None
    assert steps0 == pytest.approx(6.0)  # empty engine: own prefill only
    # probing stamped/changed nothing
    assert req.t_arrival is None and req.status is RequestStatus.PENDING
    assert not eng.scheduler.waiting
    # load raises the cost: queue another request and re-probe
    other = Request(prompt=_toks(rng, 6), max_new_tokens=4)
    assert eng.try_submit(other) is None
    _, steps1 = eng.probe(req)
    assert steps1 > steps0
    # an in-flight request probes ALREADY_IN_FLIGHT (no finalize)
    reason, _ = eng.probe(other)
    assert reason is not None
    assert reason.code is RejectionCode.ALREADY_IN_FLIGHT
    assert other.status is RequestStatus.QUEUED
    # an engine-infeasible request carries the typed reason
    fat = Request(prompt=_toks(rng, 20), max_new_tokens=4)
    reason, _ = eng.probe(fat)
    assert reason is not None
    assert reason.code is RejectionCode.PROMPT_TOO_LONG
    assert fat.status is RequestStatus.PENDING  # not finalized


def test_attained_ttft_not_refused_at_readmission():
    """Review regression: a request that already produced its first
    token (preempted/recovered/migrated survivor) must not be refused
    DEADLINE_INFEASIBLE against the TTFT budget it already met — same
    rule pick_shed_victim applies."""
    ctl = AdmissionController(
        AdmissionConfig(max_queue=64, step_time_init_s=0.010),
        n_slots=1)
    # 20 prompt steps * 10ms = 200ms >> 50ms budget: infeasible fresh
    fresh = Request(prompt=list(range(20)), max_new_tokens=4,
                    ttft_budget_ms=50.0)
    r = ctl.probe(fresh, queue_depth=0, queued_tokens=0)
    assert r is not None and r.code is RejectionCode.DEADLINE_INFEASIBLE
    # the same shape with its first token attained: admissible
    survivor = Request(prompt=list(range(20)), max_new_tokens=4,
                       ttft_budget_ms=50.0)
    survivor.t_first_token = 1.0
    assert ctl.probe(survivor, queue_depth=0, queued_tokens=0) is None
    assert ctl.check(survivor, queue_depth=0, queued_tokens=0) is None


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def test_router_spreads_load_and_keeps_token_identity(tiny_model):
    cfg, params = tiny_model
    rng = np.random.default_rng(7)
    reqs = [Request(prompt=_toks(rng, L), max_new_tokens=6,
                    arrival_step=i)
            for i, L in enumerate((8, 5, 11, 6, 9, 4))]
    fleet = ReplicaFleet(cfg, params, n_replicas=2, n_slots=2,
                         num_pages=12, max_prompt_len=16)
    out = fleet.generate(reqs, max_steps=2000)
    fleet.check_invariants()
    assert fleet.page_leaks() == 0
    st = fleet.last_stats
    assert st["completed"] == len(reqs) and st["requests_lost"] == 0
    # both replicas took work (lowest-cost dispatch alternates under
    # symmetric load) and attribution reached the summary
    assert {r.replica_id for r in reqs} == {0, 1}
    assert sum(st["per_replica"][k]["served"]
               for k in ("0", "1")) == len(reqs)
    for r in reqs:
        assert out[r.rid] == reference_decode(
            cfg, params, r.prompt, r.max_new_tokens), r.rid


def test_router_skips_loaded_replica(tiny_model):
    """A replica carrying a deep queue costs more; a fresh request
    routes to the empty one."""
    cfg, params = tiny_model
    rng = np.random.default_rng(11)
    fleet = ReplicaFleet(cfg, params, n_replicas=2, n_slots=1,
                         num_pages=8, max_prompt_len=16)
    # load replica 0 directly (behind the router's back)
    for _ in range(3):
        hog = Request(prompt=_toks(rng, 6), max_new_tokens=6)
        assert fleet.replicas[0].engine.try_submit(hog) is None
    fresh = Request(prompt=_toks(rng, 6), max_new_tokens=6)
    rep, refusals = fleet.route(fresh)
    assert rep is fleet.replicas[1] and not refusals


def test_no_feasible_replica_is_typed_fleet_rejection(tiny_model):
    """Saturate both replicas' admission doors: the fleet refuses with
    NO_FEASIBLE_REPLICA, the detail names every replica's own code,
    the request is finalized REJECTED with a reject event."""
    cfg, params = tiny_model
    rng = np.random.default_rng(13)
    ring = RingBufferRecorder()
    fleet = ReplicaFleet(
        cfg, params, n_replicas=2, n_slots=1, num_pages=8,
        max_prompt_len=16, sink=ring,
        admission=AdmissionConfig(max_queue=4, high_watermark=0.5,
                                  low_watermark=0.25))
    # two queued per replica -> both at the high watermark (2 of 4)
    for _ in range(4):
        assert fleet.try_submit(
            Request(prompt=_toks(rng, 4), max_new_tokens=4)) is None
    bounced = Request(prompt=_toks(rng, 4), max_new_tokens=4)
    reason = fleet.try_submit(bounced)
    assert reason is not None
    assert reason.code is RejectionCode.NO_FEASIBLE_REPLICA
    assert reason.detail["replicas"] == {
        "0": "backpressure", "1": "backpressure"}
    assert bounced.status is RequestStatus.REJECTED
    assert bounced.end_reason == "no_feasible_replica"
    rejects = ring.events("reject")
    assert any(r["rid"] == bounced.rid
               and r["code"] == "no_feasible_replica" for r in rejects)
    # the raising door throws the same typed error
    with pytest.raises(RejectionError, match="no feasible replica"):
        fleet.submit(Request(prompt=_toks(rng, 4), max_new_tokens=4))
    # drain everything; the fleet ends clean
    fleet.generate([], max_steps=500)
    fleet.check_invariants()
    assert fleet.page_leaks() == 0


# ---------------------------------------------------------------------------
# replica kill + migration (THE acceptance proof)
# ---------------------------------------------------------------------------

def test_kill_replica_mid_storm_migrates_token_identical(tiny_model):
    """3 replicas, one killed mid-storm: its in-flight requests migrate
    to the survivors riding the replay carrier and complete
    BYTE-identically to an undisturbed run (the dense greedy
    reference); requests_lost == 0; events are attributable."""
    cfg, params = tiny_model
    rng = np.random.default_rng(23)
    reqs = [Request(prompt=_toks(rng, int(rng.integers(4, 12))),
                    max_new_tokens=6, arrival_step=i)
            for i in range(9)]
    chaos = ServingChaos().kill_replica_at(1, 6)
    ring = RingBufferRecorder()
    fleet = ReplicaFleet(cfg, params, n_replicas=3, sink=ring,
                         chaos=chaos, n_slots=2, num_pages=12,
                         max_prompt_len=24)
    out = fleet.generate(reqs, max_steps=3000)
    fleet.check_invariants()
    assert chaos.faults_fired == [("kill_replica", 1, 6)]
    st = fleet.last_stats
    assert st["replica_deaths"] == 1
    assert st["requests_lost"] == 0
    assert st["migrated"] >= 1
    assert st["migrated"] == st["migration_readmitted"]
    assert st["by_status"]["completed"] == len(reqs)
    assert fleet.replicas[1].state is ReplicaState.DEAD
    assert st["per_replica"]["1"]["state"] == "dead"
    assert st["per_replica"]["1"]["migrated_out"] == st["migrated"]
    # the dead replica's work survived token-identically — migrated
    # requests kept their generated tokens and replayed on a survivor
    downs = ring.events("replica_down")
    assert len(downs) == 1 and downs[0]["replica_id"] == 1
    migrated_rids = {e["rid"] for e in ring.events("migrate")}
    assert migrated_rids == set(downs[0]["rids"]) and migrated_rids
    for r in reqs:
        assert r.status is RequestStatus.COMPLETED, r.rid
        assert out[r.rid] == reference_decode(
            cfg, params, r.prompt, r.max_new_tokens), r.rid
        if r.rid in migrated_rids:
            assert r.restarts == 1 and r.replica_id != 1
    assert fleet.page_leaks() == 0
    # every engine-side request_end carries its replica
    for e in ring.events("request_end"):
        assert "replica_id" in e, e


def test_migrated_requests_honor_original_deadlines(tiny_model):
    """Migration preserves t_arrival: a migrant whose latency budget
    expires while waiting for placement is finalized TIMED_OUT by the
    fleet (never silently dropped), under the migration RetryPolicy's
    pacing."""
    cfg, params = tiny_model
    rng = np.random.default_rng(29)
    clock = VirtualClock(dt=1.0)
    ring = RingBufferRecorder()
    # one replica only: when it dies there is nowhere to go until the
    # budget expires
    doomed = Request(prompt=_toks(rng, 6), max_new_tokens=6,
                     latency_budget_ms=30_000.0)
    free = Request(prompt=_toks(rng, 6), max_new_tokens=6)
    chaos = ServingChaos().kill_replica_at(0, 3)
    fleet = ReplicaFleet(cfg, params, n_replicas=2, sink=ring,
                         chaos=chaos, clock=clock, n_slots=1,
                         num_pages=8, max_prompt_len=16,
                         migration_retry=RetryPolicy(attempts=1000))
    # pin both requests to replica 0 by loading it directly, then kill
    assert fleet.replicas[0].engine.try_submit(doomed) is None
    doomed.replica_id = 0
    # saturate replica 1 so migrants cannot place (single slot + queue
    # full via admission-free deep queue of long work)
    for _ in range(6):
        assert fleet.replicas[1].engine.try_submit(
            Request(prompt=_toks(rng, 8), max_new_tokens=8)) is None
    fleet.try_submit(free)
    out = fleet.generate([], max_steps=4000)  # noqa: F841 - drive it
    st = fleet.last_stats
    assert fleet.replicas[0].state is ReplicaState.DEAD
    assert is_terminal(doomed.status)
    # the doomed migrant either placed late and timed out on-engine, or
    # expired in the fleet's migration queue — both are typed TIMED_OUT
    # (the budget was virtual-clock tight); it is never lost silently
    assert doomed.status in (RequestStatus.TIMED_OUT,
                             RequestStatus.COMPLETED)
    ends = [e for e in ring.events("request_end")
            if e["rid"] == doomed.rid]
    assert len(ends) == 1


def test_migration_retry_policy_bounds_placement(tiny_model):
    """With a tight RetryPolicy attempts budget and no feasible
    survivor, migrants are finalized REJECTED(migration_exhausted)
    instead of spinning forever."""
    cfg, params = tiny_model
    rng = np.random.default_rng(31)
    ring = RingBufferRecorder()
    chaos = ServingChaos().kill_replica_at(0, 2)
    fleet = ReplicaFleet(
        cfg, params, n_replicas=2, sink=ring, chaos=chaos,
        n_slots=1, num_pages=8, max_prompt_len=16,
        admission=AdmissionConfig(max_queue=2, high_watermark=0.5,
                                  low_watermark=0.25),
        migration_retry=RetryPolicy(attempts=3))
    victim = Request(prompt=_toks(rng, 6), max_new_tokens=6)
    assert fleet.replicas[0].engine.try_submit(victim) is None
    # replica 1 saturated at its admission door: one hog in the slot,
    # one in the queue (depth 1 = high watermark for max_queue=2)
    hogs = [Request(prompt=_toks(rng, 6), max_new_tokens=6)
            for _ in range(2)]
    assert fleet.replicas[1].engine.try_submit(hogs[0]) is None
    fleet.replicas[1].engine.run_step()  # hog 0 takes the slot
    assert fleet.replicas[1].engine.try_submit(hogs[1]) is None
    fleet.generate([], max_steps=2000)
    assert victim.status is RequestStatus.REJECTED
    assert victim.end_reason == "migration_exhausted"
    exhausted = ring.events("migrate_exhausted")
    assert len(exhausted) == 1 and exhausted[0]["rid"] == victim.rid
    assert exhausted[0]["attempts"] == 3
    # the hogs themselves completed; nothing leaked on the survivor
    assert all(h.status is RequestStatus.COMPLETED for h in hogs)
    assert fleet.page_leaks() == 0


# ---------------------------------------------------------------------------
# drain / join (zero-drop weight swap)
# ---------------------------------------------------------------------------

def test_rolling_update_swaps_weights_with_zero_drops(tiny_model):
    """A rolling weight update mid-traffic: every replica drains,
    swaps via cast_params_for_inference, rejoins; zero requests
    dropped; requests submitted AFTER the update decode per the NEW
    weights (and in-flight work finished on the old ones)."""
    cfg, params = tiny_model
    params2 = jax.tree_util.tree_map(lambda x: x, params)
    params2["embedding"]["position"] = (
        params["embedding"]["position"] * 0.5)
    rng = np.random.default_rng(37)
    ring = RingBufferRecorder()
    fleet = ReplicaFleet(cfg, params, n_replicas=2, sink=ring,
                         n_slots=2, num_pages=12, max_prompt_len=16)
    phase1 = [Request(prompt=_toks(rng, 6), max_new_tokens=5,
                      arrival_step=i) for i in range(4)]
    fleet.schedule_rolling_update(params2)
    with pytest.raises(SchedulerError, match="already scheduled"):
        fleet.schedule_rolling_update(params2)
    out1 = fleet.generate(phase1, max_steps=2000)
    assert fleet.rolling_update_done
    fleet.check_invariants()
    assert fleet.page_leaks() == 0
    # zero drops: everything completed, nothing rejected/timed out
    st = fleet.last_stats
    assert st["by_status"]["completed"] == len(phase1)
    assert st["requests_lost"] == 0
    swaps = ring.events("weight_swap")
    assert [e["replica_id"] for e in swaps] == [0, 1]
    assert ring.events("rolling_update_done")
    drains = ring.events("replica_drain")
    joins = ring.events("replica_join")
    assert len(drains) == 2 and len(joins) == 2
    # a request is served wholly by one replica under one params
    # version — its tokens match exactly one of the two references
    for r in phase1:
        ref_old = reference_decode(cfg, params, r.prompt,
                                   r.max_new_tokens)
        ref_new = reference_decode(cfg, params2, r.prompt,
                                   r.max_new_tokens)
        assert out1[r.rid] in (ref_old, ref_new), r.rid
    # post-update traffic decodes per the NEW weights on every replica
    phase2 = [Request(prompt=_toks(rng, 6), max_new_tokens=5)
              for _ in range(4)]
    out2 = fleet.generate(phase2, max_steps=2000)
    assert {r.replica_id for r in phase2} == {0, 1}
    for r in phase2:
        assert out2[r.rid] == reference_decode(
            cfg, params2, r.prompt, r.max_new_tokens), r.rid


def test_drain_excludes_replica_from_routing_until_join(tiny_model):
    cfg, params = tiny_model
    rng = np.random.default_rng(41)
    fleet = ReplicaFleet(cfg, params, n_replicas=2, n_slots=1,
                         num_pages=8, max_prompt_len=16)
    fleet.drain(0)
    assert fleet.replicas[0].state is ReplicaState.DRAINING
    with pytest.raises(SchedulerError, match="not active"):
        fleet.drain(0)
    for _ in range(3):
        req = Request(prompt=_toks(rng, 5), max_new_tokens=4)
        assert fleet.try_submit(req) is None
        assert req.replica_id == 1
    # idle drained replica joins immediately (no swap)
    assert fleet.try_join(0)
    assert fleet.replicas[0].state is ReplicaState.ACTIVE
    fleet.generate([], max_steps=500)
    assert fleet.page_leaks() == 0


def test_restart_replica_rejoins_after_death(tiny_model):
    """The replica-restart path: a DEAD replica comes back as a fresh
    engine (same weights/policies) and takes traffic again."""
    cfg, params = tiny_model
    rng = np.random.default_rng(43)
    chaos = ServingChaos().kill_replica_at(0, 2)
    ring = RingBufferRecorder()
    fleet = ReplicaFleet(cfg, params, n_replicas=2, sink=ring,
                         chaos=chaos, n_slots=2, num_pages=12,
                         max_prompt_len=16)
    reqs = [Request(prompt=_toks(rng, 6), max_new_tokens=5,
                    arrival_step=i) for i in range(4)]
    fleet.generate(reqs, max_steps=2000)
    assert fleet.replicas[0].state is ReplicaState.DEAD
    assert fleet.last_stats["requests_lost"] == 0
    fleet.restart_replica(0)
    assert fleet.replicas[0].state is ReplicaState.ACTIVE
    assert ring.events("replica_restart")
    late = [Request(prompt=_toks(rng, 6), max_new_tokens=5)
            for _ in range(4)]
    out = fleet.generate(late, max_steps=2000)
    assert {r.replica_id for r in late} == {0, 1}
    for r in late:
        assert out[r.rid] == reference_decode(
            cfg, params, r.prompt, r.max_new_tokens)


# ---------------------------------------------------------------------------
# replica_id tagging (satellite)
# ---------------------------------------------------------------------------

def test_tagged_recorder_injects_tags_record_keys_win():
    ring = RingBufferRecorder()
    tagged = TaggedRecorder(ring, replica_id=3)
    tagged.record({"event": "request_end", "rid": 1})
    tagged.record({"event": "custom", "replica_id": 9})  # rec wins
    tagged.add_scalar("loss", 1.5, 10)
    assert ring.events("request_end")[0]["replica_id"] == 3
    assert ring.events("custom")[0]["replica_id"] == 9
    sc = ring.events("scalar")[0]
    assert sc["replica_id"] == 3 and sc["name"] == "loss"
    # dict-style tags compose with kwargs
    t2 = TaggedRecorder(ring, {"pod": "a"}, replica_id=0)
    t2.record({"event": "x"})
    rec = ring.events("x")[0]
    # every sink stamps t_wall (unified schema); tags compose around it
    assert rec.pop("t_wall") > 0
    assert rec == {"event": "x", "pod": "a", "replica_id": 0}


def test_fleet_events_are_replica_attributable(tiny_model):
    """Engine-side telemetry (request_end, serving_step, degrade/shed)
    carries replica_id through the shared sink; fleet-level events
    carry it explicitly."""
    cfg, params = tiny_model
    rng = np.random.default_rng(47)
    ring = RingBufferRecorder()
    fleet = ReplicaFleet(cfg, params, n_replicas=2, sink=ring,
                         n_slots=1, num_pages=8, max_prompt_len=16,
                         record_every=1)
    reqs = [Request(prompt=_toks(rng, 5), max_new_tokens=4,
                    arrival_step=i) for i in range(4)]
    fleet.generate(reqs, max_steps=1000)
    ends = ring.events("request_end")
    assert len(ends) == 4
    assert {e["replica_id"] for e in ends} == {0, 1}
    for e in ring.events("serving_step"):
        assert e["replica_id"] in (0, 1)
    for e in ring.events("dispatch"):
        assert e["replica_id"] in (0, 1)
    # summary carries the per-replica breakdown alongside fleet totals
    st = fleet.last_stats
    assert set(st["per_replica"]) == {"0", "1"}
    for k, row in st["per_replica"].items():
        assert {"state", "steps", "served", "completed", "occupancy",
                "migrated_out", "page_leaks"} <= set(row)


# ---------------------------------------------------------------------------
# CI wiring: serving_check fleet legs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("leg", ["fleet_kill_migrate",
                                 "fleet_drain_join"])
def test_serving_check_fleet_legs_pass(leg):
    import tools.serving_check as sc

    assert sc.main(["--self", "--check", leg]) == 0


def test_resubmit_after_fleet_rejection_is_fresh_attempt(tiny_model):
    """Review regression: resubmitting a fleet-rejected (terminal)
    request must start a fresh lifecycle attempt — not trip the
    double-finalize guard — keeping the original t_arrival; and a
    duplicate submit of in-flight work is refused ALREADY_IN_FLIGHT
    without disturbing the live submission."""
    cfg, params = tiny_model
    rng = np.random.default_rng(61)
    ring = RingBufferRecorder()
    fleet = ReplicaFleet(
        cfg, params, n_replicas=2, n_slots=1, num_pages=8,
        max_prompt_len=16, sink=ring,
        admission=AdmissionConfig(max_queue=4, high_watermark=0.5,
                                  low_watermark=0.25))
    hogs = [Request(prompt=_toks(rng, 4), max_new_tokens=4)
            for _ in range(4)]
    for h in hogs:
        assert fleet.try_submit(h) is None
    bounced = Request(prompt=_toks(rng, 4), max_new_tokens=4)
    r = fleet.try_submit(bounced)
    assert r is not None and r.code is RejectionCode.NO_FEASIBLE_REPLICA
    assert bounced.status is RequestStatus.REJECTED
    t_first = bounced.t_arrival
    # a RUNNING duplicate is refused without finalizing
    fleet.run_boundary()
    running = next(h for h in hogs
                   if h.status is RequestStatus.RUNNING)
    dup = fleet.try_submit(running)
    assert dup is not None
    assert dup.code is RejectionCode.ALREADY_IN_FLIGHT
    assert running.status is RequestStatus.RUNNING  # intact
    fleet.generate([], max_steps=500)  # drain the hogs
    # resubmit the SAME rejected object: fresh attempt, original stamp
    assert fleet.try_submit(bounced) is None
    assert bounced.status is RequestStatus.QUEUED
    assert bounced.t_arrival == t_first
    fleet.generate([], max_steps=500)
    assert bounced.status is RequestStatus.COMPLETED
    assert list(bounced.out_tokens) == reference_decode(
        cfg, params, bounced.prompt, bounced.max_new_tokens)
    ends = [e for e in ring.events("request_end")
            if e["rid"] == bounced.rid]
    assert [e["status"] for e in ends] == ["rejected", "completed"]


def test_replica_dead_during_rolling_update_restarts_on_new_weights(
        tiny_model):
    """Review regression: a replica that dies mid-update misses its
    swap; restart_replica must apply the missed swap — a restarted
    replica never rejoins the router serving the pre-update weights."""
    cfg, params = tiny_model
    params2 = jax.tree_util.tree_map(lambda x: x, params)
    params2["embedding"]["position"] = (
        params["embedding"]["position"] * 0.5)
    rng = np.random.default_rng(67)
    ring = RingBufferRecorder()
    # kill replica 0 at boundary 0 — while the update is draining it
    chaos = ServingChaos().kill_replica_at(0, 0)
    fleet = ReplicaFleet(cfg, params, n_replicas=2, sink=ring,
                         chaos=chaos, n_slots=2, num_pages=12,
                         max_prompt_len=16)
    reqs = [Request(prompt=_toks(rng, 6), max_new_tokens=5,
                    arrival_step=i) for i in range(3)]
    fleet.schedule_rolling_update(params2)
    fleet.generate(reqs, max_steps=2000)
    assert fleet.rolling_update_done
    assert fleet.replicas[0].state is ReplicaState.DEAD
    assert fleet.last_stats["requests_lost"] == 0
    # replica 1 swapped in the wave; replica 0 missed its swap...
    assert fleet.replicas[1].swaps == 1
    assert fleet.replicas[0].swaps == 0
    fleet.restart_replica(0)
    # ...and received it at restart
    assert fleet.replicas[0].swaps == 1
    swaps = ring.events("weight_swap")
    assert sorted(e["replica_id"] for e in swaps) == [0, 1]
    late = [Request(prompt=_toks(rng, 6), max_new_tokens=5)
            for _ in range(4)]
    out = fleet.generate(late, max_steps=2000)
    assert {r.replica_id for r in late} == {0, 1}
    for r in late:  # NEW weights everywhere, incl. the restarted one
        assert out[r.rid] == reference_decode(
            cfg, params2, r.prompt, r.max_new_tokens), r.rid


def test_update_scheduled_after_death_still_reaches_restart(tiny_model):
    """Review regression: a replica already DEAD when the rolling
    update is scheduled misses the wave — restart_replica must still
    deliver its swap (never revive on pre-update weights)."""
    cfg, params = tiny_model
    params2 = jax.tree_util.tree_map(lambda x: x, params)
    params2["embedding"]["position"] = (
        params["embedding"]["position"] * 0.5)
    rng = np.random.default_rng(71)
    chaos = ServingChaos().kill_replica_at(0, 1)
    fleet = ReplicaFleet(cfg, params, n_replicas=2, chaos=chaos,
                         n_slots=2, num_pages=12, max_prompt_len=16)
    reqs = [Request(prompt=_toks(rng, 6), max_new_tokens=5)
            for _ in range(2)]
    fleet.generate(reqs, max_steps=2000)         # replica 0 dies here
    assert fleet.replicas[0].state is ReplicaState.DEAD
    fleet.schedule_rolling_update(params2)       # AFTER the death
    fleet.generate([], max_steps=500)            # wave over survivors
    assert fleet.rolling_update_done
    assert fleet.replicas[1].swaps == 1
    fleet.restart_replica(0)
    assert fleet.replicas[0].swaps == 1          # missed swap applied
    late = [Request(prompt=_toks(rng, 6), max_new_tokens=5)
            for _ in range(4)]
    out = fleet.generate(late, max_steps=2000)
    assert {r.replica_id for r in late} == {0, 1}
    for r in late:
        assert out[r.rid] == reference_decode(
            cfg, params2, r.prompt, r.max_new_tokens), r.rid


def test_migrant_resubmission_refused_in_flight(tiny_model):
    """Review regression: a request sitting in the fleet's migration
    queue (status PENDING, fleet-owned) must refuse resubmission —
    double placement would strand a stale migrant / double-finalize."""
    cfg, params = tiny_model
    rng = np.random.default_rng(73)
    chaos = ServingChaos().kill_replica_at(0, 1)
    fleet = ReplicaFleet(
        cfg, params, n_replicas=2, chaos=chaos, n_slots=1,
        num_pages=8, max_prompt_len=16,
        admission=AdmissionConfig(max_queue=2, high_watermark=0.5,
                                  low_watermark=0.25))
    victim = Request(prompt=_toks(rng, 6), max_new_tokens=6)
    assert fleet.replicas[0].engine.try_submit(victim) is None
    # block the survivor so the migrant stays queued at the fleet
    hogs = [Request(prompt=_toks(rng, 6), max_new_tokens=6)
            for _ in range(2)]
    assert fleet.replicas[1].engine.try_submit(hogs[0]) is None
    fleet.replicas[1].engine.run_step()
    assert fleet.replicas[1].engine.try_submit(hogs[1]) is None
    fleet.run_boundary()  # replica 0 dies; victim joins _migrants
    fleet.run_boundary()  # placement fails (survivor backpressured)
    assert any(m.req is victim for m in fleet._migrants)
    r = fleet.try_submit(victim)
    assert r is not None
    assert r.code is RejectionCode.ALREADY_IN_FLIGHT
    assert not is_terminal(victim.status)  # still fleet-owned
    fleet.generate([], max_steps=2000)     # drains without crashing
    assert is_terminal(victim.status)


def test_manual_join_mid_update_does_not_skip_swap(tiny_model):
    """Review regression: an operator try_join()ing the rolling
    update's current replica rejoins it on old weights; the wave must
    re-drain it and deliver the swap rather than declaring done with
    a stale-weights replica."""
    cfg, params = tiny_model
    params2 = jax.tree_util.tree_map(lambda x: x, params)
    params2["embedding"]["position"] = (
        params["embedding"]["position"] * 0.5)
    rng = np.random.default_rng(83)
    fleet = ReplicaFleet(cfg, params, n_replicas=2, n_slots=2,
                         num_pages=12, max_prompt_len=16)
    fleet.schedule_rolling_update(params2)
    fleet.run_boundary()          # drains replica 0 as plan current
    assert fleet.replicas[0].state is ReplicaState.DRAINING
    assert fleet.try_join(0)      # operator interferes: old weights
    assert fleet.replicas[0].swaps == 0
    fleet.generate([], max_steps=500)   # wave must recover
    assert fleet.rolling_update_done
    assert fleet.replicas[0].swaps == 1
    assert fleet.replicas[1].swaps == 1
    reqs = [Request(prompt=_toks(rng, 6), max_new_tokens=5)
            for _ in range(4)]
    out = fleet.generate(reqs, max_steps=2000)
    assert {r.replica_id for r in reqs} == {0, 1}
    for r in reqs:   # NEW weights everywhere despite the interference
        assert out[r.rid] == reference_decode(
            cfg, params2, r.prompt, r.max_new_tokens), r.rid


def test_fleet_summary_counters_are_per_run(tiny_model):
    """Review regression: a second generate() must not smear the first
    run's deaths/migrations into its summary — migrated/replica_deaths
    /steps are per-run, like the engines' accums."""
    cfg, params = tiny_model
    rng = np.random.default_rng(79)
    chaos = ServingChaos().kill_replica_at(1, 3)
    fleet = ReplicaFleet(cfg, params, n_replicas=3, chaos=chaos,
                         n_slots=2, num_pages=12, max_prompt_len=16)
    reqs = [Request(prompt=_toks(rng, 6), max_new_tokens=5,
                    arrival_step=i) for i in range(6)]
    fleet.generate(reqs, max_steps=2000)
    st1 = fleet.last_stats
    assert st1["replica_deaths"] == 1 and st1["migrated"] >= 1
    late = [Request(prompt=_toks(rng, 6), max_new_tokens=5)
            for _ in range(3)]
    fleet.generate(late, max_steps=2000)
    st2 = fleet.last_stats
    assert st2["replica_deaths"] == 0
    assert st2["migrated"] == 0 and st2["migration_readmitted"] == 0
    assert st2["requests_lost"] == 0
    assert st2["steps"] < fleet.steps_run  # per-run, not lifetime
    for k in ("0", "2"):
        assert st2["per_replica"][k]["migrated_out"] == 0
    # per-replica counters are per-run deltas too: the death happened
    # in run 1, so run 2's breakdown shows none
    assert st1["per_replica"]["1"]["deaths"] == 1
    assert st2["per_replica"]["1"]["deaths"] == 0


def test_migrants_place_before_same_boundary_arrivals(tiny_model):
    """Review regression: a dead replica's in-flight work (older
    t_arrival) must compete for admission capacity BEFORE the same
    boundary's fresh arrivals — not lose its slot to younger requests
    and burn placement retries."""
    cfg, params = tiny_model
    rng = np.random.default_rng(97)
    chaos = ServingChaos().kill_replica_at(0, 0)
    fleet = ReplicaFleet(
        cfg, params, n_replicas=2, chaos=chaos, n_slots=1,
        num_pages=8, max_prompt_len=16,
        admission=AdmissionConfig(max_queue=2, high_watermark=0.5,
                                  low_watermark=0.25))
    victim = Request(prompt=_toks(rng, 6), max_new_tokens=6)
    assert fleet.replicas[0].engine.try_submit(victim) is None
    # survivor has exactly one queue slot (high watermark at depth 1);
    # the fresh arrival lands the boundary after the kill — the
    # migrated victim must get that slot
    fresh = Request(prompt=_toks(rng, 6), max_new_tokens=6,
                    arrival_step=1)
    out = fleet.generate([fresh], max_steps=2000)
    assert victim.status is RequestStatus.COMPLETED
    assert victim.replica_id == 1
    assert list(victim.out_tokens) == reference_decode(
        cfg, params, victim.prompt, victim.max_new_tokens)
    # the younger request was the one refused (typed, not lost)
    assert fresh.status is RequestStatus.REJECTED
    assert fresh.end_reason == "no_feasible_replica"
    assert out[fresh.rid] == []
    assert fleet.last_stats["requests_lost"] == 0


def test_all_replicas_unavailable_fails_migrants_typed(tiny_model):
    """Review regression: migrants with no ACTIVE replica to place on,
    no swap plan, and every live engine idle must reach a TYPED
    terminal state (FAILED/no_active_replica) instead of spinning
    generate() forever (max_steps defaults to None)."""
    cfg, params = tiny_model
    rng = np.random.default_rng(89)
    ring = RingBufferRecorder()
    chaos = ServingChaos().kill_replica_at(0, 0)
    fleet = ReplicaFleet(cfg, params, n_replicas=2, sink=ring,
                         chaos=chaos, n_slots=1, num_pages=8,
                         max_prompt_len=16)
    victim = Request(prompt=_toks(rng, 6), max_new_tokens=6)
    assert fleet.replicas[0].engine.try_submit(victim) is None
    fleet.drain(1)            # the survivor is DRAINING, never joined
    fleet.generate([])        # must TERMINATE (no max_steps guard)
    assert victim.status is RequestStatus.FAILED
    assert victim.end_reason == "no_active_replica"
    ends = [e for e in ring.events("request_end")
            if e["rid"] == victim.rid]
    assert len(ends) == 1 and ends[0]["status"] == "failed"


def test_fleet_chaos_trace_holds_invariants_every_boundary(tiny_model):
    """Random fleet chaos: staggered arrivals, a replica kill, stolen
    allocations, deadline budgets — live replicas hold
    check_invariants() after EVERY boundary, every request ends
    terminal, completions are token-identical, zero leaks."""
    cfg, params = tiny_model
    rng = np.random.default_rng(99)
    reqs = [Request(
        prompt=_toks(rng, int(rng.integers(3, 10))), max_new_tokens=5,
        arrival_step=int(rng.integers(0, 8)),
        priority=int(rng.integers(0, 3)))
        for _ in range(8)]
    chaos = (ServingChaos().kill_replica_at(1, 5)
             .fail_allocs(int(rng.integers(1, 3))))
    fleet = ReplicaFleet(
        cfg, params, n_replicas=3, chaos=chaos, n_slots=2,
        num_pages=6, max_prompt_len=16,
        migration_retry=RetryPolicy(attempts=200))
    pending = sorted(reqs, key=lambda r: (r.arrival_step, r.rid))
    guard = 0
    while True:
        guard += 1
        assert guard < 800, "fleet trace did not drain"
        step = fleet.steps_run
        while pending and pending[0].arrival_step <= step:
            fleet.try_submit(pending.pop(0))
        if not pending and not fleet.busy:
            break
        fleet.run_boundary()
        fleet.check_invariants()
    assert fleet.page_leaks() == 0
    for r in reqs:
        assert is_terminal(r.status), (r.rid, r.status)
        if r.status is RequestStatus.COMPLETED:
            assert list(r.out_tokens) == reference_decode(
                cfg, params, r.prompt, r.max_new_tokens), r.rid
