"""Serving under fire: lifecycle, admission control, fault isolation,
engine recovery (apex_tpu.serving.robustness + resilience.ServingChaos).

Coverage map (the ISSUE-10 acceptance surface):

- typed terminal states + the summary schema fix: percentiles over
  COMPLETED requests only, buckets by terminal state;
- one RejectionReason taxonomy: the legacy PR-6 refusal paths
  (pool-infeasible, replay-prompt-overflow) carry typed codes, the
  malformed-request storm hits every front-door check;
- deadlines: TTFT / total-latency budgets evict queued AND running
  work deterministically (VirtualClock), pages freed, events recorded;
- admission control: bounded queue, watermark hysteresis, token-budget
  (deadline-infeasibility) refusal; degradation: max_new capping and
  priority-ordered shedding under sustained pressure;
- fault isolation PROOF: a chaos-poisoned request terminates FAILED
  with slot/step provenance while every other request's tokens are
  byte-identical to the same trace without poison;
- recovery PROOF: kill-engine-mid-flight -> recover_from -> replay
  completes all in-flight requests token-identical to an uninterrupted
  run; a wedged step sync is caught by the armed HangWatchdog with
  thread stacks (and step provenance) in the hang event;
- request-level retry of FAILED-transient requests under RetryPolicy
  (attempts + wall-clock deadline);
- chaos property traces: random admit/evict/preempt/poison/timeout/
  alloc-fault interleavings hold check_invariants() at every step, end
  with all requests terminal, zero page leaks, and survivors
  token-identical to the dense greedy reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.resilience import (
    ChaosError,
    HangError,
    HangWatchdog,
    RetryPolicy,
    ServingChaos,
    request_storm,
)
from apex_tpu.serving import (
    AdmissionConfig,
    AdmissionController,
    DegradationPolicy,
    RejectionCode,
    RejectionError,
    Request,
    RequestStatus,
    Scheduler,
    SchedulerError,
    ServingEngine,
    VirtualClock,
    PagedKVSpec,
    is_terminal,
    reference_decode,
)
from apex_tpu.telemetry import RingBufferRecorder
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
# lifecycle + the summary schema fix
# ---------------------------------------------------------------------------

def test_lifecycle_and_summary_buckets_by_terminal_state(tiny_model):
    """The _summarize fix: one request completes, one times out in the
    queue — the summary buckets them by terminal state and computes the
    latency percentiles over COMPLETED requests ONLY (the timed-out
    request's stamps must not contaminate the distribution)."""
    cfg, params = tiny_model
    rng = np.random.default_rng(0)
    ring = RingBufferRecorder()
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        max_prompt_len=16, clock=VirtualClock(dt=1.0),
                        sink=ring)
    ok_req = Request(prompt=_toks(rng, 5), max_new_tokens=6)
    # waits behind ok_req on the single slot and expires in-queue
    # (budget 4 virtual seconds << the ~11 steps ok_req takes)
    late = Request(prompt=_toks(rng, 5), max_new_tokens=6,
                   latency_budget_ms=4000.0)
    eng.generate([ok_req, late], max_steps=500)
    eng.scheduler.check_invariants()
    assert ok_req.status is RequestStatus.COMPLETED
    assert late.status is RequestStatus.TIMED_OUT
    assert late.end_reason == "latency_budget"
    st = eng.last_stats
    # schema pin: the terminal-state buckets and SLO/goodput keys
    assert st["by_status"] == {"completed": 1, "rejected": 0,
                               "timed_out": 1, "failed": 0,
                               "cancelled": 0}
    assert st["completed"] == 1 and st["n_requests"] == 2
    assert sum(st["by_status"].values()) == st["n_requests"]
    for key in ("slo_attainment", "slo_attained", "goodput_tokens",
                "goodput_tokens_per_sec", "max_queue_depth", "retries"):
        assert key in st, key
    assert st["slo_attained"] == 1 and st["slo_attainment"] == 0.5
    # percentiles over the ONE completed request: a degenerate (equal)
    # distribution. Were the timed-out request included, p50 != p99.
    lat = st["latency_ms"]
    assert set(lat) == {"p50", "p90", "p99"}
    assert lat["p50"] == lat["p99"]
    # generated_tokens still counts all emitted work (the timed-out
    # request may have produced some before expiring)
    assert st["generated_tokens"] == sum(
        len(r.out_tokens) for r in (ok_req, late))
    ends = ring.events("request_end")
    assert {e["status"] for e in ends} == {"completed", "timed_out"}


def test_ttft_budget_evicts_running_prefill(tiny_model):
    """A request whose TTFT budget expires while still prefilling is
    evicted from its SLOT (not just the queue): pages freed, terminal
    TIMED_OUT with reason ttft_budget."""
    cfg, params = tiny_model
    rng = np.random.default_rng(1)
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        max_prompt_len=16, clock=VirtualClock(dt=1.0))
    req = Request(prompt=_toks(rng, 12), max_new_tokens=4,
                  ttft_budget_ms=5000.0)  # 12 prefill steps > 5 ticks
    eng.generate([req], max_steps=200)
    assert req.status is RequestStatus.TIMED_OUT
    assert req.end_reason == "ttft_budget"
    assert req.out_tokens == []
    assert eng.scheduler.allocator.used_count == 0
    eng.scheduler.check_invariants()


def test_cancel_queued_and_running(tiny_model):
    cfg, params = tiny_model
    rng = np.random.default_rng(2)
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        max_prompt_len=16)
    running = Request(prompt=_toks(rng, 6), max_new_tokens=8)
    queued = Request(prompt=_toks(rng, 6), max_new_tokens=8)
    eng.submit(running)
    eng.submit(queued)
    for _ in range(3):
        eng.run_step()
    assert running.status is RequestStatus.RUNNING
    assert eng.cancel(queued) and queued.status is RequestStatus.CANCELLED
    assert eng.cancel(running) and running.status is RequestStatus.CANCELLED
    assert not eng.cancel(running)  # already terminal: not in flight
    assert eng.scheduler.allocator.used_count == 0
    eng.scheduler.check_invariants()
    assert eng.scheduler.idle


# ---------------------------------------------------------------------------
# typed rejection taxonomy (satellite: legacy paths regression)
# ---------------------------------------------------------------------------

def test_legacy_pool_infeasible_carries_typed_reason():
    """The PR-6 'pool can never hold it' refusal now raises
    RejectionError (still a SchedulerError, same message) with code
    POOL_INFEASIBLE and structured detail."""
    spec = PagedKVSpec(1, 4, 16, page_size=16, num_pages=4,
                       pages_per_seq=4)
    sched = Scheduler(spec, n_slots=2, max_prompt_len=64)
    req = Request(prompt=list(range(1, 17)), max_new_tokens=48)
    with pytest.raises(SchedulerError, match="never be served") as e:
        sched.submit(req)
    assert isinstance(e.value, RejectionError)
    assert e.value.reason.code is RejectionCode.POOL_INFEASIBLE
    assert e.value.reason.detail["pages_needed"] == 4
    assert e.value.reason.detail["n_usable_pages"] == 3
    # validate() is the non-raising face of the same taxonomy
    reason = sched.validate(req)
    assert reason is not None
    assert reason.code is RejectionCode.POOL_INFEASIBLE
    assert not sched.waiting


def test_legacy_replay_overflow_carries_typed_reason():
    """The PR-6 preemption-replay-overflow refusal, typed."""
    spec = PagedKVSpec(1, 4, 16, page_size=16, num_pages=5,
                       pages_per_seq=4)
    sched = Scheduler(spec, n_slots=2, max_prompt_len=16)
    with pytest.raises(SchedulerError, match="replay") as e:
        sched.submit(Request(prompt=list(range(12)), max_new_tokens=20))
    assert isinstance(e.value, RejectionError)
    assert e.value.reason.code is RejectionCode.REPLAY_OVERFLOW
    assert e.value.reason.detail["worst_replay"] == 31
    # the boundary case stays admissible (12 + 5 - 1 = 16)
    sched.submit(Request(prompt=list(range(12)), max_new_tokens=5))
    assert len(sched.waiting) == 1


def test_request_storm_all_refused_with_typed_codes(tiny_model):
    """The chaos request storm: every malformed/oversized shape is
    refused with exactly the expected code, REJECTED status, a reject
    event — and zero scheduler/allocator state left behind."""
    cfg, params = tiny_model
    ring = RingBufferRecorder()
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=4,
                        max_prompt_len=16, sink=ring)
    storm = request_storm(eng)
    assert len(storm) == 5  # incl. the pool-infeasible case
    for req, code in storm:
        reason = eng.try_submit(req)
        assert reason is not None and reason.code is code, (
            f"rid {req.rid}: expected {code}, got {reason}")
        assert req.status is RequestStatus.REJECTED
        assert req.end_reason == code.value
    # the raising door throws the same typed error
    bad, code = request_storm(eng, seed=1)[0]
    with pytest.raises(RejectionError) as e:
        eng.submit(bad)
    assert e.value.reason.code is code
    rejects = ring.events("reject")
    assert len(rejects) == len(storm) + 1
    assert all("code" in r for r in rejects)
    assert not eng.scheduler.waiting
    assert eng.scheduler.allocator.used_count == 0
    eng.scheduler.check_invariants()


def test_resubmit_after_rejection_is_a_fresh_attempt(tiny_model):
    """Review regressions: resubmitting a rejected request must start a
    fresh lifecycle attempt (not trip the double-finalize guard), keep
    the ORIGINAL t_arrival (deadline budgets span resubmits), and —
    under a virtual clock — the admission EWMA must be denominated in
    that same clock (boundary-to-boundary ticks, not wall seconds)."""
    cfg, params = tiny_model
    rng = np.random.default_rng(5)
    clock = VirtualClock(dt=1.0)
    ring = RingBufferRecorder()
    eng = ServingEngine(
        cfg, params, n_slots=1, num_pages=8, max_prompt_len=16,
        clock=clock, sink=ring,
        admission=AdmissionConfig(max_queue=2, high_watermark=0.75,
                                  low_watermark=0.5))
    hog = Request(prompt=_toks(rng, 4), max_new_tokens=6)
    assert eng.try_submit(hog) is None
    bumped = Request(prompt=_toks(rng, 4), max_new_tokens=6)
    r = eng.try_submit(bumped)  # depth 1 >= high(1): backpressure
    assert r is not None and r.code is RejectionCode.BACKPRESSURE
    assert bumped.status is RequestStatus.REJECTED
    t_first_submit = bumped.t_arrival
    eng.generate([], max_steps=200)  # drain the hog
    assert hog.status is RequestStatus.COMPLETED
    # the EWMA runs in virtual time: one clock tick per boundary
    assert eng.admission.est_step_s == pytest.approx(1.0)
    # resubmit the SAME object: fresh attempt, original arrival stamp
    assert eng.try_submit(bumped) is None
    assert bumped.status is RequestStatus.QUEUED
    assert bumped.t_arrival == t_first_submit
    eng.generate([], max_steps=200)
    assert bumped.status is RequestStatus.COMPLETED
    ends = [e for e in ring.events("request_end")
            if e["rid"] == bumped.rid]
    assert [e["status"] for e in ends] == ["rejected", "completed"]


def test_duplicate_submit_of_in_flight_request_refused(tiny_model):
    """Review regression: submitting a request that is already QUEUED
    or RUNNING must be refused (ALREADY_IN_FLIGHT) without disturbing
    the live submission — a duplicate would put one Request object in
    two slots (shared out_tokens, double finalize)."""
    cfg, params = tiny_model
    rng = np.random.default_rng(6)
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        max_prompt_len=16)
    req = Request(prompt=_toks(rng, 4), max_new_tokens=6)
    assert eng.try_submit(req) is None
    dup = eng.try_submit(req)  # QUEUED
    assert dup is not None
    assert dup.code is RejectionCode.ALREADY_IN_FLIGHT
    assert req.status is RequestStatus.QUEUED  # live submission intact
    eng.run_step()  # now RUNNING
    dup = eng.try_submit(req)
    assert dup is not None and dup.code is RejectionCode.ALREADY_IN_FLIGHT
    with pytest.raises(RejectionError, match="already in flight"):
        eng.submit(req)
    eng.generate([], max_steps=200)
    assert req.status is RequestStatus.COMPLETED
    assert list(req.out_tokens) == reference_decode(
        cfg, params, req.prompt, 6)


# ---------------------------------------------------------------------------
# admission control + degradation
# ---------------------------------------------------------------------------

def test_admission_controller_watermark_hysteresis():
    """Pure host logic, no engine: hard bound, two-level watermark
    (ON at high, OFF only back at low)."""
    ctl = AdmissionController(
        AdmissionConfig(max_queue=8, high_watermark=0.5,
                        low_watermark=0.25), n_slots=1)
    req = Request(prompt=[1, 2], max_new_tokens=4)
    assert ctl.check(req, queue_depth=0, queued_tokens=0) is None
    full = ctl.check(req, queue_depth=8, queued_tokens=48)
    assert full.code is RejectionCode.QUEUE_FULL
    # depth 4 = high: backpressure latches
    bp = ctl.check(req, queue_depth=4, queued_tokens=24)
    assert bp.code is RejectionCode.BACKPRESSURE
    # still latched at depth 3 (above low=2)
    assert ctl.check(req, queue_depth=3,
                     queued_tokens=18).code is RejectionCode.BACKPRESSURE
    # drains below low: admits again
    assert ctl.check(req, queue_depth=2, queued_tokens=12) is None
    assert ctl.rejected == 3


def test_admission_token_budget_deadline_infeasible():
    """Token-budget admission: at a known step time, a budget below the
    service lower bound is refused DEADLINE_INFEASIBLE with the
    estimate in the detail; a generous budget passes."""
    ctl = AdmissionController(
        AdmissionConfig(max_queue=64, step_time_init_s=0.010),
        n_slots=2)
    # service: 8 prompt + 8 new = 16 steps ~ 160ms; queue adds
    # 32 tokens / 2 slots = 16 steps ~ 160ms -> total lb ~ 320ms
    tight = Request(prompt=list(range(8)), max_new_tokens=8,
                    latency_budget_ms=200.0)
    r = ctl.check(tight, queue_depth=2, queued_tokens=32)
    assert r is not None and r.code is RejectionCode.DEADLINE_INFEASIBLE
    assert r.detail["latency_lb_ms"] == pytest.approx(320.0)
    roomy = Request(prompt=list(range(8)), max_new_tokens=8,
                    latency_budget_ms=1000.0)
    assert ctl.check(roomy, queue_depth=2, queued_tokens=32) is None
    # TTFT-only budget: lb = (16 wait + 8 prompt) * 10ms = 240ms
    t = Request(prompt=list(range(8)), max_new_tokens=8,
                ttft_budget_ms=100.0)
    r = ctl.check(t, queue_depth=2, queued_tokens=32)
    assert r is not None and r.code is RejectionCode.DEADLINE_INFEASIBLE
    assert "ttft_lb_ms" in r.detail


def test_degradation_caps_and_sheds_under_sustained_pressure(tiny_model):
    """One long occupant pins the single slot; the queue sits at the
    high watermark for shed_after boundaries -> the policy sheds down
    to the low watermark, lowest-priority-youngest first, with shed
    events; meanwhile newly admitted work had max_new capped (degrade
    event). Everything terminal, nothing leaked."""
    cfg, params = tiny_model
    rng = np.random.default_rng(3)
    ring = RingBufferRecorder()
    eng = ServingEngine(
        cfg, params, n_slots=1, num_pages=8, max_prompt_len=16,
        sink=ring,
        admission=AdmissionConfig(max_queue=8, high_watermark=0.5,
                                  low_watermark=0.25),
        degradation=DegradationPolicy(shed_after=2, cap_max_new=4))
    hog = Request(prompt=_toks(rng, 4), max_new_tokens=12)
    eng.submit(hog)
    eng.run_step()  # hog takes the slot
    assert hog.status is RequestStatus.RUNNING
    # fill the queue to the high watermark (4); priorities distinguish
    # shed order; the last submit is capped (queue >= high -> pressure)
    queued = [Request(prompt=_toks(rng, 4), max_new_tokens=12,
                      priority=p) for p in (2, 1, 0)]
    for q in queued:
        assert eng.try_submit(q) is None
    capped = Request(prompt=_toks(rng, 4), max_new_tokens=12, priority=5)
    # depth is 3 (below high=4): accepted uncapped... so push one more
    assert eng.try_submit(capped) is None
    assert capped.max_new_tokens == 12  # depth was 3 < high at submit
    overflow = Request(prompt=_toks(rng, 4), max_new_tokens=12)
    r = eng.try_submit(overflow)  # depth 4 = high -> backpressure
    assert r is not None and r.code is RejectionCode.BACKPRESSURE
    # two pressured boundaries (slot still held by hog, queue depth 4)
    eng.run_step()
    eng.run_step()
    shed_events = ring.events("shed")
    assert shed_events, "sustained pressure must shed"
    # shed down to low watermark (2): two victims, lowest priority
    # first, youngest among equals — priorities 0 then 1
    assert len(eng.scheduler.waiting) == 2
    shed_reqs = [q for q in queued + [capped]
                 if q.status is RequestStatus.REJECTED]
    assert sorted(q.priority for q in shed_reqs) == [0, 1], (
        "shedding must take the lowest-priority victims")
    assert all(q.end_reason == "shed" for q in shed_reqs)
    # the shed event stream names the lowest-priority victim first
    assert shed_events[0]["priority"] == 0
    # drive the rest home
    eng.generate([], max_steps=300)
    eng.scheduler.check_invariants()
    assert eng.scheduler.allocator.used_count == 0
    for q in [hog, capped] + queued + [overflow]:
        assert is_terminal(q.status), q.rid
    # a pressured submit WOULD be capped: prime pressure state again
    # via the controller directly
    assert eng.admission.cap_for(
        Request(prompt=[1], max_new_tokens=12), queue_depth=4) == 4


# ---------------------------------------------------------------------------
# fault isolation (acceptance proof)
# ---------------------------------------------------------------------------

def test_poisoned_request_quarantined_others_byte_identical(tiny_model):
    """THE fault-isolation proof: the same staggered trace is run clean
    and with one request's logits chaos-poisoned mid-decode. The victim
    terminates FAILED with slot/step provenance; every other request's
    token list is BYTE-identical between the two runs (and equals the
    dense greedy reference)."""
    cfg, params = tiny_model
    rng = np.random.default_rng(11)
    lens = (6, 9, 4, 7)

    def mk_trace():
        r = np.random.default_rng(99)
        return [Request(prompt=_toks(r, L), max_new_tokens=6,
                        arrival_step=2 * i)
                for i, L in enumerate(lens)]

    clean = mk_trace()
    eng0 = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                         max_prompt_len=16)
    out_clean = eng0.generate(list(clean), max_steps=2000)

    poisoned = mk_trace()
    victim = poisoned[1]
    chaos = ServingChaos().poison_request(victim.rid, at_step=9)
    ring = RingBufferRecorder()
    eng1 = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                         max_prompt_len=16, chaos=chaos, sink=ring)
    out_poison = eng1.generate(list(poisoned), max_steps=2000)
    eng1.scheduler.check_invariants()
    assert eng1.scheduler.allocator.used_count == 0

    assert chaos.faults_fired == [("poison", victim.rid, 9)]
    assert victim.status is RequestStatus.FAILED
    f = victim.failure
    assert f["kind"] == "nonfinite_logits" and f["step"] == 9
    assert f["rid"] == victim.rid and "slot" in f and f["transient"]
    ends = [e for e in ring.events("request_end")
            if e["status"] == "failed"]
    assert len(ends) == 1 and ends[0]["failure"]["slot"] == f["slot"]
    # every NON-victim request: byte-identical to the undisturbed run
    # and to the dense greedy reference
    for i, (c, p) in enumerate(zip(clean, poisoned)):
        if p is victim:
            continue
        assert out_poison[p.rid] == out_clean[c.rid], f"request {i}"
        assert out_poison[p.rid] == reference_decode(
            cfg, params, p.prompt, p.max_new_tokens)
        assert p.status is RequestStatus.COMPLETED


def test_retry_failed_transient_completes_token_identical(tiny_model):
    """Satellite: request-level retry under RetryPolicy. The quarantined
    (transient) FAILED request is resubmitted through the replay path
    and completes token-identical to a never-poisoned run."""
    cfg, params = tiny_model
    rng = np.random.default_rng(21)
    reqs = [Request(prompt=_toks(rng, L), max_new_tokens=6)
            for L in (5, 8)]
    chaos = ServingChaos().poison_request(reqs[0].rid, at_step=6)
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                        max_prompt_len=16, chaos=chaos)
    out = eng.generate(
        list(reqs), max_steps=2000,
        retry_failed=RetryPolicy(attempts=3, retry_on=(Exception,),
                                 deadline=60.0))
    assert chaos.faults_fired and chaos.faults_fired[0][0] == "poison"
    for r in reqs:
        assert r.status is RequestStatus.COMPLETED
        assert out[r.rid] == reference_decode(cfg, params, r.prompt, 6)
    assert reqs[0].retries == 1 and reqs[1].retries == 0
    assert eng.last_stats["retries"] == 1
    assert eng.last_stats["by_status"]["completed"] == 2


# ---------------------------------------------------------------------------
# engine recovery (acceptance proof)
# ---------------------------------------------------------------------------

def test_kill_engine_mid_flight_recovers_token_identical(tiny_model):
    """THE recovery proof: chaos kills the engine mid-flight with
    requests prefilling, decoding, and queued; recover_from builds a
    fresh engine and replays them all to completion, token-identical
    to an uninterrupted run (the dense greedy reference)."""
    cfg, params = tiny_model
    rng = np.random.default_rng(31)
    reqs = [Request(prompt=_toks(rng, L), max_new_tokens=6,
                    arrival_step=i)
            for i, L in enumerate((8, 5, 11))]
    chaos = ServingChaos().kill_engine_at(10)
    ring = RingBufferRecorder()
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                        max_prompt_len=16, chaos=chaos, sink=ring)
    with pytest.raises(ChaosError, match="injected engine kill"):
        eng.generate(list(reqs), max_steps=2000)
    in_flight = [r for r in reqs if not is_terminal(r.status)]
    assert in_flight, "the kill must strand work"
    eng2, survivors = ServingEngine.recover_from(eng)
    assert {r.rid for r in survivors} == {r.rid for r in in_flight}
    eng2.generate(survivors, max_steps=2000)
    eng2.scheduler.check_invariants()
    assert eng2.scheduler.allocator.used_count == 0
    for r in reqs:
        assert r.status is RequestStatus.COMPLETED
        assert list(r.out_tokens) == reference_decode(
            cfg, params, r.prompt, r.max_new_tokens), r.rid
    assert all(r.restarts == 1 for r in survivors)
    recs = ring.events("engine_recovery")
    assert recs and recs[0]["recovered"] == len(survivors)


def test_wedged_step_caught_by_armed_watchdog(tiny_model):
    """THE wedge proof: the chaos-wedged host sync is caught by the
    armed HangWatchdog — HangError raised, hang event in the sink with
    ALL-thread stacks and the serving step number — and the stranded
    request recovers onto a fresh engine."""
    cfg, params = tiny_model
    rng = np.random.default_rng(41)
    req = Request(prompt=_toks(rng, 4), max_new_tokens=6)
    chaos = ServingChaos().wedge_step_at(5, stall_s=3.0)
    ring = RingBufferRecorder()
    wd = HangWatchdog(timeout_s=0.3, poll_s=0.02, sink=ring)
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        max_prompt_len=16, chaos=chaos, watchdog=wd,
                        sink=ring)
    with pytest.raises(HangError) as e:
        eng.generate([req], max_steps=100)
    wd.close()
    assert e.value.what == "serving_step_host_sync"
    assert "thread" in e.value.stacks
    hangs = ring.events("hang")
    assert len(hangs) == 1
    assert hangs[0]["step"] == 5                  # context= provenance
    assert "MainThread" in hangs[0]["stacks"]     # the dump is real
    assert chaos.faults_fired == [("wedge", 5)]
    # the wedge strands the request mid-flight; recovery replays it
    eng2, survivors = ServingEngine.recover_from(eng, watchdog=None)
    assert [r.rid for r in survivors] == [req.rid]
    eng2.generate(survivors, max_steps=200)
    assert req.status is RequestStatus.COMPLETED
    assert list(req.out_tokens) == reference_decode(
        cfg, params, req.prompt, 6)


# ---------------------------------------------------------------------------
# chaos property traces (satellite)
# ---------------------------------------------------------------------------

def test_chaos_property_traces_hold_invariants_every_step(tiny_model):
    """Random chaos traces: staggered admissions, tiny pool (forced
    preemption), stolen allocations, one poisoned request, deadline
    budgets, bounded-queue admission. After EVERY step:
    check_invariants() (no page leaks, no double frees, lifecycle/
    occupancy coherence) and a queue no deeper than ``max_queue``. At
    the end: every request terminal, the allocator drained, and every
    COMPLETED request token-identical to the dense greedy reference.
    Termination within the step guard IS the seniority-contract check —
    a livelock would blow it."""
    cfg, params = tiny_model
    rng = np.random.default_rng(1234)
    max_queue = 6
    for trial in range(2):
        n_req = 6
        reqs = []
        for i in range(n_req):
            plen = int(rng.integers(3, 10))
            reqs.append(Request(
                prompt=_toks(rng, plen), max_new_tokens=6,
                arrival_step=int(rng.integers(0, 10)),
                priority=int(rng.integers(0, 3)),
                # roughly half get budgets; some generous, some doomed
                latency_budget_ms=(float(rng.integers(8, 80)) * 1e3
                                   if rng.random() < 0.5 else None)))
        chaos = ServingChaos().fail_allocs(int(rng.integers(1, 4)))
        victim = reqs[int(rng.integers(0, n_req))]
        chaos.poison_request(victim.rid)
        eng = ServingEngine(
            cfg, params, n_slots=2, num_pages=5, max_prompt_len=16,
            chaos=chaos, clock=VirtualClock(dt=1.0),
            admission=AdmissionConfig(max_queue=max_queue,
                                      high_watermark=0.84,
                                      low_watermark=0.5),
            degradation=DegradationPolicy(shed_after=3))
        pending = sorted(reqs, key=lambda r: (r.arrival_step, r.rid))
        step_i = 0
        guard = 0
        while True:
            guard += 1
            assert guard < 600, f"trial {trial}: trace did not drain"
            while pending and pending[0].arrival_step <= step_i:
                eng.try_submit(pending.pop(0))
            if not pending and eng.scheduler.idle:
                break
            if not eng.scheduler.idle:
                eng.run_step()
            step_i += 1
            eng.scheduler.check_invariants()
            assert len(eng.scheduler.waiting) <= max_queue
        eng.scheduler.check_invariants()
        assert eng.scheduler.allocator.used_count == 0, f"trial {trial}"
        for r in reqs:
            assert is_terminal(r.status), (trial, r.rid, r.status)
            if r.status is RequestStatus.COMPLETED:
                assert list(r.out_tokens) == reference_decode(
                    cfg, params, r.prompt, r.max_new_tokens), (
                    trial, r.rid)
        assert victim.status in (RequestStatus.FAILED,
                                 RequestStatus.REJECTED,
                                 RequestStatus.TIMED_OUT), (
            "the poisoned request must not complete normally")


def test_recover_from_under_admission_pressure(tiny_model):
    """ISSUE-11 satellite: recover_from composed with admission
    pressure. A killed engine's survivors land on an engine whose
    queue already sits at the high watermark: re-admission must not
    deadlock or leak pages — the recovered work either queues (when
    the door opens) or is refused/shed in DegradationPolicy order
    (lowest-priority-youngest), with check_invariants() holding after
    every step and every request terminal."""
    cfg, params = tiny_model
    rng = np.random.default_rng(53)
    # engine A dies mid-flight with work running and queued
    chaos = ServingChaos().kill_engine_at(4)
    eng_a = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                          max_prompt_len=16, chaos=chaos)
    a_reqs = [Request(prompt=_toks(rng, 5), max_new_tokens=5,
                      priority=3) for _ in range(2)]
    with pytest.raises(ChaosError):
        eng_a.generate(list(a_reqs), max_steps=500)
    from apex_tpu.serving import recover_requests

    survivors = recover_requests(eng_a)
    assert survivors, "the kill must strand work"
    # engine B: bounded queue ALREADY at the high watermark (4 of
    # max_queue 8, high=0.5), slot pinned by a hog, shedding armed
    ring = RingBufferRecorder()
    eng_b = ServingEngine(
        cfg, params, n_slots=1, num_pages=8, max_prompt_len=16,
        sink=ring,
        admission=AdmissionConfig(max_queue=8, high_watermark=0.5,
                                  low_watermark=0.25),
        degradation=DegradationPolicy(shed_after=2))
    hog = Request(prompt=_toks(rng, 4), max_new_tokens=10)
    eng_b.submit(hog)
    eng_b.run_step()  # hog takes the slot
    primed = [Request(prompt=_toks(rng, 4), max_new_tokens=5,
                      priority=p) for p in (2, 1, 0, 2)]
    for q in primed:
        assert eng_b.try_submit(q) is None
    assert len(eng_b.scheduler.waiting) == eng_b.admission.high_count
    # recovered work re-enters through the same admission door: at the
    # high watermark it is refused typed (BACKPRESSURE), never dropped
    readmitted, refused = [], []
    for r in survivors:
        reason = eng_b.try_submit(r)
        (refused if reason is not None else readmitted).append(r)
        if reason is not None:
            assert reason.code is RejectionCode.BACKPRESSURE
            assert r.status is RequestStatus.REJECTED
    assert refused, "pressure must push back on recovery"
    # drive to drain with invariants checked after EVERY step; the
    # sustained pressure sheds queued work in DegradationPolicy order
    guard = 0
    while not eng_b.scheduler.idle:
        guard += 1
        assert guard < 400, "recovery-under-pressure deadlocked"
        eng_b.run_step()
        eng_b.scheduler.check_invariants()
    shed = [e for e in ring.events("shed")]
    assert shed, "sustained pressure must shed"
    shed_reqs = [q for q in primed if q.end_reason == "shed"]
    assert shed_reqs and min(q.priority for q in primed) in {
        q.priority for q in shed_reqs}, (
        "shedding must take the lowest-priority victims first")
    assert shed[0]["priority"] == min(
        q.priority for q in primed)
    for r in [hog] + primed + survivors:
        assert is_terminal(r.status), (r.rid, r.status)
    assert eng_b.scheduler.allocator.used_count == 0
    # the recovered request that got through completed token-identical
    # (replay carried its pre-kill tokens across BOTH the kill and the
    # pressure) — the composition the satellite pins
    for r in readmitted:
        if r.status is RequestStatus.COMPLETED:
            assert list(r.out_tokens) == reference_decode(
                cfg, params, r.prompt, r.max_new_tokens), r.rid


# ---------------------------------------------------------------------------
# CI wiring: serving_check chaos legs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("leg", ["poison_quarantine", "timeout_eviction",
                                 "kill_recover"])
def test_serving_check_chaos_legs_pass(leg):
    """The tier-1 CI smoke: each chaos leg runs clean under the 0/1/2
    exit-code contract."""
    import tools.serving_check as sc

    assert sc.main(["--self", "--check", leg]) == 0


def test_serving_check_chaos_leg_failure_is_exit_1(monkeypatch):
    import tools.serving_check as sc

    monkeypatch.setitem(sc.CHECKS, "poison_quarantine",
                        lambda: {"ok": False, "victim_status": "completed"})
    assert sc.main(["--self", "--check", "poison_quarantine"]) == 1
