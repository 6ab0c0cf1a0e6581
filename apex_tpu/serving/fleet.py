"""ReplicaFleet: N serving engines behind a deadline-aware router.

The fleet layer of the "millions of users" story: PR 10 made ONE
:class:`~apex_tpu.serving.engine.ServingEngine` survive deadline
pressure, poisoned batches, wedged syncs, and restarts; this module
composes N of them into the standard production topology —
data-parallel replicas today, x tensor-parallel within a replica once
the mesh substrate lands — where real outages live: a replica dies
mid-storm and the number that must hold is the FLEET's SLO attainment
over *all offered* requests, not any single engine's goodput.

Everything here rides primitives the single engine already proved:

- **routing** — each request is dispatched by *feasibility x load*:
  every ACTIVE replica is costed through the read-only
  :meth:`ServingEngine.probe` (no admission side effects — probing a
  replica must not latch its backpressure), infeasible replicas are
  excluded, and among the feasible ones the request goes to the
  lowest-cost replica, where cost = estimated steps to first token
  (token backlog / slots + replay prefill) x that replica's
  :attr:`AdmissionController.estimated_step_time_s` EWMA — the
  admission controller's step-time estimate IS the per-replica cost
  model, so a slow replica organically sheds load to fast ones. When
  NO replica is feasible the fleet refuses with the typed
  ``NO_FEASIBLE_REPLICA`` :class:`RejectionReason`, carrying every
  replica's individual refusal code in the detail.
- **drain / join** — :meth:`drain` stops new admits to a replica (the
  router skips it) while it finishes everything already admitted;
  once idle, :meth:`try_join` swaps weights through
  ``amp.cast_params_for_inference`` (the same one-shot inference cast
  the engine ctor uses) and returns it to the router.
  :meth:`schedule_rolling_update` runs that drain->swap->join wave
  across the whole fleet *while traffic flows* — a rolling weight
  update with zero dropped requests.
- **replica failure** — the fleet detects a dead engine by the typed
  failures the engine already raises (``ChaosError`` from an injected
  kill, ``HangError`` from the armed watchdog catching a wedged step)
  and migrates its in-flight requests to the survivors riding the
  recompute-replay carrier (:func:`recover_requests`: generated
  tokens are KEPT and fold into the replay prompt), so migrated
  requests decode token-identically to an undisturbed run.
- **re-admission under pressure** — migrated work re-enters the
  survivors' admission control like any other request, honoring its
  ORIGINAL deadlines (``t_arrival`` is stamped once, at first fleet
  submit — the user has been waiting the whole time). Placement
  retries each boundary under an optional
  :class:`~apex_tpu.resilience.RetryPolicy` (its ``attempts`` count
  and wall-clock ``deadline`` budget bound the retry loop), so a
  fleet near saturation sheds by priority through the engines'
  :class:`DegradationPolicy` machinery instead of cascading.

Telemetry: every engine event (``request_end``, ``hang``, quarantine
failures, ``serving_step``) reaches the shared sink through a
:class:`~apex_tpu.telemetry.TaggedRecorder` carrying ``replica_id``,
and the fleet adds its own stream (``dispatch``, ``reject``,
``replica_down``, ``migrate``, ``replica_drain``/``replica_join``/
``weight_swap``, ``fleet_summary``). :meth:`generate`'s summary holds
fleet totals (SLO attainment over all offered requests, goodput, p99
TTFT, **requests_lost** — the zero-loss failover contract) plus a
per-replica breakdown.

CPU-faked replicas (in-process engines) keep all of it tier-1
testable: ``tests/test_serving_fleet.py`` and the ``fleet_kill_migrate``
/ ``fleet_drain_join`` legs of ``tools/serving_check.py --self`` (one
replica killed mid-run, requests-lost must be 0).
"""
from __future__ import annotations

import enum
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .. import telemetry
from ..resilience.chaos import ChaosError
from ..transformer import parallel_state
from ..resilience.watchdog import HangError
from .engine import ServingEngine
from .robustness import (
    RejectionCode,
    RejectionError,
    RejectionReason,
    RequestStatus,
    already_in_flight,
    is_terminal,
    recover_requests,
    request_expired,
)
from .scheduler import Request, SchedulerError

Pytree = Any


class ReplicaState(enum.Enum):
    """Router-facing replica lifecycle."""

    ACTIVE = "active"       # takes new admits
    DRAINING = "draining"   # finishes in-flight work, no new admits
    DEAD = "dead"           # engine died; requests migrated off


@dataclass
class Replica:
    """One fleet member: the engine plus its router state."""

    idx: int
    engine: ServingEngine
    state: ReplicaState = ReplicaState.ACTIVE
    deaths: int = 0
    swaps: int = 0

    @property
    def live(self) -> bool:
        return self.state is not ReplicaState.DEAD


@dataclass
class _Migrant:
    """A request pulled off a dead replica, waiting for placement."""

    req: Request
    from_replica: int
    t0: float          # fleet clock at migration (RetryPolicy deadline)
    attempts: int = 0  # placement boundaries burned so far
    last_attempt_step: int = -1  # one attempt per fleet boundary


class ReplicaFleet:
    """N CPU- or TPU-backed :class:`ServingEngine` replicas behind one
    deadline-aware router.

    ``engine_kw`` is forwarded to every replica's engine ctor
    (``n_slots``, ``num_pages``, ``admission``, ``degradation``,
    ``watchdog``, ...); each engine gets the shared ``clock`` and a
    ``TaggedRecorder(sink, replica_id=i)`` so its telemetry is
    attributable. ``chaos`` (a ``resilience.ServingChaos``) is both
    forwarded to the engines (poison/wedge/alloc faults, engine-step
    kills) and consulted per fleet boundary for
    :meth:`~apex_tpu.resilience.ServingChaos.kill_replica_at` replica
    kills.

    ``migration_retry`` (a :class:`~apex_tpu.resilience.RetryPolicy`)
    bounds migrant placement: one attempt per fleet boundary under the
    policy's ``attempts`` count and wall-clock ``deadline`` budget
    (only those pacing knobs apply — there is no exception to filter).
    ``None`` retries until the request's own deadline (or the trace's
    ``max_steps`` guard) gives out.

    ``health`` (a :class:`~apex_tpu.telemetry.alerts.HealthMonitor`)
    arms the fleet health plane: the monitor's metrics aggregator is
    fanned into the shared record stream, its SLO trackers are
    evaluated once per scheduling boundary (with the boundary's
    already-read clock value — zero new reads), and firing alerts
    drive the fleet's own actuators (degradation, replica restart,
    rolling-update abort) through the default
    :class:`~apex_tpu.telemetry.alerts.FleetResponder`.
    """

    def __init__(
        self,
        cfg,
        params: Pytree,
        *,
        n_replicas: int = 2,
        tp: int = 1,
        sink=None,
        clock: Optional[Callable[[], float]] = None,
        chaos=None,
        migration_retry=None,
        trace: bool = True,
        health=None,
        **engine_kw,
    ):
        if n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        self.cfg = cfg
        #: DP×TP topology: the fleet's data-parallel axis is its
        #: replica list (each replica an independent engine with its
        #: own pool and scheduler), the tensor axis lives INSIDE each
        #: engine — replica ``i`` shard_maps over TP device group ``i``
        #: (``parallel_state.tp_submesh(tp, replica=i)``), so
        #: ``n_replicas * tp`` chips serve with no cross-replica
        #: collective. The router/migration/rolling-update machinery
        #: is topology-blind: it only ever talks to engines.
        self.tp = int(tp)
        self.sink = sink if sink is not None else telemetry.NullRecorder()
        #: fleet health plane (telemetry.alerts.HealthMonitor): the
        #: monitor's aggregator is fanned INTO the record stream — every
        #: replica-tagged engine event and fleet lifecycle event feeds
        #: the metrics the SLO trackers evaluate — and its alert manager
        #: is evaluated once per scheduling boundary with the clock
        #: value the boundary already read (zero new clock reads).
        self.health = health
        if health is not None:
            self.sink = telemetry.MultiRecorder(
                self.sink, health.aggregator)
        self._clock = clock if clock is not None else time.perf_counter
        #: fleet-side tracing: the router/migration/rolling-update hops
        #: of every request's span tree (engines emit their own spans
        #: through the same shared sink, replica-tagged). Lifecycle
        #: spans reuse the latest clock value the fleet already read
        #: (``_t_last``) — tracing adds zero clock reads, so
        #: VirtualClock-denominated deadline budgets are untouched.
        self.tracer = (telemetry.Tracer(sink=self.sink, clock=self._clock)
                       if trace else None)
        self._t_last = 0.0
        self._chaos = chaos
        self.migration_retry = migration_retry
        self.replicas: List[Replica] = []
        for i in range(n_replicas):
            devs = (list(parallel_state.tp_submesh(
                self.tp, replica=i).devices.reshape(-1))
                if self.tp > 1 else None)
            eng = ServingEngine(
                cfg, params,
                sink=telemetry.TaggedRecorder(self.sink, replica_id=i,
                                              tp=self.tp),
                clock=self._clock, chaos=chaos, tp=self.tp,
                devices=devs, trace=trace, **engine_kw)
            self.replicas.append(Replica(idx=i, engine=eng))
        self._migrants: List[_Migrant] = []
        self._last_route: Dict[str, Any] = {}
        self._migrated_rids: set = set()
        self._migrated_from: Dict[int, int] = {}
        self._swap_plan: Optional[dict] = None
        # weights a rolling update could NOT deliver (replica dead or
        # already draining when its turn came) — applied when the
        # replica comes back (restart_replica / try_join), so a
        # revived replica never rejoins the router on stale weights
        self._missed_swaps: Dict[int, Pytree] = {}
        self.replica_deaths = 0
        self.migrated = 0
        self.migration_readmitted = 0
        self.steps_run = 0
        self._stalled_boundaries = 0
        self.last_stats: Dict[str, Any] = {}
        if health is not None and health.fleet_responder is None:
            # default actuator wiring: alert/response events land in
            # the same fan-in stream, so they reach the aggregator too
            health.attach_fleet(self, sink=self.sink)

    def _read_clock(self) -> float:
        """The fleet's only clock accessor: every read remembers its
        value so lifecycle spans (drain/join/swap/restart) can be
        stamped WITHOUT additional reads — VirtualClock sequences stay
        byte-identical with tracing on or off."""
        t = self._clock()
        self._t_last = t
        return t

    # -- router ------------------------------------------------------------
    def route(self, req: Request) -> Tuple[
            Optional[Replica], List[Tuple[int, RejectionReason]]]:
        """Pick the replica this request should go to: feasibility
        (every ACTIVE replica probed read-only) x load (lowest
        estimated wall-clock cost to first token wins; a replica with
        no measured step time yet is costed at the fleet's slowest
        known estimate — no free lunch for being new). Returns
        ``(replica, [])`` or ``(None, [(idx, reason), ...])`` with
        every candidate's refusal."""
        cands = [r for r in self.replicas
                 if r.state is ReplicaState.ACTIVE]
        probed = []
        refusals: List[Tuple[int, RejectionReason]] = []
        for rep in cands:
            reason, steps = rep.engine.probe(req)
            if reason is not None:
                refusals.append((rep.idx, reason))
            else:
                ctl = rep.engine.admission
                est = ctl.estimated_step_time_s if ctl is not None else 0.0
                probed.append((steps, est, rep))
        if not probed:
            self._last_route = {
                "refused": {str(i): r.code.value for i, r in refusals}}
            return None, refusals
        # cost model: steps-to-first-token x EWMA step time. Replicas
        # without an estimate yet borrow the slowest measured one
        # (pessimistic), falling back to raw steps when nobody has
        # measured anything (cold fleet = pure load balancing).
        default_est = max((e for _, e, _ in probed if e > 0), default=1.0)
        cost, _, rep = min(
            ((steps * (est if est > 0 else default_est), r.idx, r)
             for steps, est, r in probed),
            key=lambda t: (t[0], t[1]))
        # the decision record the "route" span carries: every probed
        # replica's cost-model inputs + every refusal, so a waterfall
        # shows WHY the router sent the request where it did
        self._last_route = {
            "costs": {str(r.idx): {
                "steps": steps,
                "est_step_s": round(est if est > 0 else default_est, 6),
                "cost": round(steps * (est if est > 0 else default_est),
                              6)} for steps, est, r in probed},
            "refused": {str(i): r.code.value for i, r in refusals},
        }
        return rep, refusals

    def try_submit(self, req: Request) -> Optional[RejectionReason]:
        """Route and admit one request; the fleet's non-raising front
        door. ``t_arrival`` is stamped HERE (once): deadline budgets
        span routing, migration, and every re-admission — the user has
        been waiting since first submit. When no replica is feasible
        the request is finalized ``REJECTED`` with the fleet-level
        ``NO_FEASIBLE_REPLICA`` reason naming each replica's refusal."""
        now = self._read_clock()
        migrating = any(m.req is req for m in self._migrants)
        if (req.status in (RequestStatus.QUEUED, RequestStatus.RUNNING)
                or migrating):
            # duplicate submission of in-flight work — queued/running
            # on a replica, OR sitting in the migration queue (status
            # PENDING but owned by the fleet): refuse WITHOUT
            # finalizing; admitting it twice would place one Request
            # on an engine AND leave a stale migrant behind (double
            # finalize / a migrant that can never place)
            reason = already_in_flight(
                req, where="awaiting migration" if migrating else None)
            self.sink.record({"event": "reject", "rid": req.rid,
                              **reason.as_record()})
            return reason
        if is_terminal(req.status):
            # resubmitting a terminal request (e.g. after a fleet-level
            # rejection) starts a fresh lifecycle attempt; t_arrival is
            # stamped only once, so budgets span resubmits
            req.status = RequestStatus.PENDING
            req.end_reason = None
        if req.t_arrival is None:
            req.t_arrival = now
        ctx = None
        if self.tracer is not None:
            # the fleet stamps the trace identity; the engine's own
            # begin_request_trace is then a no-op (idempotent), so the
            # router hop and the engine hops share ONE tree
            ctx = self.tracer.begin_request_trace(req)
            telemetry.attr_init(req, now)
            telemetry.attr_account(req, now, "queue_wait")
        rep, refusals = self.route(req)
        if self.tracer is not None and ctx is not None:
            self.tracer.emit(
                "route", ctx.trace_id, now, now, parent_id=ctx.span_id,
                chosen=(rep.idx if rep is not None else None),
                **self._last_route)
        if rep is None:
            reason = self._no_replica_reason(req, refusals)
            self.sink.record({"event": "reject", "rid": req.rid,
                              **reason.as_record()})
            self._finalize(req, RequestStatus.REJECTED,
                           reason.code.value, now=now)
            return reason
        reason = rep.engine.try_submit(req)
        if reason is None:
            req.replica_id = rep.idx
            self.sink.record({"event": "dispatch", "rid": req.rid,
                              "replica_id": rep.idx,
                              "queue_depth":
                              len(rep.engine.scheduler.waiting)})
        return reason

    def submit(self, req: Request) -> None:
        """The raising intake: refusal raises
        :class:`~.robustness.RejectionError`."""
        reason = self.try_submit(req)
        if reason is not None:
            raise RejectionError(reason)

    @staticmethod
    def _no_replica_reason(req: Request,
                           refusals: Sequence[Tuple[int, RejectionReason]]
                           ) -> RejectionReason:
        per = {str(i): r.code.value for i, r in refusals}
        return RejectionReason(
            RejectionCode.NO_FEASIBLE_REPLICA,
            f"request {req.rid}: no feasible replica "
            f"({len(per) or 'zero'} candidates refused)"
            if per else
            f"request {req.rid}: no feasible replica (none active)",
            {"replicas": per})

    # -- lifecycle (fleet-held requests) -----------------------------------
    def _finalize(self, req: Request, status: RequestStatus,
                  reason: str, *, now: Optional[float] = None,
                  term: str = "queue_wait") -> None:
        """Finalize a request the fleet holds (fleet-rejected, or a
        migrant that could not be placed) — same double-finalize guard
        and ``request_end`` schema as the engine's (no ``t_done``
        stamp: the fleet never finalizes COMPLETED, the only status
        the engine timestamps). ``now`` is the clock value the caller
        already read (never re-read here); ``term`` names the
        attribution bucket for the final interval — "migration" on the
        migrant paths, "queue_wait" on router rejects."""
        if is_terminal(req.status):
            raise AssertionError(
                f"request {req.rid} finalized twice "
                f"({req.status.name} -> {status.name})")
        req.status = status
        req.end_reason = reason
        rec = {
            "event": "request_end", "rid": req.rid,
            "status": status.value, "reason": reason,
            "generated": len(req.out_tokens),
            "preemptions": req.preemptions,
            "restarts": req.restarts,
        }
        # health-plane enrichment, mirroring the engine's: latency from
        # stamps already taken, SLO verdict from static budgets —
        # zero new clock reads
        if req.t_arrival is not None:
            t_end = req.t_done if req.t_done is not None else now
            if req.t_first_token is not None:
                rec["ttft_ms"] = round(
                    1e3 * (req.t_first_token - req.t_arrival), 6)
            if t_end is not None:
                rec["latency_ms"] = round(
                    1e3 * (t_end - req.t_arrival), 6)
        rec["slo_ok"] = ServingEngine._within_budget(req)
        if req.labels:
            rec["labels"] = dict(req.labels)
        self.sink.record(rec)
        if self.tracer is not None:
            t = now if now is not None else getattr(
                req, "_t_attr", req.t_arrival)
            if t is None:
                t = self._t_last
            telemetry.spans.emit_terminal_span(
                self.tracer, req, status.value, reason, now=t,
                term=term, slo_ok=ServingEngine._within_budget(req))

    # -- drain / join ------------------------------------------------------
    def drain(self, replica_id: int) -> None:
        """Stop new admits to a replica; it keeps stepping until
        everything already admitted (slots AND its waiting queue)
        finishes. The first half of a zero-drop weight swap."""
        rep = self.replicas[replica_id]
        if rep.state is not ReplicaState.ACTIVE:
            raise SchedulerError(
                f"replica {replica_id} is {rep.state.value}, not active")
        rep.state = ReplicaState.DRAINING
        self.sink.record({"event": "replica_drain",
                          "replica_id": replica_id,
                          "in_flight": rep.engine.scheduler.n_active,
                          "queued":
                          len(rep.engine.scheduler.waiting)})
        if self.tracer is not None:
            # lifecycle spans are stamped with the latest clock value
            # the fleet already read (zero extra reads); one shared
            # trace holds the whole drain -> swap -> join story
            self.tracer.emit(
                "replica_drain", "fleet-lifecycle", self._t_last,
                self._t_last, replica_id=replica_id,
                in_flight=rep.engine.scheduler.n_active,
                queued=len(rep.engine.scheduler.waiting))
        rep._drain_t0 = self._t_last

    def try_join(self, replica_id: int,
                 params: Optional[Pytree] = None) -> bool:
        """Rejoin a drained replica — once idle. ``params`` swaps the
        weights first (through ``cast_params_for_inference``, the same
        one-shot cast the ctor runs); with ``params=None`` a swap a
        rolling update could not deliver to this replica (it was
        draining/dead when its turn came) is applied instead, so a
        rejoin never reintroduces stale weights. Returns False while
        in-flight work remains."""
        rep = self.replicas[replica_id]
        if rep.state is not ReplicaState.DRAINING:
            raise SchedulerError(
                f"replica {replica_id} is {rep.state.value}, "
                "not draining")
        if not rep.engine.scheduler.idle:
            return False
        pending = self._missed_swaps.pop(replica_id, None)
        if params is None:
            params = pending
        if params is not None:
            # swap_params casts through the inference tables AND
            # flushes the replica's prefix cache — K/V cached under the
            # old weights must not survive a rolling update
            rep.engine.swap_params(params)
            rep.swaps += 1
            self.sink.record({"event": "weight_swap",
                              "replica_id": replica_id,
                              "swaps": rep.swaps})
        rep.state = ReplicaState.ACTIVE
        self.sink.record({"event": "replica_join",
                          "replica_id": replica_id})
        if self.tracer is not None:
            # drain -> join as ONE span: t_start is the clock value
            # remembered at drain(), t_end the latest fleet read
            self.tracer.emit(
                "replica_join", "fleet-lifecycle",
                getattr(rep, "_drain_t0", self._t_last), self._t_last,
                replica_id=replica_id, swapped=params is not None,
                swaps=rep.swaps)
        return True

    def schedule_rolling_update(self, params: Pytree) -> None:
        """Arm a rolling weight update: one replica at a time is
        drained, swapped to ``params``, and rejoined while the rest
        carry the traffic. Consumed boundary-by-boundary inside
        :meth:`generate` (or by manual :meth:`run_boundary` callers);
        :meth:`generate` does not return until the wave completes."""
        if self._swap_plan is not None:
            raise SchedulerError("a rolling update is already scheduled")
        self._swap_plan = {
            "params": params,
            "queue": [r.idx for r in self.replicas if r.live],
            "current": None,
            "requeued": set(),   # manual-rejoin interference, once each
        }
        # replicas ALREADY dead cannot take the wave — remember their
        # swap so restart_replica revives them on the new weights, not
        # the ones they died with
        for r in self.replicas:
            if not r.live:
                self._missed_swaps[r.idx] = params

    @property
    def rolling_update_done(self) -> bool:
        return self._swap_plan is None

    def _advance_swap_plan(self) -> None:
        plan = self._swap_plan
        if plan is None:
            return
        cur = plan["current"]
        if cur is not None:
            rep = self.replicas[cur]
            if rep.state is ReplicaState.DEAD:
                # died mid-drain: move on, but REMEMBER the swap it
                # missed — restart_replica must not bring it back on
                # stale weights after the update declares done
                self._missed_swaps[cur] = plan["params"]
                plan["current"] = None
            elif rep.state is ReplicaState.DRAINING:
                if not self.try_join(cur, params=plan["params"]):
                    return               # still draining
                plan["current"] = None
            else:
                # manually rejoined mid-drain (try_join with no params
                # consumed no missed-swap entry — none existed yet):
                # the swap was NOT delivered. Re-queue it once so the
                # wave drains it again; on repeated interference fall
                # back to a missed-swap entry (delivered at the next
                # drain/join or restart) rather than looping forever.
                if cur not in plan["requeued"]:
                    plan["requeued"].add(cur)
                    plan["queue"].append(cur)
                else:
                    self._missed_swaps[cur] = plan["params"]
                plan["current"] = None
        while plan["current"] is None and plan["queue"]:
            idx = plan["queue"].pop(0)
            rep = self.replicas[idx]
            if rep.state is not ReplicaState.ACTIVE:
                # dead or manually draining when its turn came: skip,
                # but carry the swap forward to its rejoin/restart
                self._missed_swaps[idx] = plan["params"]
                continue
            self.drain(idx)
            plan["current"] = idx
        if plan["current"] is None and not plan["queue"]:
            self._swap_plan = None
            self.sink.record({"event": "rolling_update_done",
                              "swapped":
                              [r.idx for r in self.replicas
                               if r.swaps > 0]})
            if self.tracer is not None:
                self.tracer.emit(
                    "rolling_update_done", "fleet-lifecycle",
                    self._t_last, self._t_last,
                    swapped=[r.idx for r in self.replicas
                             if r.swaps > 0],
                    missed=sorted(self._missed_swaps))

    def abort_rolling_update(self) -> int:
        """Cancel an in-flight rolling update mid-wave — the health
        plane's fast-burn actuator (a fleet on fire must stop churning
        capacity through drain cycles). The replica currently draining
        for the wave rejoins on its OLD weights once idle (a normal
        :meth:`try_join` with no params — the plan's swap is dropped,
        not remembered), queued replicas never drain, and missed-swap
        entries this plan created are discarded so a later restart does
        not resurrect the aborted weights. Returns the number of live
        replicas the wave had NOT yet swapped. No-op (returns 0) when
        no update is scheduled."""
        plan = self._swap_plan
        if plan is None:
            return 0
        remaining = len(plan["queue"])
        cur = plan["current"]
        if cur is not None:
            remaining += 1
            rep = self.replicas[cur]
            if rep.state is ReplicaState.DRAINING:
                # rejoin on old weights, now if idle or via the caller's
                # next try_join; either way the swap is cancelled
                self._missed_swaps.pop(cur, None)
                if rep.engine.scheduler.idle:
                    self.try_join(cur)
        self._swap_plan = None
        # drop the missed-swap IOUs this plan wrote for dead/draining
        # replicas — identity is the plan's params object
        for idx in [i for i, p in self._missed_swaps.items()
                    if p is plan["params"]]:
            del self._missed_swaps[idx]
        self.sink.record({"event": "rolling_update_aborted",
                          "remaining": remaining,
                          "current": cur})
        if self.tracer is not None:
            self.tracer.emit(
                "rolling_update_aborted", "fleet-lifecycle",
                self._t_last, self._t_last,
                remaining=remaining, current=cur)
        return remaining

    # -- replica failure + migration ---------------------------------------
    def _on_replica_death(self, rep: Replica, err: BaseException,
                          fleet_step: int) -> None:
        """Mark the replica dead and pull its in-flight requests onto
        the migration queue, riding the replay carrier (generated
        tokens kept — re-admission folds them into the replay prompt,
        so survivors decode token-identically)."""
        rep.state = ReplicaState.DEAD
        rep.deaths += 1
        self.replica_deaths += 1
        survivors = recover_requests(rep.engine)
        self.sink.record({
            "event": "replica_down", "replica_id": rep.idx,
            "step": fleet_step,
            "error": f"{type(err).__name__}: {err}",
            "in_flight": len(survivors),
            "rids": [r.rid for r in survivors],
        })
        now = self._read_clock()
        rep._death_t = now
        if self.tracer is not None:
            # the dead engine's flight ring IS the black box: replay it
            # into the shared sink (tagged with the replica id by the
            # engine's own TaggedRecorder tags) before the engine is
            # abandoned, stacks-style post-mortem for replica chaos
            dead_tracer = getattr(rep.engine, "tracer", None)
            if dead_tracer is not None:
                dead_tracer.dump_blackbox(
                    reason="replica_down", sink=self.sink,
                    replica_id=rep.idx, step=fleet_step,
                    error=f"{type(err).__name__}: {err}")
        for r in survivors:
            self._migrants.append(
                _Migrant(req=r, from_replica=rep.idx, t0=now))
            self._migrated_rids.add(r.rid)
            if self.tracer is not None:
                # from the death instant the request is in migration
                # limbo: account the tail of its on-replica interval
                # now, and tell the NEXT engine's try_submit (which
                # accounts up to its own admit instant) the same
                telemetry.attr_account(r, now, "migration")
                r._migrating = True
            self.sink.record({"event": "migrate", "rid": r.rid,
                              "from_replica": rep.idx,
                              "generated": len(r.out_tokens)})
        self.migrated += len(survivors)
        self._migrated_from[rep.idx] = (
            self._migrated_from.get(rep.idx, 0) + len(survivors))

    def restart_replica(self, replica_id: int) -> None:
        """Bring a DEAD replica back: a fresh engine from the dead
        one's captured ctor kwargs (same geometry/policies — the fleet
        twin of ``ServingEngine.recover_from``; its requests already
        migrated at death, so nothing is replayed here). A weight swap
        a rolling update could not deliver while the replica was dead
        is applied now — a restart never rejoins the router on the
        pre-update weights."""
        rep = self.replicas[replica_id]
        if rep.state is not ReplicaState.DEAD:
            raise SchedulerError(
                f"replica {replica_id} is {rep.state.value}, not dead")
        old = rep.engine
        pending = self._missed_swaps.pop(replica_id, None)
        rep.engine = ServingEngine.rebuild_like(old, params=pending)
        if pending is not None:
            rep.swaps += 1
            self.sink.record({"event": "weight_swap",
                              "replica_id": replica_id,
                              "swaps": rep.swaps})
        rep.state = ReplicaState.ACTIVE
        self.sink.record({"event": "replica_restart",
                          "replica_id": replica_id,
                          "dead_steps_run": old.steps_run})
        if self.tracer is not None:
            self.tracer.emit(
                "replica_restart", "fleet-lifecycle",
                getattr(rep, "_death_t", self._t_last), self._t_last,
                replica_id=replica_id, dead_steps_run=old.steps_run,
                swapped=pending is not None)

    def _place_migrants(self, now: float) -> None:
        """One placement attempt per waiting migrant: expired requests
        are finalized ``TIMED_OUT`` (original deadlines hold across
        migration), placeable ones re-enter a survivor's admission
        control, the rest wait for the next boundary under the
        ``migration_retry`` policy's attempts/deadline budget."""
        if not self._migrants:
            return
        pol = self.migration_retry
        any_live = any(r.live for r in self.replicas)
        still: List[_Migrant] = []
        for m in self._migrants:
            req = m.req
            if self.tracer is not None:
                # still in limbo at this boundary: keep the ledger's
                # cursor current so however the migrant ends (placed,
                # expired, exhausted) the wait is already attributed
                telemetry.attr_account(req, now, "migration")
            why = request_expired(req, now)
            if why is not None:
                self._finalize(req, RequestStatus.TIMED_OUT, why,
                               now=now, term="migration")
                continue
            if not any_live:
                self._finalize(req, RequestStatus.FAILED,
                               "no_live_replica", now=now,
                               term="migration")
                continue
            rep, refusals = self.route(req)
            if rep is not None:
                reason = rep.engine.try_submit(req)
                if reason is None:
                    req.replica_id = rep.idx
                    self.migration_readmitted += 1
                    self.sink.record({
                        "event": "migrate_admitted", "rid": req.rid,
                        "from_replica": m.from_replica,
                        "replica_id": rep.idx,
                        "attempts": m.attempts + 1})
                    ctx = getattr(req, "trace", None)
                    if self.tracer is not None and ctx is not None:
                        self.tracer.emit(
                            "migration", ctx.trace_id, m.t0, now,
                            parent_id=ctx.span_id,
                            from_replica=m.from_replica,
                            to_replica=rep.idx,
                            attempts=m.attempts + 1,
                            generated=len(req.out_tokens))
                # an engine-side refusal finalized the request REJECTED
                # (shed-by-admission is a terminal outcome, not a retry
                # loop — the probe said feasible, so this only happens
                # if state moved between probe and submit)
                continue
            # one attempt per fleet boundary, however many placement
            # passes run in it (generate() places before arrivals AND
            # run_boundary places again)
            if m.last_attempt_step != self.steps_run:
                m.attempts += 1
                m.last_attempt_step = self.steps_run
            exhausted = pol is not None and (
                m.attempts >= pol.attempts
                or (pol.deadline is not None
                    and now - m.t0 >= pol.deadline))
            if exhausted:
                reason = self._no_replica_reason(req, refusals)
                self.sink.record({
                    "event": "migrate_exhausted", "rid": req.rid,
                    "attempts": m.attempts, **reason.as_record()})
                self._finalize(req, RequestStatus.REJECTED,
                               "migration_exhausted", now=now,
                               term="migration")
                continue
            still.append(m)
        self._migrants = still

    # -- the loop ----------------------------------------------------------
    @property
    def busy(self) -> bool:
        """Work anywhere: a non-idle live engine, a waiting migrant,
        or an unfinished rolling update."""
        return (bool(self._migrants) or self._swap_plan is not None
                or any(r.live and not r.engine.scheduler.idle
                       for r in self.replicas))

    def run_boundary(self) -> None:
        """One fleet scheduling boundary: advance any rolling update,
        attempt migrant placement, then step every live non-idle
        replica — catching replica death (``ChaosError`` /
        ``HangError``) and migrating its in-flight work."""
        step = self.steps_run
        self._advance_swap_plan()
        self._place_migrants(self._read_clock())
        # stall guard: migrants waiting, no ACTIVE replica to take
        # them, no swap plan that would auto-join one, and every live
        # engine idle — nothing can change without outside action, so
        # an unbudgeted migrant set would spin generate() forever.
        # After a few such boundaries, fail the migrants TYPED instead
        # of hanging (a DRAINING replica the operator joins in time
        # resets the counter via the placement above).
        if (self._migrants and self._swap_plan is None
                and not any(r.state is ReplicaState.ACTIVE
                            for r in self.replicas)
                and all(r.engine.scheduler.idle
                        for r in self.replicas if r.live)):
            self._stalled_boundaries += 1
            if self._stalled_boundaries >= 8:
                now = self._read_clock()
                for m in self._migrants:
                    self.sink.record({
                        "event": "migrate_exhausted", "rid": m.req.rid,
                        "attempts": m.attempts,
                        "code": "no_active_replica"})
                    self._finalize(m.req, RequestStatus.FAILED,
                                   "no_active_replica", now=now,
                                   term="migration")
                self._migrants = []
        else:
            self._stalled_boundaries = 0
        for rep in self.replicas:
            if not rep.live:
                continue
            if self._chaos is not None:
                try:
                    self._chaos.maybe_kill_replica(rep.idx, step)
                except ChaosError as e:
                    self._on_replica_death(rep, e, step)
                    continue
            if rep.engine.scheduler.idle:
                continue
            try:
                rep.engine.run_step()
            except (ChaosError, HangError) as e:
                self._on_replica_death(rep, e, step)
        self.steps_run += 1
        if self.health is not None:
            # evaluate SLOs/alerts at the clock value this boundary
            # already read (_place_migrants / death handling refreshed
            # _t_last) — the health plane adds zero clock reads
            self.health.on_boundary(self._t_last, step=self.steps_run)

    def generate(self, requests: Sequence[Request] = (),
                 max_steps: Optional[int] = None
                 ) -> Dict[int, List[int]]:
        """Drive a request trace to completion across the fleet.

        Requests are submitted at their ``arrival_step`` (fleet steps)
        through the router; every request ends in exactly one terminal
        state — on an engine, or fleet-finalized (no feasible replica,
        migration exhausted/expired). Returns ``{rid: tokens}`` and
        fills :attr:`last_stats` with fleet totals + the per-replica
        breakdown."""
        for rep in self.replicas:
            rep.engine.begin_run()
        pending = sorted(requests, key=lambda r: (r.arrival_step, r.rid))
        all_reqs = list(pending)
        t_start = time.perf_counter()
        start_step = self.steps_run
        # counter snapshot: the summary reports THIS run's deltas (the
        # engines reset their accums above; fleet lifetime counters
        # must not smear a previous run's deaths into this summary)
        base = {
            "migrated": self.migrated,
            "migration_readmitted": self.migration_readmitted,
            "replica_deaths": self.replica_deaths,
            "migrated_from": dict(self._migrated_from),
            "rep_deaths": {r.idx: r.deaths for r in self.replicas},
            "rep_swaps": {r.idx: r.swaps for r in self.replicas},
        }
        while True:
            step = self.steps_run - start_step
            # seniority: migrants (strictly older t_arrival) compete
            # for admission capacity BEFORE this boundary's fresh
            # arrivals — a dead replica's in-flight work must not lose
            # its queue slot to younger requests and burn placement
            # retries (run_boundary's placement pass is then a no-op
            # for anything placed here; attempts count once per
            # boundary either way)
            if (self._migrants and pending
                    and pending[0].arrival_step <= step):
                self._place_migrants(self._read_clock())
            while pending and pending[0].arrival_step <= step:
                self.try_submit(pending.pop(0))
            if not pending and not self.busy:
                break
            if max_steps is not None and step >= max_steps:
                raise SchedulerError(
                    f"fleet generate exceeded max_steps={max_steps} "
                    f"with {len(pending)} pending, "
                    f"{len(self._migrants)} migrants")
            self.run_boundary()
        wall = time.perf_counter() - t_start
        self.last_stats = self._summarize(
            all_reqs, wall, base=base,
            run_steps=self.steps_run - start_step)
        self.sink.record({"event": "fleet_summary", **self.last_stats})
        return {r.rid: list(r.out_tokens) for r in all_reqs}

    # -- accounting --------------------------------------------------------
    def check_invariants(self) -> None:
        """Every live replica's scheduler invariants (page accounting,
        lifecycle/occupancy coherence). Dead replicas are exempt —
        migration pulls their requests without releasing the dead
        allocator's pages, exactly like a crashed process's memory."""
        for rep in self.replicas:
            if rep.live:
                rep.engine.scheduler.check_invariants()

    def page_leaks(self) -> int:
        """Allocator pages still held across live replicas (must be 0
        after a drained trace)."""
        return sum(rep.engine.scheduler.allocator.used_count
                   for rep in self.replicas if rep.live)

    def _summarize(self, reqs: Sequence[Request], wall_s: float, *,
                   base: Optional[Dict[str, Any]] = None,
                   run_steps: Optional[int] = None) -> Dict[str, Any]:
        base = base or {"migrated": 0, "migration_readmitted": 0,
                        "replica_deaths": 0, "migrated_from": {},
                        "rep_deaths": {}, "rep_swaps": {}}
        base_from = base["migrated_from"]
        completed = [r for r in reqs
                     if r.status is RequestStatus.COMPLETED]
        by_status = {
            s.value: sum(r.status is s for r in reqs)
            for s in (RequestStatus.COMPLETED, RequestStatus.REJECTED,
                      RequestStatus.TIMED_OUT, RequestStatus.FAILED,
                      RequestStatus.CANCELLED)}
        non_terminal = [r for r in reqs if not is_terminal(r.status)]
        # the zero-loss failover contract: a request migrated off a
        # dead replica that did not COMPLETE is lost, as is anything
        # left non-terminal — this is the number the replica-kill
        # chaos legs pin at 0
        lost = {r.rid for r in non_terminal} | {
            r.rid for r in reqs
            if r.rid in self._migrated_rids
            and r.status is not RequestStatus.COMPLETED}
        total_tokens = sum(len(r.out_tokens) for r in reqs)
        slo = [r for r in completed
               if ServingEngine._within_budget(r)]
        goodput_tokens = sum(len(r.out_tokens) for r in slo)
        lat_ms = [(r.t_done - r.t_arrival) * 1e3 for r in completed
                  if r.t_done is not None and r.t_arrival is not None]
        ttft_ms = [(r.t_first_token - r.t_arrival) * 1e3
                   for r in completed
                   if r.t_first_token is not None
                   and r.t_arrival is not None]
        per_replica = {}
        fleet_hits = fleet_misses = fleet_hit_tokens = 0
        fleet_drafted = fleet_accepted = 0
        fleet_decode_tokens = fleet_decode_slot_steps = 0
        for rep in self.replicas:
            a = rep.engine.run_accum
            served = [r for r in reqs if r.replica_id == rep.idx]
            cache_stats = rep.engine.prefix_cache_run_stats()
            if cache_stats is not None:
                fleet_hits += cache_stats["hits"]
                fleet_misses += cache_stats["misses"]
                fleet_hit_tokens += cache_stats["hit_tokens"]
            fleet_drafted += a.get("drafted_tokens", 0)
            fleet_accepted += a.get("accepted_tokens", 0)
            fleet_decode_tokens += a.get("decode_tokens", 0)
            fleet_decode_slot_steps += a.get("decode_slot_steps", 0)
            per_replica[str(rep.idx)] = {
                "state": rep.state.value,
                "steps": a["steps"],
                "prefix_cache": cache_stats,
                "drafted_tokens": a.get("drafted_tokens", 0),
                "accepted_tokens": a.get("accepted_tokens", 0),
                # per-run deltas, like the fleet-level counters — a
                # warm fleet's second trace must not report the first
                # trace's deaths/swaps
                "deaths": (rep.deaths
                           - base["rep_deaths"].get(rep.idx, 0)),
                "weight_swaps": (rep.swaps
                                 - base["rep_swaps"].get(rep.idx, 0)),
                "served": len(served),
                "completed": sum(r.status is RequestStatus.COMPLETED
                                 for r in served),
                "migrated_out": (self._migrated_from.get(rep.idx, 0)
                                 - base_from.get(rep.idx, 0)),
                "occupancy": round(
                    a["active_slot_steps"]
                    / (a["steps"] * rep.engine.n_slots), 4)
                if a["steps"] else None,
                "page_leaks": (
                    rep.engine.scheduler.allocator.used_count
                    if rep.live else None),
            }
        return {
            "n_replicas": len(self.replicas),
            # DP×TP geometry: total chips = n_replicas * tp; the
            # per-shard pool footprint and the per-program collective
            # budget come from any live engine (all replicas share one
            # geometry by construction)
            "tp": self.tp,
            "total_chips": len(self.replicas) * self.tp,
            "kv_bytes_per_shard": next(
                (r.engine.spec_local.cache_bytes()
                 for r in self.replicas if r.live), None),
            "psum_per_program": next(
                (r.engine.program_psum_counts()
                 for r in self.replicas if r.live), None),
            "comm_volume": next(
                (r.engine.program_comm_volume()
                 for r in self.replicas if r.live), None),
            "n_requests": len(reqs),
            "completed": len(completed),
            "by_status": by_status,
            "requests_lost": len(lost),
            "migrated": self.migrated - base["migrated"],
            "migration_readmitted": (self.migration_readmitted
                                     - base["migration_readmitted"]),
            "replica_deaths": (self.replica_deaths
                               - base["replica_deaths"]),
            "preemptions": sum(r.preemptions for r in reqs),
            "restarts": sum(r.restarts for r in reqs),
            "steps": (run_steps if run_steps is not None
                      else self.steps_run),
            "wall_s": round(wall_s, 4),
            "generated_tokens": total_tokens,
            "tokens_per_sec": round(total_tokens / wall_s, 2)
            if wall_s > 0 else None,
            # the headline number: SLO attainment over ALL offered
            # requests — fleet-rejected / migrated-and-lost / shed
            # work counts against it, that is the point of a fleet
            "slo_attained": len(slo),
            "slo_attainment": round(len(slo) / len(reqs), 4)
            if reqs else None,
            "goodput_tokens": goodput_tokens,
            "goodput_tokens_per_sec": round(goodput_tokens / wall_s, 2)
            if wall_s > 0 else None,
            "latency_ms": telemetry.percentiles(lat_ms),
            "ttft_ms": telemetry.percentiles(ttft_ms),
            # fleet-wide prefix-cache view (per-REPLICA caches — a hit
            # only ever matches pages in the replica's own pool; the
            # router's post-hit cost estimate is what concentrates
            # shared-prefix traffic where its pages already live)
            "prefix_hits": fleet_hits,
            "prefix_hit_rate": (
                round(fleet_hits / (fleet_hits + fleet_misses), 4)
                if (fleet_hits + fleet_misses) else None),
            "prefix_hit_tokens": fleet_hit_tokens,
            # fleet-wide speculative-decoding view (per-replica engines
            # draft/verify independently; the router keeps billing one
            # token per slot-step, so speculation only ever ADDS slack
            # to its feasibility estimates)
            "drafted_tokens": fleet_drafted,
            "accepted_tokens": fleet_accepted,
            "spec_accept_rate": (
                round(fleet_accepted / fleet_drafted, 4)
                if fleet_drafted else None),
            "decode_tokens_per_step": (
                round(fleet_decode_tokens / fleet_decode_slot_steps, 4)
                if fleet_decode_slot_steps else None),
            # fleet-level latency attribution: the same exact-sum
            # ledger the engines fill, folded over every OFFERED
            # request (migration limbo shows up as its own term here —
            # a single engine never sees it)
            "attribution": telemetry.attribution_summary(
                reqs, violators=[
                    r for r in reqs
                    if not ServingEngine._within_budget(r)]),
            "per_replica": per_replica,
        }
