"""Real-process serving fleet: a router supervising worker subprocesses.

:class:`ReplicaFleet` (``serving.fleet``) proves the zero-loss routing
contracts against in-process replica objects — fast, deterministic, the
tier-1 default. This module is the same router discipline against
replicas that can actually DIE: each replica is a
:mod:`~apex_tpu.serving.worker` subprocess (one ``ServingEngine``, a
per-step :class:`~apex_tpu.resilience.liveness.Heartbeat` file, framed
RPC over pipes — :mod:`~apex_tpu.serving.transport`), and the
:class:`FleetSupervisor` is the PR-15 elastic
:class:`~apex_tpu.resilience.elastic.Supervisor`'s serving twin:

- **death** is an exit code / pipe EOF; **hang** is heartbeat
  staleness behind an unresponsive RPC; either way the supervisor
  SIGKILLs the replica (no graceful anything — a preempted real host
  gets no goodbye), restarts it at ``incarnation+1``, and re-routes
  its in-flight requests over the SAME recompute-replay migration
  carrier the in-process fleet uses: generated tokens are kept, the
  replay prompt is ``prompt + out_tokens``, budgets are re-based to
  the REMAINING wall-clock so the original deadline is honored —
  ``requests_lost == 0`` and migrant tokens byte-identical to an
  undisturbed run;
- **at-most-once stepping**: a ``step`` RPC that fails is never
  blindly re-sent (the worker may have executed it before the reply
  was lost) — the failure is an incident, and replay-from-reported
  -tokens re-derives whatever the lost reply carried. Every OTHER
  router→worker RPC (probe/submit/stats/shutdown) routes through
  :data:`~apex_tpu.resilience.retry.TRANSPORT_POLICY`, so a worker
  mid-restart reads as one slow RPC, not an exception;
- **corpse hygiene**: respawn first sweeps beat/staging files whose
  writer pid is dead (:func:`~apex_tpu.resilience.liveness.
  sweep_stale`), so a new incarnation can never read its predecessor's
  heartbeat as fresh — and NEVER touches a live sibling's files;
- **MTTR** is measured detect → restarted incarnation's ``ready``
  frame, per incident (:class:`~apex_tpu.resilience.elastic.Incident`
  records, the elastic supervisor's schema).

Telemetry: each worker incarnation appends to its own
``<workdir>/replica-<i>.<incarnation>.jsonl`` through the
multi-process-safe ``JsonlRecorder`` (O_APPEND + single-write
records), tagged with ``replica_id``/``incarnation`` — a SIGKILLed
writer's torn tail stays the final line of its own file, which is the
tear ``read_jsonl`` tolerates; ``tools/fleet_status.py`` replays a
whole directory of them merged by ``t_wall``.

Scope honesty: process mode is OPT-IN (the in-process fleet stays the
default and byte-identical), and the engines inside the workers are
the same CPU-faked tiny models the tier-1 legs always used — what is
REAL here is the process boundary: SIGKILL, torn frames, corpse
heartbeats, restart, and the zero-loss accounting across them.
"""
from __future__ import annotations

import collections
import json
import os
import subprocess
import sys
import time
from typing import Any, Deque, Dict, List, Optional, Sequence

from ..resilience.elastic import Incident
from ..resilience.liveness import Heartbeat, live_beat, sweep_stale
from ..resilience.retry import TRANSPORT_POLICY, as_record, retry_call
from .robustness import RequestStatus, is_terminal
from .scheduler import Request
from .transport import (
    Channel,
    WorkerUnavailable,
    request_to_wire,
)

__all__ = ["FleetSupervisor"]


class _Worker:
    """Router-side record of one replica subprocess."""

    def __init__(self, idx: int):
        self.idx = idx
        self.incarnation = -1
        self.proc: Optional[subprocess.Popen] = None
        self.chan: Optional[Channel] = None
        self.hb_path = ""
        self.log_fh = None
        self.state = "down"      # down | ready | dead
        self.deaths = 0
        self.steps_done = 0      # this incarnation (first step compiles)
        self.platform: Optional[str] = None  # jax backend the worker reports

    @property
    def ready(self) -> bool:
        return self.state == "ready"


class FleetSupervisor:
    """Launch, drive, and keep alive ``n_replicas`` worker processes.

    ``model_spec`` is the JSON-safe spec
    :func:`~apex_tpu.serving.worker.model_from_spec` consumes (model
    geometry + ``"engine"`` kwargs) — the supervisor itself never
    touches params, exactly like the elastic supervisor never touches
    training state. ``chaos`` (a
    :class:`~apex_tpu.resilience.ServingChaos` carrying worker faults)
    arms incarnation 0 only: restarted workers relaunch unarmed.
    """

    def __init__(self, model_spec: dict, n_replicas: int = 2, *,
                 workdir: str,
                 chaos=None,
                 heartbeat_timeout_s: float = 2.0,
                 startup_timeout_s: float = 180.0,
                 rpc_timeout_s: float = 15.0,
                 max_restarts: int = 4,
                 dispatch_patience: int = 500,
                 sink=None,
                 rpc_policy=TRANSPORT_POLICY,
                 python: Optional[str] = None):
        self.model_spec = dict(model_spec)
        self.workdir = str(workdir)
        os.makedirs(self.workdir, exist_ok=True)
        self.chaos = chaos
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.startup_timeout_s = float(startup_timeout_s)
        self.rpc_timeout_s = float(rpc_timeout_s)
        self.max_restarts = int(max_restarts)
        self.dispatch_patience = int(dispatch_patience)
        self.sink = sink
        self._record = as_record(sink) or (lambda rec: None)
        self.rpc_policy = rpc_policy
        self.python = python or sys.executable
        self._workers = [_Worker(i) for i in range(int(n_replicas))]
        self.incidents: List[Incident] = []
        self.migrated = 0
        self._migrated_rids: set = set()
        self._torn_frames = 0
        self.steps_run = 0
        self.last_stats: Dict[str, Any] = {}
        # per-rid routing state (spans one generate() run)
        self._t_dispatch: Dict[int, float] = {}   # first dispatch time
        self._orig_budget: Dict[int, tuple] = {}  # (ttft_ms, lat_ms)
        self._hold: Dict[int, int] = {}           # all-reject patience

    # -- lifecycle ---------------------------------------------------------
    def launch(self) -> None:
        for w in self._workers:
            self._spawn(w)

    def _spawn(self, w: _Worker) -> None:
        w.incarnation += 1
        w.hb_path = os.path.join(self.workdir, f"hb-{w.idx}")
        # corpse-incarnation hygiene: dead writers' beat/staging files
        # go, live siblings' files stay (the PR-15 multi-writer rule)
        swept = sweep_stale(self.workdir, prefix="hb-")
        if swept:
            self._record({"event": "sweep_stale", "removed": swept})
        # one JSONL per INCARNATION: a SIGKILLed writer's torn tail
        # stays the FINAL line of its own file (read_jsonl tolerates
        # final tears, raises on mid-file ones — appending a new
        # incarnation onto the corpse's half-line would corrupt it)
        telem = os.path.join(
            self.workdir, f"replica-{w.idx}.{w.incarnation}.jsonl")
        spec = "" if (self.chaos is None or w.incarnation > 0) \
            else self.chaos.worker_spec(w.idx)
        argv = [self.python, "-m", "apex_tpu.serving.worker",
                "--replica", str(w.idx),
                "--incarnation", str(w.incarnation),
                "--heartbeat", w.hb_path,
                "--spec", json.dumps(self.model_spec),
                "--telemetry", telem]
        if spec:
            argv += ["--chaos", spec]
        if w.log_fh is not None:
            w.log_fh.close()
        w.log_fh = open(os.path.join(
            self.workdir, f"worker-{w.idx}.{w.incarnation}.log"), "w")
        env = dict(os.environ)
        # set, not defaulted: an exported TPU platform must not reach the
        # child (the worker pins itself to the CPU as well)
        env["JAX_PLATFORMS"] = "cpu"
        # the worker must draw the SAME init params as the router's
        # reference: mirror the parent's PRNG-impl config (the test
        # harness flips it in-process, where child env can't see it)
        try:
            import jax

            env["JAX_THREEFRY_PARTITIONABLE"] = (
                "1" if jax.config.jax_threefry_partitionable else "0")
        except Exception:
            pass
        w.proc = subprocess.Popen(argv, stdin=subprocess.PIPE,
                                  stdout=subprocess.PIPE,
                                  stderr=w.log_fh, env=env)
        w.chan = Channel(w.proc.stdin.fileno(), w.proc.stdout.fileno())
        w.steps_done = 0
        self._record({"event": "worker_launched", "replica": w.idx,
                      "incarnation": w.incarnation, "pid": w.proc.pid,
                      "chaos": spec})
        # startup rendezvous: the worker's unprompted ready frame
        try:
            hello = w.chan.recv(timeout=self.startup_timeout_s)
        except WorkerUnavailable as e:
            self._kill(w)
            raise RuntimeError(
                f"replica {w.idx} (incarnation {w.incarnation}) failed "
                f"startup rendezvous: {e}") from e
        if hello is None or hello.get("op") != "ready":
            self._kill(w)
            raise RuntimeError(
                f"replica {w.idx} (incarnation {w.incarnation}) sent "
                f"{hello!r} instead of ready")
        w.state = "ready"
        w.platform = hello.get("platform")
        self._record({"event": "worker_ready", "replica": w.idx,
                      "incarnation": w.incarnation,
                      "pid": hello.get("pid"),
                      "platform": w.platform})

    def _kill(self, w: _Worker) -> None:
        """SIGKILL, reap, and retire this incarnation's channel
        (banking its torn-frame count)."""
        if w.chan is not None:
            self._torn_frames += w.chan.torn_frames
            w.chan = None
        if w.proc is not None:
            try:
                w.proc.kill()
            except OSError:
                pass
            try:
                w.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        w.state = "down"

    def close(self) -> None:
        """Shut the fleet down: polite shutdown RPC, SIGKILL on any
        worker that does not comply."""
        for w in self._workers:
            if w.ready and w.chan is not None:
                try:
                    w.chan.rpc({"op": "shutdown"}, timeout=10.0)
                    w.proc.wait(timeout=10)
                except (OSError, subprocess.TimeoutExpired,
                        RuntimeError):
                    pass
            self._kill(w)
            if w.log_fh is not None:
                w.log_fh.close()
                w.log_fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- RPC ---------------------------------------------------------------
    def _rpc_once(self, w: _Worker, msg: dict,
                  timeout: Optional[float]) -> dict:
        if not w.ready or w.chan is None:
            raise WorkerUnavailable(f"replica {w.idx} is {w.state}")
        return w.chan.rpc(msg, timeout=timeout)

    def _rpc(self, w: _Worker, msg: dict,
             timeout: Optional[float] = None) -> dict:
        """The retried router->worker call (probe/submit/stats): a
        worker restart mid-call reads as one slow RPC under
        ``rpc_policy`` (:data:`TRANSPORT_POLICY` by default). NOT used
        for ``step`` — stepping is at-most-once (see module doc)."""
        return retry_call(
            lambda: self._rpc_once(w, msg,
                                   timeout or self.rpc_timeout_s),
            policy=self.rpc_policy,
            tag=f"replica{w.idx}:{msg.get('op')}", sink=self.sink)

    # -- failure handling --------------------------------------------------
    def _classify(self, w: _Worker, err: BaseException) -> str:
        if w.proc is not None:
            # pipe EOF can land a beat before the child is reapable
            # (do_exit closes fds before exit_notify) — give the
            # corpse a moment, or a self-SIGKILL reads as a timeout
            try:
                w.proc.wait(timeout=0.5)
                return "worker_death"
            except subprocess.TimeoutExpired:
                pass
        beat = live_beat(w.hb_path)
        age = Heartbeat.age_s(w.hb_path)
        if beat is None or age is None or age > self.heartbeat_timeout_s:
            return "worker_hang"
        return "worker_timeout"  # alive + beating, reply lost

    def _incident(self, w: _Worker, err: BaseException, step: int,
                  reqs: Sequence[Request],
                  pending: Deque[Request]) -> None:
        t_detect = time.perf_counter()
        kind = self._classify(w, err)
        inc = Incident(kind=kind, host=w.idx,
                       incarnation=w.incarnation,
                       detail=f"step {step}: {type(err).__name__}: "
                              f"{err}",
                       t_detect=t_detect)
        self.incidents.append(inc)
        self._record({"event": kind, "replica": w.idx,
                      "incarnation": w.incarnation, "step": step,
                      "detail": inc.detail})
        self._kill(w)
        w.deaths += 1
        # migrate: every non-terminal mirror assigned here re-enters
        # the dispatch queue on the recompute-replay carrier —
        # generated tokens KEPT, budgets re-based at re-dispatch
        migrants = [r for r in reqs
                    if r.replica_id == w.idx
                    and not is_terminal(r.status)]
        for r in migrants:
            r.status = RequestStatus.PENDING
            r.end_reason = None
            r.replica_id = None
            r.restarts += 1
            self._migrated_rids.add(r.rid)
            self._record({"event": "migrate", "rid": r.rid,
                          "from_replica": w.idx, "step": step,
                          "tokens_kept": len(r.out_tokens)})
        self.migrated += len(migrants)
        pending.extendleft(reversed(migrants))
        if w.deaths <= self.max_restarts:
            self._spawn(w)  # raises if the restart itself fails
            inc.recovery_s = time.perf_counter() - t_detect
            self._record({"event": "worker_restarted",
                          "replica": w.idx,
                          "incarnation": w.incarnation,
                          "mttr_s": round(inc.recovery_s, 3)})
        else:
            w.state = "dead"
            self._record({"event": "worker_abandoned",
                          "replica": w.idx, "deaths": w.deaths})

    # -- routing -----------------------------------------------------------
    def _wire(self, req: Request, now: float) -> dict:
        """Serialize with budgets re-based to REMAINING wall-clock:
        the worker's deadline clock starts at its own admission, but
        the user has been waiting since FIRST dispatch — a migrant
        must honor the original deadline, not get a fresh one."""
        wire = request_to_wire(req)
        t0 = self._t_dispatch.get(req.rid)
        if t0 is None:
            self._t_dispatch[req.rid] = now
            self._orig_budget[req.rid] = (req.ttft_budget_ms,
                                          req.latency_budget_ms)
            return wire
        elapsed_ms = (now - t0) * 1e3
        ttft, lat = self._orig_budget[req.rid]
        # TTFT already achieved before migration stays achieved
        wire["ttft_budget_ms"] = (
            None if (ttft is None or req.t_first_token is not None)
            else max(1.0, ttft - elapsed_ms))
        wire["latency_budget_ms"] = (
            None if lat is None else max(1.0, lat - elapsed_ms))
        return wire

    def _dispatch(self, req: Request, step: int) -> bool:
        """Probe every ready replica, submit to the cheapest accepting
        one. False = nobody can take it right now (requeue)."""
        now = time.perf_counter()
        wire = self._wire(req, now)
        best, best_cost = None, None
        for w in self._workers:
            if not w.ready:
                continue
            try:
                r = self._rpc(w, {"op": "probe", "req": wire})
            except OSError:
                continue  # probed a corpse: the step loop will notice
            if r.get("ok") and r.get("reason") is None:
                cost = float(r.get("est_steps", 0.0))
                if best is None or cost < best_cost:
                    best, best_cost = w, cost
        if best is None:
            held = self._hold.get(req.rid, 0) + 1
            self._hold[req.rid] = held
            if held > self.dispatch_patience:
                req.status = RequestStatus.REJECTED
                req.end_reason = "no_replica"
                self._record({"event": "reject", "rid": req.rid,
                              "code": "no_replica", "step": step})
                return True  # terminal: do not requeue
            return False
        try:
            r = self._rpc(best, {"op": "submit", "req": wire})
        except OSError:
            return False  # worker died between probe and submit
        if r.get("reason") is not None:
            return False  # admission race: requeue
        req.status = RequestStatus.QUEUED
        req.replica_id = best.idx
        if req.t_arrival is None:
            req.t_arrival = now
        self._hold.pop(req.rid, None)
        return True

    def _apply_updates(self, w: _Worker, updates: List[dict],
                       now: float) -> None:
        for up in updates:
            req = self._by_rid.get(int(up["rid"]))
            if req is None or req.replica_id != w.idx:
                continue  # stale echo from a superseded assignment
            new = up.get("new_tokens") or []
            if new and req.t_first_token is None:
                req.t_first_token = now
            req.out_tokens.extend(int(t) for t in new)
            status = RequestStatus(up["status"])
            req.status = status
            req.end_reason = up.get("end_reason")
            if is_terminal(status) and req.t_done is None:
                req.t_done = now

    # -- the drive loop ----------------------------------------------------
    def generate(self, requests: Sequence[Request],
                 max_steps: int = 10_000) -> Dict[int, List[int]]:
        """Run a request trace to completion across the process fleet.

        The caller's :class:`Request` objects are the router-side
        mirrors (mutated in place, like ``ReplicaFleet``): tokens,
        lifecycle state and router-clock timestamps land on them.
        Returns ``{rid: tokens}`` and fills :attr:`last_stats`.
        """
        reqs = list(requests)
        self._by_rid = {r.rid: r for r in reqs}
        self._t_dispatch.clear()
        self._orig_budget.clear()
        self._hold.clear()
        base_incidents = len(self.incidents)
        pending: Deque[Request] = collections.deque(
            sorted(reqs, key=lambda r: (r.arrival_step, r.rid)))
        t0 = time.perf_counter()
        step = 0
        while step < max_steps:
            # admission: everything due this step, migrants first
            # (extendleft put them at the head)
            requeue = []
            while pending and pending[0].arrival_step <= step:
                req = pending.popleft()
                if is_terminal(req.status):
                    continue
                if not self._dispatch(req, step):
                    requeue.append(req)
            pending.extendleft(reversed(requeue))
            # step every ready replica: AT MOST ONCE each — a lost
            # reply is an incident, never a resend
            for w in self._workers:
                if not w.ready:
                    continue
                timeout = (self.startup_timeout_s if w.steps_done == 0
                           else self.rpc_timeout_s)
                try:
                    reply = self._rpc_once(
                        w, {"op": "step", "step": step}, timeout)
                except OSError as e:
                    self._incident(w, e, step, reqs, pending)
                    continue
                w.steps_done += 1
                if not reply.get("ok"):
                    self._incident(
                        w, RuntimeError(reply.get("error", "step "
                                                          "refused")),
                        step, reqs, pending)
                    continue
                self._apply_updates(w, reply.get("updates") or [],
                                    time.perf_counter())
            if not pending and all(is_terminal(r.status) for r in reqs):
                step += 1
                break
            step += 1
        # anything still non-terminal is LOST — the summary says so
        self.steps_run = step
        wall = time.perf_counter() - t0
        self.last_stats = self._summarize(
            reqs, wall, incidents=self.incidents[base_incidents:])
        self._record({"event": "proc_fleet_summary", **self.last_stats})
        return {r.rid: list(r.out_tokens) for r in reqs}

    # -- accounting --------------------------------------------------------
    def page_leaks(self) -> int:
        """Allocator pages still held across READY workers (0 after a
        drained trace). Dead workers are exempt — their pool died with
        the process, exactly like crashed memory."""
        leaks = 0
        for w in self._workers:
            if w.ready:
                r = self._rpc(w, {"op": "stats"})
                leaks += int(r.get("used_pages", 0))
        return leaks

    def torn_frames(self) -> int:
        """Torn transport frames observed across all incarnations so
        far (dead channels banked + live channels' counters)."""
        return self._torn_frames + sum(
            w.chan.torn_frames for w in self._workers
            if w.chan is not None)

    def _summarize(self, reqs: Sequence[Request], wall_s: float, *,
                   incidents: Sequence[Incident]) -> Dict[str, Any]:
        from .. import telemetry
        from .engine import ServingEngine

        completed = [r for r in reqs
                     if r.status is RequestStatus.COMPLETED]
        by_status = {
            s.value: sum(r.status is s for r in reqs)
            for s in (RequestStatus.COMPLETED, RequestStatus.REJECTED,
                      RequestStatus.TIMED_OUT, RequestStatus.FAILED,
                      RequestStatus.CANCELLED)}
        lost = {r.rid for r in reqs if not is_terminal(r.status)} | {
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
        mttr = [i.recovery_s for i in incidents
                if i.recovery_s is not None]
        return {
            "mode": "process",
            "n_replicas": len(self._workers),
            # where the engines that produced these numbers ran
            "worker_platforms": sorted(
                {w.platform for w in self._workers if w.platform}),
            "n_requests": len(reqs),
            "completed": len(completed),
            "by_status": by_status,
            "requests_lost": len(lost),
            "migrated": len(self._migrated_rids),
            "replica_deaths": sum(w.deaths for w in self._workers),
            "incidents": [{"kind": i.kind, "replica": i.host,
                           "incarnation": i.incarnation,
                           "recovery_s": i.recovery_s}
                          for i in incidents],
            "mttr_s": round(max(mttr), 3) if mttr else None,
            "mttr_mean_s": (round(sum(mttr) / len(mttr), 3)
                            if mttr else None),
            "restarts": sum(r.restarts for r in reqs),
            "torn_frames": self.torn_frames(),
            "steps": self.steps_run,
            "wall_s": round(wall_s, 4),
            "generated_tokens": total_tokens,
            "tokens_per_sec": round(total_tokens / wall_s, 2)
            if wall_s > 0 else None,
            "slo_attained": len(slo),
            "slo_attainment": round(len(slo) / len(reqs), 4)
            if reqs else None,
            "goodput_tokens": goodput_tokens,
            "goodput_tokens_per_sec": round(goodput_tokens / wall_s, 2)
            if wall_s > 0 else None,
            "latency_ms": telemetry.percentiles(lat_ms),
            "ttft_ms": telemetry.percentiles(ttft_ms),
            "per_replica": {
                str(w.idx): {"state": w.state,
                             "incarnation": w.incarnation,
                             "deaths": w.deaths,
                             "served": sum(r.replica_id == w.idx
                                           for r in reqs),
                             "completed": sum(
                                 r.replica_id == w.idx
                                 and r.status is RequestStatus.COMPLETED
                                 for r in reqs)}
                for w in self._workers},
        }
