"""Replica host for the real-process serving fleet.

One subprocess, one :class:`~apex_tpu.serving.engine.ServingEngine`.
The router (:class:`~apex_tpu.serving.proc_fleet.FleetSupervisor`)
launches this module (``python -m apex_tpu.serving.worker``) with pipes
on stdin/stdout and drives it with framed records
(:mod:`~apex_tpu.serving.transport`):

- on startup the worker builds its engine from the model spec, beats
  its :class:`~apex_tpu.resilience.liveness.Heartbeat` file, and sends
  an unprompted ``ready`` frame — the startup rendezvous;
- thereafter it is a strict RPC server: ``probe`` / ``submit`` /
  ``step`` / ``stats`` / ``shutdown``, one reply frame per request.
  Each ``step`` runs at most one engine step and reports per-request
  DELTAS (new tokens since the last report + lifecycle transitions),
  so the router's mirrors stay current without re-shipping whole
  requests;
- every ``step`` beats the heartbeat — staleness IS the hang signal.

Protocol discipline: fd 1 belongs to the frame channel, so the first
thing ``main`` does is dup it away and point ``stdout`` at stderr — a
stray ``print`` (jax warmup chatter, a debug line) can then never
corrupt a frame. Exit is ``os._exit``: the engine may hold XLA state
whose interpreter-teardown destructors abort on some platforms, and a
replica host's death must be *silent and clean* or *SIGKILL*, never a
third thing.

Determinism: the model is built from the spec by
:func:`model_from_spec` — the same function the router-side reference
uses — so worker tokens are byte-comparable against an in-process
engine run. Chaos (:class:`~apex_tpu.resilience.chaos.WorkerChaos`,
armed via ``--chaos`` spec string) injects the transport-level faults:
SIGKILL at a step (optionally mid-frame, leaving a torn reply AND a
torn telemetry line), heartbeat wedge, dropped reply frames.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, Optional

# script-mode safety: repo root importable when run as a file
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def model_from_spec(spec: dict):
    """Deterministically build ``(cfg, params)`` from a JSON-safe model
    spec — the ONE constructor the worker, the supervisor's reference
    path, and the tests share, so byte-identity claims compare like
    with like. ``kind: tiny_gpt`` is the CPU-faked model backing the
    tier-1 legs (same recipe as ``tools/serving_check.py``)."""
    import jax
    import jax.numpy as jnp

    from ..transformer.testing import GPTConfig, init_gpt_params

    kind = spec.get("kind", "tiny_gpt")
    if kind != "tiny_gpt":
        raise ValueError(f"unknown model kind {kind!r}")
    cfg = GPTConfig(
        num_layers=int(spec.get("num_layers", 2)),
        hidden_size=int(spec.get("hidden_size", 64)),
        num_attention_heads=int(spec.get("num_attention_heads", 4)),
        vocab_size=int(spec.get("vocab_size", 128)),
        max_position_embeddings=int(
            spec.get("max_position_embeddings", 64)),
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype=jnp.float32, compute_dtype=jnp.float32)
    params = init_gpt_params(cfg, jax.random.PRNGKey(
        int(spec.get("seed", 0))))
    # position-sensitive continuations (the serving_check recipe): a
    # plain random init greedy-decodes into a fixed point
    params["embedding"]["position"] = (
        params["embedding"]["position"]
        * float(spec.get("pos_scale", 40.0)))
    return cfg, params


class _WorkerServer:
    """The RPC loop body, factored for testability."""

    def __init__(self, engine, hb, chaos, sink, out_fd: int,
                 telemetry_path: Optional[str]):
        self.engine = engine
        self.hb = hb
        self.chaos = chaos
        self.sink = sink
        self.out_fd = out_fd
        self.telemetry_path = telemetry_path
        self.requests: Dict[int, object] = {}
        self._reported_tokens: Dict[int, int] = {}
        self._reported_status: Dict[int, str] = {}

    # -- ops ---------------------------------------------------------------
    def op_probe(self, msg: dict) -> dict:
        from .transport import request_from_wire

        req = request_from_wire(msg["req"])
        reason, est = self.engine.probe(req)
        return {"ok": True,
                "reason": None if reason is None else reason.code.value,
                "est_steps": int(est)}

    def op_submit(self, msg: dict) -> dict:
        from .transport import request_from_wire

        req = request_from_wire(msg["req"])
        self.requests[req.rid] = req
        self._reported_tokens.setdefault(req.rid, len(req.out_tokens))
        reason = self.engine.try_submit(req)
        return {"ok": True,
                "reason": None if reason is None else reason.code.value,
                "status": req.status.value,
                "end_reason": req.end_reason}

    def _updates(self) -> list:
        """Per-request deltas since the last report: new tokens +
        lifecycle transitions. ``out_tokens`` is append-only across
        preemption replay (recompute mode keeps generated tokens), so
        a token index is reported exactly once."""
        ups = []
        for rid, req in self.requests.items():
            n_rep = self._reported_tokens.get(rid, 0)
            new = [int(t) for t in req.out_tokens[n_rep:]]
            status = req.status.value
            if not new and self._reported_status.get(rid) == status:
                continue
            self._reported_tokens[rid] = len(req.out_tokens)
            self._reported_status[rid] = status
            up = {"rid": int(rid), "new_tokens": new, "status": status,
                  "end_reason": req.end_reason,
                  "preemptions": int(req.preemptions)}
            for k in ("t_arrival", "t_first_token", "t_done"):
                v = getattr(req, k)
                if v is not None:
                    up[k] = float(v)
            ups.append(up)
        return ups

    def op_step(self, msg: dict) -> dict:
        step_i = int(msg.get("step", 0))
        if not self.engine.scheduler.idle:
            self.engine.run_step()
        self.hb.beat(step_i)
        return {"ok": True, "step": step_i,
                "idle": bool(self.engine.scheduler.idle),
                "updates": self._updates()}

    def op_stats(self, msg: dict) -> dict:
        a = self.engine.run_accum
        return {"ok": True,
                "used_pages": int(
                    self.engine.scheduler.allocator.used_count),
                "steps": int(a.get("steps", 0)),
                "engine_steps": int(self.engine.steps_run)}

    # -- loop --------------------------------------------------------------
    def _tear_and_die(self) -> None:
        """The mid-message SIGKILL: half a reply frame on the wire,
        half a telemetry line in the JSONL, then death — the torn
        artifacts every tolerant reader must count, not crash on."""
        from ..resilience.chaos import WorkerChaos
        from .transport import frame_bytes

        if self.telemetry_path:
            fd = os.open(self.telemetry_path,
                         os.O_WRONLY | os.O_CREAT | os.O_APPEND)
            os.write(fd, b'{"event": "torn_by_sigkill", "half')
        data = frame_bytes({"ok": True, "step": -1, "idle": False,
                            "updates": [], "pad": "x" * 256})
        os.write(self.out_fd, data[:len(data) // 2])
        WorkerChaos.die()

    def handle(self, msg: dict) -> Optional[dict]:
        """Dispatch one frame; None means 'send no reply' (dropped
        frame chaos / shutdown already replied)."""
        from ..resilience.chaos import WorkerChaos
        from .transport import write_frame

        op = msg.get("op")
        if op == "step":
            step_i = int(msg.get("step", 0))
            stall = self.chaos.take_wedge(step_i)
            if stall is not None:
                # a wedge is a HANG, not a death: stop beating and sit.
                # The supervisor's staleness detector must fire (and
                # SIGKILL lands mid-sleep; the sleep bound is a belt).
                self.sink.record({"event": "chaos_wedge",
                                  "step": step_i, "stall_s": stall})
                time.sleep(stall)
            mid = self.chaos.take_kill(step_i)
            if mid is not None:
                self.sink.record({"event": "chaos_kill",
                                  "step": step_i, "mid_frame": mid})
                if mid:
                    self._tear_and_die()
                WorkerChaos.die()
        try:
            fn = getattr(self, f"op_{op}", None)
            if fn is None:
                reply = {"ok": False, "error": f"unknown op {op!r}"}
            else:
                reply = fn(msg)
        except Exception as e:  # engine fault -> typed error reply
            reply = {"ok": False,
                     "error": f"{type(e).__name__}: {e}"}
        if op == "step" and self.chaos.take_drop(int(msg.get("step", 0))):
            self.sink.record({"event": "chaos_drop_frame",
                              "step": msg.get("step")})
            return None  # swallow the reply: the router must time out
        if op == "shutdown":
            write_frame(self.out_fd, {"ok": True, "bye": True})
            return None
        return reply


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--replica", type=int, required=True)
    p.add_argument("--incarnation", type=int, default=0)
    p.add_argument("--heartbeat", required=True)
    p.add_argument("--spec", required=True,
                   help="model/engine spec JSON (see model_from_spec)")
    p.add_argument("--telemetry", default="",
                   help="per-replica JSONL path (O_APPEND-safe)")
    p.add_argument("--chaos", default="",
                   help="WorkerChaos spec, e.g. 'killmid@6,wedge@9:30'")
    args = p.parse_args(argv)

    # a worker subprocess is a CPU stand-in: a chip belongs to ONE process
    # (the supervisor's, if any), so the worker must never ask for it —
    # whatever platform its environment exports
    import jax

    jax.config.update("jax_platforms", "cpu")

    # fd discipline: the frame channel owns fd 1; stray prints go to
    # stderr so they can never corrupt a frame
    out_fd = os.dup(1)
    os.dup2(2, 1)
    in_fd = 0

    from ..resilience.chaos import WorkerChaos
    from ..resilience.liveness import Heartbeat
    from ..telemetry.recorder import (
        JsonlRecorder, NullRecorder, TaggedRecorder,
    )
    from .engine import ServingEngine
    from .transport import FrameReader, write_frame

    spec = json.loads(args.spec)
    chaos = WorkerChaos.parse(args.chaos)
    hb = Heartbeat(args.heartbeat, host=args.replica)
    base_sink = (JsonlRecorder(args.telemetry,
                               only_logging_process=False, append=True)
                 if args.telemetry else NullRecorder())
    sink = TaggedRecorder(base_sink, replica_id=args.replica,
                          incarnation=args.incarnation, owns_sink=True)

    cfg, params = model_from_spec(spec)
    engine = ServingEngine(cfg, params, sink=sink,
                           **spec.get("engine", {}))
    engine.begin_run()
    hb.beat(0)
    sink.record({"event": "worker_ready", "pid": os.getpid()})
    write_frame(out_fd, {"op": "ready", "replica": args.replica,
                         "incarnation": args.incarnation,
                         "pid": os.getpid(),
                         "platform": jax.default_backend()})

    server = _WorkerServer(engine, hb, chaos, sink, out_fd,
                           args.telemetry or None)
    reader = FrameReader(in_fd)
    while True:
        msg = reader.read_frame()
        if msg is None:
            break  # router hung up: die quietly
        reply = server.handle(msg)
        if msg.get("op") == "shutdown":
            break
        if reply is not None:
            write_frame(out_fd, reply)
    sink.close()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)  # dodge XLA static-teardown aborts


if __name__ == "__main__":
    main()
