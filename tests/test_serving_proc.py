"""Real-process serving fleet: crash-safe transport, shared liveness,
multi-process telemetry, and the supervised failover CI wiring
(apex_tpu.serving.transport / worker / proc_fleet — ISSUE-20).

Coverage map (the ISSUE-20 acceptance surface):

- transport: length-prefixed newline-JSON framing round-trips typed
  records over a real pipe; a writer SIGKILLed mid-frame leaves a torn
  FINAL frame that is COUNTED (`torn_frames`) and folded into EOF —
  never crashed on — while mid-stream corruption still raises
  `TransportError`; `Request` survives the wire byte-exactly
  (sampling params, budgets, replay carrier fields included);
- shared liveness (satellite): `Heartbeat` lives in
  `resilience.liveness`, `elastic` re-exports the SAME object, and the
  pinned beat file format round-trips; corpse-incarnation hygiene —
  a beat whose recorded writer pid is dead is NOT live, and
  `sweep_stale` removes dead writers' droppings while sparing live
  ones;
- multi-process JsonlRecorder (satellite): two REAL subprocess writers
  hammer one sink file with records larger than a stdio buffer; every
  line reads back intact (O_APPEND + one os.write per record — the
  red test that fails under buffered fwrite);
- retry wiring (satellite): `TRANSPORT_POLICY` retries on OSError,
  `WorkerUnavailable` IS an OSError, and `FleetSupervisor` routes
  RPCs through it by default;
- chaos spec grammar: `WorkerChaos` specs round-trip through
  `to_spec`/`parse` and fire exactly once on step crossing;
- CI wiring: the `proc_fleet_failover` serving_check leg (SIGKILL one
  worker mid-frame AND wedge another in the SAME run) passes tier-1.
"""
import json
import os
import signal
import subprocess
import sys
import tempfile
import textwrap
import time

import pytest

from apex_tpu.resilience import (
    RetryPolicy,
    ServingChaos,
    TRANSPORT_POLICY,
    WorkerChaos,
    live_beat,
    sweep_stale,
    writer_alive,
)
from apex_tpu.resilience.liveness import Heartbeat
from apex_tpu.serving import (
    FrameReader,
    Request,
    TransportError,
    WorkerUnavailable,
    read_frames,
    request_from_wire,
    request_to_wire,
    write_frame,
)
from apex_tpu.serving.sampling import SamplingParams
from apex_tpu.telemetry import JsonlRecorder, read_jsonl


# ---------------------------------------------------------------------------
# transport framing
# ---------------------------------------------------------------------------

def test_frame_roundtrip_over_pipe():
    rfd, wfd = os.pipe()
    try:
        msgs = [{"op": "probe", "rid": "r-0"},
                {"op": "step", "updates": [{"rid": "r-1",
                                            "new_tokens": [1, 2, 3]}]},
                {"unicode": "päivää", "nested": {"a": [None, True]}}]
        for m in msgs:
            write_frame(wfd, m)
        reader = FrameReader(rfd)
        got = [reader.read_frame(timeout=2.0) for _ in msgs]
        assert got == msgs
        assert reader.torn_frames == 0
    finally:
        os.close(rfd)
        os.close(wfd)


def test_read_frame_timeout_is_worker_unavailable():
    rfd, wfd = os.pipe()
    try:
        reader = FrameReader(rfd)
        with pytest.raises(WorkerUnavailable):
            reader.read_frame(timeout=0.05)
        # WorkerUnavailable must be an OSError so TRANSPORT_POLICY
        # (retry_on=(OSError,)) classifies it transient
        assert issubclass(WorkerUnavailable, OSError)
    finally:
        os.close(rfd)
        os.close(wfd)


def test_midstream_corruption_raises_not_skips():
    """A torn frame is only tolerable at EOF; garbage mid-stream is
    corruption and must raise, never be silently resynced over."""
    path = os.path.join(tempfile.mkdtemp(prefix="frames-"), "s.frames")
    with open(path, "wb") as f:
        f.write(b"not a length prefix\n")
        from apex_tpu.serving.transport import frame_bytes

        f.write(frame_bytes({"ok": 1}))
    with pytest.raises(TransportError):
        read_frames(path)


def test_writer_sigkilled_mid_frame_leaves_counted_torn_tail():
    """THE red test for torn-frame tolerance: a REAL subprocess writer
    is SIGKILLed after writing half a frame. The reader must return
    every complete frame, count exactly one torn frame, and not
    raise."""
    wd = tempfile.mkdtemp(prefix="torn-")
    path = os.path.join(wd, "out.frames")
    prog = textwrap.dedent("""
        import os, sys, time
        sys.path.insert(0, %r)
        from apex_tpu.serving.transport import frame_bytes
        fd = os.open(%r, os.O_WRONLY | os.O_CREAT, 0o644)
        for i in range(3):
            os.write(fd, frame_bytes({"seq": i}))
        half = frame_bytes({"seq": 3, "pad": "x" * 256})
        os.write(fd, half[: len(half) // 2])
        os.fsync(fd)
        print("TORN", flush=True)
        time.sleep(60)
    """) % (os.path.dirname(os.path.dirname(__file__)), path)
    proc = subprocess.Popen([sys.executable, "-c", prog],
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "TORN"
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=10)
        stats = {}
        frames = read_frames(path, stats=stats)
        assert frames == [{"seq": 0}, {"seq": 1}, {"seq": 2}]
        assert stats["torn_frames"] == 1
    finally:
        if proc.poll() is None:
            proc.kill()


def test_request_wire_roundtrip_carries_replay_state():
    req = Request(prompt=[5, 6, 7], max_new_tokens=9, arrival_step=3,
                  priority=2, ttft_budget_ms=120.0,
                  latency_budget_ms=4000.0,
                  sampling=SamplingParams(temperature=0.7, top_k=40,
                                          top_p=0.9, seed=17),
                  labels={"tenant": "a"})
    req.out_tokens.extend([11, 12])   # mid-flight migration state
    req.restarts = 1
    req.retries = 2
    wire = json.loads(json.dumps(request_to_wire(req)))  # must be JSON
    back = request_from_wire(wire)
    assert back.rid == req.rid
    assert back.prompt == [5, 6, 7]
    assert back.out_tokens == [11, 12]
    assert back.restarts == 1 and back.retries == 2
    assert back.sampling == req.sampling
    assert back.ttft_budget_ms == 120.0
    assert back.labels == {"tenant": "a"}


# ---------------------------------------------------------------------------
# shared liveness (satellite): Heartbeat factoring + corpse hygiene
# ---------------------------------------------------------------------------

def test_heartbeat_is_shared_and_format_pinned():
    """elastic re-exports THE liveness.Heartbeat (no fork of the beat
    format), and the on-disk schema is pinned: host/step/pid/t_wall,
    staged via tmp-<pid> then atomic replace."""
    from apex_tpu.resilience import elastic, liveness

    assert elastic.Heartbeat is liveness.Heartbeat
    wd = tempfile.mkdtemp(prefix="hb-")
    path = os.path.join(wd, "hb-0.json")
    hb = Heartbeat(path, host=0)
    hb.beat(7)
    raw = json.load(open(path))
    assert raw == {"host": 0, "step": 7, "pid": os.getpid(),
                   "t_wall": pytest.approx(time.time(), abs=30.0)}
    got = Heartbeat.read(path)
    assert got["step"] == 7
    assert Heartbeat.age_s(path) < 30.0
    assert not [p for p in os.listdir(wd) if ".tmp-" in p]


def _spawn_corpse():
    """A real dead pid: fork a subprocess and let it exit."""
    p = subprocess.Popen([sys.executable, "-c", "pass"])
    p.wait(timeout=10)
    return p.pid


def test_dead_writers_beat_is_never_fresh():
    """Corpse-incarnation hygiene: a beat file whose recorded pid is
    dead must not read as live, however recent its mtime — else a
    supervisor would trust a corpse's last words."""
    wd = tempfile.mkdtemp(prefix="hb-")
    path = os.path.join(wd, "hb-1.json")
    Heartbeat(path, host=1).beat(3)
    beat = json.load(open(path))
    beat["pid"] = _spawn_corpse()
    with open(path, "w") as f:
        json.dump(beat, f)
    assert writer_alive(os.getpid())
    assert not writer_alive(beat["pid"])
    assert live_beat(path) is None          # dead writer => not live
    fresh = os.path.join(wd, "hb-2.json")
    Heartbeat(fresh, host=2).beat(4)
    assert live_beat(fresh)["step"] == 4    # we are alive


def test_sweep_stale_removes_corpse_files_spares_live():
    wd = tempfile.mkdtemp(prefix="sweep-")
    corpse = _spawn_corpse()
    # dead writer's droppings: staging tmp + committed beat
    open(os.path.join(wd, f"hb-9.json.tmp-{corpse}"), "w").write("{")
    dead_beat = os.path.join(wd, "hb-9.json")
    json.dump({"host": 9, "step": 1, "pid": corpse,
               "t_wall": time.time()}, open(dead_beat, "w"))
    # live writer's beat + an unrelated file must survive
    live = os.path.join(wd, "hb-0.json")
    Heartbeat(live, host=0).beat(1)
    other = os.path.join(wd, "replica-0.0.jsonl")
    open(other, "w").write("{}\n")
    removed = sweep_stale(wd, prefix="hb-")
    assert len(removed) >= 2
    assert not os.path.exists(dead_beat)
    assert not [p for p in os.listdir(wd) if ".tmp-" in p]
    assert os.path.exists(live) and os.path.exists(other)


# ---------------------------------------------------------------------------
# multi-process JsonlRecorder (satellite red test)
# ---------------------------------------------------------------------------

def test_jsonl_recorder_two_subprocess_writers_interleave_intact():
    """TWO real subprocess writers append large records (bigger than
    any stdio buffer) to ONE file concurrently. O_APPEND + a single
    os.write per record keeps every line intact; a buffered-fwrite
    implementation shears records across the other writer's output."""
    wd = tempfile.mkdtemp(prefix="mpjsonl-")
    path = os.path.join(wd, "shared.jsonl")
    n, size = 40, 64 * 1024
    prog = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        from apex_tpu.telemetry import JsonlRecorder
        tag, n, size = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
        rec = JsonlRecorder(%r, only_logging_process=False, append=True)
        for i in range(n):
            rec.record({"writer": tag, "i": i, "pad": tag * size})
        rec.close()
    """) % (os.path.dirname(os.path.dirname(__file__)), path)
    procs = [subprocess.Popen(
        [sys.executable, "-c", prog, tag, str(n), str(size)])
        for tag in ("a", "b")]
    for p in procs:
        assert p.wait(timeout=120) == 0
    stats = {}
    records = read_jsonl(path, stats=stats)
    assert stats.get("torn_lines", 0) == 0
    assert len(records) == 2 * n
    by_writer = {"a": [], "b": []}
    for r in records:
        assert r["pad"] == r["writer"] * size   # no shearing
        by_writer[r["writer"]].append(r["i"])
    # per-writer order preserved (O_APPEND never reorders one fd)
    assert by_writer["a"] == list(range(n))
    assert by_writer["b"] == list(range(n))


def test_jsonl_recorder_single_write_per_record(tmp_path):
    """The mechanism itself: record() issues exactly ONE os.write."""
    path = str(tmp_path / "one.jsonl")
    rec = JsonlRecorder(path, only_logging_process=False)
    calls = []
    real_write = os.write

    def counting_write(fd, data):
        calls.append(len(data))
        return real_write(fd, data)

    try:
        os.write = counting_write
        rec.record({"event": "x", "pad": "y" * (64 * 1024)})
    finally:
        os.write = real_write
    rec.close()
    assert len(calls) == 1
    assert read_jsonl(path)[0]["pad"] == "y" * (64 * 1024)


# ---------------------------------------------------------------------------
# retry wiring (satellite)
# ---------------------------------------------------------------------------

def test_transport_policy_shape_and_default_wiring():
    from apex_tpu.serving.proc_fleet import FleetSupervisor

    assert isinstance(TRANSPORT_POLICY, RetryPolicy)
    assert OSError in TRANSPORT_POLICY.retry_on
    assert TRANSPORT_POLICY.deadline is not None  # wall-clock bound
    assert TRANSPORT_POLICY.max_delay <= TRANSPORT_POLICY.deadline
    sup = FleetSupervisor({"kind": "tiny_gpt"}, 1,
                          workdir=tempfile.mkdtemp(prefix="pol-"))
    assert sup.rpc_policy is TRANSPORT_POLICY


# ---------------------------------------------------------------------------
# chaos spec grammar
# ---------------------------------------------------------------------------

def test_worker_chaos_spec_roundtrip_and_single_fire():
    c = (WorkerChaos().kill_at(6, mid_frame=True)
         .wedge_at(9, stall_s=30.0).drop_at(5, n=2))
    spec = c.to_spec()
    back = WorkerChaos.parse(spec)
    assert back.to_spec() == spec
    # crossing the armed step fires exactly once, even if stepped past
    assert back.take_kill(5) is None
    assert back.take_kill(7) is True        # mid_frame flag
    assert back.take_kill(8) is None        # already fired
    assert back.take_wedge(9) == 30.0
    assert back.take_wedge(10) is None
    drops = [back.take_drop(s) for s in range(4, 9)]
    assert drops == [False, True, True, False, False]  # n=2 budget
    assert WorkerChaos.parse("").armed is False
    # ServingChaos hands each replica its own spec string
    sc = ServingChaos().kill_worker_at(1, 4).wedge_worker_at(2, 6)
    assert sc.worker_spec(0) == ""
    assert WorkerChaos.parse(sc.worker_spec(1)).armed
    assert WorkerChaos.parse(sc.worker_spec(2)).armed


# ---------------------------------------------------------------------------
# CI wiring: serving_check proc leg
# ---------------------------------------------------------------------------

def test_serving_check_proc_fleet_leg_passes():
    """THE tier-1 chaos bar: 3 real worker subprocesses, one SIGKILLed
    mid-frame AND one wedged in the SAME run; zero requests lost,
    token-identical migrants, torn frame + torn telemetry line counted
    (see tools/serving_check.py::check_proc_fleet_failover)."""
    import tools.serving_check as sc

    assert sc.main(["--self", "--check", "proc_fleet_failover"]) == 0
