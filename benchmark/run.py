"""The benchmark's one command::

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One cell, one new process. Weights and inputs come from ``--seed``; every
shape the window uses is warmed up first and counted as set-up; the window
measures for ``--seconds``; then the timed path's outputs are compared
with the plain reference. The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``); the numbers compared stand beside
their limits in it and on the last lines of standard error.

No accelerator, or fewer chips than the cell asks for: a non-zero exit and
no result. ``--rehearse 1`` is the CPU rehearsal: the same code at the tiny
sizes of ``rehearsal.json`` with interpreted kernels, every line naming the
platform, and no metric reported.
"""
from __future__ import annotations

import os
import sys
import time

_T_IMPORT = time.perf_counter()


def _process_age_s() -> float:
    """Seconds since this process started (from /proc), so that set-up
    counts the interpreter's own start."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_T_START = _T_IMPORT - _process_age_s()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

from benchmark import manifest as mf  # noqa: E402

_T_IMPORTED = time.perf_counter()

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """jax's own account of compilation: seconds in the backend compiler
    or in loading from the persistent cache, requests, and cache hits."""

    def __init__(self):
        import jax

        self.seconds, self.requests, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, event: str, secs: float, **_kw) -> None:
        if event == _COMPILE:
            self.seconds += secs
            self.requests += 1

    def _event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.hits += 1

    def snapshot(self) -> Dict[str, float]:
        return {"seconds": self.seconds, "requests": self.requests,
                "hits": self.hits, "misses": self.requests - self.hits}


class _Tracer:
    """Traces a slice of the window. ``tick()`` is called at the top of
    every iteration of the window's loop: the trace starts at the first
    tick ``after_s`` into the window and stops at the tick ``length``
    iterations later; a train cell's per-step numbers then divide by the
    whole steps the slice holds (``trace_reduce.whole_steps``). Off unless
    ``--trace 1``."""

    def __init__(self, ctx, after_s: float, length: int):
        self.ctx, self.after_s, self.length = ctx, after_s, length
        self.state = "waiting" if ctx.trace else "off"
        self.count = 0
        self._window = None

    def tick(self) -> None:
        if self.state == "waiting":
            if time.perf_counter() - self.ctx.t_window >= self.after_s:
                self._start()
        elif self.state == "on":
            self.count += 1
            if self.count >= self.length:
                self.close()

    def _start(self) -> None:
        import jax

        self.ctx.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.ctx.trace_dir, profiler_options=opts)
        self.state, self.ctx.tracing = "on", True
        self._window = jax.profiler.TraceAnnotation("bench.trace_window")
        self._window.__enter__()

    def close(self) -> None:
        if self.state != "on":
            return
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state, self.ctx.tracing = "done", False
        self.ctx.traced_units = self.count


class Context:
    """What a cell's runner gets from the harness."""

    def __init__(self, cell: mf.Cell, seed: int, seconds: float,
                 trace: bool, devices, interpret: bool, compile_log):
        self.cell = cell
        self.config, self.mix, self.limits = (
            cell.config, cell.mix, cell.limits)
        self.chips = cell.chips
        self.seed, self.seconds, self.trace = seed, float(seconds), trace
        self.devices, self.interpret = devices, interpret
        self.compile_log = compile_log
        self.marks: Dict[str, float] = {}
        self.t_window: Optional[float] = None
        self.compile_at_window: Dict[str, float] = {}
        self.compile_at_close: Dict[str, float] = {}
        self.tracing = False
        self.trace_dir: Optional[str] = None
        self.traced_units = 0
        self.peak_bytes: Optional[int] = None

    def mark(self, name: str) -> None:
        self.marks[name] = time.perf_counter()

    def window_open(self) -> None:
        self.t_window = time.perf_counter()
        self.compile_at_window = self.compile_log.snapshot()

    def window_close(self) -> None:
        self.compile_at_close = self.compile_log.snapshot()

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def tracer(self, after_s: float, length: int):
        return _Tracer(self, after_s, length)

    def memory_peak(self) -> None:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats()
            if stats and "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.peak_bytes = max(peaks) if peaks else None

    def free(self) -> None:
        import jax

        gc.collect()
        jax.clear_caches()
        gc.collect()


def _say(platform: str, msg: str) -> None:
    print(f"[bench {platform}] {msg}", file=sys.stderr, flush=True)


def configure_cache(root: str) -> str:
    """The persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else ``<checkout>/.jax_cache`` (a fixed path: the path is part of
    the key). Every program is kept, however quick its compile, whether or
    not the variable is set, so that a warm run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def judge(compared: Dict[str, float], limits: Dict[str, Any]):
    """Each number compared beside its limit. A limit on a number the run
    did not produce, or a number that is not finite, fails."""
    rows, ok = {}, True
    for kind in ("max", "min"):
        for name, limit in limits.get(kind, {}).items():
            value = compared.get(name)
            good = (value is not None and value == value
                    and (value <= limit if kind == "max" else value >= limit))
            rows[name] = {"value": value, "limit": limit, "kind": kind,
                          "ok": bool(good)}
            ok = ok and good
    if not rows:
        ok = False          # nothing compared is not correct
    return ok, rows


def rehearsal_cell(cell: mf.Cell) -> None:
    """Cut the cell to the tiny sizes of ``rehearsal.json`` (CPU only)."""
    with open(os.path.join(cell.bench_dir, "rehearsal.json")) as f:
        tiny = json.load(f)
    cell.config = {**cell.config, **tiny["config"]}
    cell.config["assumed"] = {**cell.config["assumed"],
                              **tiny["config_assumed"]}
    cell.mix = {**cell.mix, **tiny["mix"][cell.kind]}
    cell.limits = tiny["limits"][cell.kind]


def run_cell(cell: mf.Cell, seed: int, seconds: float, trace: bool, *,
             rehearse: bool = False, runner_kw: Optional[dict] = None,
             out=None) -> int:
    import jax

    compile_log = CompileLog()
    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            raise SystemExit("--rehearse is the CPU rehearsal; this "
                             f"process sees {platform}")
        rehearsal_cell(cell)
    elif platform != "tpu" or len(devices) < cell.chips:
        _say(platform, f"{cell.name} needs {cell.chips} TPU chip(s); jax "
             f"sees {len(devices)} {platform} device(s). A measurement "
             "does not fall back to another backend.")
        return 2
    devices = devices[:cell.chips]
    t_backend = time.perf_counter()
    cache_dir = None if rehearse else configure_cache(mf.ROOT)
    _say(platform, f"{cell.name} seed={seed} seconds={seconds} "
         f"trace={int(trace)} cache={cache_dir}")

    ctx = Context(cell, seed, seconds, trace, devices, rehearse, compile_log)
    if cell.kind == "train":
        from benchmark import train_cell as runner
    else:
        raise SystemExit(f"unknown traffic kind {cell.kind!r}")
    result = runner.run(ctx, **(runner_kw or {}))
    t_done = time.perf_counter()

    # ---- phases: every run prints them -----------------------------------
    t_build = ctx.marks.get("build", t_backend)
    phases = {
        "entry.host_start_s": t_backend - _T_START,
        "entry.compile_s": ctx.compile_at_window.get("seconds", 0.0),
        "entry.cache_misses": ctx.compile_at_window.get("misses", 0),
        "entry.build_s": t_build - t_backend,
        "entry.warm_s": ctx.t_window - t_build,
        "import_s": _T_IMPORTED - _T_START,
        "state_s": ctx.marks.get("state", t_build) - t_backend,
        "check_s": t_done - ctx.marks.get("check", t_done),
        "compiled_in_window": (ctx.compile_at_close.get("requests", 0)
                               - ctx.compile_at_window.get("requests", 0)),
    }
    # set-up is what the program and the harness do: backend up to the
    # window's first instant. The interpreter's and the TPU runtime's own
    # start (entry.host_start_s: 11-15 s on the v5e hosts, +-2 s from run to
    # run with nothing of this repository in it) is printed beside it.
    setup_s = ctx.t_window - t_backend
    phases["process_to_window_s"] = ctx.t_window - _T_START
    _say(platform, "phases " + json.dumps(
        {"setup_s": setup_s, **phases}))

    # ---- metrics -----------------------------------------------------------
    kind = devices[0].device_kind
    run: Dict[str, Any] = {
        "cell": cell.name, "platform": platform, "device_kind": kind,
        "phases": phases, "counters": result["counters"],
        "end_to_end": {**result["end_to_end"], "setup_s": setup_s},
        "trace": None, "peaks": None,
        "notes": dict(result.get("notes", {}))}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": ctx.peak_bytes}
    breakdown = None
    metrics: Dict[str, Dict[str, Any]] = {}
    if rehearse:
        pass                # a CPU run reports no metric under any name
    elif trace:
        from benchmark import peaks, trace_reduce

        run["peaks"] = peaks.peaks_for(kind)
        if ctx.trace_dir is not None:
            run["trace"] = trace_reduce.load(ctx.trace_dir)
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
            if cell.kind == "train":
                run["trace"], ctx.traced_units = trace_reduce.whole_steps(
                    run["trace"])
            bw = trace_reduce.busy_and_window(run["trace"])
            if bw is not None:
                device["busy_s"], device["window_s"] = bw
            breakdown = {
                "device_ops": trace_reduce.top_ops(run["trace"]),
                "idle_gaps": trace_reduce.idle_gaps(run["trace"])}
        run["traced_units"] = ctx.traced_units
        for m in cell.per_layer:
            value = mf.reader(m["name"], cell.bench_dir)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = run["end_to_end"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct, rows = judge(result["compared"], cell.limits)
    if phases["compiled_in_window"]:
        _say(platform, f"{phases['compiled_in_window']} program(s) compiled "
             "inside the window")
    readings = {k: v for k, v in result["compared"].items() if k not in rows}
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": result["attempted"],
        "failed": result["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if rehearse:
        line["rehearsal"] = True
    line["phases"] = {"setup_s": setup_s, **phases}
    line["notes"] = run["notes"]
    line["readings"] = readings
    line["compared"] = rows
    _write_record(cell.name, seed, trace, line)
    for name, row in rows.items():
        _say(platform, f"compared {name} = {row['value']} "
             f"({'<=' if row['kind'] == 'max' else '>='} {row['limit']}) "
             f"{'ok' if row['ok'] else 'NOT OK'}")
    _say(platform, f"correct = {bool(correct)}")
    print(json.dumps(line), file=out or sys.stdout, flush=True)
    return 0


def _write_record(cell: str, seed: int, trace: bool, line: dict) -> None:
    """The run's line, kept in a small file of its own (git-ignored)."""
    d = os.path.join(mf.ROOT, "chiprun_out", "bench")
    try:
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(
                d, f"{cell}-s{seed}-t{int(trace)}-{int(time.time())}.json"),
                "w") as f:
            json.dump(line, f)
    except OSError:
        pass


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = mf.Cell(mf.load_manifest(), args.workload)
    return run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    rehearse=bool(args.rehearse))


if __name__ == "__main__":
    sys.exit(main())
