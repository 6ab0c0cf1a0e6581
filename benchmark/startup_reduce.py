"""Set-up from the inside: what jax traced, lowered and compiled or loaded
before the window, by function.

The program keeps a compile ledger (``apex_tpu/telemetry/compiles.py``):
jax's own trace, lower and compile-or-load spans with the function's name,
on ``time.perf_counter()``'s clock, and the persistent cache's events with
the instant each was heard. This file places the window on that clock and
reduces plain tuples; the ledger's module is its ONE import of the program
(the spans, the cache's events and the two pieces of interval arithmetic,
``union_s`` and ``self_seconds``, which have one owner), and a program
without the ledger (a parent commit) gives nothing.

Rules:

- **The window's first instant.** ``run`` holds no absolute instant, only
  ``phases["process_to_window_s"]``: the window's opening less the
  process's start as ``run.py`` reckons it from ``/proc``. The same
  reckoning at reading time places the process's start on
  ``perf_counter`` (to about 10 ms, ``/proc``'s tick). Without that phase
  there is no window to place: nothing, never 0.
- **Set-up's spans** are those that START before that instant (one that
  straddles it counts whole); the persistent cache's events those heard
  before it. Nothing compiles in a window, and the reference's own
  compiles come after it, seconds past the instant.
- Per kind the seconds are the **union** of the spans' intervals: a jitted
  function's trace inside the step's trace is not counted twice.
- By function the seconds are **self** seconds: a span's own length less
  the spans of the same kind inside it.
- ``startup.largest_program_s``: a program is one ``compile`` span (one
  executable) with the ``trace`` and ``lower`` spans of its ``fun_name``
  that ended before it and after the previous ``compile`` of that name
  (their union, per kind). The largest is the step, cold or warm: three
  programs that share the name ``<lambda>`` are three, not one.

One reduction per run; the first reader prints it on standard error
(``[bench <platform>] startup {...}``): the five numbers, the seconds of
``entry.build_s + entry.warm_s`` that no span covers (device execution of
the state's initialisation and of the warm-up steps, host-to-device
copies, Python outside jax), the events under the ledger's floor, and the
ten largest ``(kind, fun_name)`` by self seconds with their counts.
"""
from __future__ import annotations

import importlib
import json
import sys
import time
from typing import Dict, List, Optional, Tuple

from benchmark.run import _process_age_s

KINDS = ("trace", "lower", "compile")
CACHE_COUNTS = ("requests", "hits", "misses")
TOP = 10

Span = Tuple[str, str, float, float]          # kind, fun_name, start, end
CacheEvent = Tuple[float, str, float]         # instant, counter, value


def _ledger():
    """The program's compile ledger; ``None`` in a program from before
    it."""
    try:
        return importlib.import_module("apex_tpu.telemetry.compiles")
    except ImportError:
        return None


def programs(spans: List[Span]) -> List[Tuple[float, str]]:
    """``(seconds, fun_name)`` of every executable: its ``compile`` span
    and the same-named ``trace`` and ``lower`` spans since the previous
    ``compile`` of that name."""
    union_s = _ledger().union_s
    out: List[Tuple[float, str]] = []
    waiting: Dict[str, Dict[str, list]] = {}
    for kind, name, start, end in sorted(spans, key=lambda s: s[3]):
        if kind != "compile":
            waiting.setdefault(name, {}).setdefault(kind, []).append(
                (start, end))
            continue
        mine = waiting.pop(name, {})
        out.append((end - start + sum(union_s(v) for v in mine.values()),
                    name))
    return out


def reduce(spans: List[Span], cache: List[CacheEvent], window: float
           ) -> Dict:
    """The table of one run's set-up: the five numbers under their metric
    names, ``covered_s`` (the union of all three kinds), ``largest_program``
    (its name), ``cache`` (requests, hits and misses heard before the
    window) and ``top`` (``[kind, fun_name, count, self_s]`` rows)."""
    ledger = _ledger()
    union_s = ledger.union_s
    spans = [tuple(s) for s in spans if s[2] < window]
    cache = [e for e in cache if e[0] < window]
    by_kind = {k: [(s[2], s[3]) for s in spans if s[0] == k] for k in KINDS}
    rows: Dict[Tuple[str, str], List[float]] = {}
    for (kind, name, _, _), own in zip(spans, ledger.self_seconds(spans)):
        row = rows.setdefault((kind, name), [0, 0.0])
        row[0] += 1
        row[1] += own
    largest_s, largest = max(programs(spans), default=(0.0, None))
    top = sorted(rows.items(), key=lambda kv: -kv[1][1])[:TOP]
    return {
        "startup.trace_s": union_s(by_kind["trace"]),
        "startup.lower_s": union_s(by_kind["lower"]),
        "startup.cache_load_s": sum(
            (v for _, name, v in cache if name == "retrieval_s"), 0.0),
        "startup.programs": float(len(by_kind["compile"])),
        "startup.largest_program_s": largest_s,
        "largest_program": largest,
        "compile_s": union_s(by_kind["compile"]),
        "covered_s": union_s((s[2], s[3]) for s in spans),
        "cache": {k: sum(1 for _, name, _ in cache if name == k)
                  for k in CACHE_COUNTS},
        "top": [[k, n, int(c), round(s, 4)] for (k, n), (c, s) in top],
    }


def window_instant(process_to_window_s: float) -> float:
    """The window's first instant on ``perf_counter``'s clock."""
    return time.perf_counter() - _process_age_s() + process_to_window_s


_TABLES: Dict[Tuple[str, float], Optional[Dict]] = {}


def table_of(run) -> Optional[Dict]:
    """The run's table, reduced once; ``None`` without a window to place
    or a ledger to read."""
    to_window = run.get("phases", {}).get("process_to_window_s")
    if to_window is None:
        return None
    key = (run["cell"], to_window)
    if key not in _TABLES:
        _TABLES[key] = _table(run, to_window)
    return _TABLES[key]


def _table(run, to_window: float) -> Optional[Dict]:
    ledger = _ledger()
    if ledger is None:
        return None
    window = window_instant(to_window)
    spans = ledger.spans()
    if not any(s[2] < window for s in spans):
        return None              # a ledger that was not listening
    table = reduce(spans, ledger.cache_events(), window)
    phases = run["phases"]
    said = {k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in table.items()}
    said["uncovered_s"] = round(
        phases.get("entry.build_s", 0.0) + phases.get("entry.warm_s", 0.0)
        - table["covered_s"], 4)
    said["under_floor_whole_run"] = ledger.totals()["under_floor"]
    print(f"[bench {run.get('platform', '?')}] startup {json.dumps(said)}",
          file=sys.stderr, flush=True)
    return table


def value(run, name: str) -> Optional[float]:
    """One of the five numbers, or ``None`` where there is no table."""
    table = table_of(run)
    return None if table is None else table[name]
