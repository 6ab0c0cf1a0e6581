"""The compile ledger: what jax traced, lowered and compiled or loaded in
this process, by function, on ``time.perf_counter()``'s clock.

jax publishes the start and the end of every trace (a function's Python
body run once to a jaxpr), every lowering (jaxpr to StableHLO, a Pallas
kernel's body in it) and every compile-or-load (the backend compiler, or
the read from the persistent cache), each with the function's name
(``jax.monitoring``; ``jax/_src/dispatch.py``, ``log_elapsed_time``). The
ledger listens; it patches nothing and wraps nothing. It is installed when
``apex_tpu.telemetry`` is imported and has no off: a listener costs one
dictionary lookup and a comparison for an event under the floor, and jax
emits no event for a call of a compiled function.

- A span is ``(kind, fun_name, start, end)``, ``kind`` one of ``"trace"``,
  ``"lower"``, ``"compile"``. jax stamps its spans with ``time.time()``;
  the listener runs at the span's end, reads both clocks there once and
  keeps the span on ``perf_counter``'s. ``fun_name`` is jax's, less the
  ``jit(...)`` / ``pmap(...)`` in which it wraps the name of a function's
  module, so that the three kinds of one function share a name.
- Every ``jax.numpy`` call inside a trace is a trace span of its own, tens
  of thousands in an unrolled step and nearly all of microseconds: a
  ``trace`` or ``lower`` span under ``FLOOR_S`` bumps a count and a sum
  for its kind and is not kept. ``compile`` spans are executables, few,
  and all kept.
- At most ``CAP`` spans are kept. Past it a span is folded into the sums
  of its ``(kind, fun_name)``: its seconds stay, its place in time goes.
- ``steady()`` is the operator's call once warm-up is over. After it every
  ``compile`` span, and every ``trace`` span over the floor, counts in
  ``recompiles_after_steady`` and is logged once per function at WARNING:
  the step that recompiles in the middle of a run.
"""
from __future__ import annotations

import logging
import time
from typing import Dict, List, NamedTuple, Tuple

import jax

logger = logging.getLogger(__name__)

TRACE, LOWER, COMPILE = "trace", "lower", "compile"
KINDS = (TRACE, LOWER, COMPILE)
FLOOR_S = 1e-3
CAP = 4096

_KIND_OF = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE,
}
_COUNTER_OF = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_SECONDS_OF = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
}


class Span(NamedTuple):
    kind: str
    fun_name: str
    start: float        # perf_counter seconds
    end: float


def union_s(intervals) -> float:
    """Seconds covered by ``(start, end)`` intervals, overlaps once."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def self_seconds(spans) -> List[float]:
    """Each span's own seconds: its length less the spans of the same
    kind that lie inside it (a jitted kernel's trace inside the step's
    trace is the kernel's). One entry per span, in the order given;
    ``spans`` are ``Span``s or plain ``(kind, fun_name, start, end)``."""
    own = [end - start for _, _, start, end in spans]
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], spans[i][2], -spans[i][3]))
    stack: List[int] = []
    for i in order:
        kind, _, start, end = spans[i]
        while stack and (spans[stack[-1]][0] != kind
                         or spans[stack[-1]][3] <= start):
            stack.pop()
        if stack:
            own[stack[-1]] -= max(0.0, min(end, spans[stack[-1]][3]) - start)
        stack.append(i)
    return [max(0.0, x) for x in own]


class CompileLedger:
    """The spans and counters of one process. ``apex_tpu.telemetry`` holds
    the one that listens (``LEDGER``); a test feeds its own."""

    def __init__(self, floor_s: float = FLOOR_S, cap: int = CAP):
        self.floor_s, self.cap = floor_s, cap
        self._spans: List[Span] = []
        self._short = {k: [0, 0.0] for k in KINDS}      # count, seconds
        self._folded: Dict[Tuple[str, str], List[float]] = {}
        # (perf_counter instant, counter, value): three or four a compile
        self._cache: List[Tuple[float, str, float]] = []
        self._cache_folded: Dict[str, float] = {}
        self._steady = False
        self._warned: set = set()
        self.recompiles_after_steady = 0

    # -- the listeners: O(1), no I/O ---------------------------------------
    def on_span(self, event: str, start: float, end: float,
                fun_name: str = "", **_kw) -> None:
        kind = _KIND_OF.get(event)
        if kind is None:
            return
        seconds = end - start
        if seconds < self.floor_s and kind != COMPILE:
            short = self._short[kind]
            short[0] += 1
            short[1] += seconds
            return
        if kind != TRACE and fun_name.endswith(")"):
            fun_name = fun_name[fun_name.find("(") + 1:-1]   # jit(step)
        if len(self._spans) < self.cap:
            # jax's stamps are wall-clock; no replayed decision reads a span
            to_perf = (time.perf_counter()
                       - time.time())  # det-lint: ok (jax's own stamps)
            self._spans.append(
                Span(kind, fun_name, start + to_perf, end + to_perf))
        else:
            folded = self._folded.setdefault((kind, fun_name), [0, 0.0])
            folded[0] += 1
            folded[1] += seconds
        if self._steady and kind != LOWER:
            self._after_steady(kind, fun_name, seconds)

    def on_event(self, event: str, **_kw) -> None:
        name = _COUNTER_OF.get(event)
        if name is not None:
            self._count(name, 1)

    def on_seconds(self, event: str, seconds: float, **_kw) -> None:
        name = _SECONDS_OF.get(event)
        if name is not None:
            self._count(name, seconds)

    def _count(self, name: str, value: float) -> None:
        if len(self._cache) < self.cap:
            self._cache.append((time.perf_counter(), name, value))
        else:
            self._cache_folded[name] = (
                self._cache_folded.get(name, 0) + value)

    def _after_steady(self, kind: str, fun_name: str, seconds: float) -> None:
        self.recompiles_after_steady += 1
        if fun_name not in self._warned:
            self._warned.add(fun_name)
            logger.warning(
                "%s of %s took %.3f s after steady(): the function was "
                "called with a new shape, dtype or static value", kind,
                fun_name, seconds)

    # -- what it answers -----------------------------------------------------
    def steady(self) -> None:
        """Warm-up is over: whatever is traced or compiled from here on is
        counted in ``recompiles_after_steady`` and logged once per
        function."""
        self._steady = True

    def spans(self) -> List[Span]:
        """The kept spans, in the order they ended."""
        return list(self._spans)

    def cache_events(self) -> List[Tuple[float, str, float]]:
        """``(perf_counter instant, counter, value)`` of every event of
        the persistent cache that was kept: ``requests``, ``hits``,
        ``misses`` (value 1), ``retrieval_s`` (seconds)."""
        return list(self._cache)

    def by_function(self) -> Dict[Tuple[str, str], Dict[str, float]]:
        """``{(kind, fun_name): {"count", "total_s", "self_s"}}``. Folded
        spans count whole in both sums (their nesting is not known)."""
        spans = self.spans()
        table: Dict[Tuple[str, str], Dict[str, float]] = {}
        for s, own in zip(spans, self_seconds(spans)):
            row = table.setdefault((s.kind, s.fun_name),
                                   {"count": 0, "total_s": 0.0, "self_s": 0.0})
            row["count"] += 1
            row["total_s"] += s.end - s.start
            row["self_s"] += own
        for key, (count, seconds) in list(self._folded.items()):
            row = table.setdefault(
                key, {"count": 0, "total_s": 0.0, "self_s": 0.0})
            row["count"] += count
            row["total_s"] += seconds
            row["self_s"] += seconds
        return table

    def totals(self) -> Dict[str, float]:
        """Per kind the union of the spans' intervals (``trace_s``,
        ``lower_s``, ``compile_s``: a nested span is not counted twice;
        folded spans add their seconds to their kind) and of all three
        (``covered_s``), the number of ``compile`` spans (``programs``:
        executables compiled or loaded), the persistent cache's counters
        (``requests``, ``hits``, ``misses``, ``retrieval_s``: reading and
        deserializing executables) and ``recompiles_after_steady``.
        ``under_floor``: per kind the count and the seconds of the events
        that were not kept; ``folded``: spans past the cap."""
        spans = self.spans()
        out: Dict[str, float] = {}
        for kind in KINDS:
            out[f"{kind}_s"] = union_s(
                (s.start, s.end) for s in spans if s.kind == kind)
        out["covered_s"] = union_s((s.start, s.end) for s in spans)
        out["programs"] = sum(1 for s in spans if s.kind == COMPILE)
        out.update({name: 0 for name in _COUNTER_OF.values()})
        out.update({name: 0.0 for name in _SECONDS_OF.values()})
        for _, name, value in list(self._cache):
            out[name] += value
        for name, value in list(self._cache_folded.items()):
            out[name] += value
        for (kind, _), (count, seconds) in list(self._folded.items()):
            out[f"{kind}_s"] += seconds
            out["programs"] += count if kind == COMPILE else 0
        out["under_floor"] = {k: tuple(v) for k, v in self._short.items()}
        out["folded"] = sum(c for c, _ in self._folded.values())
        out["recompiles_after_steady"] = self.recompiles_after_steady
        return out


LEDGER = CompileLedger()
_installed = False


def install() -> None:
    """Register ``LEDGER``'s three listeners with jax, once a process."""
    global _installed
    if _installed:
        return
    _installed = True
    jax.monitoring.register_event_time_span_listener(LEDGER.on_span)
    jax.monitoring.register_event_listener(LEDGER.on_event)
    jax.monitoring.register_event_duration_secs_listener(LEDGER.on_seconds)


spans = LEDGER.spans
cache_events = LEDGER.cache_events
by_function = LEDGER.by_function
totals = LEDGER.totals
steady = LEDGER.steady
