"""From a profiler trace to numbers: device busy time (the union of the
intervals in which an operation ran), kernel sums by name, idle gaps
attributed to what the host was doing.

The reduction works on plain tuples so that it can be checked on a small
recorded trace (``tests/bench_tests``): ``load`` turns an ``.xplane.pb``
into :class:`Trace`, everything else is arithmetic on it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

Event = Tuple[str, float, float]        # name, start_ns, duration_ns

_SUFFIX = re.compile(r"(\.\d+)+$")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


class Trace(NamedTuple):
    device_ops: Dict[int, List[Event]]   # device id -> op events
    spans: List[Event]                   # the harness's own host spans
    modules: Dict[int, List[Event]] = {}  # device id -> program executions


def op_name(raw: str) -> str:
    """``fusion.123`` -> ``fusion``; kernel names are kept whole. An op's
    trace name may carry its HLO text (``%fusion.1 = ...``): the name is
    what stands before the first space, without the ``%``."""
    name = raw.split(" ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", name) or raw


def load(trace_dir: str) -> Trace:
    """Read every ``.xplane.pb`` under ``trace_dir`` with jax's own
    reader."""
    from jax.profiler import ProfileData

    device_ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for path in sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True)):
        for plane in ProfileData.from_file(path).planes:
            m = _DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    into = {OPS_LINE: device_ops,
                            MODULES_LINE: modules}.get(line.name)
                    if into is not None:
                        into.setdefault(int(m.group(1)), []).extend(
                            (ev.name, float(ev.start_ns),
                             float(ev.duration_ns)) for ev in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend(
                        (ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events
                        if ev.name.startswith(SPAN_PREFIX))
    return Trace(device_ops, spans, modules)


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals of the events, in order."""
    merged: List[List[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def window_of(trace: Trace) -> Optional[Tuple[float, float]]:
    """The traced window: the ``bench.trace_window`` span where the host
    recorded one, else the extent of the device's events."""
    for name, start, dur in trace.spans:
        if name == SPAN_PREFIX + "trace_window":
            return start, start + dur
    evs = [e for ops in trace.device_ops.values() for e in ops]
    if not evs:
        return None
    return (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))


def whole_steps(trace: Trace):
    """``(trace, n)``: the trace with its window cut to the ``n`` whole
    executions of the step program that lie inside it (the program that
    ran longest on the lowest device), so that per-step numbers divide by
    steps the window really holds. ``(trace, 0)`` where there is none."""
    win = window_of(trace)
    if win is None or not trace.modules:
        return trace, 0
    mods = trace.modules[min(trace.modules)]
    by_name: Dict[str, float] = {}
    for name, _, dur in mods:
        by_name[name] = by_name.get(name, 0.0) + dur
    step = max(by_name, key=by_name.get)
    # an execution may be listed twice, or trail a sliver under the same
    # name: a step is an event at least half as long as the longest
    longest = max(d for n, _, d in mods if n == step)
    whole: List[Tuple[float, float]] = []
    for s, e in sorted((s, s + d) for n, s, d in mods
                       if n == step and d >= 0.5 * longest
                       and s >= win[0] and s + d <= win[1]):
        if not whole or s >= whole[-1][1]:    # one execution, listed once
            whole.append((s, e))
    if not whole:
        return trace, 0
    lo, hi = whole[0][0], whole[-1][1]
    spans = [s for s in trace.spans if s[0] != SPAN_PREFIX + "trace_window"]
    spans.append((SPAN_PREFIX + "trace_window", lo, hi - lo))
    return trace._replace(spans=spans), len(whole)


def _clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_and_window(trace: Trace) -> Optional[Tuple[float, float]]:
    """``(busy_s, window_s)``: seconds in which an operation ran, averaged
    over the devices that ran any, and the length of the traced window."""
    win = window_of(trace)
    if win is None or not trace.device_ops:
        return None
    lo, hi = win
    busy = [sum(b - a for a, b in union(_clip(ops, lo, hi)))
            for ops in trace.device_ops.values()]
    busy = [b for b in busy if b > 0]
    if not busy:
        return None
    return sum(busy) / len(busy) * 1e-9, (hi - lo) * 1e-9


def self_times(events: Iterable[Event]) -> List[Event]:
    """Events with their duration cut to self time: a ``while`` or ``cond``
    encloses the operations of its body on the same line, and its own time
    is what they leave."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List] = []
    stack: List[int] = []                      # indices into out, open
    for name, start, dur in evs:
        while stack and start >= out[stack[-1]][3]:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[2] -= min(dur, parent[3] - start)
        out.append([name, start, dur, start + dur])
        stack.append(len(out) - 1)
    return [(n, s, max(d, 0.0)) for n, s, d, _ in out]


def op_seconds(trace: Trace, device: Optional[int] = None
               ) -> Dict[str, float]:
    """Self seconds by operation name inside the window, on one device
    (the lowest id by default)."""
    win = window_of(trace)
    if win is None or not trace.device_ops:
        return {}
    dev = min(trace.device_ops) if device is None else device
    out: Dict[str, float] = {}
    for name, _, dur in self_times(_clip(trace.device_ops[dev], *win)):
        key = op_name(name)
        out[key] = out.get(key, 0.0) + dur * 1e-9
    return out


def kernel_seconds(trace: Trace, prefix) -> Optional[float]:
    """Summed seconds of the operations whose name starts with ``prefix``
    (a string or a tuple of them; one device); ``None`` where none ran."""
    hits = [v for k, v in op_seconds(trace).items() if k.startswith(prefix)]
    return sum(hits) if hits else None


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    ops = sorted(op_seconds(trace).items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ops[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The idle gaps of the device (lowest id) inside the window, summed
    by the harness span that covers most of each gap; ``_no_span_`` where
    none does."""
    win = window_of(trace)
    if win is None or not trace.device_ops:
        return []
    lo, hi = win
    busy = union(_clip(trace.device_ops[min(trace.device_ops)], lo, hi))
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    # the harness's spans follow one another on one thread, so the span
    # that covers most of a gap is among the few that touch it
    spans = sorted((s for s in trace.spans
                    if s[0] != SPAN_PREFIX + "trace_window"),
                   key=lambda s: s[1])
    ends = [s[1] + s[2] for s in spans]
    out: Dict[str, float] = {}
    for a, b in gaps:
        best, best_cover = "_no_span_", 0.0
        i = bisect.bisect_right(ends, a)
        while i < len(spans) and spans[i][1] < b:
            name, start, dur = spans[i]
            cover = min(b, start + dur) - max(a, start)
            if cover > best_cover:
                best, best_cover = name, cover
            i += 1
        out[best] = out.get(best, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]
