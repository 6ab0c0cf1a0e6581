"""Render a telemetry JSONL stream into a numerics health report.

The numerics monitor (``apex_tpu.telemetry.numerics``) streams structured
``anomaly`` / ``numerics_health`` / ``activation`` events (alongside the
PR-2 ``metrics`` records) into the recorder sinks; this tool folds one
such JSONL file into a per-leaf / per-tap health table with
first-bad-step attribution — the "which tensor, which layer, which step"
answer the reference amp never gives.

Usage::

    python tools/health_report.py run.jsonl            # human table
    python tools/health_report.py run.jsonl --json     # machine-readable

The aggregation core (:func:`health_from_records`) is pure and
unit-tested on canned records (``tests/test_numerics.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from typing import Iterable, List, Optional

# script-mode invocation (`python tools/health_report.py ...`) puts
# tools/ at sys.path[0]; the repo root must be importable for apex_tpu
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _num(v):
    """JSONL round-trips non-finite floats as repr strings ('nan'/'inf')
    — see telemetry.recorder._jsonable. Fold them back to floats."""
    if isinstance(v, str):
        try:
            return float(v)
        except ValueError:
            return None
    return v


def health_from_records(records: Iterable[dict]) -> dict:
    """Fold telemetry records into the health summary.

    Returns::

        {
          "steps_seen": int,            # max step observed anywhere
          "first_bad_step": int|None,   # first nonfinite_grads step
          "anomalies": [...],           # the anomaly events, in order
          "anomaly_counts": {kind: n},
          "leaves": {name: {"first_bad_step", "nonfinite_events",
                            "last_norm", "last_maxabs", "max_maxabs"}},
          "taps": {(name, layer) keys as "name[@layer]":
                   {"events", "nonfinite_events", "first_bad_step",
                    "max_maxabs", "last_norm"}},
          "run": {...}                  # last metrics-record snapshot
        }
    """
    step_stamps: dict = defaultdict(list)
    leaves: dict = defaultdict(lambda: {
        "first_bad_step": None, "nonfinite_events": 0,
        "last_norm": None, "last_maxabs": None, "max_maxabs": None})
    taps: dict = defaultdict(lambda: {
        "events": 0, "nonfinite_events": 0, "first_bad_step": None,
        "max_maxabs": None, "last_norm": None})
    anomalies: List[dict] = []
    counts: dict = defaultdict(int)
    run: dict = {}
    steps_seen = 0
    first_bad: Optional[int] = None

    def _maxok(cur, v):
        return v if cur is None or (v is not None and v > cur) else cur

    for r in records:
        ev = r.get("event")
        step = r.get("step")
        if isinstance(step, int):
            steps_seen = max(steps_seen, step)
        if ev == "anomaly":
            anomalies.append(r)
            counts[r.get("kind", "?")] += 1
            if r.get("kind") == "nonfinite_grads":
                if first_bad is None and isinstance(step, int):
                    first_bad = step
                for leaf in r.get("leaves", []):
                    d = leaves[leaf["name"]]
                    d["nonfinite_events"] += 1
                    if d["first_bad_step"] is None:
                        d["first_bad_step"] = step
                    d["last_norm"] = _num(leaf.get("norm"))
                    d["last_maxabs"] = _num(leaf.get("maxabs"))
        elif ev == "numerics_health":
            for name, st in (r.get("leaves") or {}).items():
                d = leaves[name]
                d["last_norm"] = _num(st.get("norm"))
                d["last_maxabs"] = _num(st.get("maxabs"))
                d["max_maxabs"] = _maxok(
                    d["max_maxabs"], _num(st.get("maxabs")))
                if _num(st.get("nonfinite")):
                    d["nonfinite_events"] += 1
                    if d["first_bad_step"] is None:
                        d["first_bad_step"] = step
        elif ev == "activation":
            key = r["name"]
            if r.get("layer") is not None:
                key = f"{key}@layer{r['layer']}"
            d = taps[key]
            d["events"] += 1
            d["max_maxabs"] = _maxok(d["max_maxabs"], _num(r.get("maxabs")))
            d["last_norm"] = _num(r.get("norm"))
            if _num(r.get("nonfinite")):
                d["nonfinite_events"] += 1
                if d["first_bad_step"] is None:
                    d["first_bad_step"] = step
            # packed-buffer taps attribute leaves too
            for leaf in r.get("leaves") or []:
                ld = leaves[leaf["name"]]
                ld["nonfinite_events"] += 1
                if ld["first_bad_step"] is None:
                    ld["first_bad_step"] = step
        elif ev == "metrics":
            run = {k: r[k] for k in (
                "step", "loss", "loss_scale", "overflow_skips",
                "scale_growths", "grad_norm") if k in r}
        elif ev == "step" and isinstance(r.get("t_dispatch"), (int, float)):
            step_stamps[r.get("leg") or "?"].append(float(r["t_dispatch"]))

    # per-leg percentiles over gaps between a trainer's per-step
    # t_dispatch stamps, via the shared telemetry.percentiles reducer
    # (no hand-rolled percentile math here or in the serving leg).
    # These are DISPATCH intervals — the stamps are taken host-side
    # with no sync, so on an async backend they measure how
    # fast the host issues steps, not how long the device takes; true
    # step time is the leg summary's step_ms.
    from apex_tpu.telemetry import percentiles

    dispatch_interval_ms = {
        leg: percentiles([1e3 * (b - a) for a, b in zip(ts, ts[1:])])
        for leg, ts in step_stamps.items() if len(ts) >= 2
    }
    dispatch_interval_ms = {
        k: v for k, v in dispatch_interval_ms.items() if v}

    return {
        "dispatch_interval_ms": dispatch_interval_ms,
        "steps_seen": steps_seen,
        "first_bad_step": first_bad,
        "anomalies": anomalies,
        "anomaly_counts": dict(counts),
        "leaves": {k: dict(v) for k, v in leaves.items()},
        "taps": {k: dict(v) for k, v in taps.items()},
        "run": run,
    }


def _fmt(v, nd=4):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}g}"
    return str(v)


def format_table(headers: List[str], rows: List[List]) -> str:
    cells = [[_fmt(c) for c in row] for row in rows]
    widths = [max(len(h), *(len(r[i]) for r in cells)) if cells else len(h)
              for i, h in enumerate(headers)]
    line = "  ".join(h.ljust(w) for h, w in zip(headers, widths))
    sep = "  ".join("-" * w for w in widths)
    body = "\n".join(
        "  ".join(c.ljust(w) for c, w in zip(row, widths)) for row in cells)
    return "\n".join([line, sep, body]) if cells else "\n".join([line, sep])


def render_report(h: dict) -> str:
    out = []
    fb = h["first_bad_step"]
    out.append(f"steps seen: {h['steps_seen']}   "
               f"first bad step: {fb if fb is not None else 'never'}")
    if h["anomaly_counts"]:
        out.append("anomalies: " + ", ".join(
            f"{k}={v}" for k, v in sorted(h["anomaly_counts"].items())))
    if h["run"]:
        out.append("last metrics: " + ", ".join(
            f"{k}={_fmt(v)}" for k, v in h["run"].items()))
    if h.get("dispatch_interval_ms"):
        for leg, ps in sorted(h["dispatch_interval_ms"].items()):
            out.append(f"dispatch interval [{leg}]: " + ", ".join(
                f"{k}={_fmt(v)}ms" for k, v in ps.items()))
    if h["leaves"]:
        out.append("\nper-tensor health (grads)")
        rows = [
            [name, d["first_bad_step"], d["nonfinite_events"],
             d["last_norm"], d["last_maxabs"]]
            for name, d in sorted(
                h["leaves"].items(),
                key=lambda kv: (kv[1]["first_bad_step"] is None,
                                kv[1]["first_bad_step"], kv[0]))
        ]
        out.append(format_table(
            ["tensor", "first_bad", "nonfinite_events", "last_norm",
             "last_max|g|"], rows))
    if h["taps"]:
        out.append("\nactivation watch (per tap/layer)")
        rows = [
            [name, d["events"], d["first_bad_step"], d["nonfinite_events"],
             d["max_maxabs"]]
            for name, d in sorted(h["taps"].items())
        ]
        out.append(format_table(
            ["tap", "events", "first_bad", "nonfinite_events",
             "max_max|x|"], rows))
    if not h["leaves"] and not h["taps"] and not h["anomalies"]:
        out.append("no numerics events in this stream — healthy run "
                   "(or the monitor was not enabled)")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Numerics health report from a telemetry JSONL stream")
    ap.add_argument("jsonl", help="telemetry JSONL file (bench or train)")
    ap.add_argument("--json", action="store_true",
                    help="emit the aggregate as JSON instead of a table")
    args = ap.parse_args(argv)
    from apex_tpu.telemetry import read_jsonl

    h = health_from_records(read_jsonl(args.jsonl))
    if args.json:
        json.dump(h, sys.stdout, indent=2, default=str)
        print()
    else:
        print(render_report(h))
    # exit code: 1 when the run saw non-finite grads (CI-gateable)
    return 1 if h["first_bad_step"] is not None else 0


if __name__ == "__main__":
    sys.exit(main())
