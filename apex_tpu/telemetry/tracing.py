"""Trace sessions and per-op device-time attribution.

The reference publishes per-kernel timings through nvprof/nsys ranges;
the TPU analogue is a ``jax.profiler`` xplane trace. This module owns

- :func:`trace_session` — a context manager around ``jax.profiler.trace``
  that yields a session handle whose :meth:`~TraceSession.op_breakdown`
  parses the captured device plane into a categorized top-op table;
- :func:`profile_step` — one-shot: run a step function ``n_steps`` times
  under a trace and return the breakdown table, falling back to the
  compiled step's ``cost_analysis()`` (flops/bytes attribution) on
  backends with no device plane (CPU CI) so every environment gets a
  table rather than ``None``;
- the pure xplane/HLO op-name helpers (:func:`short_op_name`,
  :func:`categorize_op`, :func:`aggregate_op_times`,
  :func:`breakdown_table`) — factored out of ``tools/op_breakdown.py``
  so they unit-test on canned fixtures without a TPU or tensorflow.

``tools/op_breakdown.py`` re-exports all of this for script use.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, Optional, Tuple


# ---------------------------------------------------------------------------
# pure helpers (fixture-testable, no jax/tf imports)
# ---------------------------------------------------------------------------

def short_op_name(hlo_text: str) -> str:
    """'%convolution_tanh_fusion.3 = bf16[...] ...' -> 'convolution_tanh_fusion'."""
    name = hlo_text.split(" = ", 1)[0].strip()
    name = name.lstrip("%")
    return re.sub(r"\.\d+$", "", name)


_CATEGORIES = (
    ("flash|attention", "attention-kernel"),
    ("custom-call", "custom-call"),
    ("convolution|dot|gemm|matmul|einsum", "matmul/conv"),
    ("all-reduce|all-gather|reduce-scatter|collective|permute|all-to-all",
     "collective"),
    ("copy|transpose|bitcast|reshape|data formatting", "data-movement"),
    ("scatter|gather|dynamic", "gather/scatter"),
    ("reduce", "reduce"),
    ("fusion|elementwise", "fusion(elementwise)"),
)

# container ops (while/conditional) span their body ops, which are ALSO
# events on the XLA Ops line — counting both would double the loop time
_CONTAINER_PREFIXES = ("while", "conditional")

# fusion names with no semantic content: XLA's generic auto-named
# fusions. "convolution_tanh_fusion" carries its ops in the name;
# "fusion"/"fused_computation" carry nothing — without an hlo_category
# hint they must NOT be claimed as elementwise (the round-5 table put
# 42.7% of the GPT step into "fusion(elementwise)" this way while the
# dense GEMMs were hiding inside those generic fusions; with MXU ops at
# the claimed 32% share, the measured true-MFU 0.533 would have been
# arithmetically impossible).
_GENERIC_FUSION = re.compile(r"^(loop_|input_|output_)?"
                             r"(fusion|fused_computation)$")


def categorize_op(op: str, hlo_category: Optional[str] = None,
                  raw: Optional[str] = None) -> str:
    """Category of one op, most-reliable signal first.

    1. An attention-kernel NAME (``apex_tpu_flash_*`` etc.): our named
       custom-call kernels keep their identity — the profiler's stat for
       them is just the generic "custom-call".
    2. ``hlo_category`` — the profiler's own per-op category stat from
       the xplane (XLA derives it from the fused computation's root op,
       e.g. ``"convolution fusion"``); authoritative when present.
    3. The op NAME, when it carries semantic content
       (``convolution_tanh_fusion`` -> matmul/conv).
    4. For a generic ``fusion.N``, the callee name inside the raw HLO
       text (``calls=%convolution_fusion.3``) when available.
    5. A generic fusion with no signal is reported honestly as
       ``fusion(unattributed)`` — never silently booked as elementwise.
    """
    if re.search(_CATEGORIES[0][0], op.lower()):
        return _CATEGORIES[0][1]
    if hlo_category:
        low = hlo_category.lower()
        for pat, cat in _CATEGORIES:
            if re.search(pat, low):
                return cat
    low = op.lower()
    if _GENERIC_FUSION.match(low):
        if raw:
            m = re.search(r"calls=%?([\w.-]+)", raw)
            if m:
                callee = re.sub(r"\.\d+$", "", m.group(1))
                if not _GENERIC_FUSION.match(callee.lower()):
                    return categorize_op(callee)
        return "fusion(unattributed)"
    for pat, cat in _CATEGORIES:
        if re.search(pat, low):
            return cat
    return "other"


def aggregate_op_times(
    events: Iterable[Tuple],
) -> Tuple[int, Dict[Tuple[str, str], int]]:
    """Fold raw xplane events into ``(total_ps, per_op)`` with
    ``per_op`` keyed ``(short_op_name, category)``, dropping container
    ops.

    Events are ``(hlo_op_text, duration_ps)`` or ``(hlo_op_text,
    duration_ps, hlo_category)`` — the third element is the profiler's
    per-op category stat, which disambiguates XLA's generic auto-named
    fusions (every ``%fusion.N`` shares one stripped name, but a
    convolution fusion and a loop fusion must NOT share one category —
    the round-5 misattribution). Keying by (name, category) keeps them
    separate through the merge.

    This is the parsing core of the xplane breakdown, taking already
    decoded events so it is unit-testable on a canned fixture (no
    tensorflow protobuf needed).
    """
    per_op: Dict[Tuple[str, str], int] = defaultdict(int)
    total = 0
    for item in events:
        raw, ps = item[0], int(item[1])
        hint = item[2] if len(item) > 2 else None
        name = short_op_name(raw)
        if name.startswith(_CONTAINER_PREFIXES):
            continue
        per_op[(name, categorize_op(name, hint, raw))] += ps
        total += ps
    return total, dict(per_op)


def _normalize_per_op(per_op) -> Dict[Tuple[str, str], int]:
    """Accept both the (name, category)-keyed dict and the legacy
    name-keyed dict (pre-fix captures, e.g. archived BENCH_r0* parsing)."""
    out: Dict[Tuple[str, str], int] = defaultdict(int)
    for k, ps in per_op.items():
        if isinstance(k, tuple):
            out[k] += int(ps)
        else:
            out[(k, categorize_op(k))] += int(ps)
    return dict(out)


def breakdown_table(total_ps: int, per_op, n_steps: int = 1,
                    top: int = 10) -> Optional[dict]:
    """The published table: top-``top`` ops + per-category totals.

    Ops on the device ``XLA Ops`` line are leaf HLO instructions, so
    durations are self-times. Returns ``None`` when nothing was captured.
    """
    if not total_ps:
        return None
    norm = _normalize_per_op(per_op)
    rows = sorted(norm.items(), key=lambda kv: -kv[1])
    ops = [
        {
            "op": name,
            "category": cat,
            "ms_per_step": round(ps / 1e9 / n_steps, 3),
            "pct": round(100.0 * ps / total_ps, 2),
        }
        for (name, cat), ps in rows[:top]
    ]
    by_cat: Dict[str, int] = defaultdict(int)
    for (name, cat), ps in norm.items():
        by_cat[cat] += ps
    categories = {
        cat: {
            "ms_per_step": round(ps / 1e9 / n_steps, 3),
            "pct": round(100.0 * ps / total_ps, 2),
        }
        for cat, ps in sorted(by_cat.items(), key=lambda kv: -kv[1])
    }
    return {
        "source": "xplane",
        "device_ms_per_step": round(total_ps / 1e9 / n_steps, 3),
        "ops": ops,
        "categories": categories,
    }


# ---------------------------------------------------------------------------
# xplane extraction (needs the tensorflow protobuf; TPU images have it)
# ---------------------------------------------------------------------------

def _stat_value(plane, st):
    """String value of one XStat, following ref_value indirection."""
    if st.str_value:
        return st.str_value
    if st.ref_value and st.ref_value in plane.stat_metadata:
        return plane.stat_metadata[st.ref_value].name
    return ""


def _event_hlo_category(plane, ev, md) -> Optional[str]:
    """The profiler's per-op category stat (``hlo_category``), from the
    event's stats or the event-metadata's constant stats. This is XLA's
    own attribution (derived from the fused computation's root op), so a
    generic ``%fusion.N`` whose root is a convolution reports
    "convolution fusion" — the signal the breakdown's categories key on.
    """
    for stats in (ev.stats, md.stats):
        for st in stats:
            smd = plane.stat_metadata.get(st.metadata_id)
            if smd is not None and smd.name == "hlo_category":
                return _stat_value(plane, st) or None
    return None


def iter_xplane_events(trace_dir: str):
    """Yield ``(raw_op_name, duration_ps, hlo_category_or_None)`` for
    every event on a device plane's ``XLA Ops`` line under ``trace_dir``.
    Empty iterator when the tensorflow protobuf is unavailable or nothing
    was captured."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception:  # tensorflow not present on this image
        return
    for path in glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ):
        xs = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xs.ParseFromString(f.read())
        for plane in xs.planes:
            if "/device:TPU" not in plane.name:
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    md = plane.event_metadata[ev.metadata_id]
                    yield (md.name, ev.duration_ps,
                           _event_hlo_category(plane, ev, md))


def parse_xspace_op_times(trace_dir: str):
    """Aggregate XLA-op self-times from every .xplane.pb under
    ``trace_dir``: ``(total_ps, {(op_name, category): ps})`` summed over
    all captured device planes and steps."""
    return aggregate_op_times(iter_xplane_events(trace_dir))


# ---------------------------------------------------------------------------
# sessions
# ---------------------------------------------------------------------------

class TraceSession:
    """Handle to one profiler capture (yielded by :func:`trace_session`)."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.active = True

    def op_breakdown(self, n_steps: int = 1, top: int = 10):
        """Parse the capture into a categorized table (after the ``with``
        block exits). ``None`` when no device plane was captured."""
        if self.active:
            raise RuntimeError(
                "trace_session is still active — parse after the with "
                "block exits (the profiler writes the xplane on stop)")
        total_ps, per_op = parse_xspace_op_times(self.logdir)
        return breakdown_table(total_ps, per_op, n_steps=n_steps, top=top)


@contextlib.contextmanager
def trace_session(logdir: Optional[str] = None):
    """Capture a ``jax.profiler`` trace around a block of training code.

    Yields a :class:`TraceSession`; after the block exits, call
    ``session.op_breakdown(n_steps=...)`` for the categorized device-time
    table, or point ``tensorboard --logdir`` / Perfetto at
    ``session.logdir`` for the full timeline (named scopes from
    ``jax.named_scope`` — ``apex_tpu.flash_attention``,
    ``apex_tpu.packed_adam``, ``apex_tpu.pipeline_rounds``, ... —
    annotate the op names).

    ::

        with telemetry.trace_session("/tmp/trace") as sess:
            for _ in range(3):
                state = step(*state)
            jax.block_until_ready(state)
        table = sess.op_breakdown(n_steps=3)
    """
    import jax

    d = logdir or tempfile.mkdtemp(prefix="apex_tpu_trace_")
    session = TraceSession(d)
    try:
        with jax.profiler.trace(d):
            yield session
    finally:
        # the profiler has stopped (and written the xplane) even when
        # the traced block raised — the partial capture stays parseable
        session.active = False


def cost_analysis_breakdown(step_fn, state) -> Optional[dict]:
    """Static flops/bytes attribution from ``Compiled.cost_analysis()``.

    The off-TPU fallback: no device timeline exists on the CPU backend,
    but XLA's post-optimization cost model still attributes the step's
    algorithmic work — enough for CI to catch a step whose flops or
    traffic regress. Returns ``None`` only if even compilation fails.
    """
    import jax

    try:
        lower = getattr(step_fn, "lower", None)
        if lower is None:
            lower = jax.jit(step_fn).lower
        ca = lower(*state).compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0] if ca else {}
        ca = dict(ca or {})
    except Exception:
        return None
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    return {
        "source": "cost_analysis",
        "device_ms_per_step": None,  # static model: no timing off-TPU
        "flops_per_step": flops,
        "gflops_per_step": round(flops / 1e9, 3),
        "bytes_accessed_per_step": bytes_accessed,
        "transcendentals_per_step": float(ca.get("transcendentals", 0.0)),
        "arithmetic_intensity": (
            round(flops / bytes_accessed, 3) if bytes_accessed else None),
        "ops": [],
        "categories": {},
    }


def profile_step(step_fn, state, n_steps: int = 3, top: int = 10):
    """One-shot step profile: trace ``n_steps`` chained executions and
    return the top-``top`` device-time table. Off-TPU there is no device
    plane and the answer is the static ``cost_analysis()`` attribution;
    on a TPU a trace without a device plane is an error — a caller on
    the chip is never handed the static table in a device table's place.

    ``step_fn(*state) -> state`` must be chainable (the bench step
    contract). The final state is fenced inside the trace so every step
    is captured.
    """
    import jax

    if jax.default_backend() != "tpu":
        # no device plane exists to capture — skip the n_steps of traced
        # execution entirely and go straight to the static attribution
        return cost_analysis_breakdown(step_fn, state)
    with trace_session() as sess:
        cur = state
        for _ in range(n_steps):
            cur = step_fn(*cur)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x,
            cur[-1],
        )
    table = sess.op_breakdown(n_steps=n_steps, top=top)
    if table is None:
        raise RuntimeError(
            f"profile_step traced {n_steps} steps on a TPU but the "
            "profile holds no readable device plane (is the xplane "
            "protobuf reader importable?)")
    return table
