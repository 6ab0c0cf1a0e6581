"""Per-op device-time breakdown for a jitted step via the JAX profiler.

The reference publishes per-kernel timings through nvprof/nsys and the
Megatron timers (``apex/transformer/pipeline_parallel/_timers.py`` usage
in the fork's scaling scripts); the TPU analogue is an xplane trace. The
implementation now lives in :mod:`apex_tpu.telemetry.tracing` (so the
parser unit-tests on canned fixtures and trace sessions are a library
feature); this module remains the script-facing entry point and keeps
its historical names.

Usage::

    from tools.op_breakdown import profile_step_breakdown
    table = profile_step_breakdown(step_fn, state, n_steps=3)

Returns ``{"source": "xplane", "device_ms_per_step": float, "ops":
[{"op", "category", "ms_per_step", "pct"}, ...], "categories": {...},
"scopes": {layer scope: {"ms_per_step", "pct", "phases": {...}}}}`` on
TPU (``scopes``: device time by the ``apex_tpu.<layer>`` named scopes of
``telemetry.tracing.LAYER_SCOPES``, forward / backward / recompute apart). On backends with no device plane (CPU CI) it now returns the
``Compiled.cost_analysis()`` flops/bytes attribution (``"source":
"cost_analysis"``) instead of ``None`` — every environment gets a table.

Category attribution (round-5 VERDICT fix): generic ``%fusion.N`` ops
are no longer all booked as "fusion(elementwise)" — the profiler's own
per-op ``hlo_category`` stat (XLA derives it from the fused
computation's root op) drives the bucket, so a fusion whose root is a
dot/convolution lands in "matmul/conv". Without the stat, a generic
fusion falls back to the ``calls=%...`` callee name in the HLO text,
and failing that is reported honestly as "fusion(unattributed)" rather
than claimed elementwise. Pinned by the golden xplane fixtures in
``tests/test_op_breakdown.py``.
"""
from __future__ import annotations

from apex_tpu.telemetry.tracing import (  # noqa: F401
    LAYER_SCOPES,
    aggregate_op_times,
    aggregate_scope_times,
    breakdown_table,
    categorize_op,
    cost_analysis_breakdown,
    iter_xplane_events,
    parse_xspace_op_times,
    profile_step,
    scope_index,
    scope_of,
    short_op_name,
    trace_session,
)

# historical private names (pinned by tests/test_op_breakdown.py)
_short_op_name = short_op_name
_category = categorize_op


def profile_step_breakdown(step_fn, state, n_steps: int = 3, top: int = 10):
    """Trace ``n_steps`` chained executions of ``step_fn`` and return the
    top-``top`` ops by device self-time (XLA Ops line; ops on that line
    are leaf HLO instructions, so durations are self-times), falling back
    to the static cost-analysis attribution off-TPU."""
    return profile_step(step_fn, state, n_steps=n_steps, top=top)
