"""apex_tpu.telemetry: training-run observability.

Four parts, designed so instrumentation costs nothing on the hot path:

- :mod:`~apex_tpu.telemetry.metrics` — a jit-resident
  :class:`MetricsState` pytree accumulated on device inside the step
  function and drained every N steps through an async
  ``jax.debug.callback`` (zero extra host syncs);
- :mod:`~apex_tpu.telemetry.recorder` — host sinks (JSONL writer, ring
  buffer, fan-out) with rank-0 gating and the ``add_scalar`` writer
  protocol ``Timers.write`` expects;
- :mod:`~apex_tpu.telemetry.tracing` — ``trace_session`` /
  ``profile_step`` around ``jax.profiler`` with a categorized per-op
  device-time table (xplane) and a ``cost_analysis()`` flops/bytes
  fallback off-TPU;
- :mod:`~apex_tpu.telemetry.pipeline` — pipeline bubble accounting:
  analytic warmup/steady/cooldown timelines per rank and a measured
  :class:`TickTimeline` fed by the schedules' ``tick_hook``;
- :mod:`~apex_tpu.telemetry.spans` — end-to-end request tracing over
  the recorder sinks: :class:`Tracer`/:class:`TraceContext` span
  records (deterministic under ``VirtualClock``), the exact-sum
  latency-attribution ledger, and the bounded flight-recorder ring
  dumped as a black box on hangs/crashes;
- :mod:`~apex_tpu.telemetry.timeseries` / :mod:`~apex_tpu.telemetry.slo`
  / :mod:`~apex_tpu.telemetry.alerts` — the fleet health plane:
  bounded-memory labeled aggregation over the recorder stream
  (counters/gauges + log-bucket histograms with exact deterministic
  merges), SLO error budgets with multi-window multi-burn-rate alert
  evaluation, and the :class:`AlertManager` that routes firing alerts
  to the fleet's proven actuators (degradation, replica restart,
  rolling-update abort, supervisor escalation);
- :mod:`~apex_tpu.telemetry.numerics` — the numerics health monitor:
  per-tensor overflow provenance (pytree and packed flat-buffer paths),
  opt-in activation-watch taps, and an anomaly-rule engine
  (non-finite grads / grad-norm spike / loss-scale collapse) emitting
  structured events through the same cond-gated async drain path;
- :mod:`~apex_tpu.telemetry.compiles` — the compile ledger: jax's own
  trace, lower and compile-or-load spans by function on
  ``perf_counter``'s clock and the persistent cache's counters, listening
  from the moment this package is imported (no switch); ``steady()``
  marks the end of warm-up, after which a recompile is counted and logged.

See ``docs/observability.md`` for the end-to-end story.
"""
from . import compiles, numerics  # noqa: F401
from .metrics import (  # noqa: F401
    MetricsState,
    accumulate,
    drain,
    init_metrics,
    observe_scale_update,
    summarize,
)
from .numerics import (  # noqa: F401
    ActivationWatch,
    NumericsMonitor,
    NumericsState,
    activation_watch,
)
from .pipeline import (  # noqa: F401
    TickTimeline,
    analytic_bubble_fraction,
    bubble_report,
    classify_phase,
    schedule_ticks,
    tick_phases,
)
from .recorder import (  # noqa: F401
    JsonlRecorder,
    MultiRecorder,
    NullRecorder,
    RingBufferRecorder,
    TaggedRecorder,
    is_logging_process,
    percentiles,
    read_jsonl,
    stamp_wall,
)
from .alerts import (  # noqa: F401
    AlertManager,
    EscalationResponder,
    FleetResponder,
    HealthMonitor,
)
from .slo import (  # noqa: F401
    SLO,
    AlertState,
    ErrorBudget,
    SLOTracker,
    default_serving_slos,
)
from .spans import (  # noqa: F401
    ATTR_TERMS,
    TraceContext,
    Tracer,
    attr_account,
    attr_init,
    attr_snapshot_ttft,
    attribution_summary,
    dominant_cause,
)
from .timeseries import (  # noqa: F401
    BASE_LABELS,
    LogBucketHistogram,
    MetricsAggregator,
    format_labels,
    label_key,
)
from .tracing import (  # noqa: F401
    LAYER_SCOPES,
    TraceSession,
    aggregate_op_times,
    aggregate_scope_times,
    breakdown_table,
    categorize_op,
    cost_analysis_breakdown,
    parse_xspace_op_times,
    profile_step,
    scope_index,
    scope_of,
    short_op_name,
    trace_session,
)

# the ledger listens from here on: every program that trains imports this
# package (``optimizers/fused_adam.py``) before it compiles anything
compiles.install()

__all__ = [
    "compiles",
    "MetricsState", "accumulate", "drain", "init_metrics",
    "observe_scale_update", "summarize",
    "numerics", "NumericsMonitor", "NumericsState", "ActivationWatch",
    "activation_watch",
    "TickTimeline", "analytic_bubble_fraction", "bubble_report",
    "classify_phase", "schedule_ticks", "tick_phases",
    "JsonlRecorder", "MultiRecorder", "NullRecorder",
    "RingBufferRecorder", "TaggedRecorder", "is_logging_process",
    "percentiles", "read_jsonl", "stamp_wall",
    "ATTR_TERMS", "TraceContext", "Tracer", "attr_account", "attr_init",
    "attr_snapshot_ttft", "attribution_summary", "dominant_cause",
    "BASE_LABELS", "LogBucketHistogram", "MetricsAggregator",
    "format_labels", "label_key",
    "SLO", "AlertState", "ErrorBudget", "SLOTracker",
    "default_serving_slos",
    "AlertManager", "EscalationResponder", "FleetResponder",
    "HealthMonitor",
    "LAYER_SCOPES", "TraceSession", "aggregate_op_times",
    "aggregate_scope_times", "breakdown_table", "categorize_op",
    "cost_analysis_breakdown", "parse_xspace_op_times", "profile_step",
    "scope_index", "scope_of", "short_op_name", "trace_session",
]
