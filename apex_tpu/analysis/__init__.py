"""apex_tpu.analysis: static auditing of traced training steps.

The invariants PRs 1–3 rely on — packed state donated into the jitted
step, debug callbacks cond-gated, matmuls in low precision, PackSpec
ROW/chunk alignment — are enforced here mechanically, by tracing the
step with ``jax.make_jaxpr`` (no execution, runs on CPU) and walking
the jaxpr. Audit the program, not the run.

Entry points:

- :func:`audit_step` — trace + run the rule families, returns an
  :class:`AuditReport` of structured :class:`Finding` records;
- :func:`assert_step_clean` — the pytest one-liner (raises on findings
  at/above a severity);
- :func:`check_pack_spec` — standalone :class:`PackSpec` verification
  (the ROADMAP sharded-packed precondition);
- :func:`comm_volume` — static per-program
  ``{collective: {count, bytes, axes}}`` report (the serving psum pins
  are stated in it);
- :func:`check_shard_specs` — standalone PartitionSpec-vs-mesh
  verification (the mesh-rebase pre-trace gate);
- :func:`kernel_inventory` — every ``pallas_call`` a program traces
  to, with its name and whether it is compiled or interpreted (what
  ``chip_smoke.py`` asserts the kernels from);
- ``RULES`` — the rule registry (``donation``, ``host_sync``,
  ``dtype_flow``, ``constants``, ``packing``, ``scopes``,
  ``collectives``, ``sharding``).

CLI: ``python tools/static_audit.py --self`` audits the repo's own
headline steps (CI-gateable exit codes). See ``docs/static_analysis.md``.
"""
from .auditor import (  # noqa: F401
    StepTrace,
    assert_step_clean,
    audit_step,
    trace_step,
)
from .collectives import (  # noqa: F401
    CollectiveBudget,
    CollectiveRecord,
    check_collective_budget,
    check_shard_specs,
    collective_inventory,
    comm_volume,
)
from .report import AuditReport, Finding, SEVERITIES  # noqa: F401
from .rules import (  # noqa: F401
    RULES,
    AuditConfig,
    check_pack_spec,
    check_reshard,
)
from .walk import (  # noqa: F401
    KernelRecord,
    WalkCtx,
    collect_consts,
    kernel_inventory,
    walk,
)

__all__ = [
    "AuditConfig",
    "AuditReport",
    "CollectiveBudget",
    "CollectiveRecord",
    "Finding",
    "KernelRecord",
    "RULES",
    "SEVERITIES",
    "StepTrace",
    "WalkCtx",
    "assert_step_clean",
    "audit_step",
    "check_collective_budget",
    "check_pack_spec",
    "check_reshard",
    "check_shard_specs",
    "collect_consts",
    "collective_inventory",
    "comm_volume",
    "kernel_inventory",
    "trace_step",
    "walk",
]
