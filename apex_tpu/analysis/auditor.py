"""Trace a training/optimizer step and audit it — no execution, CPU-only.

``audit_step(fn, *args)`` runs ``jax.make_jaxpr`` on the step (a pure
trace: no kernels launch, no TPU is touched, abstract
``ShapeDtypeStruct`` args work), reconstructs the donation picture from
the traced ``pjit`` equation (or an explicit ``donate_argnums``), and
walks the program with the rule families in :mod:`.rules`. The PR-1..3
performance story rests on invariants nothing else checks — packed
buffers donated, callbacks cond-gated, matmuls in low precision,
PackSpec ROW-aligned; this pass enforces them mechanically at test time
("audit the program, not the run").

Usage::

    from apex_tpu import analysis

    report = analysis.audit_step(train_step, params, opt_state, batch)
    print(report.table())
    assert report.ok                      # no error-severity findings

    # or as a one-line pytest gate:
    analysis.assert_step_clean(train_step, params, opt_state, batch)

``fn`` may be jit-wrapped (donation is read from its traced
``donated_invars``) or a plain function (pass ``donate_argnums=`` the
way you would to ``jax.jit``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax

from ..multi_tensor_apply.packing import PackSpec
from ..optimizers._packed import PackedState
from .report import AuditReport, Finding, SEVERITIES, _SEV_RANK
from .rules import RULES, AuditConfig
from .walk import collect_consts

Pytree = Any


@dataclasses.dataclass
class StepTrace:
    """Everything the rules need, captured once per audited step."""

    name: str
    closed: Any                       # ClosedJaxpr of the whole step
    leaves: List[Any]                 # flat input leaves (concrete or SDS)
    paths: List[str]                  # human path per leaf ("[0].w" ...)
    argnums: List[int]                # top-level argnum per leaf
    donated: List[bool]               # per leaf
    state_leaf_ids: frozenset         # leaf indices inside *State containers
    pack_specs: List[PackSpec]
    consts: List[Any]

    @property
    def in_avals(self):
        return self.closed.in_avals

    @property
    def out_avals(self):
        return self.closed.out_avals


def _is_state_container(x) -> bool:
    """This repo's optimizer/telemetry state convention: PackedState or a
    NamedTuple whose type name ends in 'State' (FusedAdamState,
    MetricsState, ...)."""
    if isinstance(x, PackedState):
        return True
    return (isinstance(x, tuple) and hasattr(x, "_fields")
            and type(x).__name__.endswith("State"))


def _flatten_args(args: Tuple) -> Tuple[List[Any], List[str], List[int]]:
    flat = jax.tree_util.tree_flatten_with_path(tuple(args))[0]
    leaves, paths, argnums = [], [], []
    for path, leaf in flat:
        leaves.append(leaf)
        argnum = getattr(path[0], "idx", 0) if path else 0
        argnums.append(int(argnum))
        paths.append("[" + str(argnum) + "]"
                     + jax.tree_util.keystr(path[1:]))
    return leaves, paths, argnums


def _state_leaf_ids(args: Tuple, leaves: List[Any]) -> frozenset:
    containers: List[Any] = []

    def is_leaf(x):
        if _is_state_container(x):
            containers.append(x)
            return True
        return False

    jax.tree_util.tree_flatten(tuple(args), is_leaf=is_leaf)
    state_ids = set()
    for c in containers:
        for leaf in jax.tree_util.tree_leaves(c):
            state_ids.add(id(leaf))
    return frozenset(
        i for i, leaf in enumerate(leaves) if id(leaf) in state_ids)


def _collect_pack_specs(args: Tuple) -> List[PackSpec]:
    specs: List[PackSpec] = []

    def is_leaf(x):
        if isinstance(x, PackedState):
            specs.append(x.spec)
            return True
        return False

    jax.tree_util.tree_flatten(tuple(args), is_leaf=is_leaf)
    # dedupe by IDENTITY, not __eq__: PackSpec equality keys on the
    # construction inputs (treedef/shapes/chunk), so a corrupted copy of
    # a clean spec still compares equal — and must still be audited
    out: List[PackSpec] = []
    for s in specs:
        if not any(s is o for o in out):
            out.append(s)
    return out


def _donated_flags(closed, n_leaves: int, args: Tuple,
                   donate_argnums: Optional[Sequence[int]]) -> List[bool]:
    """Donation per flat input leaf.

    Two sources, or-ed: an explicit ``donate_argnums`` (the plain-fn
    spelling), and the ``donated_invars`` of the traced ``pjit``
    equation when ``fn`` was already jit-wrapped — read straight from
    the jaxpr, so the audit needs no lowering and works identically on
    every backend.
    """
    flags = [False] * n_leaves
    if donate_argnums:
        donate = set(int(d) for d in donate_argnums)
        flat = jax.tree_util.tree_flatten_with_path(tuple(args))[0]
        for i, (path, _) in enumerate(flat):
            argnum = getattr(path[0], "idx", 0) if path else 0
            if int(argnum) in donate:
                flags[i] = True
    jaxpr = closed.jaxpr
    # jax 0.9 spells the jit call primitive "jit" (older: "pjit")
    if len(jaxpr.eqns) == 1 and jaxpr.eqns[0].primitive.name == "jit":
        eqn = jaxpr.eqns[0]
        don = eqn.params.get("donated_invars")
        if don is not None:
            by_var = {id(v): bool(d) for v, d in zip(eqn.invars, don)}
            for i, v in enumerate(jaxpr.invars[:n_leaves]):
                flags[i] = flags[i] or by_var.get(id(v), False)
    return flags


def trace_step(fn: Callable, *args, donate_argnums=None,
               name: str = "step") -> StepTrace:
    """Trace ``fn(*args)`` and capture the audit surface."""
    closed = jax.make_jaxpr(fn)(*args)
    leaves, paths, argnums = _flatten_args(args)
    if len(leaves) != len(closed.in_avals):
        raise ValueError(
            f"flattened args ({len(leaves)} leaves) do not line up with "
            f"the traced program ({len(closed.in_avals)} inputs) — "
            "static/aux arguments are not supported; close over them "
            "with functools.partial")
    return StepTrace(
        name=name,
        closed=closed,
        leaves=leaves,
        paths=paths,
        argnums=argnums,
        donated=_donated_flags(closed, len(leaves), args, donate_argnums),
        state_leaf_ids=_state_leaf_ids(args, leaves),
        pack_specs=_collect_pack_specs(args),
        consts=collect_consts(closed),
    )


def audit_step(
    fn: Callable,
    *args,
    donate_argnums: Optional[Sequence[int]] = None,
    rules: Optional[Sequence[str]] = None,
    name: str = "step",
    pack_specs: Optional[Sequence[PackSpec]] = None,
    min_bytes: int = 64 * 1024,
    const_bytes: int = 1 << 20,
    const_bytes_error: int = 64 << 20,
    compute_dtype: Optional[str] = None,
    strict_dtype: bool = False,
    shard_count: Optional[int] = None,
    collective_budget=None,
    replicated_bytes: int = 1 << 20,
    loop_collective_threshold: int = 4,
) -> AuditReport:
    """Statically audit one training/optimizer step. See module docs.

    ``rules`` selects rule families (default: all of
    ``analysis.RULES``). ``compute_dtype`` pins the amp policy for the
    dtype rule ("bfloat16"/"float16"/"float32"); ``None`` infers it from
    the step's own matmul mix. ``min_bytes`` is the noise floor: buffers
    smaller than this never produce donation/dtype findings.
    ``collective_budget`` declares the program's communication contract
    (a :class:`~apex_tpu.analysis.CollectiveBudget`: exact per-kind eqn
    counts, allowed named axes, per-gather byte cap) for the
    ``collectives`` rule; ``replicated_bytes`` is the floor above which
    a fully replicated shard_map operand is reported by ``sharding``.
    """
    unknown = set(rules or ()) - set(RULES)
    if unknown:
        raise ValueError(
            f"unknown rules {sorted(unknown)}; available: {sorted(RULES)}")
    trace = trace_step(fn, *args, donate_argnums=donate_argnums, name=name)
    if pack_specs:
        for s in pack_specs:
            if not any(s is o for o in trace.pack_specs):
                trace.pack_specs.append(s)
    cfg = AuditConfig(
        min_bytes=min_bytes,
        const_bytes=const_bytes,
        const_bytes_error=const_bytes_error,
        compute_dtype=compute_dtype,
        strict_dtype=strict_dtype,
        shard_count=shard_count,
        collective_budget=collective_budget,
        replicated_bytes=replicated_bytes,
        loop_collective_threshold=loop_collective_threshold,
    )
    selected = tuple(rules) if rules else tuple(RULES)
    findings: List[Finding] = []
    for r in selected:
        findings.extend(RULES[r](trace, cfg))
    return AuditReport(name, findings, rules_run=selected)


def assert_step_clean(fn: Callable, *args, severity: str = "error",
                      **kwargs) -> AuditReport:
    """Pytest helper: audit ``fn(*args)`` and fail on findings at or
    above ``severity`` ("error" gates errors only; "warning" gates
    warnings too). Returns the report for further assertions. All
    :func:`audit_step` keywords pass through.
    """
    if severity not in SEVERITIES:
        raise ValueError(f"severity must be one of {SEVERITIES}")
    report = audit_step(fn, *args, **kwargs)
    bad = [f for f in report.findings
           if _SEV_RANK[f.severity] <= _SEV_RANK[severity]]
    if bad:
        raise AssertionError(
            f"step audit found {len(bad)} finding(s) at severity "
            f">= {severity}:\n{report.table()}")
    return report
