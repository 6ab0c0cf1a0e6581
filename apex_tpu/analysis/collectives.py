"""Mesh-aware collective & sharding rules (ISSUE-19 contract).

The serving engine's 3-psums-per-program pin and the DDP
psum-count==n_buckets pin are *collective budgets*: statements about how
many reductions a traced program is allowed to contain and which named
axes they may cross. Until now they leaned on textual
``str(jaxpr).count("psum")`` matching — which also matches "psum" inside
scope strings and cannot see axes or bytes. This module walks the traced
program instead (same "audit the program, not the run" contract as the
rest of :mod:`apex_tpu.analysis`):

- :func:`collective_inventory` — every collective equation in a jaxpr
  (``psum`` / ``all_gather`` / ``ppermute`` / ``all_to_all`` / ``pmax``
  / ``pmin`` / ``reduce_scatter``) with its named axes, operand avals
  and static output bytes, found at any nesting depth (pjit, shard_map,
  cond branches, scan/while bodies).
- :func:`comm_volume` — the public per-program
  ``{collective: {count, bytes, axes}}`` report; trace-time only, no
  execution, CPU-safe. Loop bodies are counted once (static program
  shape, matching the pinned-count convention). Bytes follow the
  repo-wide convention of ``tests/test_comm_volume.py``: each collective
  is charged its OUTPUT buffer size.
- :class:`CollectiveBudget` + :func:`rule_collectives` — budget
  enforcement (exact count pins, allowed axes, per-gather byte caps)
  plus the always-on SPMD lints: collectives appearing in only one
  branch of a ``lax.cond`` (divergence/deadlock hazard — one shard
  takes the branch, its peers do not, and the collective hangs) and
  per-leaf collectives inside scan/loop bodies (the pre-bucketing
  anti-pattern ``GradBuckets`` exists to kill).
- :func:`check_shard_specs` + :func:`rule_sharding` — PartitionSpec
  validation against the mesh (axis exists, sharded dim divisible,
  duplicate axis use), the Megatron pairing lint (a psum whose input
  chain reaches another psum over the same axis with no matmul between
  double-counts by the axis size — ``column → row → exactly one psum
  tail``), and bytes-ranked warnings for large replicated shard_map
  operands a named axis could shard (the scouting report for the
  training-half mesh rebase).
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from .report import Finding
from .walk import (
    _LOOPING,
    name_stack_str,
    subjaxprs,
    transparent_subjaxprs,
    walk,
    WalkCtx,
)

# every named-axis communication primitive jax emits for the lax
# collectives (psum_scatter lowers to ``reduce_scatter``)
COLLECTIVE_PRIMS = (
    "psum", "all_gather", "ppermute", "all_to_all", "pmax", "pmin",
    "reduce_scatter",
)
# reductions whose per-leaf use inside a loop body is the bucketing
# anti-pattern (gathers/permutes in loops are pipeline schedules, not
# gradient sync)
_REDUCTION_PRIMS = ("psum", "pmax", "pmin", "reduce_scatter")
_MATMUL_PRIMS = ("dot_general", "conv_general_dilated")
_GATHER_PRIMS = ("all_gather", "all_to_all")


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def collective_axes(eqn) -> Tuple[str, ...]:
    """Named axes of one collective eqn ('axes' on psum/pmax/pmin,
    'axis_name' on the rest; either may be a bare name or a tuple, and
    vmap can add positional ints, which are not *named* axes)."""
    ax = eqn.params.get("axes", eqn.params.get("axis_name"))
    if ax is None:
        return ()
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


@dataclasses.dataclass(frozen=True)
class CollectiveRecord:
    """One collective equation found in the traced program."""

    name: str                  # primitive name ("psum", "all_gather", ...)
    axes: Tuple[str, ...]      # named axes it communicates over
    in_bytes: int              # total operand bytes
    out_bytes: int             # total result bytes (the charged volume)
    where: str                 # name stack or structural path
    cond_depth: int = 0
    loop_depth: int = 0

    @property
    def axes_key(self) -> str:
        return ",".join(self.axes)


def collective_inventory(jaxpr, ctx: WalkCtx = WalkCtx()
                         ) -> List[CollectiveRecord]:
    """Every collective eqn in ``jaxpr`` (recursive, each counted once)."""
    out: List[CollectiveRecord] = []
    for eqn, ectx in walk(jaxpr, ctx):
        if eqn.primitive.name not in COLLECTIVE_PRIMS:
            continue
        out.append(CollectiveRecord(
            name=eqn.primitive.name,
            axes=collective_axes(eqn),
            in_bytes=sum(_aval_bytes(v.aval) for v in eqn.invars
                         if hasattr(v, "aval")),
            out_bytes=sum(_aval_bytes(v.aval) for v in eqn.outvars),
            where=name_stack_str(eqn) or ectx.describe(),
            cond_depth=ectx.cond_depth,
            loop_depth=ectx.loop_depth,
        ))
    return out


def _aggregate(inventory: Sequence[CollectiveRecord]) -> Dict[str, Dict]:
    agg: Dict[str, Dict] = {}
    for rec in inventory:
        a = agg.setdefault(rec.name, {"count": 0, "bytes": 0, "axes": set()})
        a["count"] += 1
        a["bytes"] += rec.out_bytes
        a["axes"].update(rec.axes)
    return {name: {"count": a["count"], "bytes": a["bytes"],
                   "axes": sorted(a["axes"])}
            for name, a in sorted(agg.items())}


def comm_volume(fn, *args) -> Dict[str, Dict]:
    """Static per-program communication report.

    Traces ``fn(*args)`` with ``jax.make_jaxpr`` (no execution; abstract
    ``ShapeDtypeStruct`` args work) and returns
    ``{collective: {"count": int, "bytes": int, "axes": [str, ...]}}``
    over every collective primitive in the program. Equations inside
    scan/while bodies are counted once — this is the *program's* shape,
    the quantity the serving psum pins are stated in, not a
    per-iteration runtime volume.
    """
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return _aggregate(collective_inventory(closed.jaxpr))


# ---------------------------------------------------------------------------
# collective budgets
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class CollectiveBudget:
    """Declared communication contract for one program.

    ``counts`` pins the exact static eqn count per collective kind —
    kinds absent from the mapping are pinned at zero, so a program that
    grows a new collective family fails the budget instead of slipping
    past it. Exact (not max) pinning also catches a *vanished*
    collective: a psum that disappears from the traced program means
    the reduction it implemented is gone, which is a numerics bug, not
    a perf win. ``axes`` is the closed set of named axes collectives may
    communicate over. ``max_gather_bytes`` caps the OUTPUT bytes of any
    single gather-type collective (all_gather / all_to_all) — the
    machine form of the "no pool-scale gather" serving invariant.
    """

    counts: Optional[Mapping[str, int]] = None
    axes: Optional[Tuple[str, ...]] = None
    max_gather_bytes: Optional[int] = None


def check_collective_budget(
        inventory: Sequence[CollectiveRecord],
        budget: CollectiveBudget, *, where: str = "") -> List[Finding]:
    """Enforce one :class:`CollectiveBudget` against an inventory."""
    out: List[Finding] = []
    if budget.counts is not None:
        actual = Counter(rec.name for rec in inventory)
        for name in sorted(set(actual) | set(budget.counts)):
            want = int(budget.counts.get(name, 0))
            got = int(actual.get(name, 0))
            if got > want:
                out.append(Finding(
                    "collectives", "over_budget_collective", "error",
                    f"{got} {name} eqns traced, budget declares {want} — "
                    "an unbudgeted collective entered the program "
                    "(declare it in CollectiveBudget.counts or remove it)",
                    where=where,
                    data={"collective": name, "budget": want, "actual": got}))
            elif got < want:
                out.append(Finding(
                    "collectives", "missing_collective", "error",
                    f"{got} {name} eqns traced, budget declares {want} — "
                    "a budgeted reduction vanished from the program "
                    "(numerics hazard, not a perf win)",
                    where=where,
                    data={"collective": name, "budget": want, "actual": got}))
    if budget.axes is not None:
        allowed = set(budget.axes)
        for rec in inventory:
            unknown = sorted(set(rec.axes) - allowed)
            if unknown:
                out.append(Finding(
                    "collectives", "unknown_axis_collective", "error",
                    f"{rec.name} communicates over undeclared axis "
                    f"{unknown} (budget allows {sorted(allowed)})",
                    where=rec.where,
                    data={"collective": rec.name, "axes": list(rec.axes),
                          "allowed": sorted(allowed)}))
    if budget.max_gather_bytes is not None:
        for rec in inventory:
            if (rec.name in _GATHER_PRIMS
                    and rec.out_bytes > budget.max_gather_bytes):
                out.append(Finding(
                    "collectives", "oversized_gather", "error",
                    f"{rec.name} materializes {rec.out_bytes:,} B "
                    f"(budget caps gathers at "
                    f"{budget.max_gather_bytes:,} B) — a pool-scale "
                    "gather on the hot path",
                    where=rec.where,
                    data={"collective": rec.name,
                          "bytes": rec.out_bytes,
                          "max_gather_bytes": budget.max_gather_bytes}))
    return out


def _branch_signature(jaxpr) -> Dict[str, int]:
    """Collective multiset of one cond branch, as JSON-stable
    ``{"name@axes": count}``."""
    sig = Counter(f"{rec.name}@{rec.axes_key}"
                  for rec in collective_inventory(jaxpr))
    return {k: sig[k] for k in sorted(sig)}


def rule_collectives(trace, cfg) -> List[Finding]:
    out: List[Finding] = []
    inventory = collective_inventory(trace.closed.jaxpr)

    budget = getattr(cfg, "collective_budget", None)
    if budget is not None:
        out += check_collective_budget(inventory, budget,
                                       where=trace.name)

    threshold = int(getattr(cfg, "loop_collective_threshold", 4))
    for eqn, ctx in walk(trace.closed.jaxpr):
        name = eqn.primitive.name
        if name == "cond":
            sigs = [_branch_signature(sub) for sub in subjaxprs(eqn)]
            if sigs and any(s != sigs[0] for s in sigs[1:]):
                out.append(Finding(
                    "collectives", "cond_divergent_collective", "warning",
                    "cond branches contain different collectives — if "
                    "the predicate can diverge across shards, the branch "
                    "that issues the collective blocks on peers that "
                    "took the other branch (SPMD deadlock); hoist the "
                    "collective out of the cond or prove the predicate "
                    "replicated",
                    where=name_stack_str(eqn) or ctx.describe(),
                    data={"branches": sigs}))
        elif name in _LOOPING:
            per_axes = Counter()
            for sub in subjaxprs(eqn):
                for rec in collective_inventory(sub):
                    if rec.name in _REDUCTION_PRIMS:
                        per_axes[rec.axes_key] += 1
            for axes_key, n in sorted(per_axes.items()):
                if n >= threshold:
                    out.append(Finding(
                        "collectives", "unbucketed_loop_collectives",
                        "warning",
                        f"{n} reduction collectives over axis "
                        f"'{axes_key}' inside one {name} body — the "
                        "per-leaf sync anti-pattern; hoist them out of "
                        "the loop and bucket (GradBuckets / "
                        "sync_gradients_bucketed pays one psum per "
                        "bucket, docs/distributed.md)",
                        where=name_stack_str(eqn) or ctx.describe(),
                        data={"axes": axes_key, "count": n,
                              "loop": name}))
    return out


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------
def _axis_sizes(mesh) -> Dict[str, int]:
    """``{axis name: size}`` from a Mesh/AbstractMesh or a plain dict."""
    if isinstance(mesh, Mapping):
        return {str(k): int(v) for k, v in mesh.items()}
    return {str(k): int(v) for k, v in dict(mesh.shape).items()}


def _norm_spec(spec) -> Tuple[Tuple[str, ...], ...]:
    """Normalize a PartitionSpec / shard_map names-dict / tuple to a
    per-dimension tuple of axis-name tuples."""
    if isinstance(spec, Mapping):  # shard_map in_names/out_names entry
        if not spec:
            return ()
        ndim = max(spec) + 1
        return tuple(tuple(spec.get(d, ())) for d in range(ndim))
    out = []
    for entry in tuple(spec):
        if entry is None:
            out.append(())
        elif isinstance(entry, str):
            out.append((entry,))
        else:
            out.append(tuple(entry))
    return tuple(out)


def check_shard_specs(mesh, specs, shapes=None, *,
                      where: str = "") -> List[Finding]:
    """Validate PartitionSpecs against a mesh — statically, pre-trace.

    ``mesh`` is a ``jax.sharding.Mesh`` / ``AbstractMesh`` or a plain
    ``{axis: size}`` mapping; ``specs`` a sequence of ``PartitionSpec``
    (or raw tuples, or shard_map names-dicts); ``shapes`` an optional
    aligned sequence of array shapes for the divisibility check. This is
    the ``check_pack_spec``-style standalone gate: jax itself raises at
    trace time on an indivisible shard_map dim, so the mesh-rebase
    workflow runs this on its planned specs *before* committing to a
    trace. :func:`rule_sharding` applies the same checks to already-
    traced shard_map equations as belt and braces.
    """
    sizes = _axis_sizes(mesh)
    out: List[Finding] = []
    shapes = list(shapes) if shapes is not None else [None] * len(tuple(specs))
    for i, spec in enumerate(tuple(specs)):
        norm = _norm_spec(spec)
        w = where or f"spec[{i}]"
        used: Counter = Counter()
        for dim, axes in enumerate(norm):
            for ax in axes:
                used[ax] += 1
                if ax not in sizes:
                    out.append(Finding(
                        "sharding", "unknown_mesh_axis", "error",
                        f"spec[{i}] dim {dim} shards over axis "
                        f"'{ax}' which is not in the mesh "
                        f"({sorted(sizes)})",
                        where=w,
                        data={"spec": i, "dim": dim, "axis": ax,
                              "mesh_axes": sorted(sizes)}))
            factor = int(np.prod([sizes.get(ax, 1) for ax in axes])) \
                if axes else 1
            shape = shapes[i] if i < len(shapes) else None
            if (shape is not None and dim < len(shape) and factor > 1
                    and int(shape[dim]) % factor):
                out.append(Finding(
                    "sharding", "indivisible_shard_dim", "error",
                    f"spec[{i}] dim {dim} of size {shape[dim]} is not "
                    f"divisible by the axis product {factor} "
                    f"({'*'.join(axes)}) — shard_map will reject this "
                    "layout at trace time",
                    where=w,
                    data={"spec": i, "dim": dim,
                          "dim_size": int(shape[dim]), "factor": factor,
                          "axes": list(axes)}))
        for ax, n in sorted(used.items()):
            if n > 1:
                out.append(Finding(
                    "sharding", "duplicate_mesh_axis", "error",
                    f"spec[{i}] uses axis '{ax}' on {n} dimensions — "
                    "each mesh axis may shard at most one dimension of "
                    "an operand",
                    where=w,
                    data={"spec": i, "axis": ax, "uses": n}))
    return out


def _psum_pairing(jaxpr, where_default: str) -> List[Finding]:
    """The Megatron pairing lint, per jaxpr level (vars are local to a
    level, so producer chains never cross a sub-jaxpr boundary — the
    recursion handles each level independently and stops, conservatively,
    at any equation that owns sub-jaxprs).

    A psum whose input chain reaches another psum over the same axes
    WITHOUT crossing a matmul multiplies the already-reduced value by
    the axis size: the column-parallel → row-parallel contract is
    exactly one psum tail per GEMM pair, and hand-inserted extra
    reductions double-count (the classic tensor-parallel mappings bug).
    """
    out: List[Finding] = []
    producer = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            producer[id(v)] = eqn
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "psum":
            axes = collective_axes(eqn)
            seen = set()
            stack = list(eqn.invars)
            while stack:
                v = stack.pop()
                if id(v) in seen:
                    continue
                seen.add(id(v))
                p = producer.get(id(v))
                if p is None:
                    continue
                pname = p.primitive.name
                if pname in _MATMUL_PRIMS:
                    continue  # a GEMM resets the pairing on this path
                if pname == "psum" and collective_axes(p) == axes:
                    out.append(Finding(
                        "sharding", "unpaired_psum_tail", "warning",
                        f"psum over {list(axes)} consumes another psum "
                        "over the same axes with no matmul between — "
                        "the value is already fully reduced and the "
                        "second psum multiplies it by the axis size "
                        "(column GEMM -> row GEMM -> exactly one psum "
                        "tail)",
                        where=name_stack_str(eqn) or where_default,
                        data={"axes": list(axes)}))
                    break
                if transparent_subjaxprs(p):
                    continue  # don't reason across control flow
                stack.extend(p.invars)
        for sub in transparent_subjaxprs(eqn):
            out.extend(_psum_pairing(sub, where_default))
    return out


def rule_sharding(trace, cfg) -> List[Finding]:
    out: List[Finding] = []
    replicated_bytes = int(getattr(cfg, "replicated_bytes", 1 << 20))
    for eqn, ctx in walk(trace.closed.jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        where = name_stack_str(eqn) or ctx.describe()
        mesh = eqn.params.get("mesh")
        try:
            sizes = _axis_sizes(mesh)
        except Exception:  # pragma: no cover - mesh API drift
            continue
        in_specs = eqn.params.get("in_specs") or ()
        out_specs = eqn.params.get("out_specs") or ()
        for io, names, vars_ in (("in", in_specs, eqn.invars),
                                 ("out", out_specs, eqn.outvars)):
            shapes = [getattr(v, "aval", None) and tuple(v.aval.shape)
                      for v in vars_]
            out.extend(
                f for f in check_shard_specs(
                    {a: s for a, s in sizes.items()}, names,
                    shapes=shapes, where=f"{where} [{io}_specs]")
            )
        # replicated operands a named axis could shard, largest first
        repl = []
        for i, (spec, v) in enumerate(zip(in_specs, eqn.invars)):
            if any(_norm_spec(spec)) or not hasattr(v, "aval"):
                continue
            b = _aval_bytes(v.aval)
            if b >= replicated_bytes:
                repl.append((b, i, v.aval))
        for b, i, aval in sorted(repl, reverse=True, key=lambda t: t[:2])[:8]:
            out.append(Finding(
                "sharding", "large_replicated_operand", "warning",
                f"shard_map operand {i} ({b:,} B "
                f"{np.dtype(aval.dtype)}{list(aval.shape)}) is fully "
                "replicated — every device holds a copy; a named axis "
                "could shard it (the ZeRO/mesh-rebase scouting report)",
                where=where,
                data={"operand": i, "bytes": b,
                      "shape": list(aval.shape),
                      "dtype": str(np.dtype(aval.dtype))}))
        body = eqn.params.get("jaxpr")
        if body is not None:
            out.extend(_psum_pairing(
                body.jaxpr if hasattr(body, "jaxpr") else body, where))
    return out
