"""The audit rules: each one walks a traced step and emits findings.

Five rule families (ISSUE-4 contract), plus the named-scope coverage
check:

- ``donation``   — optimizer-state / packed-buffer args consumed by the
                   step but not donated; double-donation of aliased
                   buffers; packed Pallas calls without
                   ``input_output_aliases``.
- ``host_sync``  — host callbacks (``debug_callback`` / ``io_callback``
                   / ``pure_callback``) not gated under ``lax.cond``;
                   callbacks inside scan bodies (dropped when the scan
                   is differentiated through — docs/observability.md);
                   ordered io_callbacks (serialize the whole step).
- ``dtype_flow`` — fp32 matmuls/convs inside a step whose compute policy
                   is bf16/fp16 (the amp-list contract: the matmul
                   family is ``LOW_PRECISION_FUNCS``), and
                   precision-losing f32 -> half -> f32 double-casts.
- ``constants``  — large array constants baked into the jaxpr (closure
                   capture duplicating HBM) and weak-type scalar input
                   avals that fragment the jit cache.
- ``packing``    — :class:`PackSpec` invariants: ROW/chunk alignment,
                   non-overlap, the shard-alignment precondition of the
                   ROADMAP sharded-packed follow-on.
- ``scopes``     — kernels (``pallas_call``) and pipeline-shaped scans
                   missing an ``apex_tpu.*`` named scope (xplane
                   breakdowns cannot attribute them otherwise).

Severity policy: **error** marks a violation of a performance/correctness
invariant the repo's hot paths rely on (silent full-state copy, per-step
host sync, corrupted pack layout); **warning** marks a hazard that needs
human judgement; **info** is context. CI gates on errors
(:meth:`AuditReport.ok`).
"""
from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..multi_tensor_apply.packing import ROW, PackSpec
from .report import Finding
from .walk import (
    name_stack_str,
    pallas_kernel_name,
    transparent_subjaxprs,
    walk,
)

_CALLBACK_PRIMS = ("debug_callback", "io_callback", "pure_callback")
_MATMUL_PRIMS = ("dot_general", "conv_general_dilated")
_LOW_DTYPES = ("bfloat16", "float16")
# leaf-path fragments that mark optimizer/master state (backup for the
# type-based detection in auditor._state_leaf_ids)
_STATE_PATH_RE = re.compile(r"exp_avg|momentum|master|opt_state")


@dataclasses.dataclass
class AuditConfig:
    """Knobs shared by the rules (see :func:`apex_tpu.analysis.audit_step`)."""

    min_bytes: int = 64 * 1024        # ignore buffers smaller than this
    const_bytes: int = 1 << 20        # large-constant warning threshold
    const_bytes_error: int = 64 << 20  # ... error threshold
    compute_dtype: Optional[str] = None  # "bfloat16"/"float16"/"float32"/None=infer
    strict_dtype: bool = False        # fp32 matmul -> error instead of warning
    shard_count: Optional[int] = None  # PackSpec shard-alignment check
    collective_budget: Optional[Any] = None  # CollectiveBudget for this program
    loop_collective_threshold: int = 4  # reductions-in-one-loop-body warning
    replicated_bytes: int = 1 << 20   # large replicated shard_map operand floor


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def _sig(aval) -> Tuple:
    return (tuple(aval.shape), str(np.dtype(aval.dtype)))


# ---------------------------------------------------------------------------
# donation / aliasing
# ---------------------------------------------------------------------------
def rule_donation(trace, cfg: AuditConfig) -> List[Finding]:
    findings: List[Finding] = []
    avals = trace.in_avals
    out_sig = Counter(_sig(a) for a in trace.out_avals)

    # Donated leaves consume matching outputs first (jax's donation
    # matcher pairs donated inputs with outputs by shape/dtype). Among
    # the UNDONATED there is no consumption: every undonated leaf whose
    # signature still lacks a donated home is flagged, so when e.g.
    # grads and params share an aval the report names BOTH instead of
    # letting whichever comes first shadow the other — donating either
    # gives that output an in-place home and silences both.
    carried = [False] * len(avals)
    for i in range(len(avals)):
        if not trace.donated[i]:
            continue
        s = _sig(avals[i])
        if out_sig.get(s, 0) > 0:
            out_sig[s] -= 1
            carried[i] = True
    for i in range(len(avals)):
        if not trace.donated[i] and out_sig.get(_sig(avals[i]), 0) > 0:
            carried[i] = True

    # aggregate undonated carried leaves per top-level argnum
    per_arg: Dict[int, Dict[str, Any]] = {}
    for i, aval in enumerate(avals):
        if trace.donated[i] or not carried[i]:
            continue
        is_state = (i in trace.state_leaf_ids
                    or bool(_STATE_PATH_RE.search(trace.paths[i])))
        a = per_arg.setdefault(trace.argnums[i], {
            "bytes": 0, "n": 0, "state_bytes": 0, "paths": []})
        b = _aval_bytes(aval)
        a["bytes"] += b
        a["n"] += 1
        if is_state:
            a["state_bytes"] += b
        if len(a["paths"]) < 3:
            a["paths"].append(trace.paths[i])

    for argnum in sorted(per_arg):
        a = per_arg[argnum]
        if a["bytes"] < cfg.min_bytes:
            continue
        if a["state_bytes"] > 0:
            findings.append(Finding(
                "donation", "undonated_state", "error",
                f"optimizer/packed state consumed by the step but not "
                f"donated — XLA copies {a['bytes']:,} B every step "
                f"(jax.jit(..., donate_argnums=({argnum},)))",
                where=f"arg {argnum} ({a['paths'][0]}, ...)",
                data={"argnum": argnum, "bytes": a["bytes"],
                      "n_leaves": a["n"], "example_paths": a["paths"]},
            ))
        else:
            findings.append(Finding(
                "donation", "undonated_carry", "warning",
                f"carried buffer(s) not donated — {a['bytes']:,} B "
                f"could be updated in place (donate_argnums=({argnum},))",
                where=f"arg {argnum} ({a['paths'][0]}, ...)",
                data={"argnum": argnum, "bytes": a["bytes"],
                      "n_leaves": a["n"], "example_paths": a["paths"]},
            ))

    findings += _double_donation(trace)
    findings += _pallas_alias(trace, cfg)
    return findings


def _buffer_key(leaf):
    """A stable per-device-buffer key, or None when not a concrete array."""
    try:
        return int(leaf.unsafe_buffer_pointer())
    except Exception:
        return None


def _double_donation(trace) -> List[Finding]:
    """Two donated leaves backed by ONE buffer: XLA donates it twice.

    The ``no_update_mv`` hazard documented in ``optimizers/_packed.py``:
    for a single fp32 leaf of exact chunk-multiple size, ``pack()`` is
    the identity, so an fp32 master built without ``copy=True`` ALIASES
    the live param buffer — donating params and state then donates the
    same HBM twice (an XLA error on TPU, silent corruption elsewhere).
    """
    seen: Dict[int, int] = {}
    by_id: Dict[int, int] = {}
    out: List[Finding] = []
    for i, leaf in enumerate(trace.leaves):
        if not trace.donated[i]:
            continue
        key = _buffer_key(leaf)
        first = None
        if key is not None:
            first = seen.get(key)
            seen.setdefault(key, i)
        else:  # abstract audit: fall back to object identity
            first = by_id.get(id(leaf))
            by_id.setdefault(id(leaf), i)
        if first is not None:
            out.append(Finding(
                "donation", "double_donation", "error",
                "two donated args share one device buffer (aliased "
                "master/param? see optimizers/_packed.py) — donation "
                "would hand the same HBM to XLA twice",
                where=f"{trace.paths[first]} aliases {trace.paths[i]}",
                data={"paths": [trace.paths[first], trace.paths[i]]},
            ))
    return out


def _pallas_alias(trace, cfg: AuditConfig) -> List[Finding]:
    out: List[Finding] = []
    for eqn, ctx in walk(trace.closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        aliases = tuple(eqn.params.get("input_output_aliases") or ())
        if aliases:
            continue
        in_sigs = Counter(
            _sig(v.aval) for v in eqn.invars
            if _aval_bytes(v.aval) >= cfg.min_bytes)
        match_bytes = 0
        for v in eqn.outvars:
            b = _aval_bytes(v.aval)
            if b >= cfg.min_bytes and in_sigs.get(_sig(v.aval), 0) > 0:
                in_sigs[_sig(v.aval)] -= 1
                match_bytes += b
        if match_bytes:
            ns = name_stack_str(eqn)
            # the packed/multi-tensor kernel family's CONTRACT is the
            # in-place update (docs/packed_optimizers.md) — a missing
            # alias there is a violation; for other kernels (attention,
            # norms) out-of-place is often deliberate, so the finding
            # is informational
            packed_family = ("apex_tpu.packed" in ns
                             or "apex_tpu.multi_tensor" in ns)
            out.append(Finding(
                "donation", "pallas_no_alias",
                "warning" if packed_family else "info",
                f"pallas_call updates {match_bytes:,} B of buffers with "
                "no input_output_aliases — the kernel writes fresh HBM "
                "instead of updating in place",
                where=ns or ctx.describe(),
                data={"bytes": match_bytes},
            ))
    return out


# ---------------------------------------------------------------------------
# host-sync discipline
# ---------------------------------------------------------------------------
def _cb_name(cb) -> str:
    """A deterministic label for a callback param (never a repr with a
    memory address — the JSON output must be golden-fixture stable)."""
    n = getattr(cb, "__name__", None)
    if n:
        return n
    inner = getattr(cb, "func", None) or getattr(cb, "callback", None)
    n = getattr(inner, "__name__", None)
    return n if n else type(cb).__name__


def rule_host_sync(trace, cfg: AuditConfig) -> List[Finding]:
    out: List[Finding] = []
    for eqn, ctx in walk(trace.closed.jaxpr):
        name = eqn.primitive.name
        if name not in _CALLBACK_PRIMS:
            continue
        where = name_stack_str(eqn) or ctx.describe()
        cb = _cb_name(eqn.params.get("callback"))
        if name == "io_callback" and eqn.params.get("ordered"):
            out.append(Finding(
                "host_sync", "ordered_io_callback", "error",
                f"ordered io_callback ({cb}) serializes every step "
                "against the host — use an unordered callback or "
                "jax.debug.callback",
                where=where, data={"callback": cb}))
        if not ctx.gated:
            sev = "error"
            out.append(Finding(
                "host_sync", "ungated_callback", sev,
                f"{name} ({cb}) fires on EVERY step — gate it under "
                "lax.cond like telemetry.drain (docs/observability.md) "
                "so healthy steps pay zero host work",
                where=where,
                data={"primitive": name, "callback": cb,
                      "loop_depth": ctx.loop_depth}))
        if ctx.in_loop:
            out.append(Finding(
                "host_sync", "callback_in_scan", "warning",
                f"{name} ({cb}) inside a scan/while body: current jax "
                "drops debug callbacks from scans differentiated "
                "THROUGH (docs/observability.md) and each surviving "
                "iteration emits host traffic",
                where=where,
                data={"primitive": name, "callback": cb,
                      "loop_depth": ctx.loop_depth}))
    return out


# ---------------------------------------------------------------------------
# amp dtype flow
# ---------------------------------------------------------------------------
def _amp_policy_note() -> str:
    """Cross-check hook against the O1 autocast lists: the matmul family
    is LOW_PRECISION_FUNCS there, so an fp32 dot inside a low-precision
    step contradicts the declared policy surface."""
    try:
        from ..amp.lists import jax_overrides as _lists

        return (f"amp lists: {len(_lists.LOW_PRECISION_FUNCS)} "
                "low-precision (matmul-family) entries")
    except Exception:  # pragma: no cover
        return "amp lists unavailable"


def rule_dtype_flow(trace, cfg: AuditConfig) -> List[Finding]:
    out: List[Finding] = []
    dots = []  # (eqn, ctx, lhs_dtype, rhs_dtype, weight_bytes)
    for eqn, ctx in walk(trace.closed.jaxpr):
        if eqn.primitive.name in _MATMUL_PRIMS:
            lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
            w = _aval_bytes(lhs) + _aval_bytes(rhs)
            dots.append((eqn, ctx, str(np.dtype(lhs.dtype)),
                         str(np.dtype(rhs.dtype)), w))

    # resolve the step's compute policy; inference weights by operand
    # ELEMENT count (bytes would bias toward f32, whose operands are
    # twice the bytes of bf16 at equal size), ties leaning low precision
    # (any bf16 matmul signals a low-precision-intent step)
    policy = cfg.compute_dtype
    if policy is None and dots:
        def elems(eqn):
            return int(sum(int(np.prod(v.aval.shape)) for v in eqn.invars[:2]))

        low_w = sum(elems(d[0]) for d in dots
                    if d[2] in _LOW_DTYPES or d[3] in _LOW_DTYPES)
        f32_w = sum(elems(d[0]) for d in dots
                    if d[2] == "float32" and d[3] == "float32")
        policy = "bfloat16" if low_w and low_w >= f32_w else "float32"
    if policy is not None:
        policy = str(np.dtype(policy)) if policy not in (
            "bf16", "fp16", "f32") else {
            "bf16": "bfloat16", "fp16": "float16", "f32": "float32"}[policy]

    if policy in _LOW_DTYPES:
        sev = "error" if cfg.strict_dtype else "warning"
        for eqn, ctx, l, r, w in dots:
            if l == "float32" and r == "float32" and w >= cfg.min_bytes:
                out.append(Finding(
                    "dtype_flow", "fp32_matmul", sev,
                    f"fp32 {eqn.primitive.name} inside a {policy} step "
                    f"({w:,} B of operands) — the matmul family belongs "
                    f"in low precision ({_amp_policy_note()})",
                    where=name_stack_str(eqn) or ctx.describe(),
                    data={"primitive": eqn.primitive.name,
                          "operand_bytes": w,
                          "shape": [list(eqn.invars[0].aval.shape),
                                    list(eqn.invars[1].aval.shape)]}))

    out += _double_casts(trace.closed.jaxpr, cfg)
    return out


def _double_casts(jaxpr, cfg: AuditConfig) -> List[Finding]:
    """f32 -> half -> f32 round-trips: the second cast cannot restore the
    mantissa bits the first one dropped, so the chain silently halves
    precision while paying two convert sweeps."""
    out: List[Finding] = []
    producer = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            producer[id(v)] = eqn
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            src = eqn.invars[0]
            prev = producer.get(id(src))
            if (prev is None
                    or prev.primitive.name != "convert_element_type"
                    or not hasattr(prev.invars[0], "aval")):
                continue
            # truncating a fresh matmul accumulation onto the low-precision
            # rail is amp policy (and its upcast twin appears in the
            # transposed program by construction) — not a violation
            feeder = producer.get(id(prev.invars[0]))
            if feeder is not None and feeder.primitive.name in _MATMUL_PRIMS:
                continue
            orig = str(np.dtype(prev.invars[0].aval.dtype))
            mid = str(np.dtype(src.aval.dtype))
            final = str(np.dtype(eqn.outvars[0].aval.dtype))
            b = _aval_bytes(eqn.outvars[0].aval)
            if (orig == "float32" and mid in _LOW_DTYPES
                    and final == "float32" and b >= cfg.min_bytes):
                out.append(Finding(
                    "dtype_flow", "double_cast", "warning",
                    f"f32 -> {mid} -> f32 round-trip ({b:,} B): precision "
                    "is already lost at the first cast; keep one dtype "
                    "or cast once at the consumer",
                    where=name_stack_str(eqn),
                    data={"chain": [orig, mid, final], "bytes": b}))
        for sub in transparent_subjaxprs(eqn):
            out.extend(_double_casts(sub, cfg))
    return out


# ---------------------------------------------------------------------------
# constant bloat & recompile hazards
# ---------------------------------------------------------------------------
def rule_constants(trace, cfg: AuditConfig) -> List[Finding]:
    out: List[Finding] = []
    for c in trace.consts:
        try:
            b = int(np.asarray(c).nbytes)
            shape = list(np.shape(c))
            dt = str(np.asarray(c).dtype)
        except Exception:
            continue
        if b < cfg.const_bytes:
            continue
        sev = "error" if b >= cfg.const_bytes_error else "warning"
        out.append(Finding(
            "constants", "large_constant", sev,
            f"{b:,} B {dt}{shape} constant baked into the jaxpr — "
            "closure-captured arrays are duplicated into every "
            "executable (and re-uploaded per compile); pass it as an "
            "argument instead",
            where=f"const {dt}{shape}",
            data={"bytes": b, "dtype": dt, "shape": shape}))

    for i, aval in enumerate(trace.in_avals):
        if getattr(aval, "weak_type", False):
            out.append(Finding(
                "constants", "weak_type_input", "warning",
                "weak-type scalar aval fragments the jit cache (the "
                "strong-typed sibling of the same value traces a second "
                "executable) — pass jnp.asarray(x, dtype) instead of a "
                "Python scalar",
                where=trace.paths[i],
                data={"path": trace.paths[i],
                      "dtype": str(np.dtype(aval.dtype))}))
    return out


# ---------------------------------------------------------------------------
# PackSpec invariants
# ---------------------------------------------------------------------------
def check_pack_spec(spec: PackSpec, *, shard_count: Optional[int] = None,
                    where: str = "") -> List[Finding]:
    """Static verification of one :class:`PackSpec`'s layout invariants.

    ROW alignment is the precondition of every per-tensor reduction in
    the packed path (``segment_sum`` over ``row_leaf_ids``) and of the
    ROADMAP sharded-packed follow-on; chunk alignment is the kernel grid
    contract. A violated spec produces silently-wrong per-tensor norms,
    so every check here is error-severity.
    """
    out: List[Finding] = []
    w = where or repr(spec)

    def err(code, msg, **data):
        out.append(Finding("packing", code, "error", msg, where=w,
                           data=data or None))

    # a length-truncated layout (a leaf with no offset at all) must not
    # audit clean: every per-leaf check below zips these tuples, and zip
    # silently drops the unmatched tail
    lens = {"offsets": len(spec.offsets), "sizes": len(spec.sizes),
            "padded_sizes": len(spec.padded_sizes),
            "shapes": len(spec.shapes), "dtypes": len(spec.dtypes)}
    if len(set(lens.values())) != 1 or lens["offsets"] != spec.n_leaves:
        err("inconsistent_leaf_tables",
            f"per-leaf tables disagree in length ({lens}, n_leaves="
            f"{spec.n_leaves}) — some leaf has no offset/size entry and "
            "every per-tensor mapping through this spec misattributes",
            n_leaves=spec.n_leaves, **lens)
    if spec.align % ROW:
        err("align_not_row_multiple",
            f"align {spec.align} is not a multiple of ROW ({ROW}) — "
            "rows straddle leaf boundaries and per-tensor segment "
            "reductions mix tensors", align=spec.align, row=ROW)
    if spec.chunk_size % spec.align:
        err("chunk_not_aligned",
            f"chunk_size {spec.chunk_size} is not a multiple of align "
            f"{spec.align} — grid blocks straddle leaf padding",
            chunk_size=spec.chunk_size, align=spec.align)
    if spec.total % spec.chunk_size:
        err("total_not_chunk_multiple",
            f"total {spec.total} is not a multiple of chunk_size "
            f"{spec.chunk_size} — the fixed-size chunk grid cannot tile "
            "the buffer", total=spec.total, chunk_size=spec.chunk_size)

    end = 0
    for i, (off, n, pn) in enumerate(zip(spec.offsets, spec.sizes,
                                         spec.padded_sizes)):
        name = f"leaf {i}"
        if off % ROW:
            err("misaligned_offset",
                f"{name} offset {off} is not ROW-aligned ({ROW}) — its "
                "rows are shared with the previous leaf and per-tensor "
                "norms/provenance misattribute", leaf=i, offset=off)
        if off < end:
            err("overlapping_leaves",
                f"{name} offset {off} overlaps the previous leaf's "
                f"padded extent {end}", leaf=i, offset=off, prev_end=end)
        if pn < n:
            err("padded_size_too_small",
                f"{name} padded size {pn} < element count {n}",
                leaf=i, size=n, padded=pn)
        end = off + pn
    if end > spec.total:
        err("leaves_exceed_total",
            f"leaf extents end at {end} > total {spec.total}",
            end=end, total=spec.total)

    # bucketed layouts (GradBuckets): bucket boundaries must sit on chunk
    # multiples (each bucket is a whole number of kernel chunks, so the
    # per-bucket psum sub-buffers concatenate back into exactly the
    # buffer the chunk-gridded optimizer kernels sweep) and the leaf
    # ranges must partition the leaves in order
    bounds = getattr(spec, "bucket_bounds", None)
    ranges = getattr(spec, "bucket_leaf_ranges", None)
    if bounds is not None:
        if bounds[0] != 0 or bounds[-1] != spec.total:
            err("bucket_bounds_cover",
                f"bucket bounds {bounds[0]}..{bounds[-1]} do not cover "
                f"[0, {spec.total})", first=bounds[0], last=bounds[-1],
                total=spec.total)
        prev = None
        for b in bounds:
            if b % spec.chunk_size:
                err("bucket_not_chunk_aligned",
                    f"bucket boundary {b} is not a multiple of chunk_size "
                    f"{spec.chunk_size} — bucket sub-buffers straddle "
                    "kernel chunks", boundary=b, chunk_size=spec.chunk_size)
            if prev is not None and b <= prev:
                err("bucket_bounds_not_increasing",
                    f"bucket boundary {b} does not increase past {prev}",
                    boundary=b, prev=prev)
            prev = b
        if ranges is not None:
            # corrupt tables (truncated leaf tuples, a ranges/bounds
            # length mismatch) must produce findings, not crash the
            # walk — cap every index at what the tables actually hold
            n_tab = min(spec.n_leaves, len(spec.offsets),
                        len(spec.padded_sizes))
            if len(ranges) != len(bounds) - 1:
                err("bucket_tables_mismatch",
                    f"{len(ranges)} bucket leaf ranges for "
                    f"{len(bounds) - 1} buckets — the bucket tables "
                    "disagree and per-bucket packing misattributes",
                    n_ranges=len(ranges), n_buckets=len(bounds) - 1)
            expect = 0
            for bi, (lo, hi) in enumerate(ranges[:len(bounds) - 1]):
                if lo != expect or hi < lo:
                    err("bucket_leaves_not_partition",
                        f"bucket {bi} leaf range ({lo}, {hi}) breaks the "
                        f"in-order partition (expected start {expect})",
                        bucket=bi, lo=lo, hi=hi)
                for li in range(lo, min(hi, n_tab)):
                    o, pn = spec.offsets[li], spec.padded_sizes[li]
                    if o < bounds[bi] or o + pn > bounds[bi + 1]:
                        err("leaf_outside_bucket",
                            f"leaf {li} extent [{o}, {o + pn}) escapes "
                            f"bucket {bi} bounds [{bounds[bi]}, "
                            f"{bounds[bi + 1]})", leaf=li, bucket=bi)
                expect = hi
            if expect != spec.n_leaves:
                err("bucket_leaves_not_partition",
                    f"bucket leaf ranges end at {expect}, expected "
                    f"{spec.n_leaves}", end=expect, n_leaves=spec.n_leaves)

    if shard_count:
        if spec.total % shard_count:
            err("shard_unaligned_total",
                f"total {spec.total} not divisible by shard_count "
                f"{shard_count} — the sharded-packed layout needs equal "
                "per-shard extents", total=spec.total,
                shard_count=shard_count)
        elif (spec.total // shard_count) % ROW:
            err("shard_not_row_aligned",
                f"shard size {spec.total // shard_count} is not "
                f"ROW-aligned ({ROW}) — shard boundaries split rows and "
                "shard-local segment reductions mix leaves",
                shard_size=spec.total // shard_count, row=ROW)
    return out


def check_reshard(old_spec: PackSpec, new_spec: PackSpec, *,
                  old_count: Optional[int] = None,
                  new_count: Optional[int] = None,
                  where: str = "") -> List[Finding]:
    """Static verification that a packed buffer laid out under
    ``old_spec`` can be re-flattened bit-exactly into ``new_spec`` — the
    machine check of the elastic topology-resume path
    (``resilience.elastic.reflatten_flat``): a checkpoint saved at world
    size W (``old_count`` shards of ``old_spec``) restoring onto W′
    hosts (``new_count`` shards of ``new_spec``).

    Both specs must individually pass :func:`check_pack_spec` at their
    shard counts, AND describe the same logical leaves (shapes + dtypes
    in flatten order — offsets/padding/bucketing may differ freely;
    those are exactly what re-flattening rewrites). A mismatch in the
    leaf sequence means the two layouts belong to different models and
    any element copy between them is silent corruption, so it is
    error-severity.
    """
    w = where or f"{old_spec!r} -> {new_spec!r}"
    out: List[Finding] = []
    out.extend(check_pack_spec(old_spec, shard_count=old_count,
                               where=f"{w} [old]"))
    out.extend(check_pack_spec(new_spec, shard_count=new_count,
                               where=f"{w} [new]"))
    old_dtypes = tuple(str(d) for d in old_spec.dtypes)
    new_dtypes = tuple(str(d) for d in new_spec.dtypes)
    if old_spec.shapes != new_spec.shapes or old_dtypes != new_dtypes:
        if old_spec.n_leaves != new_spec.n_leaves:
            detail = (f"{old_spec.n_leaves} vs {new_spec.n_leaves} "
                      "leaves")
            bad = []
        else:
            bad = [i for i, (os_, ns, od, nd) in enumerate(
                zip(old_spec.shapes, new_spec.shapes,
                    old_dtypes, new_dtypes))
                if os_ != ns or od != nd]
            i0 = bad[0]
            detail = (f"{len(bad)} of {old_spec.n_leaves} leaves "
                      f"differ; first: leaf {i0} "
                      f"{old_spec.shapes[i0]}/{old_dtypes[i0]} vs "
                      f"{new_spec.shapes[i0]}/{new_dtypes[i0]}")
        out.append(Finding(
            "packing", "reshard_leaf_mismatch", "error",
            f"old and new PackSpecs describe different leaf sequences "
            f"({detail}) — re-flattening between them copies elements "
            "across unrelated tensors", where=w,
            data={"old_n_leaves": old_spec.n_leaves,
                  "new_n_leaves": new_spec.n_leaves,
                  "mismatched_leaves": bad[:8]}))
    return out


def rule_packing(trace, cfg: AuditConfig) -> List[Finding]:
    out: List[Finding] = []
    for i, spec in enumerate(trace.pack_specs):
        out.extend(check_pack_spec(
            spec, shard_count=cfg.shard_count, where=f"PackSpec[{i}] {spec!r}"))
    return out


# ---------------------------------------------------------------------------
# named-scope coverage
# ---------------------------------------------------------------------------
def _contains_prim(jaxpr, names: Sequence[str],
                   max_depth: Optional[int] = None) -> bool:
    """True when any equation at any transparent nesting depth is one of
    ``names``. Unbounded by default: the old ``max_depth=4`` cap let a
    collective nested under cond-in-scan-in-shard_map silently escape
    the scan-shape detection (jaxprs are finite, so the recursion always
    terminates — a cap only ever *hides* equations)."""
    if max_depth is not None and max_depth < 0:
        return False
    sub_depth = None if max_depth is None else max_depth - 1
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            return True
        for sub in transparent_subjaxprs(eqn):
            if _contains_prim(sub, names, sub_depth):
                return True
    return False


def rule_scopes(trace, cfg: AuditConfig) -> List[Finding]:
    out: List[Finding] = []
    for eqn, ctx in walk(trace.closed.jaxpr):
        name = eqn.primitive.name
        ns = name_stack_str(eqn)
        if name == "pallas_call" and "apex_tpu." not in ns:
            kname = pallas_kernel_name(eqn)
            out.append(Finding(
                "scopes", "unscoped_kernel", "warning",
                f"pallas_call kernel '{kname}' carries no apex_tpu.* "
                "named scope — xplane breakdowns cannot attribute its "
                "device time (wrap with jax.named_scope)",
                where=ns or ctx.describe(), data={"kernel": kname}))
        elif (name == "scan" and "apex_tpu." not in ns
              and "scan" not in ctx.path  # outermost schedule scan only
              and _contains_prim(eqn.params["jaxpr"].jaxpr, ("ppermute",))):
            out.append(Finding(
                "scopes", "unscoped_schedule", "warning",
                "pipeline-shaped scan (body contains ppermute) without "
                "an apex_tpu.* named scope — schedule ticks are "
                "unattributable in traces",
                where=ns or ctx.describe(), data=None))
    out.extend(_unscoped_layer_findings(trace.closed.jaxpr))
    return out


def _unscoped_layer_findings(jaxpr) -> List[Finding]:
    """A step that names its layers (some equation stands under a
    ``telemetry.tracing.LAYER_SCOPES`` scope) and has equations outside
    every one of them: their device time reaches no layer of the by-scope
    table. One finding for the step, naming the first such equation. An
    inner jaxpr's name stacks are relative to the equation that calls it,
    so the path is built on the way down."""
    from ..telemetry.tracing import scope_of

    scoped = 0
    outside: List[Tuple[str, str]] = []     # (primitive, path)

    def rec(jaxpr, prefix):
        nonlocal scoped
        for eqn in jaxpr.eqns:
            path = f"{prefix}/{name_stack_str(eqn)}"
            subs = transparent_subjaxprs(eqn)
            if subs:        # a call, branch or loop: its body does the work
                for sub in subs:
                    rec(sub, path)
            elif scope_of(path)[0] is not None:
                scoped += 1
            else:
                outside.append((eqn.primitive.name, path.strip("/")))

    rec(jaxpr, "")
    if not scoped or not outside:
        return []
    prim, path = outside[0]
    return [Finding(
        "scopes", "unscoped_layer", "info",
        f"{len(outside)} of {scoped + len(outside)} equations stand "
        "outside every layer scope (telemetry.tracing.LAYER_SCOPES) — "
        f"their device time reaches no layer; first: '{prim}'",
        where=path or "<top>",
        data={"outside": len(outside), "scoped": scoped})]


# imported last: collectives.py depends on report/walk only, never on
# this module, so the registry import below cannot cycle
from .collectives import rule_collectives, rule_sharding  # noqa: E402

RULES = {
    "donation": rule_donation,
    "host_sync": rule_host_sync,
    "dtype_flow": rule_dtype_flow,
    "constants": rule_constants,
    "packing": rule_packing,
    "scopes": rule_scopes,
    "collectives": rule_collectives,
    "sharding": rule_sharding,
}
