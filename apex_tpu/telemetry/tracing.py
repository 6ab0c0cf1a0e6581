"""Trace sessions and device-time attribution, by operation and by layer.

The reference publishes per-kernel timings through nvprof/nsys ranges;
the TPU analogue is a ``jax.profiler`` xplane trace. This module owns

- :data:`LAYER_SCOPES` — the ``apex_tpu.<name>`` named scopes that bound
  the layers of a train step, and :func:`scope_of` / :func:`scope_index`,
  which read them back: every instruction of a compiled step carries its
  whole scope path in ``metadata={op_name=...}``, and the device trace
  names each event by its instruction, so joining the two gives device
  time by layer and by forward / backward / recompute;
- :func:`trace_session` — a context manager around ``jax.profiler.trace``
  that yields a session handle whose :meth:`~TraceSession.op_breakdown`
  parses the captured device plane into a top-op table with per-category
  and (given the step's HLO text) per-scope totals;
- :func:`profile_step` — one-shot: run a step function ``n_steps`` times
  under a trace and return the breakdown table, falling back to the
  compiled step's ``cost_analysis()`` (flops/bytes attribution) on
  backends with no device plane (CPU CI) so every environment gets a
  table rather than ``None``;
- the pure op-name helpers (:func:`short_op_name`, :func:`categorize_op`,
  :func:`aggregate_op_times`, :func:`aggregate_scope_times`,
  :func:`breakdown_table`), which unit-test on canned fixtures without a
  TPU.

The xplane is read with ``jax.profiler.ProfileData`` (ships with jax).
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
# layer scopes (pure, no jax import)
# ---------------------------------------------------------------------------

# The named scopes that bound the layers of a train step, and what each
# bounds. Kernel scopes (``apex_tpu.flash_attention``, ``apex_tpu.
# packed_adam``, ...) nest inside these and count to the enclosing layer.
LAYER_SCOPES = (
    "apex_tpu.embed",              # token + position lookup
    "apex_tpu.layer_stack",        # the layers' scan: slices of the stacked parameters, stacking of their gradients
    "apex_tpu.transformer_layer",  # one layer; directly under it, under neither child: norms and residual tails
    "apex_tpu.attention",          # qkv GEMM, layout changes, flash kernel, out projection; with latent attention the two below nested in it
    "apex_tpu.mla_latent",         # in attention: the K/V side of latent attention from the normed input to the kernel's operands: down-projection, the latent's RMSNorm, up-projection, assembling k
    "apex_tpu.mla_rope",           # in attention: the partial rotary, one pass over the whole q row (its rotary lanes rotated by a product with a constant signed permutation, the others passed through) and one over the shared rotary key
    "apex_tpu.mlp",                # both GEMMs and the activation; on an expert layer the whole expert MLP, the four below nested in it
    "apex_tpu.moe_router",         # in mlp: float32 scores, top-k, weights
    "apex_tpu.moe_dispatch",       # in mlp: sort, gather into expert order, each row in use added back into its token
    "apex_tpu.moe_experts",        # in mlp: the grouped products over the experts held, and their activation over the row tiles in use
    "apex_tpu.moe_shared",         # in mlp: the shared expert
    "apex_tpu.lm_head",            # final layer norm and the logits GEMM
    "apex_tpu.cross_entropy",      # the loss and its scan (with the head GEMM where gpt_loss chunk-fuses the two)
    "apex_tpu.amp_scaler",         # loss scaling, unscale, overflow probe, scale update
    "apex_tpu.optimizer_step",     # a fused optimizer's whole step: casts, norms, trust ratios, kernels
    "apex_tpu.pack",               # pytree -> flat buffer (in optimizer_step or sync_gradients, or alone)
    "apex_tpu.unpack",             # flat buffer -> pytree
    "apex_tpu.sync_gradients",     # bucket fill + all-reduce
    "apex_tpu.grad_bucket",        # one bucket's all-reduce (inside sync_gradients)
)
# scopes that hold other layers: time is theirs only where no layer scope
# stands inside them
CONTAINER_SCOPES = ("apex_tpu.layer_stack", "apex_tpu.transformer_layer")
PHASES = ("fwd", "bwd", "recompute")

_SCOPE_NAME = re.compile(r"apex_tpu\.\w+")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) [^=]*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def scope_of(op_name_path: str) -> Tuple[Optional[str], str]:
    """``(layer_scope, phase)`` of one instruction's ``op_name`` path.

    The layer is the outermost :data:`LAYER_SCOPES` scope of the path,
    read through jax's wrappers (``jvp(...)``, ``transpose(...)``,
    ``checkpoint``); a container (:data:`CONTAINER_SCOPES`) gives way to a
    layer scope inside it, and ``None`` means the path names no layer. The
    phase is ``recompute`` where the path holds ``rematted_computation``,
    ``bwd`` where it holds ``transpose(``, else ``fwd`` — jax writes both
    into the path itself."""
    layer = None
    for name in _SCOPE_NAME.findall(op_name_path):
        if name in CONTAINER_SCOPES:
            layer = name
        elif name in LAYER_SCOPES:
            layer = name
            break
    if "rematted_computation" in op_name_path:
        phase = "recompute"
    elif "transpose(" in op_name_path:
        phase = "bwd"
    else:
        phase = "fwd"
    return layer, phase


def instruction_name(raw: str) -> str:
    """``'%fusion.12 = bf16[...] fusion(...)'`` -> ``'fusion.12'``: a
    trace event's name as the HLO text spells the instruction."""
    return raw.split(" ", 1)[0].lstrip("%")


def scope_index(hlo_text: str) -> Dict[str, str]:
    """``{instruction: op_name path}`` of a compiled step's HLO text
    (``compiled.as_text()``). A fusion (or call) that carries no path of
    its own takes the path of its computation's root.

    The path is the metadata of the executable as it was compiled: one
    loaded from the persistent compilation cache keeps the names of the
    source that compiled it (jax leaves metadata out of the cache key
    unless ``jax_compilation_cache_include_metadata_in_key`` is set)."""
    paths: Dict[str, str] = {}
    callee: Dict[str, str] = {}      # instruction without a path -> calls=
    roots: Dict[str, str] = {}       # computation -> its root's path
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        path = _OP_NAME.search(line)
        if path is not None:
            paths[m.group(2)] = path.group(1)
            if m.group(1) and computation is not None:
                roots[computation] = path.group(1)
        else:
            calls = _CALLS.search(line)
            if calls is not None:
                callee[m.group(2)] = calls.group(1)
    for name, comp in callee.items():
        if comp in roots:
            paths[name] = roots[comp]
    return paths


# ---------------------------------------------------------------------------
# op-name helpers (fixture-testable, no jax import)
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
    decoded events so it is unit-testable on a canned fixture.
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


def aggregate_scope_times(
    events: Iterable[Tuple], index: Dict[str, str],
) -> Dict[Tuple[str, str], int]:
    """Fold the same events into ``{(layer_scope, phase): ps}`` through a
    :func:`scope_index` of the step that ran. Containers are dropped as in
    :func:`aggregate_op_times`; an event whose instruction the index does
    not hold, or whose path names no layer, goes to ``"_unscoped_"``."""
    per_scope: Dict[Tuple[str, str], int] = defaultdict(int)
    for item in events:
        name = instruction_name(item[0])
        if name.startswith(_CONTAINER_PREFIXES):
            continue
        layer, phase = scope_of(index.get(name, ""))
        per_scope[(layer or "_unscoped_", phase)] += int(item[1])
    return dict(per_scope)


def breakdown_table(total_ps: int, per_op, n_steps: int = 1,
                    top: int = 10, per_scope=None) -> Optional[dict]:
    """The published table: top-``top`` ops, per-category totals and,
    given :func:`aggregate_scope_times`' fold, per-layer totals
    (``"scopes"``: layer scope -> ms per step, share, and the same by
    phase).

    Ops on the device ``XLA Ops`` line are leaf HLO instructions, so
    durations are self-times. Returns ``None`` when nothing was captured.
    """
    if not total_ps:
        return None

    def cell(ps):
        return {"ms_per_step": round(ps / 1e9 / n_steps, 3),
                "pct": round(100.0 * ps / total_ps, 2)}

    rows = sorted(per_op.items(), key=lambda kv: -kv[1])
    ops = [{"op": name, "category": cat, **cell(ps)}
           for (name, cat), ps in rows[:top]]
    by_cat: Dict[str, int] = defaultdict(int)
    for (name, cat), ps in per_op.items():
        by_cat[cat] += ps
    table = {
        "source": "xplane",
        "device_ms_per_step": round(total_ps / 1e9 / n_steps, 3),
        "ops": ops,
        "categories": {
            cat: cell(ps)
            for cat, ps in sorted(by_cat.items(), key=lambda kv: -kv[1])},
    }
    if per_scope is not None:
        by_layer: Dict[str, int] = defaultdict(int)
        for (layer, phase), ps in per_scope.items():
            by_layer[layer] += ps
        table["scopes"] = {
            layer: {**cell(ps), "phases": {
                phase: cell(per_scope[(layer, phase)]) for phase in PHASES
                if (layer, phase) in per_scope}}
            for layer, ps in sorted(by_layer.items(), key=lambda kv: -kv[1])}
    return table


# ---------------------------------------------------------------------------
# xplane extraction (jax's own reader)
# ---------------------------------------------------------------------------

def iter_xplane_events(trace_dir: str):
    """Yield ``(raw_op_name, duration_ps, hlo_category_or_None)`` for
    every event on a device plane's ``XLA Ops`` line under ``trace_dir``
    (``hlo_category`` is the profiler's per-op category stat, XLA's own
    attribution from the fused computation's root). Empty when nothing
    was captured."""
    from jax.profiler import ProfileData

    for path in sorted(glob.glob(
            os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)):
        for plane in ProfileData.from_file(path).planes:
            if "/device:TPU" not in plane.name:
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    yield (ev.name, int(round(ev.duration_ns * 1e3)),
                           dict(ev.stats).get("hlo_category") or None)


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

    def op_breakdown(self, n_steps: int = 1, top: int = 10,
                     hlo_text: Optional[str] = None):
        """Parse the capture into a categorized table (after the ``with``
        block exits). ``None`` when no device plane was captured. With
        ``hlo_text`` — ``compiled.as_text()`` of the step that ran — the
        table also holds ``"scopes"``: device time by :data:`LAYER_SCOPES`
        layer and phase."""
        if self.active:
            raise RuntimeError(
                "trace_session is still active — parse after the with "
                "block exits (the profiler writes the xplane on stop)")
        events = list(iter_xplane_events(self.logdir))
        total_ps, per_op = aggregate_op_times(events)
        per_scope = None
        if hlo_text is not None:
            per_scope = aggregate_scope_times(events, scope_index(hlo_text))
        return breakdown_table(total_ps, per_op, n_steps=n_steps, top=top,
                               per_scope=per_scope)


@contextlib.contextmanager
def trace_session(logdir: Optional[str] = None):
    """Capture a ``jax.profiler`` trace around a block of training code.

    Yields a :class:`TraceSession`; after the block exits, call
    ``session.op_breakdown(n_steps=..., hlo_text=...)`` for the
    device-time table by operation, category and layer scope, or point
    ``tensorboard --logdir`` / Perfetto at ``session.logdir`` for the full
    timeline, where the same names (:data:`LAYER_SCOPES`, and inside them
    ``apex_tpu.flash_attention``, ``apex_tpu.packed_adam``, ...) annotate
    the op names.

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


def _compile(step_fn, state):
    """``step_fn`` compiled ahead of time for ``state``."""
    import jax

    lower = getattr(step_fn, "lower", None)
    if lower is None:
        lower = jax.jit(step_fn).lower
    return lower(*state).compile()


def cost_analysis_breakdown(step_fn, state) -> Optional[dict]:
    """Static flops/bytes attribution from ``Compiled.cost_analysis()``.

    The off-TPU fallback: no device timeline exists on the CPU backend,
    but XLA's post-optimization cost model still attributes the step's
    algorithmic work — enough for CI to catch a step whose flops or
    traffic regress. Returns ``None`` only if even compilation fails.
    """
    try:
        ca = _compile(step_fn, state).cost_analysis()
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
    return the top-``top`` device-time table with its per-category and
    per-layer (``"scopes"``) totals. Off-TPU there is no device plane and
    the answer is the static ``cost_analysis()`` attribution; on a TPU a
    trace without a device plane is an error — a caller on the chip is
    never handed the static table in a device table's place.

    ``step_fn(*state) -> state`` must be chainable (the bench step
    contract). The step is compiled once, ahead of time, and that
    executable both runs under the trace and gives the HLO text the
    scopes are read from. The final state is fenced inside the trace so
    every step is captured.
    """
    import jax

    if jax.default_backend() != "tpu":
        # no device plane exists to capture — skip the n_steps of traced
        # execution entirely and go straight to the static attribution
        return cost_analysis_breakdown(step_fn, state)
    compiled = _compile(step_fn, state)
    with trace_session() as sess:
        cur = state
        for _ in range(n_steps):
            cur = compiled(*cur)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x,
            cur[-1],
        )
    table = sess.op_breakdown(n_steps=n_steps, top=top,
                              hlo_text=compiled.as_text())
    if table is None:
        raise RuntimeError(
            f"profile_step traced {n_steps} steps on a TPU but the "
            "profile holds no device plane")
    return table
