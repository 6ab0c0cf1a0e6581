"""Device time by the program's named scopes: who owns which part of the
step.

The program bounds its layers with ``jax.named_scope("apex_tpu.<layer>")``.
A scope is metadata, not an operation: every instruction of the compiled
step carries its whole scope path in ``metadata={op_name="..."}``, and the
device trace names each event by its instruction (``fusion.123``). Joining
the two gives device time by layer, by forward / backward / recompute, and
by whether the operation computes or only moves data. The reduction is the
benchmark's own (it imports nothing of ``apex_tpu.telemetry``), and every
scope name it reads is spelled out here, so that no change to the program
moves the yardstick.

Rules:

- An event's scope is its instruction's ``op_name`` path in the compiled
  step's HLO text. A fusion carries its root's path: XLA gives the fusion
  instruction the metadata of the fused computation's root, and where the
  instruction has none the root's is read from the computation. The layer
  is the outermost scope of ``LAYERS`` in the path; a container
  (``CONTAINERS``: the layer stack, the layer) gives way to a layer scope
  inside it, so that what stays with ``apex_tpu.transformer_layer`` is the
  norms and the residual tails. Kernel scopes nest inside and count to the
  enclosing layer.
- Phase: ``recompute`` where the path holds ``rematted_computation``,
  ``bwd`` where it holds ``transpose(``, else ``fwd``; jax writes both.
- An operation the compiler inserted without metadata (a layout ``copy``,
  a ``bitcast``) takes the scope of an instruction that consumes it, else
  of one that produces it (the operands are in the text). What is still
  without a layer is ``_unscoped_``; so is an operation whose path names no
  layer. Directly-scoped and neighbour-scoped time are reported apart on
  standard error.
- An operation only moves data (``relayout``) when its opcode, or every
  opcode of the computation a fusion calls, is one of ``MOVERS``: a
  ``copy``, a ``reshape`` that changes the layout, a fusion of a slice and
  a bitcast. The text decides, not the name: on the chip a
  ``bitcast_dynamic-update-slice_fusion`` is as a rule a weight-gradient
  GEMM that writes into a stacked buffer, and computes.
- Time is self time (``trace_reduce.self_times``: a ``while`` keeps what
  its body leaves) on the lowest device inside the whole-steps window, in
  ms per step (``run["traced_units"]``).

Where the HLO text comes from: by the time a reader runs, the harness has
deleted the program and the trace directory. ``step_text`` rebuilds the
cell's step through ``train_cell.build_program`` and compiles it: the same
program, so the same instruction names. It is not the same entry of the
persistent cache: a Pallas kernel's serialized body holds the source
locations of its call stack, ten frames deep, so a step with such a kernel
in its optimizer is keyed by who called ``lower`` too, and the rebuild
compiles cold once for each checkout and cell (about as long as the timed
step's own cold compile) and loads from the cache after. Everything else
of the metadata is left out of the key by jax, so an executable that a
source with other scope names compiled can come back from the cache;
where the text lacks a scope that the fresh lowering has, the step is
compiled once more under a key that holds the metadata. A lowering that
does not name its attention, MLP and optimizer (a program from before the
scopes) gives nothing, at the cost of the lowering alone. Under 99% of the
traced time found in the text: nothing. One rebuild per process; the
first reader prints the whole table.
"""
from __future__ import annotations

import json
import re
import sys
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from benchmark import trace_reduce

EMBED = "apex_tpu.embed"
STACK = "apex_tpu.layer_stack"
LAYER = "apex_tpu.transformer_layer"
ATTENTION = "apex_tpu.attention"
MLP = "apex_tpu.mlp"
HEAD = "apex_tpu.lm_head"
LOSS = "apex_tpu.cross_entropy"
SCALER = "apex_tpu.amp_scaler"
OPTIMIZER = "apex_tpu.optimizer_step"
PACK = "apex_tpu.pack"
UNPACK = "apex_tpu.unpack"
SYNC = "apex_tpu.sync_gradients"
BUCKET = "apex_tpu.grad_bucket"

MODEL = (EMBED, STACK, LAYER, ATTENTION, MLP, HEAD, LOSS)
LAYERS = MODEL + (SCALER, OPTIMIZER, PACK, UNPACK, SYNC, BUCKET)
CONTAINERS = (STACK, LAYER)
UNSCOPED = "_unscoped_"
MOVERS = frozenset((
    "copy", "copy-start", "copy-done", "reshape", "transpose", "bitcast",
    "slice", "slice-start", "slice-done", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "broadcast", "constant",
    "parameter", "tuple", "get-tuple-element"))
COVERAGE = 0.99

_SCOPE = re.compile(r"apex_tpu\.\w+")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) [^=]*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def layer_and_phase(path: str) -> Tuple[Optional[str], str]:
    """The layer scope and the phase of one ``op_name`` path."""
    layer = None
    for name in _SCOPE.findall(path):
        if name in CONTAINERS:
            layer = name
        elif name in LAYERS:
            layer = name
            break
    if "rematted_computation" in path:
        return layer, "recompute"
    return layer, "bwd" if "transpose(" in path else "fwd"


def _opcode(rest: str) -> str:
    """The opcode of an instruction, from what follows ``name = ``: the
    word before the operands' parenthesis, after the shape (which, for a
    tuple, holds parentheses and spaces of its own)."""
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if ch == " " and depth == 0:
            return rest[i + 1:].split("(", 1)[0]
    return ""


class Scoped(NamedTuple):
    layer: str       # a scope of LAYERS, or UNSCOPED
    phase: str
    how: str         # "direct", "neighbour" or "none"
    path: str        # the op_name path the layer was read from
    moves: bool = False     # only moves data


def scopes_of_text(hlo_text: str) -> Dict[str, Scoped]:
    """Every instruction of the text with its layer, by the rules above."""
    paths: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    opcodes: Dict[str, str] = {}
    called: Dict[str, str] = {}          # instruction -> calls=
    roots: Dict[str, str] = {}           # computation -> its root's path
    within: Dict[str, set] = {}          # computation -> its opcodes
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(2)
        body = line[m.end():].split(", metadata=", 1)[0]
        operands[name] = _OPERAND.findall(body)
        opcodes[name] = _opcode(body)
        within.setdefault(computation, set()).add(opcodes[name])
        calls = _CALLS.search(line)
        if calls is not None:
            called[name] = calls.group(1)
        path = _OP_NAME.search(line)
        if path is not None:
            paths[name] = path.group(1)
            if m.group(1):
                roots[computation] = path.group(1)
    for name, comp in called.items():
        if name not in paths and comp in roots:
            paths[name] = roots[comp]
    moves = {name: (within.get(called.get(name, "?"), {"?"}) <= MOVERS
                    if op == "fusion" else op in MOVERS)
             for name, op in opcodes.items()}

    out: Dict[str, Scoped] = {}
    for name, path in paths.items():
        layer, phase = layer_and_phase(path)
        out[name] = (
            Scoped(layer, phase, "direct", path, moves[name]) if layer
            else Scoped(UNSCOPED, phase, "none", path, moves[name]))
    consumers: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for op in ops:
            consumers.setdefault(op, []).append(name)
    bare = [n for n in operands if n not in paths]
    for _ in range(4):          # a copy of a bitcast of a copy
        left = []
        for name in bare:
            near = [out[n] for n in (consumers.get(name, [])
                                     + operands[name])
                    if n in out and out[n].how != "none"]
            if near:
                out[name] = near[0]._replace(how="neighbour",
                                             moves=moves[name])
            else:
                left.append(name)
        if len(left) == len(bare):
            break
        bare = left
    for name in bare:
        out[name] = Scoped(UNSCOPED, "fwd", "none", "", moves[name])
    return out


def reduce(events, scopes: Dict[str, Scoped], window, steps: int
           ) -> Optional[Dict]:
    """The table of one device's events (``(name, start_ns, dur_ns)``) cut
    to ``window``: ms per step by layer, phase and kind, with the totals
    the readers use. ``None`` where under ``COVERAGE`` of the time is of
    instructions the text holds."""
    lo, hi = window
    clipped = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            clipped.append((name, a, b - a))
    per_ms = 1e-6 / steps
    rows: Dict[Tuple[str, str, str], float] = {}
    how = {"direct": 0.0, "neighbour": 0.0, "none": 0.0}
    inside: Dict[str, float] = {}      # by any scope of the path
    busy = found = 0.0
    left: Dict[str, float] = {}        # unscoped time by operation name
    moved: Dict[str, float] = {}       # relayout time by layer, phase, op
    for raw, _, dur in trace_reduce.self_times(clipped):
        ms = dur * per_ms
        busy += ms
        s = scopes.get(raw.split(" ", 1)[0].lstrip("%"))
        if s is None:
            continue
        found += ms
        kind = "relayout" if s.moves else "compute"
        key = (s.layer, s.phase, kind)
        rows[key] = rows.get(key, 0.0) + ms
        how[s.how] += ms
        if kind == "relayout":
            op = f"{s.layer}|{s.phase}|{trace_reduce.op_name(raw)}"
            moved[op] = moved.get(op, 0.0) + ms
        for name in set(_SCOPE.findall(s.path)):
            inside[name] = inside.get(name, 0.0) + ms
        if s.layer == UNSCOPED:
            op = trace_reduce.op_name(raw)
            left[op] = left.get(op, 0.0) + ms
    if not busy or found < COVERAGE * busy:
        return None
    top = lambda d, n: dict(sorted(d.items(), key=lambda kv: -kv[1])[:n])
    return {"rows": rows, "busy_ms": busy, "found_ms": found, "how": how,
            "inside": inside, "unscoped_ops": top(left, 8),
            "relayout_ops": top(moved, 16)}


def total(table: Dict, layers=None, phase=None, kind=None) -> Optional[float]:
    """ms per step of the rows that match; ``None`` where none does."""
    hits = [v for (la, ph, ki), v in table["rows"].items()
            if (layers is None or la in layers)
            and (phase is None or ph == phase)
            and (kind is None or ki == kind)]
    return sum(hits) if hits else None


def inside(table: Dict, scopes) -> Optional[float]:
    """ms per step under any of ``scopes``, wherever in the path they
    stand (for scopes that nest in several layers: pack, unpack)."""
    hits = [table["inside"][s] for s in scopes if s in table["inside"]]
    return sum(hits) if hits else None


def step_text(cell, devices, interpret: bool = False) -> Optional[str]:
    """The compiled step's HLO text for ``cell`` (a ``manifest.Cell``), or
    ``None`` where the program lowered here does not name its attention,
    MLP and optimizer (a checkout from before the scopes): nothing is
    compiled then."""
    import jax

    from benchmark import traffic, train_cell, weights

    program = train_cell.build_program(cell.config, cell.mix, 0, devices,
                                       interpret)
    mix = traffic.train_mix(cell.mix, cell.chips)
    batch = program.put(*traffic.train_batch(
        0, 0, mix["batch"], mix["seq"],
        weights.model_dims(cell.config)["vocab"], mix["labels"]))
    lower = lambda: program._jit.lower(*program.state, *batch)
    lowered = lower()
    named = set(_SCOPE.findall(lowered.as_text(debug_info=True)))
    if not named >= {ATTENTION, MLP, OPTIMIZER}:
        return None
    text = lowered.compile().as_text()
    if not named & set(LAYERS) <= set(_SCOPE.findall(text)):
        # a cached executable keeps the names of the source that compiled
        # it: compile this source's under a key of its own (jax keeps a
        # lowering and its executable, so both are dropped first)
        key = "jax_compilation_cache_include_metadata_in_key"
        old = getattr(jax.config, key)
        jax.config.update(key, True)
        try:
            jax.clear_caches()
            text = lower().compile().as_text()
        finally:
            jax.config.update(key, old)
    return text


_TABLES: Dict[str, Optional[Dict]] = {}


def table_of(run) -> Optional[Dict]:
    """The run's table, rebuilt once per process; ``None`` (and no
    rebuild) without a trace or whole steps in it."""
    trace, steps = run.get("trace"), run.get("traced_units")
    if trace is None or not steps or not trace.device_ops:
        return None
    cell = run["cell"]
    if cell not in _TABLES:
        _TABLES[cell] = _table(run, trace, steps)
    return _TABLES[cell]


def _table(run, trace, steps) -> Optional[Dict]:
    """Rebuild, reduce, and print the whole table on standard error."""
    import jax

    from benchmark import manifest as mf

    say = lambda msg: print(f"[bench {run['platform']}] scopes {msg}",
                            file=sys.stderr, flush=True)
    t0 = time.perf_counter()
    cell = mf.Cell(mf.load_manifest(), run["cell"])
    text = step_text(cell, jax.devices()[:cell.chips])
    if text is None:
        say("none: the program names no layer scope")
        return None
    table = reduce(trace.device_ops[min(trace.device_ops)],
                   scopes_of_text(text), trace_reduce.window_of(trace),
                   steps)
    if table is None:
        say("none: the rebuilt step's text does not hold the traced "
            "operations")
        return None
    say(json.dumps({
        "ms_per_step": {f"{la}|{ph}|{ki}": round(v, 3) for (la, ph, ki), v
                        in sorted(table["rows"].items())},
        "busy_ms": round(table["busy_ms"], 3),
        "directly_scoped_ms": round(table["how"]["direct"], 3),
        "neighbour_scoped_ms": round(table["how"]["neighbour"], 3),
        "unscoped_ms": round(table["how"]["none"], 3),
        "unscoped_ops": {k: round(v, 3)
                         for k, v in table["unscoped_ops"].items()},
        "relayout_ops": {k: round(v, 3)
                         for k, v in table["relayout_ops"].items()},
        "inside": {k: round(v, 3) for k, v in sorted(
            table["inside"].items())},
        "rebuild_s": round(time.perf_counter() - t0, 2)}))
    return table
