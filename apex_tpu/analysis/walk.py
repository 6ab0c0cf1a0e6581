"""Recursive jaxpr traversal with structural context.

The rules need to know not just *which* equations a step contains but
*where* they sit: is this ``debug_callback`` under a ``lax.cond`` branch
(the sync-free drain discipline) or naked on the hot path? Is this
``dot_general`` inside a scan body that runs per microbatch? The walker
yields every equation of a (closed) jaxpr — descending into ``pjit``,
``cond`` branches, ``scan``/``while`` bodies, ``remat`` and custom-AD
call jaxprs — together with a :class:`WalkCtx` carrying cond/loop depth
and the primitive path from the root.

Pallas kernel bodies are NOT descended into: the inner jaxpr describes
one grid step over refs, and auditing its arithmetic with whole-program
rules (dtype flow, callbacks) would only produce noise — the
``pallas_call`` equation itself (aliases, name stack) is the audit
surface.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, NamedTuple, Tuple

import jax
from jax._src import core as jax_core

ClosedJaxpr = jax_core.ClosedJaxpr
Jaxpr = jax_core.Jaxpr

# primitives whose sub-jaxprs are conditional branches: reaching an eqn
# inside one requires the predicate to be taken
_BRANCHING = ("cond",)
# primitives whose sub-jaxprs execute repeatedly
_LOOPING = ("scan", "while")
# primitives whose sub-jaxprs are a foreign execution model — do not
# descend (see module docstring)
_OPAQUE = ("pallas_call",)


@dataclasses.dataclass(frozen=True)
class WalkCtx:
    """Structural position of an equation within the traced program."""

    cond_depth: int = 0   # number of enclosing cond branches
    loop_depth: int = 0   # number of enclosing scan/while bodies
    path: Tuple[str, ...] = ()  # primitive names from root to here

    @property
    def gated(self) -> bool:
        """Inside at least one ``cond`` branch (the drain discipline)."""
        return self.cond_depth > 0

    @property
    def in_loop(self) -> bool:
        return self.loop_depth > 0

    def describe(self) -> str:
        return "/".join(self.path) if self.path else "<top>"


def subjaxprs(eqn) -> List[Jaxpr]:
    """All sub-jaxprs of one equation (unwrapped to ``Jaxpr``)."""
    out: List[Jaxpr] = []
    for v in eqn.params.values():
        if isinstance(v, ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, Jaxpr):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for vv in v:
                if isinstance(vv, ClosedJaxpr):
                    out.append(vv.jaxpr)
                elif isinstance(vv, Jaxpr):
                    out.append(vv)
    return out


def transparent_subjaxprs(eqn) -> List[Jaxpr]:
    """Sub-jaxprs of one equation, honoring the opaque-primitive policy
    (pallas kernel bodies are never descended into — the module
    docstring's contract, shared by :func:`walk` and the rules' own
    recursions)."""
    if eqn.primitive.name in _OPAQUE:
        return []
    return subjaxprs(eqn)


def walk(jaxpr: Jaxpr, ctx: WalkCtx = WalkCtx()) -> Iterator[Tuple]:
    """Yield ``(eqn, ctx)`` for every equation, depth-first."""
    for eqn in jaxpr.eqns:
        yield eqn, ctx
        name = eqn.primitive.name
        if name in _OPAQUE:
            continue
        subs = subjaxprs(eqn)
        if not subs:
            continue
        sub_ctx = WalkCtx(
            cond_depth=ctx.cond_depth + (1 if name in _BRANCHING else 0),
            loop_depth=ctx.loop_depth + (1 if name in _LOOPING else 0),
            path=ctx.path + (name,),
        )
        for sub in subs:
            yield from walk(sub, sub_ctx)


def collect_consts(closed: ClosedJaxpr) -> List:
    """Every constant carried by this closed jaxpr or any nested one.

    Closure-captured arrays surface here: a jitted step that closes over
    a device array gets it as a const of the inner ``pjit`` jaxpr —
    exactly the HBM-duplication hazard the constants rule prices.
    """
    out = list(closed.consts)
    seen = {id(closed.jaxpr)}

    def rec(jaxpr: Jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.params.values():
                vs = v if isinstance(v, (tuple, list)) else (v,)
                for vv in vs:
                    if isinstance(vv, ClosedJaxpr) and id(vv.jaxpr) not in seen:
                        seen.add(id(vv.jaxpr))
                        out.extend(vv.consts)
                        rec(vv.jaxpr)
                    elif isinstance(vv, Jaxpr) and id(vv) not in seen:
                        seen.add(id(vv))
                        rec(vv)

    rec(closed.jaxpr)
    return out


def name_stack_str(eqn) -> str:
    """The eqn's named-scope stack as a string ('' when unavailable)."""
    try:
        return str(eqn.source_info.name_stack)
    except Exception:  # pragma: no cover - source info shape drift
        return ""


def pallas_kernel_name(eqn) -> str:
    """A ``pallas_call`` eqn's kernel name: its ``name=``, else the
    kernel function's own name."""
    return eqn.params["name"] or eqn.params["jaxpr"].debug_info.func_name


class KernelRecord(NamedTuple):
    """One ``pallas_call`` of a traced program."""

    name: str        # the kernel's ``name=`` (its function name if unset)
    compiled: bool   # False when the call runs under the interpreter


def kernel_inventory(program, *args) -> List[KernelRecord]:
    """Every ``pallas_call`` that ``program(*args)`` traces to, at any
    depth (cond branches, scans, remat, custom-AD) — trace only, no
    execution. ``program`` may also be an already-traced
    ``ClosedJaxpr`` (``jax.jit(f).trace(*args).jaxpr``), which saves
    re-tracing a large step.

    Model code selects XLA paths silently when a gate says no, and
    several wrappers flip ``interpret=True`` off-TPU; this is how a
    caller proves from the program itself that the kernel it expects is
    present and was handed to Mosaic rather than the interpreter.
    """
    closed = (program if isinstance(program, ClosedJaxpr)
              else jax.make_jaxpr(program)(*args))
    return [
        KernelRecord(pallas_kernel_name(eqn), not eqn.params["interpret"])
        for eqn, _ in walk(closed.jaxpr)
        if eqn.primitive.name == "pallas_call"
    ]
