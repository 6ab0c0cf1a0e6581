"""Seconds of set-up inside jax's ``trace`` spans, as their union
(``/jax/core/compile/jaxpr_trace_duration`` by the program's compile
ledger): Python turning the program's functions into jaxprs. Moves
``setup_s``."""
from benchmark import startup_reduce as su


def read(run):
    return su.value(run, "startup.trace_s")
