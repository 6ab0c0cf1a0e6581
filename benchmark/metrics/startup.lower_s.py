"""Seconds of set-up inside jax's ``lower`` spans, as their union
(``/jax/core/compile/jaxpr_to_mlir_module_duration`` by the program's
compile ledger): jaxpr to StableHLO, the Pallas kernels' lowering in it.
Moves ``setup_s``."""
from benchmark import startup_reduce as su


def read(run):
    return su.value(run, "startup.lower_s")
