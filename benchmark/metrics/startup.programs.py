"""Executables compiled or loaded before the window (``compile`` spans of
the program's compile ledger,
``/jax/core/compile/backend_compile_duration``): each a cache key, a file
read and a load, however small. Moves ``setup_s``."""
from benchmark import startup_reduce as su


def read(run):
    return su.value(run, "startup.programs")
