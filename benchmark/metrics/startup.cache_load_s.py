"""Seconds of set-up spent reading and deserializing executables from the
persistent cache (``/jax/compilation_cache/cache_retrieval_time_sec``
summed by the program's compile ledger): the part of ``entry.compile_s``
that follows the executable's size. Moves ``setup_s``."""
from benchmark import startup_reduce as su


def read(run):
    return su.value(run, "startup.cache_load_s")
