"""Seconds jax spent in the backend compiler or loading executables from
the persistent cache before the window (jax monitoring events). Moves
``setup_s``."""


def read(run):
    return run["phases"]["entry.compile_s"]
