"""Programs compiled before the window that the persistent cache did not
hold (compile requests minus cache hits, jax monitoring events): 0 in a
warm run. Moves ``setup_s``."""


def read(run):
    return float(run["phases"]["entry.cache_misses"])
