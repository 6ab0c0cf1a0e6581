"""Backend up to the timed program and its state standing on the device:
the program's imports, weights and optimizer state (or the KV pool) made
from the seed, trace, lower, and compile or cache load. Harness clock."""


def read(run):
    return run["phases"]["entry.build_s"]
