"""Device time per step in the backward pass (the path holds
``transpose(``) under any model scope; recomputation is not in it."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.total(t, layers=sr.MODEL, phase="bwd")
