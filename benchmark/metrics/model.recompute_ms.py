"""Device time per step spent computing again what the forward pass did
not keep (the path holds ``rematted_computation``)."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.total(t, phase="recompute")
