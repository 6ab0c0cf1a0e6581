"""Device time per step of operations that only move data (copies,
reshapes, transposes, bitcasts, slices and dynamic-(update-)slice fusions:
``scope_reduce.MOVERS``) under any model scope."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.total(t, layers=sr.MODEL, kind="relayout")
