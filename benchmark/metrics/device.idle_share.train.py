"""The device's idle share in the traced slice: 1 - (union of the
intervals in which an operation ran) / window."""
from benchmark import trace_reduce


def read(run):
    if run["trace"] is None:
        return None
    bw = trace_reduce.busy_and_window(run["trace"])
    return None if bw is None else 100.0 * (1.0 - bw[0] / bw[1])
