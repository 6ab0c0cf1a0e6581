"""Device busy time per train step: the union of the intervals in which
an operation ran in the traced steps, over their number (trace)."""
from benchmark import trace_reduce


def read(run):
    if run["trace"] is None or not run.get("traced_units"):
        return None
    bw = trace_reduce.busy_and_window(run["trace"])
    return None if bw is None else 1e3 * bw[0] / run["traced_units"]
