"""Device time of the cross-chip sums per step on the lowest device
(trace): the all-reduce operations, which the trace lists under the name
of the ``psum`` they came from. Whether compute hides them is not in this
number."""
from benchmark import trace_reduce


def read(run):
    if run["trace"] is None or not run.get("traced_units"):
        return None
    ops = trace_reduce.op_seconds(run["trace"])
    hits = [v for k, v in ops.items()
            if k.startswith(("psum", "all-reduce", "all_reduce"))]
    return 1e3 * sum(hits) / run["traced_units"] if hits else None
