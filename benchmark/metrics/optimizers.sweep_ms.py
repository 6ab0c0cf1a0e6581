"""Device time of the packed optimizer's sweeps (``apex_tpu_packed_*``)
per step (trace, one device)."""
from benchmark import trace_reduce


def read(run):
    if run["trace"] is None or not run.get("traced_units"):
        return None
    s = trace_reduce.kernel_seconds(run["trace"], "apex_tpu_packed_")
    return None if s is None else 1e3 * s / run["traced_units"]
