"""Device time of the ``apex_tpu_grouped_matmul_*`` kernels per step (trace,
one device): the experts' products forward, by their input and by their
weight, recomputed forwards included. Silent where the program ran none."""
from benchmark import trace_reduce

KERNELS = "apex_tpu_grouped_matmul_"


def read(run):
    if run["trace"] is None or not run.get("traced_units"):
        return None
    s = trace_reduce.kernel_seconds(run["trace"], KERNELS)
    return None if s is None else 1e3 * s / run["traced_units"]
