"""Device time of the ``apex_tpu_flash_*`` training kernels per step
(trace, one device). Silent where the program ran none."""
from benchmark import trace_reduce

KERNELS = ("apex_tpu_flash_fwd", "apex_tpu_flash_bwd")


def read(run):
    if run["trace"] is None or not run.get("traced_units"):
        return None
    s = trace_reduce.kernel_seconds(run["trace"], KERNELS)
    return None if s is None else 1e3 * s / run["traced_units"]
