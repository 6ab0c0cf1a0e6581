"""The grouped matmul's share of its roofline: the FLOPs and bytes the
experts' products need in one step on one chip at the expected load, as the
configuration's family counted them from shapes
(``counters["kernel_work"]["grouped_matmul"]``), as the least time the chip
could take, over the ``apex_tpu_grouped_matmul_*`` kernels' time per step (a
forward replayed by recomputation earns nothing and is in the time). Which
roof binds goes into the run's notes. Silent where the family counted none."""
from benchmark import flops, trace_reduce

KERNELS = "apex_tpu_grouped_matmul_"


def read(run):
    if (run["trace"] is None or run["peaks"] is None
            or not run.get("traced_units")):
        return None
    s = trace_reduce.kernel_seconds(run["trace"], KERNELS)
    work = run["counters"].get("kernel_work", {}).get("grouped_matmul")
    if s is None or work is None:
        return None
    share, roof = flops.roofline_share(*work, s / run["traced_units"],
                                       run["peaks"])
    run["notes"]["grouped_matmul_roof"] = roof
    return share
