"""Flash attention's share of its roofline: the FLOPs and bytes attention
needs in one step, from shapes (``flops.flash_attention_step``), as the
least time the chip could take, over the ``apex_tpu_flash_*`` kernels' time
per step. Which roof binds goes into the run's notes."""
from benchmark import flops, trace_reduce

KERNELS = ("apex_tpu_flash_fwd", "apex_tpu_flash_bwd")


def read(run):
    if (run["trace"] is None or run["peaks"] is None
            or not run.get("traced_units")):
        return None
    s = trace_reduce.kernel_seconds(run["trace"], KERNELS)
    if s is None:
        return None
    c = run["counters"]
    d = c["dims"]
    fl, by = flops.flash_attention_step(
        d["layers"], d["heads"], d["head_dim"],
        c["batch"] // c["chips"], c["seq"], c["causal"])
    share, roof = flops.roofline_share(fl, by, s / run["traced_units"],
                                       run["peaks"])
    run["notes"]["flash_attention_roof"] = roof
    return share
