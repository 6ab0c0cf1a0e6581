"""The whole step's share of the chips' peak: the matmul FLOPs one
forward + backward step needs (``flops.train_flops_per_step``; recompute
earns nothing) times the steps of the window, over window x chips x peak."""


def read(run):
    c = run["counters"]
    if run["peaks"] is None or not c.get("steps"):
        return None
    return 100.0 * c["train_flops_per_step"] * c["steps"] / (
        c["window_s"] * c["chips"] * run["peaks"].flops_per_s)
