"""Cross-chip sums in the timed program, counted from its traced jaxpr
(``analysis.collective_inventory``): one per gradient bucket, one for the
loss."""


def read(run):
    v = run["counters"].get("psums_per_step")
    return None if v is None else float(v)
