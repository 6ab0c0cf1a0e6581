"""Device time per step under ``apex_tpu.amp_scaler``: loss scaling,
unscale, the overflow probe and the scale update."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.total(t, layers=(sr.SCALER,))
