"""Device time per step under ``apex_tpu.optimizer_step``: the whole
optimizer step (casts, norms, trust ratios, pack and unpack, kernels); it
holds ``optimizers.sweep_ms``."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.total(t, layers=(sr.OPTIMIZER,))
