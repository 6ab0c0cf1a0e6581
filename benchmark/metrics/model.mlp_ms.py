"""Device time per step under ``apex_tpu.mlp`` in all phases: both GEMMs
and the activation."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.total(t, layers=(sr.MLP,))
