"""Device time per step of the model's two ends: ``apex_tpu.embed`` +
``apex_tpu.lm_head`` + ``apex_tpu.cross_entropy`` (where the head GEMM and
the loss are one chunk-fused function, the last holds both)."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.total(t, layers=(sr.EMBED, sr.HEAD, sr.LOSS))
