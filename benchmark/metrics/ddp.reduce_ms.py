"""Device time per step under ``apex_tpu.sync_gradients`` /
``apex_tpu.grad_bucket``: the bucket fill and the all-reduces (it holds
``ddp.allreduce_ms``)."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.total(t, layers=(sr.SYNC, sr.BUCKET))
