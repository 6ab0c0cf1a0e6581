"""Device time per step under ``apex_tpu.moe_router`` in all phases (it
nests in ``apex_tpu.mlp``): the router: scores in float32, top-k, weights. Silent where the step names no such scope."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.inside(t, ("apex_tpu.moe_router",))
