"""Device time per step under ``apex_tpu.moe_dispatch`` in all phases (it
nests in ``apex_tpu.mlp``): the data movement expert routing costs: the sort, the gather into expert order and the weighted gather back. Silent where the step names no such scope."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.inside(t, ("apex_tpu.moe_dispatch",))
