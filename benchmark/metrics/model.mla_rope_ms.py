"""Device time per step under ``apex_tpu.mla_rope`` in all phases (it
nests in ``apex_tpu.attention``): latent attention's partial rotary on the
rotary lanes of q and on the one shared rotary key, with the slices and
joins round it. Silent where the step names no such scope."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.inside(t, ("apex_tpu.mla_rope",))
