"""Device time per step under ``apex_tpu.mla_latent`` in all phases (it
nests in ``apex_tpu.attention``): latent attention's K/V side from the
normed input to the kernel's operands: the down-projection, the latent's
RMSNorm, the up-projection, assembling k. Silent where the step names no such scope."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.inside(t, ("apex_tpu.mla_latent",))
