"""Device time per step under ``apex_tpu.pack`` or ``apex_tpu.unpack``
(pytree <-> flat buffer), wherever they stand: inside the optimizer step,
inside the gradient reduction, or alone."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    return None if t is None else sr.inside(t, (sr.PACK, sr.UNPACK))
