"""Share of the device's busy time in the traced steps that no layer scope
owns, even by the neighbour rule (``scope_reduce``): what the by-scope
table cannot attribute."""
from benchmark import scope_reduce as sr


def read(run):
    t = sr.table_of(run)
    if t is None:
        return None
    owned = t["how"]["direct"] + t["how"]["neighbour"]
    return 100.0 * (1.0 - owned / t["busy_ms"])
