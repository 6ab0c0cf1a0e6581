"""The compiler's own account (``memory_analysis()``) of the timed
program's device memory: arguments + temporaries + outputs not aliased to
arguments. ``memory_stats()`` after the run leaves the temporaries out."""


def read(run):
    v = run["counters"].get("hbm_program_bytes")
    return None if not v else v / 1e9
