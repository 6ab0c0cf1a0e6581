"""Trace + lower + compile-or-load seconds of the one program with the
largest sum (the step; the program's compile ledger: one ``compile`` span
and the ``trace`` and ``lower`` spans of its ``fun_name`` since the last
``compile`` of that name): what is left of the three kinds is the small
programs round it. Moves ``setup_s``."""
from benchmark import startup_reduce as su


def read(run):
    return su.value(run, "startup.largest_program_s")
