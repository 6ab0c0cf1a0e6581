"""Program ready to the window's first instant: the warm-up steps, which
are the first steps from the seed that the check follows. Harness clock."""


def read(run):
    return run["phases"]["entry.warm_s"]
