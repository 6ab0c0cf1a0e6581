"""Steps the loss scaler skipped (steps run minus the optimizer's step
count), read from the program's state after the window."""


def read(run):
    v = run["counters"].get("skipped_steps")
    return None if v is None else float(v)
