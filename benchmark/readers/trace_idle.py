"""Share of the traced window in which no op ran on device 0 (0..1)."""


def read(args, run):
    r = run.reduction
    return None if r is None else 1.0 - r.busy_s / r.window_s
