"""Seconds per step that device 0's op line spends inside collective ops
(their self time: the line is the core's one sequential timeline, so
while it sits in a collective nothing else computes there)."""

from benchmark.lib.xplane import COLLECTIVE_RE


def read(args, run):
    r, steps = run.reduction, run.driver.get("steps")
    if r is None or not steps:
        return None
    total = r.kernel_s(COLLECTIVE_RE.pattern)
    return None if total is None else total / steps
