"""Median wall time of a step, from the benchmark's own clock between
fit's synced log boundaries (seconds)."""

from benchmark.lib import stats


def read(args, run):
    walls = run.driver.get("step_walls_s")
    return stats.median(walls) if walls else None
