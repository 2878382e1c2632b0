"""A percentile of the durations of the program's spans of one name that
started inside the window."""

from benchmark.lib import stats


def read(args, run):
    t0, t1 = run.driver["window_wall"]
    durs = [r["dur_s"] for r in run.driver.get("records", [])
            if r.get("kind") == "span" and r.get("name") == args["name"]
            and t0 <= r["ts"] <= t1]
    return stats.percentile(durs, args["q"]) if durs else None
