"""A percentile of one of the serving engine's histograms over what it
observed inside the window (the driver resets them at its start and reads
50, 90, 95 and 99 at its end through the histogram's own API)."""


def read(args, run):
    h = run.driver.get("histograms", {}).get(args["histogram"])
    if not h or not h["count"]:
        return None
    return h["q"][args["q"]]
