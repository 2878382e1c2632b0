"""A percentile of what the clients saw in the window: time to first token
(``ttft_ms``: 50, 90, 99) or the gaps between tokens (``gap_ms``: 50, 95,
99), as the serve driver takes them for every cell.  Where BENCHMARK.json
judges these end to end, the line carries them itself; this reader is for
the cells in which they are recorded per layer."""


def read(args, run):
    series = (run.driver.get("client") or {}).get(args["series"])
    return None if not series else series[args["q"]]
