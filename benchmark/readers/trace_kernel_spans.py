"""Roofline share (0..1) of the kernels whose op name matches ``regex``,
where the work is what the program counted on its spans: for every span
named in ``spans`` that started inside the window, the family's SPAN_COSTS
function ``cost`` gives the (FLOPs, bytes) of that one compiled call (or
None: a call that does none of this work, or a program that counts
nothing); the least time the chip could take for each call, summed, over
the time the kernels took in the trace.  Every call of the kernel in the
window belongs to one of the spans, so the share reads what the kernel
did, whichever compiled body called it.  Nothing counted: None."""

from benchmark.lib import peaks


def read(args, run):
    r = run.reduction
    cost = getattr(run.cell.family, "SPAN_COSTS", {}).get(args["cost"])
    if r is None or cost is None:
        return None
    total = r.kernel_s(args["regex"])
    if total is None:
        return None
    t0, t1 = run.driver["window_wall"]
    least, counted = 0.0, 0
    for rec in run.driver.get("records", []):
        if (rec.get("kind") == "span" and rec.get("name") in args["spans"]
                and t0 <= rec["ts"] <= t1):
            work = cost(run.cell.config, rec)
            if work is not None:
                least += peaks.least_seconds(run.device_kind, *work)
                counted += 1
    return least / total if counted else None
