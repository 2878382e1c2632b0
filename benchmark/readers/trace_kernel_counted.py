"""Roofline share (0..1) of the kernels whose op name matches ``regex``,
where the work is what the program counted: ``histogram`` names an engine
histogram with one sample per call of the counted kind, whose sum over the
window (times the cell's engine setting ``engine_scale``, where given:
pages to tokens) is handed to the family's COUNTED_COSTS function
``cost``; the least time the chip could take for those FLOPs and bytes,
over the time the kernels took in the trace.

The kernels' time holds every call the trace saw.  Where some calls do
work the counter does not count (the paged decode kernel also runs inside
prefill chunks, whose pages ``serve_decode_live_pages`` leaves out), the
share reads low, never high.  No samples: None."""

from benchmark.lib import peaks


def read(args, run):
    r = run.reduction
    h = run.driver.get("histograms", {}).get(args["histogram"])
    if r is None or not h or not h["count"]:
        return None
    total = r.kernel_s(args["regex"])
    if total is None:
        return None
    counted = h["count"] * h["mean"]
    if args.get("engine_scale"):
        counted *= run.cell.workload["engine"][args["engine_scale"]]
    flops, nbytes = run.cell.family.COUNTED_COSTS[args["cost"]](
        run.cell.config, counted)
    return peaks.least_seconds(run.device_kind, flops, nbytes) / total
