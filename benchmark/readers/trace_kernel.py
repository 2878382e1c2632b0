"""Device time of the kernels whose op name matches ``regex``.

stat ``per_step_s``: seconds per step (``per`` names the driver's count to
divide by: "steps" or "decode_steps").
stat ``roofline``: the least time the chip could take for one step's calls
(``cost`` names a function of the family's STEP_COSTS giving FLOPs and
bytes; the larger of FLOPs/peak and bytes/peak) over the time
they took (0..1)."""

from benchmark.lib import peaks


def read(args, run):
    r = run.reduction
    count = run.driver.get(args.get("per", "steps"))
    if r is None or not count:
        return None
    total = r.kernel_s(args["regex"])
    if total is None:
        return None
    if args["stat"] == "per_step_s":
        return total / count
    if args["stat"] == "roofline":
        flops, nbytes = run.cell.family.STEP_COSTS[args["cost"]](
            run.cell.config, run.cell.traffic, run.cell.chips)
        return peaks.least_seconds(run.device_kind, flops,
                                   nbytes) * count / total
    raise ValueError(f"trace_kernel: unknown stat {args['stat']!r}")
