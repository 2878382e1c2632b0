"""One run of one cell:

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run.  It needs the TPU the cell asks for (no fallback),
places the compile cache through the program's own
``runtime/compile_cache.configure()``, hands the cell to its driver
(``benchmark/drivers/<driver>.py``), reads the per-layer metrics of a
traced run through their readers, holds the line it is about to print
against ``lib/contract.py`` and prints it last — or prints the faults to
stderr and exits 1 with no line.  It starts no child process.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()       # before the heavy imports: set-up counts

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return p.parse_args(argv)


def per_layer_metrics(benchmark, cell, device_kind, result, out_dir):
    """(metrics, device extras, breakdown) of a traced run."""
    from benchmark.lib import xplane
    from benchmark.lib.runtime import BENCH_DIR, load_json
    from benchmark.readers import ReaderInput, read_metric
    path = xplane.find_xplane(result["readers"]["profile_dir"])
    trace = xplane.load(path)
    with open(os.path.join(out_dir, "trace_described.txt"), "w") as f:
        f.write(xplane.describe(trace) + "\n")
    reduction = xplane.reduce_trace(trace)
    run = ReaderInput(cell=cell, device_kind=device_kind,
                      reduction=reduction, driver=result["readers"])
    units = {m["name"]: m["unit"] for m in benchmark["per_layer"]}
    metrics, unread = {}, []
    for name in cell.per_layer:
        spec = load_json(os.path.join(BENCH_DIR, "layer_metrics",
                                      name + ".json"))
        value = read_metric(spec, run)
        if value is None:
            unread.append(name)     # left out; the contract check names it
        else:
            metrics[name] = {"value": value, "unit": units[name]}
    if unread:
        print(f"benchmark: readers found nothing for {unread}",
              file=sys.stderr)
    device = {"busy_s": reduction.busy_s, "window_s": reduction.window_s}
    breakdown = {"device_ops": reduction.top_ops(10),
                 "idle_gaps": reduction.top_gaps(10)}
    return metrics, device, breakdown


def end_to_end_metrics(benchmark, cell_name, values):
    """The driver hands over every end-to-end number it takes; the line
    carries those BENCHMARK.json judges in this cell (one the driver did
    not take is missing from the line, and the contract check says so)."""
    from benchmark.lib import contract
    return {n: {"value": values[n], "unit": unit} for n, unit in
            contract.declared_metrics(benchmark, cell_name, False).items()
            if n in values}


def main(argv=None) -> int:
    args = parse_args(argv if argv is not None else sys.argv[1:])
    from benchmark.lib import contract
    from benchmark.lib.runtime import (BENCH_DIR, CompileWatch, RunContext,
                                       load_benchmark, load_cell,
                                       memory_stats_of_device0,
                                       require_tpu)
    benchmark = load_benchmark()
    cell = load_cell(benchmark, args.workload)

    from dtf_tpu.runtime import compile_cache
    cache_dir = compile_cache.configure()
    device = require_tpu(cell.chips)

    out_dir = os.path.join(BENCH_DIR, "out", cell.name)
    for sub in ("spans", "profile"):
        shutil.rmtree(os.path.join(out_dir, sub), ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    open(os.path.join(out_dir, "notes.jsonl"), "w").close()    # this run's
    ctx = RunContext(cell=cell, seed=args.seed, seconds=args.seconds,
                     traced=bool(args.trace), out_dir=out_dir,
                     t_process=_T_PROCESS, compiles=CompileWatch())
    ctx.note(phase="start", seed=args.seed, seconds=args.seconds,
             trace=args.trace, device=device, compile_cache=cache_dir)
    driver = importlib.import_module(
        f"benchmark.drivers.{cell.workload['driver']}")
    result = driver.run(ctx)
    ctx.note(phase="memory", device0=memory_stats_of_device0())

    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.trace:
        metrics, extra, breakdown = per_layer_metrics(
            benchmark, cell, device["kind"], result, out_dir)
        device.update(extra)
        line.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        line.update(metrics=end_to_end_metrics(
            benchmark, cell.name,
            dict(result["end_to_end"], setup_s=result["setup_s"])),
            device=device)
    # why a run is not correct, and each number compared beside its limit,
    # last in the line: what the driver's record keeps of a run at fault (a
    # reading that is not finite would not be JSON: null)
    line["reasons"] = [str(r)[:600] for r in result["reasons"]]
    line["compared"] = {
        name: [v if isinstance(v, (int, float)) and math.isfinite(v)
               else None for v in pair]
        for name, pair in result["compared"].items()}
    if result["reasons"]:
        print(f"benchmark: not correct: {result['reasons']}",
              file=sys.stderr)
    for name, (value, limit) in result["compared"].items():
        print(f"benchmark: compared {name} {value!r} limit {limit!r}",
              file=sys.stderr)
    faults = contract.check_line(line, benchmark, cell.name,
                                 bool(args.trace))
    if faults:
        print("benchmark: the result line breaks the contract, so it is "
              "not printed:\n  " + "\n  ".join(faults), file=sys.stderr)
        with open(os.path.join(out_dir, "refused_line.json"), "w") as f:
            json.dump({"line": line, "faults": faults}, f, default=str)
        return 1
    text = json.dumps(line)
    with open(os.path.join(out_dir, f"last_line.trace{args.trace}.json"),
              "w") as f:
        f.write(text + "\n")
    sys.stdout.flush()
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
