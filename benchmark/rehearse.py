"""A rehearsal, NOT a fallback: each driver at a toy size on the CPU.

    python3 -m benchmark.rehearse --workload <cell> [--trace 1] [--seconds 3]

It finds wrong paths, arguments and control flow before a chip call is
spent on them.  The toy sizes are the family's own (``TOY`` in
``benchmark/families/<family>.py``).  Nothing it prints is a result: the
platform is "cpu", the values carry the suffix ``.toy``, the last line
starts with ``REHEARSAL`` and ``lib/contract.py`` refuses it by design.
No number from here is ever written under a device metric's name.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=2_400_000_011)
    p.add_argument("--seconds", type=float, default=3.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--root", default=None,
                   help="another checkout's BENCHMARK.json and data files")
    args = p.parse_args(argv if argv is not None else sys.argv[1:])
    t_process = time.monotonic()

    from benchmark.lib.runtime import (BENCH_DIR, ROOT, CompileWatch,
                                       RunContext, load_benchmark, load_cell)
    root = args.root or ROOT
    benchmark = load_benchmark(root)
    cell = load_cell(benchmark, args.workload, root=root)
    # before jax is imported: the CPU, with as many virtual devices as the
    # cell has chips
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={cell.chips}")
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    import importlib

    import jax

    from benchmark.lib import contract
    toy = dict(cell.family.TOY[cell.workload["driver"]],
               distribution_strategy="mirrored")
    out_dir = os.path.join(BENCH_DIR, "out", "rehearsal", cell.name)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    ctx = RunContext(cell=cell, seed=args.seed, seconds=args.seconds,
                     traced=bool(args.trace), out_dir=out_dir,
                     t_process=t_process, compiles=CompileWatch(), toy=toy)
    driver = importlib.import_module(
        f"benchmark.drivers.{cell.workload['driver']}")
    result = driver.run(ctx)
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    values = {f"{k}.toy": v for k, v in result["end_to_end"].items()}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "device": device,
            "metrics": {k: {"value": v, "unit": "toy"}
                        for k, v in values.items()}}
    if args.trace:
        from benchmark.lib import xplane
        trace = xplane.load(xplane.find_xplane(
            result["readers"]["profile_dir"]))
        print(xplane.describe(trace)[:600])
        # the readers that need no device plane, on what the driver kept
        from benchmark.lib.runtime import load_json
        from benchmark.readers import ReaderInput, read_metric
        run = ReaderInput(cell=cell, device_kind=device["kind"],
                          reduction=None, driver=result["readers"])
        print("readers without a device trace:", {
            name: read_metric(load_json(os.path.join(
                BENCH_DIR, "layer_metrics", name + ".json")), run)
            for name in cell.per_layer})
    faults = contract.check_line(line, benchmark, cell.name,
                                 bool(args.trace))
    print("REHEARSAL (cpu, toy size; not a result) " + json.dumps(
        {"line": line, "reasons": result["reasons"],
         "contract_refuses_it_for": faults[:4]}))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
