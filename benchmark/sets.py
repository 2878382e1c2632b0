"""Run a list of benchmark runs one after another, each a process of its
own, and keep what they said.  This is the builder's tool for a chip
call; the driver calls ``benchmark.run`` itself.

    python3 -m benchmark.sets [--keep-trace N] CELL,TRACE,SECONDS,SEED ...

This process never imports jax, so every child finds the chip free.
Each child's output goes to ``chiprun_out/sets/<n>_<cell>_t<trace>.out``;
one summary line per run is printed, then for every (cell, trace 0)
group of three or more runs the median and the quartile spread of each
metric, with the first run (which may compile) left out of ``setup_s``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

from benchmark.lib import stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out", "sets")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--keep-trace", type=int, default=0,
                   help="events of the traced runs' op line to keep as JSON")
    p.add_argument("--timeout", type=float, default=1500.0)
    p.add_argument("runs", nargs="+")
    args = p.parse_args(argv if argv is not None else sys.argv[1:])
    os.makedirs(OUT, exist_ok=True)
    groups = {}
    for i, spec in enumerate(args.runs):
        cell, traced, seconds, seed = spec.split(",")
        cmd = [sys.executable, "-m", "benchmark.run", "--workload", cell,
               "--seed", seed, "--seconds", seconds, "--trace", traced]
        t0 = time.monotonic()
        tag = f"{i:02d}_{cell}_t{traced}"
        with open(os.path.join(OUT, tag + ".out"), "w") as out, \
                open(os.path.join(OUT, tag + ".err"), "w") as err:
            try:
                rc = subprocess.run(cmd, cwd=ROOT, stdout=out, stderr=err,
                                    timeout=args.timeout).returncode
            except subprocess.TimeoutExpired:
                rc = 124
        wall = time.monotonic() - t0
        with open(os.path.join(OUT, tag + ".out")) as f:
            lines = f.read().splitlines()
        last = None
        if rc == 0 and lines:
            last = json.loads(lines[-1])
        summary = {"run": tag, "seed": seed, "rc": rc, "wall_s": wall}
        if last is not None:
            summary.update(
                correct=last["correct"], attempted=last["attempted"],
                failed=last["failed"],
                metrics={k: v["value"] for k, v in last["metrics"].items()},
                device={k: v for k, v in last["device"].items()
                        if k != "kind"})
            if traced == "0":
                groups.setdefault(cell, []).append(last["metrics"])
        else:
            with open(os.path.join(OUT, tag + ".err")) as f:
                summary["stderr_tail"] = f.read()[-1500:]
        print(json.dumps(summary), flush=True)
        src = os.path.join(ROOT, "benchmark", "out", cell)
        dst = os.path.join(OUT, tag)
        os.makedirs(dst, exist_ok=True)
        # the program's spans with them: a traced run's counts are what
        # the span readers' numbers are reckoned from by hand
        for name in ("notes.jsonl", "trace_described.txt",
                     f"last_line.trace{traced}.json", "refused_line.json",
                     "idle_by_lap.json", "spans/trace_rank0.jsonl"):
            path = os.path.join(src, name)
            if os.path.exists(path) and os.path.getsize(path) < 16e6:
                shutil.copy(path, dst)
        if traced == "1" and args.keep_trace and rc == 0:
            # reading the trace needs jax's reader, not a device: a child
            # held to the CPU, after the run's process has gone
            code = (
                "import json,sys; from benchmark.lib import xplane; "
                f"t=xplane.load(xplane.find_xplane({os.path.join(src, 'profile')!r})); "
                f"json.dump(xplane.cut_down(t,{args.keep_trace}),"
                f"open({os.path.join(dst, 'trace_cut.json')!r},'w'))")
            subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                           env=dict(os.environ, JAX_PLATFORMS="cpu"),
                           timeout=300)
    for cell, runs in groups.items():
        if len(runs) < 3:
            continue
        for name in runs[0]:
            vals = [r[name]["value"] for r in runs]
            if name == "setup_s":
                vals = vals[1:]
            if len(vals) < 2:
                continue
            print(json.dumps({"cell": cell, "metric": name, "n": len(vals),
                              "median": stats.median(vals),
                              "iqr_share": stats.iqr_share(vals),
                              "min": min(vals), "max": max(vals)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
