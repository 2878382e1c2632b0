"""The knee of an open-loop cell, found once: one process, one engine, one
warm-up, then the cell's mix offered at each rate in turn, each rate with
token ids from a seed of its own (``--seed`` + its place in the list).
With one seed for all, a rate's prompts are the last rate's over again:
the engine's prefix registry hits, the shared pages move a prompt's chunk
plan off the grid the warm-up compiled, and chunk bodies
(``jit(_chunk_impl)``) that random prompts never reach are compiled inside
the window (PERF.md section 6, PR 26).

    python3 -m benchmark.sweep --workload <cell> --rates 2,4,6,8,10 --seconds 30 --seed 7

Each rate's line (offered and completed tokens, outstanding requests at the
middle and the end of its window, tails) is printed and appended to
``benchmark/out/sweeps/<cell>.jsonl``.  The knee is the highest rate at
which completed tokens are >= 97 % of those offered and no more requests
are outstanding at the end of the window than at its middle (+2, for the
grain of a count); the cell's ``rate_per_s`` is then written, by hand, as
0.8 x that.  Needs the TPU, like a run.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=7)
    args = p.parse_args(argv if argv is not None else sys.argv[1:])
    from benchmark.drivers import serve
    from benchmark.lib.runtime import (BENCH_DIR, CompileWatch, RunContext,
                                       load_benchmark, load_cell,
                                       require_tpu)
    cell = load_cell(load_benchmark(), args.workload)
    from dtf_tpu.runtime import compile_cache
    compile_cache.configure()
    require_tpu(cell.chips)
    out_dir = os.path.join(BENCH_DIR, "out", "sweeps")
    os.makedirs(out_dir, exist_ok=True)
    ctx = RunContext(cell=cell, seed=args.seed, seconds=args.seconds,
                     traced=False, out_dir=out_dir, t_process=_T_PROCESS,
                     compiles=CompileWatch())
    engine, mix, vocab, _ = serve.setup(ctx)
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        result = serve.measure(dataclasses.replace(ctx, seed=args.seed + i),
                               engine, dict(mix, rate_per_s=rate), vocab,
                               args.seconds)
        note = result["note"]
        kept_up = (note["tokens_in_window"]
                   >= 0.97 * note["tokens_offered_in_window"]
                   and note["outstanding_close"]
                   <= note["outstanding_mid"] + 2
                   and not result["failed"])
        if kept_up:
            knee = rate
        line = dict(note, cell=cell.name, kept_up=kept_up,
                    failed=result["failed"])
        with open(os.path.join(out_dir, cell.name + ".jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({"sweep": cell.name, "rate": rate,
                          "kept_up": kept_up}), flush=True)
    engine.stop(drain=False, timeout=60)
    print(json.dumps({"sweep": cell.name, "knee_per_s": knee,
                      "rate_at_0.8": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
