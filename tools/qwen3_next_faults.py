#!/usr/bin/env python
"""The injected faults of ``benchmark/families/reference_qwen3_next.py`` read
at the configuration's own size — the builder's tool behind the limits of
``qwen3next-serve-hybriddoc`` (``PERF.md`` section 2); a run of the
benchmark never runs it.

    python3 tools/qwen3_next_faults.py --workload qwen3next-serve-hybriddoc \
        --seeds 11,12 [--toy]

For each seed the cell's weights and agreement sample as a run makes them
and a random continuation of ``new_tokens`` a prompt (``logit_rms`` does
not ask whose choice the tokens were); then, each against the sound
reference's rows on the same tokens, one JSON line a control: the
reference with ONE fault (``zero_state_carry``, ``zero_filter_carry``,
``ungated``, ``rope_all``; a dropped carry falls at every multiple of the
cell's ``prefill_chunk``), with nothing but the router's input rounded to
bfloat16 (``router_bf16``), with nothing but the matrices of state rounded
to bfloat16 after every token (``state_bf16``: what the program's pool
holds), and with every matrix at 8 bits (``w8``).  ``--toy`` runs the
family's toy size on the CPU.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONTROLS = ("router_bf16", "state_bf16", "w8")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="qwen3next-serve-hybriddoc")
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", default="")
    p.add_argument("--toy", action="store_true")
    args = p.parse_args(argv)
    if args.toy:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np
    from benchmark import families
    from benchmark.drivers import serve
    from benchmark.lib.runtime import (BENCH_DIR, CompileWatch, RunContext,
                                       load_benchmark, load_cell,
                                       require_tpu)
    cell = load_cell(load_benchmark(), args.workload)
    if not args.toy:
        from dtf_tpu.runtime import compile_cache
        compile_cache.configure()
        require_tpu(cell.chips)
    reference = families.load_reference(cell.config, cell.root)
    controls = (args.controls.split(",") if args.controls
                else list(reference.FAULTS + CONTROLS))
    out_dir = os.path.join(BENCH_DIR, "out", "control")
    os.makedirs(out_dir, exist_ok=True)

    def bf16(x):
        # an explicit rounding: the TPU's compiler allows itself excess
        # precision and drops an f32 -> bf16 -> f32 round trip (state_bf16
        # read exactly 0 that way: my chip run, PR 57)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    for seed in (int(s) for s in args.seeds.split(",")):
        toy = dict(cell.family.TOY["serve"]) if args.toy else None
        ctx = RunContext(cell=cell, seed=seed, seconds=0.0, traced=False,
                         out_dir=out_dir, t_process=_T_PROCESS,
                         compiles=CompileWatch(), toy=toy)
        m = serve.model_and_sample(ctx)
        reference.FAULT_CHUNK = int(m.engine_kw["prefill_chunk"])
        prompts = [m.prompts[i] for i in m.sample]
        rng = np.random.default_rng([seed, 2])
        tokens = [rng.integers(0, m.vocab, int(m.agree["new_tokens"])
                               ).tolist() for _ in prompts]
        sound = reference.rows_that_chose(m.params, prompts, tokens)
        for name in controls:
            kw = {"w8": {"weights": reference.rounded_to(8)},
                  "router_bf16": {"router_input": bf16},
                  "state_bf16": {"state": bf16}}.get(name, {"fault": name})
            rows = reference.rows_that_chose(m.params, prompts, tokens, **kw)
            said = reference.compare(sound, tokens,
                                     float(m.agree["logit_rtol"]), rows,
                                     float(m.agree["logit_rms_limit"]))
            print(json.dumps({
                "control": cell.name, "seed": seed, "who": name,
                "refused": bool(said["logit_rms"] > said["logit_rms_limit"]),
                "logit_rms": said["logit_rms"],
                "logit_rms_limit": said["logit_rms_limit"],
                "logit_max": said["logit_max"]}), flush=True)
        m = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
