"""The readings the limits of the served-token check are set from, and its
control.  The builder's tool, once per serving cell; a run of the benchmark
never runs it.

    python3 -m benchmark.control --workload <cell> --seeds 11,12,... \
        [--control-seeds 11,12,13] [--toy]

One process.  For each control seed the control: the cell's weights and
agreement sample as a run makes them, and the plain reference with its
weight matrices rounded to 8 bits (the precision just below the
configuration's bf16, and the step that tempts: a decode step reads every
weight) and to 4 bits, put in the program's place — its greedy tokens and
its logits held to the reference's ``served_tokens_agree``: the lines
``"who": "w8"``, ``"w4"``.  Then one engine, and for each seed (the
engine's decoder is handed the seed's weights) the sample served and
replayed as ``drivers/serve.py`` does it, held to the same comparison:
``"who": "program"``.  ``--toy`` runs the family's toy size on the CPU.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--toy", action="store_true")
    args = p.parse_args(argv if argv is not None else sys.argv[1:])
    if args.toy:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmark import families
    from benchmark.drivers import serve
    from benchmark.lib import agreement
    from benchmark.lib.runtime import (BENCH_DIR, CompileWatch, RunContext,
                                       load_benchmark, load_cell,
                                       require_tpu)
    from dtf_tpu.serve.engine import ServeEngine
    cell = load_cell(load_benchmark(), args.workload)
    if not args.toy:
        from dtf_tpu.runtime import compile_cache
        compile_cache.configure()
        require_tpu(cell.chips)
    reference = families.load_reference(cell.config, cell.root)
    out_dir = os.path.join(BENCH_DIR, "out", "control")
    os.makedirs(out_dir, exist_ok=True)
    watch = CompileWatch()
    def sample_of(seed):
        ctx = RunContext(cell=cell, seed=seed, seconds=0.0, traced=False,
                         out_dir=out_dir, t_process=_T_PROCESS,
                         compiles=watch,
                         toy=dict(cell.family.TOY["serve"]) if args.toy
                         else None)
        m = serve.model_and_sample(ctx)
        return ctx, m, [m.prompts[i] for i in m.sample]

    def say(seed, who, m, prompts, tokens, logits):
        rtol = float(m.agree["logit_rtol"])
        limit = float(m.agree["logit_rms_limit"])
        said = reference.served_tokens_agree(m.params, prompts, tokens, rtol,
                                             logits, limit)
        print(json.dumps({
            "control": cell.name, "seed": seed, "who": who, "ok": said["ok"],
            "gap": said["worst_gap"] / said["logit_scale"],
            "gap_limit": 2 * rtol, "logit_rms": said["logit_rms"],
            "logit_rms_limit": limit, "logit_max": said["logit_max"],
            "greedy_identical": said["greedy_identical"],
            "tokens_compared": said["tokens_compared"]}), flush=True)

    # the controls first, while no engine's pool stands beside the weights
    # and a rounded copy of them
    m = None
    for seed in (int(s) for s in args.control_seeds.split(",") if s):
        m = None                            # one set of weights fits
        _, m, prompts = sample_of(seed)
        for bits in (8, 4):
            forward = agreement.with_weights_at(reference.forward, bits)
            tokens = agreement.greedy_tokens(forward, m.params, prompts,
                                             int(m.agree["new_tokens"]))
            say(seed, f"w{bits}", m, prompts, tokens,
                agreement.rows_that_chose(forward, m.params, prompts, tokens))
    engine = None
    for seed in (int(s) for s in args.seeds.split(",")):
        if engine is not None:
            engine.decoder.params = None
        m = None
        ctx, m, prompts = sample_of(seed)
        if engine is None:
            # no prefix registry: it would keep every seed's pages
            engine = ServeEngine(m.model, m.params, seed=ctx.key_seed,
                                 prefix_sharing=False, **m.engine_kw)
        engine.decoder.params = m.params
        handles = [engine.submit(q, max_new_tokens=int(m.agree["new_tokens"]))
                   for q in prompts]
        served = [list(h.result(timeout=1100).tokens) for h in handles]
        say(seed, "program", m, prompts, served,
            serve.replay_logits(engine, prompts, served))
    engine.stop(drain=True, timeout=60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
