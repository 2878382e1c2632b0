#!/usr/bin/env python
"""The injected faults of ``benchmark/families/reference_glm_dsa.py`` read at
the configuration's own size — the builder's tool behind the limits of
``glm52-serve-sparsectx`` (``PERF.md`` section 2); a run of the benchmark
never runs it.

    python3 tools/glm_faults.py --workload glm52-serve-sparsectx \
        --seeds 11,12 [--toy]

For each seed the cell's weights and agreement sample as a run makes them
and a random continuation of ``new_tokens`` a prompt (``logit_rms`` does
not ask whose choice the tokens were); then, each against the sound
reference's rows on the same tokens, one JSON line a control: the
reference with ONE fault (``dense``, ``shared_first``, ``stale_index``,
``no_relu``, ``no_weights``), with nothing but the choice's inputs rounded
to bfloat16 (``index_bf16``: a flipped row is this family's flipped
expert), with nothing but the experts' router's input rounded
(``router_bf16``), and with every matrix at 8 bits (``w8``).  ``--toy``
runs the family's toy size on the CPU.
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

FAULTS = ("dense", "shared_first", "stale_index", "no_relu", "no_weights")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default="glm52-serve-sparsectx")
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", default=",".join(
        FAULTS + ("index_bf16", "router_bf16", "w8")))
    p.add_argument("--toy", action="store_true")
    args = p.parse_args(argv)
    if args.toy:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax.numpy as jnp
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
    out_dir = os.path.join(BENCH_DIR, "out", "control")
    os.makedirs(out_dir, exist_ok=True)

    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    for seed in (int(s) for s in args.seeds.split(",")):
        toy = dict(cell.family.TOY["serve"]) if args.toy else None
        ctx = RunContext(cell=cell, seed=seed, seconds=0.0, traced=False,
                         out_dir=out_dir, t_process=_T_PROCESS,
                         compiles=CompileWatch(), toy=toy)
        m = serve.model_and_sample(ctx)
        engine = (toy or cell.workload)["engine"]
        reference.FAULT_PAGE = int(engine["kv_page_size"])
        prompts = [m.prompts[i] for i in m.sample]
        rng = np.random.default_rng([seed, 2])
        tokens = [rng.integers(0, m.vocab, int(m.agree["new_tokens"])
                               ).tolist() for _ in prompts]
        sound = reference.rows_that_chose(m.params, prompts, tokens)
        for name in args.controls.split(","):
            if name in FAULTS:
                kw = {"faults": (name,)}
            elif name == "w8":
                kw = {"weights": reference.rounded_to(8)}
            else:
                kw = {{"index_bf16": "router_input",
                       "router_bf16": "expert_input"}[name]: bf16}
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
