"""The control of ``benchmark/control.py`` for a configuration whose
[B, S, vocab] logits do not fit the chip beside its weights.  The builder's
tool; a run of the benchmark never runs it.

    python3 -m benchmark.control_rows --workload <cell> --control-seeds 11,12 \
        [--bits 8,4] [--tokens random] [--toy]

``control.py`` puts ``lib/agreement.with_weights_at(reference.forward)`` in
the program's place, which materialises the whole forward's logits
(7.5e9 bytes at SmallThinker's vocabulary and this cell's sample) and a
rounded copy of the tree.  Here the reference itself rounds each weight
matrix as it casts it and gathers the hidden rows before the head: it
exports ``greedy_tokens(params, prompts, n, weights)``,
``rows_that_chose(params, prompts, served, weights)`` and
``rounded_to(bits)``.  The lines are ``control.py``'s (``"who": "w8"``,
``"w4"``), so both tools' readings go into one table; the program's own
readings still come from ``control.py --seeds``.  A third line,
``"who": "router_bf16"``, is the reference with nothing but its router's
input rounded to bfloat16, on the tokens the reference itself would serve:
the share of ``logit_rms`` that top-k choices flipping between a bf16
stream and the f32 reference make, without any other rounding.
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
    p.add_argument("--control-seeds", required=True)
    p.add_argument("--bits", default="8,4")
    p.add_argument("--tokens", choices=("greedy", "random"), default="greedy",
                   help="greedy: what the rounded system would serve (a "
                        "forward a token); random: any continuation, one "
                        "forward (logit_rms does not ask whose choice the "
                        "tokens were; the gap then says nothing)")
    p.add_argument("--toy", action="store_true")
    args = p.parse_args(argv if argv is not None else sys.argv[1:])
    if args.toy:
        os.environ["JAX_PLATFORMS"] = "cpu"
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
    watch = CompileWatch()
    for seed in (int(s) for s in args.control_seeds.split(",")):
        ctx = RunContext(cell=cell, seed=seed, seconds=0.0, traced=False,
                         out_dir=out_dir, t_process=_T_PROCESS,
                         compiles=watch,
                         toy=dict(cell.family.TOY["serve"]) if args.toy
                         else None)
        m = serve.model_and_sample(ctx)
        prompts = [m.prompts[i] for i in m.sample]
        rtol = float(m.agree["logit_rtol"])
        limit = float(m.agree["logit_rms_limit"])
        for bits in (int(b) for b in args.bits.split(",")):
            weights = reference.rounded_to(bits)
            if args.tokens == "random":
                import numpy as np
                rng = np.random.default_rng([seed, 2])
                tokens = [rng.integers(0, m.vocab, int(m.agree["new_tokens"])
                                       ).tolist() for _ in prompts]
            else:
                tokens = reference.greedy_tokens(
                    m.params, prompts, int(m.agree["new_tokens"]), weights)
            said = reference.served_tokens_agree(
                m.params, prompts, tokens, rtol,
                reference.rows_that_chose(m.params, prompts, tokens,
                                          weights), limit)
            print(json.dumps({
                "control": cell.name, "seed": seed, "who": f"w{bits}",
                "ok": said["ok"],
                "gap": said["worst_gap"] / said["logit_scale"],
                "gap_limit": 2 * rtol, "logit_rms": said["logit_rms"],
                "logit_rms_limit": limit, "logit_max": said["logit_max"],
                "greedy_identical": said["greedy_identical"],
                "tokens_compared": said["tokens_compared"]}), flush=True)
        import jax.numpy as jnp
        if args.tokens != "random":
            tokens = reference.greedy_tokens(m.params, prompts,
                                             int(m.agree["new_tokens"]))
        said = reference.served_tokens_agree(
            m.params, prompts, tokens, rtol, reference.rows_that_chose(
                m.params, prompts, tokens, router_input=lambda h: h.astype(
                    jnp.bfloat16).astype(jnp.float32)), limit)
        print(json.dumps({"control": cell.name, "seed": seed,
                          "who": "router_bf16", "ok": said["ok"],
                          "logit_rms": said["logit_rms"],
                          "logit_max": said["logit_max"]}), flush=True)
        m = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
