#!/usr/bin/env python
"""What a decode step's launch costs the engine thread, piece by piece, on
the chip — the rounds behind ``dtf_tpu.serve.decode._step_operands``.

    python3 tools/step_dispatch_rounds.py --cell gpt13b-serve-loaded \\
        --out chiprun_out/step_dispatch_rounds.jsonl

A ``Decoder`` at the cell's published widths, slots and table (weights
from ``--seed``; an empty cache, every row on the scratch page: what the
host pays does not depend on what the device computes).  Each piece runs
``--rounds`` times with the device idle at its start — as a step's launch
finds it, behind the wait for the last step's tokens — and is timed on the
host's clock from its call to its RETURN (the result is waited for after
the clock stops).  One JSON line a piece, median and deciles in ms:

  the step as it is:  ``pack`` (numpy), ``operands`` (one transfer + one
      program), ``launch_args`` (both), ``call`` (the body's executable,
      its operands ready), ``call_behind_operands`` (the same call made as
      a step makes it, the operands program just enqueued), ``step`` (the
      whole launch);
  the parent's way:   ``asarray_tokens``, ``reshape`` (a program),
      ``asarray_index``, ``asarray_temperature``, ``asarray_seeds``,
      ``seed_row_keys`` (a program), ``asarray_tables``, and their sums as
      the parent's laps had them, ``parent_launch_args`` (all but the
      tables), ``parent_launch_call`` (the tables, then the call) and
      ``parent_step`` (the whole launch);
  the call's own cost: ``call_flat`` — the same executable handed the held
      tree's leaves flattened ONCE (``unsafe_call``: no pytree work a
      call) — and ``flatten`` (one ``tree_flatten`` of the call's
      arguments, what a call through the tree can spend on it at most).

PR 48's lines are kept in ``docs/pr48_step_dispatch_rounds.jsonl``.  It
needs the TPU; nothing here runs in the tests and no cell imports it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from dtf_tpu.models import build_model  # noqa: E402
from dtf_tpu.serve import Decoder  # noqa: E402
from dtf_tpu.serve import decode as sd  # noqa: E402


def _load(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def build_decoder(cell: str, seed: int) -> Decoder:
    bench = _load("BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    config = _load(next(c["file"] for c in bench["configs"]
                        if c["name"] == entry["config"]))
    engine = _load(f"benchmark/workloads/{cell}.json")["engine"]
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[config["dtype"]]
    model, _ = build_model(config["build_model"]["name"],
                           num_classes=config["vocab_size"], dtype=dtype,
                           **config["build_model"]["kwargs"])
    params = jax.jit(model.clone(use_pallas=False).init)(
        jax.random.key(seed),
        jnp.zeros((1, engine["kv_page_size"]), jnp.int32))["params"]
    return Decoder(model, params, num_slots=engine["max_batch"],
                   max_seq_len=engine["max_seq_len"],
                   kv_page_size=engine["kv_page_size"],
                   kv_pool_pages=engine["kv_pool_pages"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cell", default="gpt13b-serve-loaded")
    p.add_argument("--rounds", type=int, default=200)
    p.add_argument("--seed", type=int, default=48)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit("the rounds are the chip's: no TPU here")

    dec = build_decoder(args.cell, args.seed)
    rows, pages = dec.num_slots, dec.pages_per_slot
    rng = np.random.default_rng(args.seed)
    box = {"cache": dec.fresh_cache()}

    def host():
        """A step's arrays as ``ServingEngine._step`` builds them, new
        values a round; every table row the scratch page."""
        return (rng.integers(0, 1000, rows).astype(np.int32),
                rng.integers(0, 1000, rows).astype(np.int32),
                rng.random(rows).astype(np.float32),
                rng.integers(0, 2**32, rows, dtype=np.uint32),
                np.zeros((rows, pages), np.int32))

    def operands(step):
        return sd._step_operands(sd._pack_step_operands(*step), rows)

    # warm: the operands program, the body, the parent's two programs
    dec.decode_step(box["cache"], *host())
    box["cache"] = dec.fresh_cache()
    fn = dec._execs["decode"]
    sd._seed_row_keys(jnp.zeros((rows,), jnp.uint32),
                      jnp.zeros((rows,), jnp.int32))
    jnp.zeros((rows,), jnp.int32).reshape(-1, 1)

    def call(ops):
        toks, box["cache"], last, _ = fn(dec.params, box["cache"], *ops)
        return toks, last

    flat_params = jax.tree_util.tree_leaves(dec.params)
    cache_def = jax.tree_util.tree_structure(box["cache"])

    def flat_ready():
        return (jax.tree_util.tree_leaves(box["cache"]),
                jax.block_until_ready(operands(host())))

    def call_flat(ready):
        cache_leaves, ops = ready
        out = fn._executable.unsafe_call(*flat_params, *cache_leaves, *ops)
        box["cache"] = jax.tree_util.tree_unflatten(
            cache_def, out[1:1 + len(cache_leaves)])
        return out[0]

    def parents_args(step):
        tokens, index, temperature, seeds, _ = step
        idx = jnp.asarray(index, jnp.int32)
        return (jnp.asarray(tokens, jnp.int32).reshape(-1, 1), idx,
                jnp.asarray(temperature, jnp.float32),
                sd._seed_row_keys(jnp.asarray(seeds, jnp.uint32), idx))

    def parents_ready():
        step = host()
        return step, jax.block_until_ready(parents_args(step))

    def parents_call(step, ready):
        toks, idx, temperature, keys = ready
        return call((toks, idx, jnp.asarray(step[4], jnp.int32),
                     temperature, keys))

    on_device = {k: jnp.asarray(v) for k, v in zip(
        ("tokens", "index", "temperature", "seeds", "tables"), host())}
    # piece -> (what is prepared outside the clock, what is timed)
    pieces = {
        "pack": (host, lambda s: sd._pack_step_operands(*s)),
        "operands": (lambda: sd._pack_step_operands(*host()),
                     lambda packed: sd._step_operands(packed, rows)),
        "launch_args": (host, operands),
        "call": (lambda: jax.block_until_ready(operands(host())), call),
        "call_behind_operands": (lambda: operands(host()), call),
        "step": (host, lambda s: call(operands(s))),
        "asarray_tokens": (host, lambda s: jnp.asarray(s[0], jnp.int32)),
        "reshape": (lambda: on_device["tokens"],
                    lambda x: x.reshape(-1, 1)),
        "asarray_index": (host, lambda s: jnp.asarray(s[1], jnp.int32)),
        "asarray_temperature": (host,
                                lambda s: jnp.asarray(s[2], jnp.float32)),
        "asarray_seeds": (host, lambda s: jnp.asarray(s[3], jnp.uint32)),
        "seed_row_keys": (lambda: (on_device["seeds"], on_device["index"]),
                          lambda a: sd._seed_row_keys(*a)),
        "asarray_tables": (host, lambda s: jnp.asarray(s[4], jnp.int32)),
        "parent_launch_args": (host, parents_args),
        "parent_launch_call": (parents_ready, lambda a: parents_call(*a)),
        "parent_step": (host, lambda s: parents_call(s, parents_args(s))),
        "call_flat": (flat_ready, call_flat),
        "flatten": (lambda: (dec.params, box["cache"])
                    + tuple(jax.block_until_ready(operands(host()))),
                    lambda dyn: jax.tree_util.tree_flatten((dyn, {}))),
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    device = jax.devices()[0]
    with open(args.out, "a") as f:
        for name, (prepare, timed) in pieces.items():
            ms = []
            for _ in range(args.rounds + 10):
                ready = prepare()
                t0 = time.perf_counter()
                out = timed(ready)
                ms.append(1e3 * (time.perf_counter() - t0))
                jax.block_until_ready(out)
            ms = ms[10:]
            deciles = statistics.quantiles(ms, n=10)
            line = {"pr": 48, "cell": args.cell, "piece": name,
                    "rounds": args.rounds, "rows": rows, "table": pages,
                    "leaves": len(flat_params),
                    "median_ms": statistics.median(ms),
                    "p10_ms": deciles[0], "p90_ms": deciles[-1],
                    "device": device.device_kind, "seed": args.seed}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
