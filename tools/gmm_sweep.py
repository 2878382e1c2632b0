#!/usr/bin/env python
"""The grouped expert product on the chip, tile by tile — the sweep behind
``dtf_tpu.models.routed_decoder.gmm_tile``.

    python3 tools/gmm_sweep.py --shapes lfm2 --seed 7 --out chiprun_out/sweep.jsonl

For each product of a configuration's expert layer (``K -> N`` over ``E``
groups) and each number of (token, expert) pairs a call brings, it times
megablox ``gmm`` at every tile ``(tm, tk, tn)`` of the grid below that fits
the rule's VMEM budget, with the pairs sorted by expert as
``routed_experts`` hands them over, and prints one JSON line a timing: the
median of ``--rounds`` rounds of ``--reps`` back-to-back calls, on the
host's clock around ``block_until_ready``, beside the product's counted
least (the larger of FLOPs at the chip's peak and the touched weights'
bytes at its bandwidth: what ``moe_experts_roofline*`` divides by).  Group
sizes come from the seed: ``k`` distinct experts a token, uniform unless
``--busiest`` says how many times the mean the busiest expert holds — the
benchmark's cells read 2.0 at every chunk size (``expert_load_max`` on the
spans; ``PERF.md`` §7), and a row layout ranked under the uniform draw lost
in the cell.  With a held share the pairs of the absent experts lie behind
every group.  ``--layer`` times ``routed_experts`` itself (sort, gathers,
both products, the activation between them, the weighted sum).  PR 42's
lines are kept in ``docs/pr42_gmm_sweep.jsonl``.  It needs the TPU;
nothing here runs in the tests.
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

PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9      # one v5e chip, as published

# name: (experts computed here, experts the router chooses among, k, width,
# expert width, pairs a decode step and a chunk bring)
SHAPES = {
    "lfm2": (32, 32, 4, 2048, 1792, (384, 1024, 2048, 4096, 6144, 8192)),
    "smallthinker": (64, 64, 6, 2560, 768, (96, 6144)),
    "joyai": (256, 256, 8, 2048, 768, (192, 16384)),
    "ling": (128, 512, 8, 2560, 768, (768, 8192)),
}
ROW_TILES = (64, 128, 256, 512)


def column_tiles(n):
    return [c for c in range(n, 255, -128) if n % c == 0]


def choices(rng, pairs, k, total, busiest=1.0):
    """[pairs // k, k] expert ids: every token chooses ``k`` distinct
    experts of ``total``, uniformly or, with ``busiest`` > 1, under a bias
    an expert that gives the busiest about that many times the mean."""
    scores = rng.random((pairs // k, total))
    lean = rng.permutation(total) / total       # an expert's bias at tilt 1

    def drawn(tilt):
        return np.argpartition(scores + tilt * lean, -k,
                               axis=1)[:, -k:].astype(np.int32)

    if busiest <= 1.0:
        return drawn(0.0)
    lo, hi = 0.0, 4.0
    for _ in range(20):                 # the tilt that gives the share
        tilt = (lo + hi) / 2
        most = np.bincount(drawn(tilt).reshape(-1), minlength=total).max()
        lo, hi = (tilt, hi) if most < busiest * pairs / total else (lo, tilt)
    return drawn(hi)


def group_sizes(rng, pairs, k, held, total, busiest=1.0):
    """Rows of each held expert under :func:`choices`."""
    return np.bincount(choices(rng, pairs, k, total, busiest).reshape(-1),
                       minlength=total)[:held].astype(np.int32)


def timed(fn, reps, rounds):
    jax.block_until_ready(fn())
    jax.block_until_ready(fn())
    out = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            y = fn()
        jax.block_until_ready(y)
        out.append((time.perf_counter() - t0) / reps * 1e3)
    return statistics.median(out), min(out)


def sweep(args, emit):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    from dtf_tpu.models.routed_decoder import _GMM_VMEM, gmm_blocks_bytes
    held, total, k, d, f, pairs_list = SHAPES[args.shapes]
    rng = np.random.default_rng(args.seed)
    key = jax.random.key(args.seed % (2 ** 31))
    for kk, n in ((d, 2 * f), (f, d)):
        rhs = (jax.random.normal(jax.random.fold_in(key, n),
                                 (held, kk, n), jnp.float32)
               * kk ** -0.5).astype(jnp.bfloat16)
        for pairs in (args.pairs or pairs_list):
            sizes = group_sizes(rng, pairs, k, held, total, args.busiest)
            rows = int(sizes.sum())
            touched = int((sizes > 0).sum())
            least = max(2 * rows * kk * n / PEAK_FLOPS,
                        touched * kk * n * 2 / PEAK_BYTES) * 1e3
            for tm in args.tm or ROW_TILES:
                lhs = jax.random.normal(
                    jax.random.key(1), (-(-pairs // tm) * tm, kk),
                    jnp.float32).astype(jnp.bfloat16)
                for tn in column_tiles(n):
                    # half the contraction only where the whole does not fit
                    tk = next((c for c in (kk, kk // 2) if gmm_blocks_bytes(
                        tm, c, tn) <= _GMM_VMEM), 0)
                    if not tk:
                        continue
                    rec = {"shapes": args.shapes, "groups": held, "k": kk,
                           "n": n, "pairs": pairs, "rows": rows,
                           "busiest": int(sizes.max()),
                           "tile": [tm, tk, tn], "least_ms": least}
                    try:
                        med, best = timed(
                            lambda: gmm(lhs, rhs, jnp.asarray(sizes),
                                        jnp.float32, (tm, tk, tn)),
                            args.reps, args.rounds)
                        rec.update(ms=med, ms_min=best, of_least=least / med)
                    except Exception as e:  # noqa: BLE001 — a tile the
                        # compiler refuses is a row of the table
                        rec.update(error=f"{type(e).__name__}: "
                                         f"{str(e)[:200]}")
                    emit(rec)
        del rhs


def layer(args, emit):
    from dtf_tpu.models import routed_decoder as rd
    held, total, k, d, f, pairs_list = SHAPES[args.shapes]
    key = jax.random.key(args.seed % (2 ** 31))
    kg, kd, kx = jax.random.split(key, 3)
    w_gu = (jax.random.normal(kg, (held, d, 2 * f), jnp.float32)
            * d ** -0.5).astype(jnp.bfloat16)
    w_d = (jax.random.normal(kd, (held, f, d), jnp.float32)
           * f ** -0.5).astype(jnp.bfloat16)
    rng = np.random.default_rng(args.seed)
    share = None if held == total else (0, held)
    fn = jax.jit(lambda *a: rd.routed_experts(
        *a, activation="silu", held=share)[0])
    for pairs in (args.pairs or pairs_list):
        t = pairs // k
        x = jax.random.normal(jax.random.fold_in(kx, pairs), (t, d),
                              jnp.float32).astype(jnp.bfloat16)
        idx = jnp.asarray(choices(rng, pairs, k, total, args.busiest))
        wts = jnp.full((t, k), 1.0 / k, jnp.float32)
        rows = int(np.sum(np.asarray(idx) < held))
        least = sum(max(2 * rows * a * b / PEAK_FLOPS,
                        held * a * b * 2 / PEAK_BYTES)
                    for a, b in ((d, 2 * f), (f, d))) * 1e3
        rec = {"shapes": args.shapes, "layer": True, "pairs": pairs,
               "rows": rows, "least_ms": least,
               "rule": [rd.gmm_tile(pairs, held, a, b)
                        for a, b in ((d, 2 * f), (f, d))]}
        med, best = timed(lambda: fn(x, idx, wts, w_gu, w_d), args.reps,
                          args.rounds)
        rec.update(ms=med, ms_min=best, of_least=least / med)
        emit(rec)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--shapes", choices=sorted(SHAPES), required=True)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--pairs", type=int, nargs="*")
    p.add_argument("--tm", type=int, nargs="*")
    p.add_argument("--busiest", type=float, default=1.0,
                   help="the busiest expert's rows over the mean (the "
                        "cells' spans read 2.0; 1.0: a uniform draw)")
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--layer", action="store_true")
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit("gmm_sweep measures the chip: no TPU here")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        def emit(rec):
            f.write(json.dumps(rec) + "\n")
            f.flush()
        (layer if args.layer else sweep)(args, emit)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
