#!/usr/bin/env python
"""A prefill chunk's attention over a latent cache on the chip, ABSORBED
against EXPANDED — the sweep behind ``ops.paged_attention.latent_expands``
and ``latent_chunk_attention``'s blocks.

    python3 tools/latent_chunk_sweep.py --out chiprun_out/latent_sweep.jsonl

At the widths of ``joyai-serve-longctx`` and ``ling-serve-longgen`` (one
row; 32 heads of nope / rope / v 128 / 64 / 128 over latents of rank 512,
rows stored in 640 lanes, bfloat16, pages of 64 under a table of 544) it
times the chunk whose LAST query sees ``visible`` keys — its first position
is ``visible - chunk`` — and prints one JSON line a (form, chunk, visible):

  ``absorbed``  the kernel alone: ``paged_flash_decode`` over absorbed
                queries [1, S, 32, 640] (what every chunk ran until PR 53);
  ``expanded``  ``latent_chunk_attention`` alone: the chunk's own keys
                through ``kv_b`` and one causal ``flash_forward``, then the
                walk over the pages under the start, a step of
                ``EXPAND_KEYS`` gathered, expanded and attended at a time;
  ``layer``     a whole ``LatentAttention`` layer in decode mode (the
                projections, the page write, the attention, ``out``) as
                the chunk body calls it, once with the rule as it is
                (``expanded`` true wherever ``latent_expands``) and once
                with the rule held off (``expanded`` false).

``--chunks`` under the rule's break-even (about 160 queries at these
widths) show where it lies on the chip: the kernel-alone forms take any
chunk.  Times are the host's clock around one jitted call, least and
median of ``--reps``; ``err`` is the largest difference between the two
forms' outputs over the largest output, both in float32.  PR 53's lines
are kept in ``docs/pr53_latent_chunk_sweep.jsonl`` (``sweep``: ``forms``
the defaults and ``--chunks 64 128 192 256 512 --visible 8192``; ``tune``
``--block_q 1024 512 2048 --block_k 512 1024 --expand_keys 2048 4096``;
``variants`` three forms of the kernel that were timed and not kept, each
line's ``note`` says which).  It needs the TPU; nothing here runs in the
tests and nothing a cell runs imports it.
"""

from __future__ import annotations

import argparse
import datetime
import importlib
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

from dtf_tpu.models import routed_decoder as rd  # noqa: E402

pa = importlib.import_module("dtf_tpu.ops.paged_attention")
fa = importlib.import_module("dtf_tpu.ops.flash_attention")

HQ, RANK, NOPE, ROPE, DV, PAGE, TABLE = 32, 512, 128, 64, 128, 64, 544
D_MODEL, Q_RANK = 2048, 1536
LANES = rd.latent_row_lanes(RANK, ROPE)
SCALE = (NOPE + ROPE) ** -0.5
bf16 = jnp.bfloat16


def _timed(fn, args, reps):
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, min(times), statistics.median(times)


def _err(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/latent_sweep.jsonl")
    ap.add_argument("--seed", type=int, default=53)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--visible", type=int, nargs="+",
                    default=[2048, 8192, 16384, 32768])
    ap.add_argument("--chunks", type=int, nargs="+", default=[1024, 2048])
    ap.add_argument("--forms", nargs="+",
                    default=["absorbed", "expanded", "layer"])
    ap.add_argument("--block_q", type=int, nargs="+",
                    default=[fa.DEFAULT_BLOCK_Q])
    ap.add_argument("--block_k", type=int, nargs="+",
                    default=[fa.CHUNK_BLOCK_K])
    ap.add_argument("--expand_keys", type=int, nargs="+",
                    default=[pa.EXPAND_KEYS])
    ap.add_argument("--interpret", action="store_true",
                    help="a rehearsal on the CPU: no line is a measurement")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.interpret:
        raise SystemExit(f"the sweep times a TPU's kernels, not {dev}")
    use_pallas = "interpret" if args.interpret else True
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    base = dict(heads=HQ, rank=RANK, nope=NOPE, rope=ROPE, v=DV, page=PAGE,
                table=TABLE, pool="bfloat16", seed=args.seed,
                chip=dev.device_kind,
                date=datetime.date.today().isoformat())
    kq, kp, kw, kh, kparams = jax.random.split(jax.random.key(args.seed), 5)
    pages = TABLE + 1
    pool = jax.random.normal(kp, (pages, PAGE, LANES), bf16)
    pool = pool.at[..., RANK + ROPE:].set(0)
    w_kvb = (jax.random.normal(kw, (RANK, HQ, NOPE + DV)) * RANK ** -0.5
             ).astype(bf16)
    table = jnp.asarray(np.random.default_rng(args.seed).permutation(
        np.arange(1, pages, dtype=np.int32))[None, :])

    def absorb(q):
        q_abs = jnp.einsum("bshn,rhn->bshr", q[..., :NOPE], w_kvb[..., :NOPE],
                           preferred_element_type=jnp.float32).astype(bf16)
        return jnp.concatenate(
            [q_abs, q[..., NOPE:],
             jnp.zeros(q.shape[:3] + (LANES - RANK - ROPE,), bf16)], -1)

    @jax.jit
    def absorbed(pool, q_abs, index):
        return pa.paged_flash_decode(
            q_abs, pool, None, table, index, scale=SCALE, value_lanes=RANK,
            interpret=args.interpret)

    layer = rd.LatentAttention(
        HQ, Q_RANK, RANK, NOPE, ROPE, DV, 1e4, True, 1e-6, bf16, bf16,
        use_pallas=use_pallas, decode=True, kv_page_size=PAGE,
        kv_pool_pages=pages)
    rule = pa.latent_expands
    blocks = fa.DEFAULT_BLOCK_Q, fa.CHUNK_BLOCK_K

    with open(args.out, "a") as out:
        def emit(**line):
            line = {**base, **line}
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()

        for chunk in args.chunks:
            q = jax.random.normal(kq, (1, chunk, HQ, NOPE + ROPE), bf16)
            h = jax.random.normal(kh, (1, chunk, D_MODEL), bf16)
            params = layer.init(
                kparams, h, jnp.zeros((1, chunk), jnp.int32),
                jnp.zeros((1,), jnp.int32), table)["params"]
            for visible in args.visible:
                if visible < chunk:
                    continue
                index = jnp.asarray([visible - chunk], jnp.int32)
                # the chunk's own rows are in the pool already: the forms
                # attend the same keys
                own = jnp.take(table[0], (index[0] // PAGE) + jnp.arange(
                    chunk // PAGE))
                rows = pool[own].reshape(1, chunk, LANES)
                shape = dict(chunk=chunk, visible=visible)
                want = None
                if "absorbed" in args.forms:
                    want, least, median = _timed(
                        absorbed, (pool, absorb(q), index), args.reps)
                    want = jnp.einsum(
                        "bshr,rhv->bshv", want, w_kvb[..., NOPE:],
                        preferred_element_type=jnp.float32)
                    emit(form="absorbed", ms=least, ms_median=median, **shape)
                for bq in args.block_q:
                    for bk in args.block_k:
                        for keys in args.expand_keys:
                            if "expanded" not in args.forms:
                                continue
                            # the walk's own jit would keep the first
                            # blocks it was traced at
                            fa.DEFAULT_BLOCK_Q, fa.CHUNK_BLOCK_K = bq, bk
                            fn = jax.jit(lambda pool, q, rows, index,
                                         ppb=max(1, keys // PAGE):
                                         pa._latent_chunk_walk.__wrapped__(
                                             q, rows, w_kvb, pool, table,
                                             index, rank=RANK, nope=NOPE,
                                             scale=SCALE,
                                             use_pallas=use_pallas, ppb=ppb))
                            try:
                                got, least, median = _timed(
                                    fn, (pool, q, rows, index), args.reps)
                            except Exception as e:  # a tile Mosaic refuses
                                emit(form="expanded", block_q=bq, block_k=bk,
                                     expand_keys=keys,
                                     error=str(e)[:200], **shape)
                                continue
                            emit(form="expanded", block_q=bq, block_k=bk,
                                 expand_keys=keys, ms=least,
                                 ms_median=median,
                                 err=None if want is None
                                 else _err(got, want), **shape)
                if "layer" not in args.forms:
                    continue
                # the layer as the program runs it: the module's own blocks
                fa.DEFAULT_BLOCK_Q, fa.CHUNK_BLOCK_K = blocks
                positions = index[:, None] + jnp.arange(chunk)[None, :]
                outs = {}
                expands = rule(chunk, HQ, LANES, RANK, NOPE, ROPE, DV)
                for expanded in (True, False) if expands else (False,):
                    rd.latent_expands = rule if expanded else (
                        lambda *a: False)
                    fn = jax.jit(lambda params, pool, h, positions, index:
                                 layer.apply(
                                     {"params": params,
                                      "cache": {"paged_latent": pool}},
                                     h, positions, index, table,
                                     mutable=["cache"])[0])
                    outs[expanded], least, median = _timed(
                        fn, (params, pool, h, positions, index), args.reps)
                    emit(form="layer", expanded=expanded, ms=least,
                         ms_median=median,
                         err=_err(outs[True], outs[False])
                         if len(outs) == 2 else None, **shape)
                rd.latent_expands = rule


if __name__ == "__main__":
    main()
