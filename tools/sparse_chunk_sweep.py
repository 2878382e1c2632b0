#!/usr/bin/env python
"""A sparse chunk's attention alone on the chip, form by form — the sweep
behind ``dtf_tpu.ops.paged_attention.paged_flash_decode_tiles``'s tile.

    python3 tools/sparse_chunk_sweep.py --out chiprun_out/sparse_sweep.jsonl

At the shape of ``minicpm-sala-serve-longdoc`` (one row; a chunk of 2,048
queries of 32 heads over 2 KV heads of 128, bfloat16, pages of 2,048 under
a table of 65, blocks of 64 of which a query past ``dense_len`` reads 97:
the first, the window's 32 and the 64 best of the others) it times the
LAST chunk of a context — the chunk's first position is ``context -
2048`` — in each form and prints one JSON line a form:

  ``tiles``    the tile kernel at ``tile_q`` queries a grid point, units of
               ``keys`` (what one DMA copies and a tile skips by),
               ``product_keys`` a product (whole units scored together)
               and ``rows`` query rows a product (whole heads of the tile);
  ``gather``   what the chunk ran until PR 46: a (token, KV head) a row of
               ``paged_flash_decode`` over its own table of 97 blocks, 256
               tokens a call (``paged_block_attention``);
  ``tiles_agree``  the tile kernel where every query of the chunk chooses
               the SAME blocks, so that a tile streams ~97 blocks and skips
               the rest (what a trained model's neighbouring queries lean
               toward; random weights never do).

The choice is ``block_select.members`` / ``choose`` over uniform random
``r`` drawn from ``--seed`` — under random weights a query's 64 chosen
blocks are as good as independent of its neighbours' (PERF.md §7).  Times
are the host's clock around a jitted call that holds the kernel's call(s)
and nothing else (tables, lists and membership are built before), least
and median of ``--reps``; ``err`` is the largest difference of a form's
output from the gather form's at the same context.  PR 47's lines are kept
in ``docs/pr47_sparse_chunk_sweep.jsonl``.  It needs the TPU; nothing here
runs in the tests and nothing a cell runs imports it.
"""

from __future__ import annotations

import argparse
import datetime
import importlib
import itertools
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

from dtf_tpu.ops import block_select as bs  # noqa: E402

pa = importlib.import_module("dtf_tpu.ops.paged_attention")

SIZES = bs.Sizes(block=64, pool=32, stride=16, top=64, window=2048, init=1,
                 dense_len=8192)
CHUNK, HQ, HKV, DIM, PAGE, TABLE = 2048, 32, 2, 128, 2048, 65
GATHER_TILE = 256
bf16 = jnp.bfloat16


def _timed(fn, args, reps):
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, min(times), statistics.median(times)


def _membership(key, start, agree, chunk):
    """bool [1, Hkv, S, blocks] and the gather form's (ids, count): the
    choice over uniform ``r``, 256 queries at a time."""
    t = start + jnp.arange(chunk, dtype=jnp.int32)[None, :]
    j_all = TABLE * PAGE // SIZES.stride
    tiles = chunk // GATHER_TILE

    def some(xs):
        k_, t_ = xs
        shape = (1, 1 if agree else GATHER_TILE, HKV, j_all)
        r = jnp.broadcast_to(jax.random.uniform(k_, shape),
                             (1, GATHER_TILE, HKV, j_all))
        blocks, count = bs.choose(r, t_, SIZES)
        return bs.members(r, t_, SIZES), blocks, count
    keys = jax.random.split(key, 1 if agree else tiles)
    keys = jnp.broadcast_to(keys, (tiles,) + keys.shape[1:])
    member, blocks, count = jax.lax.map(
        some, (keys, jnp.moveaxis(t.reshape(1, tiles, GATHER_TILE), 1, 0)))
    member = jnp.moveaxis(member, 0, 1).reshape(1, chunk, HKV, -1)
    return jnp.swapaxes(member, 1, 2), blocks, count


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/sparse_sweep.jsonl")
    ap.add_argument("--seed", type=int, default=47)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--contexts", type=int, nargs="+",
                    default=[8192 + CHUNK, 16384, 32768, 65536, 131072])
    ap.add_argument("--tile_q", type=int, nargs="+", default=[64, 128, 256])
    ap.add_argument("--keys", type=int, nargs="+", default=[128, 256, 512])
    ap.add_argument("--rows", type=int, nargs="+", default=[pa._TILE_ROWS],
                    help="query rows a product (whole heads of a tile)")
    ap.add_argument("--product", type=int, nargs="+",
                    default=[pa._TILE_UNITS * pa._TILE_KEYS],
                    help="keys a product: whole units of --keys, copied "
                         "one DMA each and scored together")
    ap.add_argument("--chunk", type=int, default=CHUNK,
                    help="queries a chunk (the cell's 2,048; a rehearsal "
                         "takes fewer)")
    ap.add_argument("--interpret", action="store_true",
                    help="a rehearsal on the CPU: no line is a measurement")
    args = ap.parse_args()
    chunk = args.chunk
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.interpret:
        raise SystemExit(f"the sweep times a TPU's kernels, not {dev}")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    base = dict(chunk=chunk, heads=HQ, kv_heads=HKV, dim=DIM, page=PAGE,
                read=SIZES.read, pool="bfloat16", seed=args.seed,
                chip=dev.device_kind,
                date=datetime.date.today().isoformat())
    interpret = args.interpret
    key = jax.random.key(args.seed)
    kq, kk, kv, kr = jax.random.split(key, 4)
    pages = TABLE + 1
    pool_k = jax.random.normal(kk, (pages, PAGE, HKV, DIM), bf16)
    pool_v = jax.random.normal(kv, (pages, PAGE, HKV, DIM), bf16)
    q = jax.random.normal(kq, (1, chunk, HQ, DIM), bf16)
    table = jnp.asarray(np.random.default_rng(args.seed).permutation(
        np.arange(1, pages, dtype=np.int32))[None, :])

    @jax.jit
    def gather(pool_k, pool_v, q_, ids, count, last):
        def some(xs):
            q1, i1, c1, l1 = xs
            return pa.paged_block_attention(
                q1, pool_k, pool_v, i1, c1, l1, block=SIZES.block,
                use_pallas="interpret" if interpret else True)
        return jax.lax.map(some, (q_, ids, count, last))

    with open(args.out, "a") as out:
        def emit(**line):
            line = {**base, **line}
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()

        for context in args.contexts:
            start = jnp.asarray([context - chunk], jnp.int32)
            for agree in (False, True):
                member, blocks, count = jax.jit(
                    _membership, static_argnums=(2, 3))(kr, start, agree, chunk)
                tiles = chunk // GATHER_TILE
                ids = jax.vmap(lambda b_: bs.physical(
                    b_, table, PAGE, SIZES.block))(blocks)
                t = start[0] + jnp.arange(chunk, dtype=jnp.int32)
                want, least, median = _timed(gather, (
                    pool_k, pool_v,
                    q.reshape(tiles, GATHER_TILE, HQ, DIM),
                    ids.reshape(tiles, GATHER_TILE, HKV, -1),
                    count.reshape(tiles, GATHER_TILE),
                    (t % SIZES.block).reshape(tiles, GATHER_TILE)),
                    args.reps)
                want = want.reshape(1, chunk, HQ, DIM).astype(jnp.float32)
                if not agree:
                    emit(form="gather", context=context, ms=least,
                         ms_median=median)
                for keys_ in args.keys:
                    bits = bs.pack_members(member, keys_ // SIZES.block)
                    for tile_q, rows, product in itertools.product(
                            args.tile_q, args.rows, args.product):
                        width = product // keys_
                        if width < 1 or (agree and (
                                keys_, tile_q, rows, width) != (
                                pa._TILE_KEYS, pa._TILE_QUERIES,
                                pa._TILE_ROWS, pa._TILE_UNITS)):
                            continue
                        units, n = pa.tile_lists(bits, tile_q)
                        fn = jax.jit(lambda pk, pv, q_, b_, u_, n_,
                                     keys_=keys_, tile_q=tile_q, rows=rows,
                                     width=width:
                                     pa.paged_flash_decode_tiles(
                                         q_, pk, pv, table, start,
                                         b_, u_, n_, block=SIZES.block,
                                         unit=keys_, tile_q=tile_q,
                                         rows=rows, width=width,
                                         interpret=interpret))
                        shape = dict(context=context, tile_q=tile_q,
                                     keys=keys_, rows=rows,
                                     product_keys=width * keys_)
                        try:
                            got, least, median = _timed(
                                fn, (pool_k, pool_v, q, bits, units, n),
                                args.reps)
                        except Exception as e:   # a tile the chip refuses
                            emit(form="tiles", **shape,
                                 refused=str(e).splitlines()[0][:200])
                            continue
                        err = float(jnp.max(jnp.abs(
                            got.astype(jnp.float32) - want)))
                        emit(form="tiles_agree" if agree else "tiles",
                             **shape, ms=least, ms_median=median, err=err,
                             blocks_streamed=int(jnp.sum(n))
                             * (keys_ // SIZES.block),
                             blocks_tiles_see=HKV * sum(
                                 (context - chunk + i + tile_q - 1)
                                 // SIZES.block + 1
                                 for i in range(0, chunk, tile_q)))


if __name__ == "__main__":
    main()
