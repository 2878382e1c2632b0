#!/usr/bin/env python
"""A continuation chunk over K and V pools alone on the chip: the paged
kernel against the WALK through the flash forward — the sweep behind
``dtf_tpu.ops.paged_attention.chunk_walks``.

    python3 tools/paged_chunk_sweep.py --out chiprun_out/paged_chunk_sweep.jsonl

One JSON line each, on the host's clock over chained calls, one layer's
attention of ONE chunk (``paged_flash_decode`` and
``paged_chunk_attention`` over the same pools, table and ``index``):

  ``pair``       at the shapes of ``qwen3next-serve-hybriddoc`` (2,048
                 queries x 16 query heads over 2 KV heads of 256, pages of
                 1,024) and of ``smallthinker-serve-mixedctx``'s global
                 layers (1,024 x 28 over 4 of 128, pages of 64), 2k / 8k /
                 16k / 32k / 64k keys under ``index``;
  ``agreement``  both against the gather oracle in float32 at the first
                 context (bf16 operands, as the cells run);
  ``rows``       the rows-a-head break-even at 8k keys: the chunk's length
                 halved from the cell's down to 16 queries, and the query
                 heads a KV head at 1, 2, 4 beside the cell's;
  ``tune``       the walk at other steps (``EXPAND_KEYS`` 1,024 / 4,096)
                 and with its pages gathered whole (no
                 ``_GATHER_SLICE_BYTES``).

It needs the TPU; nothing here runs in the tests and nothing a cell runs
imports it.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

pa = importlib.import_module("dtf_tpu.ops.paged_attention")
bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

# (chunk, query heads, KV heads, head, page, the pool's pages): the two
# cells whose chunks walk
SHAPES = {"qwen3next": (2048, 16, 2, 256, 1024, 622),
          "smallthinker": (1024, 28, 4, 128, 64, 2049)}
CONTEXTS = (2048, 8192, 16384, 32768, 65536)


def timed(fn, *args, calls: int = 3):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def problem(key, s, hq, h, d, page, pages, context):
    """A row of ``context`` keys under a chunk of ``s``, its pages drawn
    from all over a pool of ``pages``; the chunk's own keys written
    (write-then-attend)."""
    m = -(-(max(CONTEXTS) + s) // page)
    ks = jax.random.split(key, 6)
    pool_k, pool_v = (jax.random.normal(k_, (pages, page, h, d), bf16)
                      for k_ in ks[:2])
    table = (1 + jax.random.permutation(ks[2], pages - 1)[:m]
             ).astype(i32)[None]
    q = (jax.random.normal(ks[3], (1, s, hq, d), f32) * 0.5).astype(bf16)
    k, v = (jax.random.normal(k_, (1, s, h, d), f32).astype(bf16)
            for k_ in ks[4:])
    index = jnp.full((1,), context, i32)
    aligned = s % page == 0
    pool_k = pa.write_pages(pool_k, k, table, index, page_aligned=aligned)
    pool_v = pa.write_pages(pool_v, v, table, index, page_aligned=aligned)
    return q, k, v, pool_k, pool_v, table, index


def kernel_ms(q, k, v, pool_k, pool_v, table, index):
    """``paged_flash_decode``; None where the shape does not compile (whole
    heads of 2,048 rows: a head group's carry outgrows the VMEM)."""
    del k, v
    try:
        return timed(pa.paged_flash_decode, q, pool_k, pool_v, table, index)
    except Exception as e:  # noqa: BLE001 — the compiler's refusal, recorded
        print(f"kernel refused: {str(e)[:300]}", file=sys.stderr)
        return None


def walk_ms(q, k, v, pool_k, pool_v, table, index):
    # jitted as a chunk body is: the pools seen in parts of a page are a
    # view there, and a copy of both pools where the reshape runs alone
    # (call 1's lines: + 4.3 ms whatever the shape, 1.3e9 B of pools)
    return timed(jax.jit(functools.partial(pa.paged_chunk_attention,
                                           use_pallas=True)),
                 q, k, v, pool_k, pool_v, table, index)


def walk_with(name: str, value: int, *prob):
    """The walk with the module's ``name`` at ``value``: a step of other
    than ``EXPAND_KEYS`` keys, a gather of larger slices than
    ``_GATHER_SLICE_BYTES``."""
    kept = getattr(pa, name)
    setattr(pa, name, value)
    try:
        return walk_ms(*prob)
    finally:
        setattr(pa, name, kept)


def agreement(args):
    """Both forms against the gather oracle in float32."""
    q, k, v, pool_k, pool_v, table, index = args
    live = -(-(int(index[0]) + q.shape[1]) // pool_k.shape[1])
    want = np.asarray(pa.paged_attention(
        q.astype(f32), pool_k[table[0, :live]].astype(f32),
        pool_v[table[0, :live]].astype(f32),
        jnp.arange(live, dtype=i32)[None], index))
    out = {}
    for name, got in (
            ("kernel", pa.paged_flash_decode(q, pool_k, pool_v, table, index)),
            ("walk", pa.paged_chunk_attention(q, k, v, pool_k, pool_v, table,
                                              index, use_pallas=True))):
        diff = np.asarray(got, np.float32) - want
        out[f"{name}_max_diff"] = float(np.abs(diff).max())
        out[f"{name}_rms_diff"] = float(np.sqrt(np.mean(diff ** 2)))
    out["o_rms"] = float(np.sqrt(np.mean(want ** 2)))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/paged_chunk_sweep.jsonl")
    ap.add_argument("--build", default="",
                    help="what is being measured, copied to every line")
    ap.add_argument("--contexts", type=int, nargs="+", default=CONTEXTS)
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("the sweep times kernels on the TPU; found "
                         f"{device.platform}")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    key = jax.random.key(58)

    with open(args.out, "a") as f:
        def say(**line):
            line.update(device=device.device_kind, build=args.build)
            f.write(json.dumps(line) + "\n")
            f.flush()
            print(json.dumps(line), flush=True)

        for name, (s, hq, h, d, page, pages) in SHAPES.items():
            shape = dict(shape=name, s=s, hq=hq, h=h, d=d, page=page,
                         pool_pages=pages)
            for i, context in enumerate(args.contexts):
                prob = problem(key, s, hq, h, d, page, pages, context)
                if i == 0:
                    say(what="agreement", context=context, **shape,
                        **agreement(prob))
                say(what="pair", context=context, **shape,
                    rows_a_head=hq // h * s,
                    tiling=list(pa._tiling(s, hq, h, d, page,
                                           prob[5].shape[1], 2)),
                    walks=pa.chunk_walks(s, hq, h),
                    kernel_ms=kernel_ms(*prob), walk_ms=walk_ms(*prob))
                if context in (8192, 32768):
                    say(what="tune", context=context, **shape,
                        walk_whole_pages_ms=walk_with(
                            "_GATHER_SLICE_BYTES", 1 << 30, *prob),
                        **{f"walk_step_{keys}_ms": walk_with(
                            "EXPAND_KEYS", keys, *prob)
                           for keys in (1024, 2048, 4096)})
            # the break-even by the rows a KV head meets, at 8k keys
            group = hq // h
            rows = [(s_, group) for s_ in (16, 32, 64, 128, 256, 512, 1024,
                                           2048) if s_ <= s]
            rows += [(s, g) for g in (1, 2, 4) if g < group]
            for s_, g in rows:
                prob = problem(key, s_, g * h, h, d, page, pages, 8192)
                say(what="rows", context=8192, **dict(shape, s=s_, hq=g * h),
                    rows_a_head=g * s_,
                    tiling=list(pa._tiling(s_, g * h, h, d, page,
                                           prob[5].shape[1], 2)),
                    walks=pa.chunk_walks(s_, g * h, h),
                    kernel_ms=kernel_ms(*prob), walk_ms=walk_ms(*prob))


if __name__ == "__main__":
    main()
