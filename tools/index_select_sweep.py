#!/usr/bin/env python
"""The kernels of learned sparse attention alone on the chip — the sweep
behind ``dtf_tpu.ops.index_select`` and ``paged_attention.latent_sparse_*``.

    python3 tools/index_select_sweep.py --out chiprun_out/index_sweep.jsonl

At the shapes of ``glm52-serve-sparsectx`` (a chunk of 2,048 queries of 64
heads over latent rows of 640 lanes, 32 index heads of 128, 2,048 rows
chosen, pages of 256; a decode step of 16 rows) and at contexts of 8k, 32k
and 64k it times, one JSON line each, on the host's clock over chained
calls: the choice (``index_select``), the attention over the choice as a
stream of the row's pages under a mask (``latent_sparse_*``, the form the
model runs), the same attention as A GATHER A QUERY (XLA's gather of the
chosen rows, 64 queries at a time: the form it is measured against) and the
dense latent kernel over every visible row; and it holds the kernels'
results to their oracles at the first context.  ``expanded`` lines (PR 56):
the same chunk attending EXPANDED under its membership
(``latent_chunk_attention`` with ``member``: kernel + the keys' expansion
through ``kv_b``), at both score forms — the rotary key in each head's row
or as a shared second product —, beside the same walk with no membership
(what the mask costs), the membership's relayout to a row a query alone,
and the membership fed both ways: tiled through a ``BlockSpec`` (the
program's: a step's blocks as they lie) or as that row a query, the whole
walk and ONE step of it.  It needs the TPU; nothing here
runs in the tests and nothing a cell runs imports it.
"""

from __future__ import annotations

import argparse
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

from dtf_tpu.ops import index_select as ix  # noqa: E402

from dtf_tpu.ops.flash_attention import flash_forward  # noqa: E402

pa = importlib.import_module("dtf_tpu.ops.paged_attention")
bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
H, W, V, HI, DI, TOP, S, ROWS = 64, 640, 512, 32, 128, 2048, 2048, 16
NOPE, ROPE, DV = 192, 64, 256
SCALE = 256 ** -0.5


def timed(fn, *args, calls: int = 3):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def ids(member, k: int):
    """(positions [.., k] int32 of bool ``member`` [.., L]'s rows in
    ascending order, -1 past their count; count [..]): a choice as the list
    a gather a query takes."""
    n = member.shape[-1]
    rank = jnp.cumsum(member, -1, dtype=i32) - 1
    slot = jnp.where(member, rank, k).reshape(-1, n)
    pos = jnp.broadcast_to(jnp.arange(n, dtype=i32), slot.shape)
    out = jnp.full((slot.shape[0], k + 1), -1, i32)
    out = out.at[jnp.arange(slot.shape[0])[:, None], slot].set(pos)
    return (out[:, :k].reshape(member.shape[:-1] + (k,)),
            jnp.sum(member, -1, dtype=i32))


def gather_a_query(q, pool, block_table, chosen, count, *, value_lanes,
                   scale):
    """The attention of ``latent_sparse_*`` as A GATHER A QUERY: ``chosen``
    [B, S, K] the positions a query attends (the first ``count`` [B, S] of
    them), each row fetched through the table by XLA's gather — K rows a
    query whatever the context, which on the TPU costs the 16 rows of a
    bfloat16 tile around each.  The form that was measured and lost (PR 49);
    the program does not hold it."""
    b, s, k = chosen.shape
    page = pool.shape[1]
    pos = jnp.maximum(chosen, 0).reshape(b, s * k)
    flat = (jnp.take_along_axis(block_table, pos // page, axis=1) * page
            + pos % page)
    rows = pool.reshape(-1, pool.shape[-1])[flat].reshape(b, s, k, -1)
    scores = jnp.einsum("bqhw,bqkw->bqhk", q, rows,
                        preferred_element_type=f32) * scale
    live = jnp.arange(k, dtype=i32) < count[..., None]
    scores = jnp.where(live[:, :, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
    return jnp.einsum("bqhk,bqkv->bqhv", probs, rows[..., :value_lanes],
                      preferred_element_type=f32).astype(q.dtype)


def untiled(tiled, s: int, n: int):
    """The kernels' tiled membership as bool [B, S, L]."""
    b, g, blocks, tile, mb = tiled.shape
    return np.asarray(jnp.swapaxes(tiled, 2, 3).reshape(
        b, g * tile, blocks * mb)[:, :s, :n] != 0)


def expanded_lines(say, context, check, latent, table, index, member, sparse,
                   ks):
    """The ``expanded`` lines at one context; ``check``: the masked stream
    (``sparse``) over the ABSORBED image of the queries drawn here is the
    same attention, and the two are held together."""
    w_kvb = (jax.random.normal(ks[0], (V, H, NOPE + DV), f32) * 0.04
             ).astype(bf16)
    q = (jax.random.normal(ks[1], (1, S, H, NOPE + ROPE), f32) * 0.5
         ).astype(bf16)
    page = latent.shape[1]
    # the chunk's own rows as the pool holds them
    at = index[0] // page + jnp.arange(S // page, dtype=i32)
    rows = latent[table[0, at]].reshape(1, S, W)
    ppb = pa.EXPAND_KEYS // page
    flat = jax.jit(pa.member_rows)
    named = flat(member)
    ms = {"member_rows_ms": timed(flat, member)}
    walk = {}
    for form, in_head in (("in_head", True), ("shared", False)):
        walk[form] = jax.jit(
            lambda q, rows, w, pool, table, index, member, in_head=in_head:
            pa._latent_chunk_walk(
                q, rows, w, pool, table, index, member, rank=V, nope=NOPE,
                scale=SCALE, use_pallas=True, ppb=ppb, in_head=in_head))
        # the membership tiled, as the program feeds it (a step's blocks
        # as they lie), and laid out a row a query beforehand
        ms[f"expanded_{form}_ms"] = timed(
            walk[form], q, rows, w_kvb, latent, table, index, member)
        ms[f"expanded_{form}_rows_ms"] = timed(
            walk[form], q, rows, w_kvb, latent, table, index, named)
        ms[f"expanded_{form}_no_member_ms"] = timed(
            walk[form], q, rows, w_kvb, latent, table, index, None)
    say(what="chunk_expanded", context=context, **ms)
    # ONE step of the walk, 2,048 expanded keys, the membership fed two ways
    t = pa.EXPAND_KEYS
    kk = (jax.random.normal(ks[2], (1, H, t, NOPE + ROPE), f32) * 0.5
          ).astype(bf16)
    vv = kk[..., :DV]
    qh = jnp.swapaxes(q, 1, 2)
    o0 = jnp.zeros((1, H, S, DV), f32)
    lse0 = jnp.zeros((1, H, S, 1), f32)
    n_live = jnp.full((1,), t, i32)
    step = jax.jit(lambda qh, kk, vv, member, o, lse: flash_forward(
        qh, kk, vv, scale=SCALE, kv_len=n_live, carry=(o, lse),
        member=member, name="latent_sparse_chunk_expanded"))
    blocks = t // ix.MEMBER_BLOCK
    say(what="expanded_step_feed", context=context, keys=t,
        a_row_a_query_ms=timed(step, qh, kk, vv, named[:, :, :t], o0, lse0,
                               calls=6),
        tiled_blockspec_ms=timed(step, qh, kk, vv, member[:, :, :blocks],
                                 o0, lse0, calls=6))
    if check:
        # the absorbed stream over the absorbed image of the same queries
        q_img = jnp.einsum("bshn,rhn->bshr", q[..., :NOPE],
                           w_kvb[..., :NOPE], preferred_element_type=f32)
        q_img = jnp.concatenate(
            [q_img.astype(bf16), q[..., NOPE:],
             jnp.zeros((1, S, H, W - V - ROPE), bf16)], -1)
        o_abs = jnp.einsum("bshr,rhv->bshv",
                           sparse(q_img, latent, table, index, member),
                           w_kvb[..., NOPE:], preferred_element_type=f32)
        o_abs = np.asarray(o_abs)
        for form in walk:
            o = np.asarray(walk[form](q, rows, w_kvb, latent, table, index,
                                      member).astype(f32))
            say(what="expanded_agreement", context=context, form=form,
                o_max_diff=float(np.abs(o - o_abs).max()),
                o_rms_diff=float(np.sqrt(np.mean((o - o_abs) ** 2))),
                o_rms=float(np.sqrt(np.mean(o_abs ** 2))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="chiprun_out/index_sweep.jsonl")
    p.add_argument("--page", type=int, default=256)
    p.add_argument("--contexts", default="8192,32768,65536")
    p.add_argument("--what", default="chunk,expanded,rest",
                   help="which lines: chunk (the choice, the masked stream, "
                        "the dense kernel), expanded, rest (the gather a "
                        "query, the decode step)")
    args = p.parse_args(argv)
    what = set(args.what.split(","))
    if jax.default_backend() != "tpu":
        raise SystemExit("the sweep needs the TPU")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    page = args.page
    m = 66560 // page
    pool_pages = 1 + ROWS * m // 4
    key = jax.random.key(0)
    ks = jax.random.split(key, 8)
    latent = (jax.random.normal(ks[0], (pool_pages, page, W), f32) * 0.5
              ).astype(bf16)
    keys = jax.random.normal(ks[1], (pool_pages, page, DI), f32).astype(bf16)
    lines = []

    def say(**kw):
        kw.update(page=page, device=jax.devices()[0].device_kind)
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    for n, context in enumerate(int(c) for c in args.contexts.split(",")):
        start = context - S
        table = (1 + jnp.arange(m, dtype=i32) % (pool_pages - 1))[None]
        index = jnp.asarray([start], i32)
        q = (jax.random.normal(ks[2], (1, S, H, W), f32) * 0.3).astype(bf16)
        qi = jax.random.normal(ks[3], (1, S, HI, DI), f32).astype(bf16)
        wi = jax.random.normal(ks[4], (1, S, HI), f32)
        select = jax.jit(lambda qi, wi, keys, table, index: ix.chunk_select(
            qi, wi, keys, table, index, k=TOP))
        member = select(qi, wi, keys, table, index)
        sparse = jax.jit(lambda q, pool, table, index, member:
                         pa.latent_sparse_chunk(q, pool, table, index, member,
                                                value_lanes=V, scale=SCALE))
        dense = jax.jit(lambda q, pool, table, index: pa.paged_flash_decode(
            q, pool, None, table, index, scale=SCALE, value_lanes=V))
        if "chunk" in what:
            say(what="chunk", context=context,
                index_select_ms=timed(select, qi, wi, keys, table, index),
                latent_sparse_ms=timed(sparse, q, latent, table, index,
                                       member),
                dense_latent_ms=timed(dense, q, latent, table, index))
        if "expanded" in what:
            expanded_lines(say, context, n == 0, latent, table, index,
                           member, sparse, ks[5:8])
        if "rest" not in what:
            continue
        # the gather a query, 64 queries of the chunk at a time
        tq = 64
        t = index[:, None] + jnp.arange(tq, dtype=i32)[None] + S - tq
        plain = ix.members(ix.scores(qi[:, -tq:], wi[:, -tq:],
                                     pa.gather_pages(keys, table), t), TOP)
        chosen, count = ids(plain, TOP)
        gather = jax.jit(lambda q, pool, table, chosen, count:
                         gather_a_query(q, pool, table, chosen,
                                                 count, value_lanes=V,
                                                 scale=SCALE))
        ms = timed(gather, q[:, -tq:], latent, table, chosen, count)
        say(what="chunk_gather_a_query", context=context, queries=tq,
            ms=ms, ms_a_chunk_of_2048=ms * S / tq)
        if n == 0:
            # a block past a tile's last visible key is not written
            seen = np.arange(m * page) <= np.asarray(t)[..., None]
            got = untiled(member, S, m * page)[:, -tq:] & seen
            want = np.asarray(plain)
            o_mask = np.asarray(sparse(q, latent, table, index, member)
                                [:, -tq:].astype(f32))
            o_gather = np.asarray(gather(q[:, -tq:], latent, table, chosen,
                                         count).astype(f32))
            say(what="chunk_agreement", context=context,
                members_differ=int((got != want).sum()),
                members=int(want.sum()),
                o_max_diff=float(np.abs(o_mask - o_gather).max()),
                o_scale=float(np.abs(o_gather).max()))
        # a decode step of 16 rows at this context
        tables = jnp.stack([1 + (jnp.arange(m, dtype=i32) * 7 + r * 13)
                            % (pool_pages - 1) for r in range(ROWS)])
        at = jnp.full((ROWS,), context - 1, i32)
        qd, qid, wid = q[0, :ROWS], qi[0, :ROWS], wi[0, :ROWS]
        dsel = jax.jit(lambda qi, wi, keys, table, t: ix.decode_select(
            qi, wi, keys, table, t, k=TOP))
        dmember = dsel(qid, wid, keys, tables, at)
        dsparse = jax.jit(lambda q, pool, table, t, member:
                          pa.latent_sparse_decode(q, pool, table, t, member,
                                                  value_lanes=V, scale=SCALE))
        ddense = jax.jit(lambda q, pool, table, t: pa.paged_flash_decode(
            q[:, None], pool, None, table, t, scale=SCALE, value_lanes=V))
        plain = ix.members(ix.scores(qid[:, None], wid[:, None],
                                     pa.gather_pages(keys, tables),
                                     at[:, None]), TOP)
        chosen, count = ids(plain, TOP)
        dgather = jax.jit(lambda q, pool, table, chosen, count:
                          gather_a_query(q[:, None], pool, table,
                                                  chosen, count,
                                                  value_lanes=V, scale=SCALE))
        say(what="decode", context=context, rows=ROWS,
            index_select_ms=timed(dsel, qid, wid, keys, tables, at, calls=10),
            latent_sparse_ms=timed(dsparse, qd, latent, tables, at, dmember,
                                   calls=10),
            gather_a_query_ms=timed(dgather, qd, latent, tables, chosen,
                                    count, calls=10),
            dense_latent_ms=timed(ddense, qd, latent, tables, at, calls=10))
        if n == 0:
            got = untiled(dmember, 1, m * page) & (
                np.arange(m * page) <= np.asarray(at)[:, None, None])
            o_mask = np.asarray(dsparse(qd, latent, tables, at, dmember
                                        ).astype(f32))
            o_gather = np.asarray(dgather(qd, latent, tables, chosen, count
                                          )[:, 0].astype(f32))
            say(what="decode_agreement", context=context,
                members_differ=int((got != np.asarray(plain)).sum()),
                members=int(np.asarray(plain).sum()),
                o_max_diff=float(np.abs(o_mask - o_gather).max()),
                o_scale=float(np.abs(o_gather).max()))
    with open(args.out, "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
