#!/usr/bin/env python
"""The state kernel alone on the chip, form by form — the sweep behind
``dtf_tpu.ops.linear_state.linear_state_decode``.

    python3 tools/state_kernel_sweep.py --out chiprun_out/state_sweep.jsonl

At the shape of ``ling-serve-longgen`` (96 rows, 32 heads of 128 x 128, a
pool of 385 pages of 1,024 tokens, every row's pages its own and its
position drawn from the seed) it runs each form of one decode step of the
state — the kernel as shipped, the form it replaced (PR 39's: both
products as lane reductions over the transposed matrix, ``v`` and ``o`` as
columns picked by a select) and the forms tried beside it — and prints one
JSON line a form: the kernel's device time a call from a profiler trace
(the op line's events named ``linear_state_decode*``: what
``linear_state_kernel_ms.longgen`` reads, a seventh of it), the whole
jitted call on the host's clock (the operands built outside the kernel
are inside; ``beside`` names the device's ops that build them), the share of the bytes' roofline (a row's matrices read once
and written once over the chip's bandwidth: what
``linear_state_roofline.longgen`` divides by), and how far ``o`` (``o_err_rel``: in units of ``1e-6 + 1e-5 |o|``) and the
stored entries (``entry_err``: in units of one spacing of the pool's dtype
plus float32's own rounding of the two terms) stand from
:func:`paged_step` evaluated in float64 on the host over the first rows:
both must read under 1.  PR 43's lines are kept in
``docs/pr43_state_kernel_sweep.jsonl``.  It needs the TPU; nothing here
runs in the tests and nothing a cell runs imports it.
"""

from __future__ import annotations

import argparse
import datetime
import functools
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from dtf_tpu.ops import linear_state as ls  # noqa: E402

PEAK_BYTES = 819e9                          # one v5e chip, as published
ROWS, HEADS, DIM, POOL, PAGE, PAGES_A_ROW = 96, 32, 128, 385, 1024, 4
f32, bf16 = jnp.float32, jnp.bfloat16
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


# ------------------------------------------- the form PR 39 shipped -----
def _columns_kernel(tbl_ref, idx_ref, q_ref, k_ref, kb_ref, alpha_ref,
                    vt_ref, pool_hbm, ot_ref, pool_out, sbuf, obuf, sem_in,
                    sem_out, *, page_size: int):
    """PR 39's body: per head 48 lane reductions (``u``, ``o`` and the
    head's column of ``vt``) and a select into the carried ``ot``."""
    live = idx_ref[pl.program_id(0)] > 0
    heads = sbuf.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)

    def advance(slot):
        def head(h, ot):
            row = pl.ds(h, 1)
            m = jnp.where(live, sbuf[slot, h].astype(f32), 0.0)
            md = m * alpha_ref[row, :]
            u = jnp.sum(md * k_ref[row, :], axis=1, keepdims=True)
            mine = lane == h
            v = jnp.sum(jnp.where(mine, vt_ref[...], 0.0), axis=1,
                        keepdims=True)
            new = md + (v - u) * kb_ref[row, :]
            obuf[slot, h] = new.astype(obuf.dtype)
            o = jnp.sum(new * q_ref[row, :], axis=1, keepdims=True)
            return jnp.where(mine, o, ot)
        ot_ref[...] = jax.lax.fori_loop(
            0, heads, head, jnp.zeros(ot_ref.shape, f32))
    ls._row_pipeline(tbl_ref, idx_ref, pool_hbm, pool_out, sbuf, obuf,
                     sem_in, sem_out, advance, page_size=page_size)


def columns(pool, q, k, v, a, beta, block_table, index, *, page_size):
    b, h, dk = q.shape
    dv = v.shape[-1]

    def rows(lanes):
        return pl.BlockSpec((None, h, lanes), lambda r, tbl, idx: (r, 0, 0))
    vt_spec = pl.BlockSpec((None, dv, h), lambda r, tbl, idx: (r, 0, 0))
    ot, pool = ls._state_call(
        functools.partial(_columns_kernel, page_size=page_size), pool,
        block_table, index,
        (q, k, beta[..., None] * k, jnp.exp(a), jnp.swapaxes(v, 1, 2)),
        [rows(dk)] * 4 + [vt_spec], vt_spec, (b, dv, h),
        name="linear_state_decode_columns")
    return jnp.swapaxes(ot, 1, 2), pool


# ---------------------------- the forms tried beside the shipped one ----
def _rows_kernel(tbl_ref, idx_ref, x_ref, alpha_ref, kb_ref, v_ref, kq_ref,
                 pool_hbm, o_ref, pool_out, sbuf, obuf, sem_in, sem_out, *,
                 page_size: int, write: str, unroll: int, parts: int):
    """``u`` and ``M (alpha q)`` as rows of ONE product against the matrix
    as stored; the rank-one write either as a second product (``mxu``: the
    pieces of ``w`` against those of ``kb``, contracted over the 16 rows)
    or on the VPU after one vreg's transposition (``vpu``)."""
    dv = sbuf.shape[2]
    sub = jax.lax.broadcasted_iota(jnp.int32, (16, dv), 0)

    def advance(slot):
        def head(h):
            row = pl.ds(h, 1)
            x = x_ref[h]                                # [16, dk] bf16
            m = sbuf[slot, h]
            res = sum(jax.lax.dot_general(x, p, _NT,
                                          preferred_element_type=f32)
                      for p in (ls._pieces(m) if parts == 3 else (m,)))
            w = v_ref[row, :] - (res[0:1] + res[1:2] + res[2:3])
            o_ref[row, :] = (res[3:4] + res[4:5] + res[5:6]
                             + w * kq_ref[row, :])
            md = m.astype(f32) * alpha_ref[row, :]
            if write == "mxu":
                w1, w2, w3 = (jnp.broadcast_to(t.astype(f32), (16, dv))
                              for t in ls._pieces(w))
                w16 = jnp.where(
                    (sub == 8) | (sub == 9) | (sub == 11), w1, jnp.where(
                        (sub == 10) | (sub == 12), w2, jnp.where(
                            sub == 13, w3, 0.0)))
                new = md + jax.lax.dot_general(
                    w16.astype(bf16), x, _TN, preferred_element_type=f32)
            else:
                wc = jnp.transpose(jnp.broadcast_to(w, (8, dv)))[:, 0:1]
                new = md + wc * kb_ref[row, :]
            obuf[slot, h] = new.astype(obuf.dtype)

        def group(i, carry):
            for j in range(unroll):
                head(i * unroll + j)
            return carry
        jax.lax.fori_loop(0, sbuf.shape[1] // unroll, group, 0)
    ls._row_pipeline(tbl_ref, idx_ref, pool_hbm, pool_out, sbuf, obuf,
                     sem_in, sem_out, advance, page_size=page_size)


def rows_form(pool, q, k, v, a, beta, block_table, index, *, page_size,
              write="vpu", unroll=0, groups=0):
    b, h, dk = q.shape
    dv = v.shape[-1]
    alpha = jnp.exp(a)
    kb = beta[..., None] * k
    kq = jnp.sum(kb * q, -1, keepdims=True)
    kbp = ls._pieces(kb)
    zero = jnp.zeros_like(kbp[0])
    x = jnp.stack(list(ls._pieces(alpha * k)) + list(ls._pieces(alpha * q))
                  + [zero, zero, kbp[0], kbp[1], kbp[0], kbp[2], kbp[1],
                     kbp[0], zero, zero], 2)            # [B, H, 16, dk]
    parts = 1 if pool.dtype == bf16 else 3

    def rows(lanes):
        return pl.BlockSpec((None, h, lanes), lambda r, tbl, idx: (r, 0, 0))
    x_spec = pl.BlockSpec((None, h, 16, dk),
                          lambda r, tbl, idx: (r, 0, 0, 0))
    if groups:
        kernel = functools.partial(_tiles_kernel, groups=groups)
        name = f"tiles{parts}x3_g{groups}"
    else:
        kernel = functools.partial(_rows_kernel, write=write, unroll=unroll)
        name = f"rows_{write}{parts}x3_u{unroll}"
    return ls._state_call(
        functools.partial(kernel, page_size=page_size, parts=parts), pool,
        block_table, index,
        (x, alpha, kb, v, jnp.broadcast_to(kq, v.shape)),
        [x_spec, rows(dk), rows(dk), rows(dv), rows(dv)], rows(dv),
        (b, h, dv), name="linear_state_decode_" + name)


def _tiles_kernel(tbl_ref, idx_ref, x_ref, alpha_ref, kb_ref, v_ref, kq_ref,
                  pool_hbm, o_ref, pool_out, sbuf, obuf, sem_in, sem_out, *,
                  page_size: int, groups: int, parts: int):
    """Eight heads a tile: their eight products first, ``v``, ``w`` and
    ``o`` as ONE ``[8, dv]`` tile, ONE transposition of ``w``'s tile for
    the eight columns, then the eight writes on the VPU; ``groups`` tiles
    an iteration of the loop."""
    heads, dv = sbuf.shape[1:3]
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, dv), 0)

    def advance(slot):
        def tile(g):
            at = pl.multiple_of(g * 8, 8)
            u = p = jnp.zeros((8, dv), f32)
            for j in range(8):
                m = sbuf[slot, at + j]
                res = sum(jax.lax.dot_general(x_ref[at + j], part, _NT,
                                              preferred_element_type=f32)
                          for part in (ls._pieces(m) if parts == 3
                                       else (m,)))
                u = jnp.where(sub == j, res[0:1] + res[1:2] + res[2:3], u)
                p = jnp.where(sub == j, res[3:4] + res[4:5] + res[5:6], p)
            rows = pl.ds(at, 8)
            w = v_ref[rows, :] - u
            o_ref[rows, :] = p + w * kq_ref[rows, :]
            wt = jnp.transpose(w)                       # [dv, 8]
            for j in range(8):
                row = pl.ds(at + j, 1)
                new = (sbuf[slot, at + j].astype(f32) * alpha_ref[row, :]
                       + wt[:, j:j + 1] * kb_ref[row, :])
                obuf[slot, at + j] = new.astype(obuf.dtype)

        def group(i, carry):
            for j in range(groups):
                tile(i * groups + j)
            return carry
        jax.lax.fori_loop(0, heads // (8 * groups), group, 0)
    ls._row_pipeline(tbl_ref, idx_ref, pool_hbm, pool_out, sbuf, obuf,
                     sem_in, sem_out, advance, page_size=page_size)


def forms():
    out = {"shipped": ls.linear_state_decode, "columns": columns}
    for write in ("mxu", "vpu"):
        for unroll in (1, 2, 4, 8, 16, 32):
            out[f"rows_{write}_u{unroll}"] = functools.partial(
                rows_form, write=write, unroll=unroll)
    for groups in (1, 2, 4):
        out[f"tiles_g{groups}"] = functools.partial(rows_form, groups=groups)
    return out


# ----------------------------------------------------------- the case ---
def case(seed, dtype):
    """The cell's shape: every row four pages of its own, its position
    anywhere in them (one row at a page's first token, one at 0)."""
    rng = np.random.default_rng(seed)
    key = jax.random.key(seed % (2 ** 31))
    pool = jax.random.normal(key, (POOL, HEADS, DIM, DIM), f32).astype(dtype)
    table = np.zeros((ROWS, 12), np.int32)
    table[:, :PAGES_A_ROW] = 1 + rng.permutation(
        ROWS * PAGES_A_ROW).reshape(ROWS, PAGES_A_ROW)
    index = rng.integers(1, PAGES_A_ROW * PAGE, ROWS).astype(np.int32)
    index[0], index[1] = 2 * PAGE, 0

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    shape = (ROWS, HEADS, DIM)
    q = unit(rng.normal(size=shape)) * DIM ** -0.5
    k = unit(rng.normal(size=shape))
    v = rng.normal(size=shape)
    a = -5.0 / (1.0 + np.exp(-rng.normal(size=shape)))
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=shape[:2])))
    token = tuple(jnp.asarray(t, f32) for t in (q, k, v, a, beta))
    return pool, token, jnp.asarray(table), jnp.asarray(index)


def oracle(pool, token, table, index, rows):
    """:func:`paged_step` in float64 on the host, the first ``rows`` rows:
    (o, the new entries, what float32 arithmetic may leave of error in
    one — 2^-22 of the two terms it adds and of the terms of ``u``, which
    matters where they cancel — and the pages the entries go to)."""
    q, k, v, _, beta = (np.asarray(t, np.float64)[:rows] for t in token)
    table, index = np.asarray(table)[:rows], np.asarray(index)[:rows]
    r = np.arange(rows)
    src = table[r, np.maximum(index - 1, 0) // PAGE]
    dst = table[r, np.minimum(index // PAGE, table.shape[1] - 1)]
    m = np.asarray(pool[jnp.asarray(src)].astype(f32), np.float64)
    m = np.where((index > 0)[:, None, None, None], m, 0.0)
    # the chip's own exp (1e-6 of relative error): the forms' arithmetic
    # is what is measured here, not the transcendental before it
    md = m * np.asarray(jnp.exp(token[3]), np.float64)[:rows, :, None, :]
    u = np.einsum("bhvk,bhk->bhv", md, k)
    rank = (beta[..., None] * (v - u))[..., None] * k[:, :, None, :]
    new = md + rank
    slack = 2.0 ** -22 * (np.abs(md) + np.abs(rank) + np.abs(
        (beta[..., None] * np.einsum("bhvk,bhk->bhv", np.abs(md), np.abs(k))
         )[..., None] * k[:, :, None, :]))
    return np.einsum("bhvk,bhk->bhv", new, q), new, slack, dst


def ulp(x, bits):
    """The spacing of a format of ``bits`` stored mantissa bits at x."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 1e-30))) - bits)


def timed(fn, pool, args, reps, rounds):
    for _ in range(2):
        o, pool = fn(pool, *args)
    jax.block_until_ready(pool)
    out = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            o, pool = fn(pool, *args)
        jax.block_until_ready((o, pool))
        out.append((time.perf_counter() - t0) / reps * 1e3)
    return statistics.median(out), pool


def traced(fn, pool, args, reps):
    """Device seconds a call of the ops named ``linear_state_decode*``."""
    from benchmark.lib import xplane
    where = tempfile.mkdtemp(prefix="state_sweep_")
    try:
        with jax.profiler.trace(where):
            for _ in range(reps):
                o, pool = fn(pool, *args)
            jax.block_until_ready((o, pool))
        red = xplane.reduce_trace(xplane.load(xplane.find_xplane(where)))
    finally:
        shutil.rmtree(where, ignore_errors=True)
    names = sorted(n for n in red.self_s if "linear_state_decode" in n)
    beside = sorted(((round(s / reps * 1e3, 4), n)
                     for n, s in red.self_s.items() if n not in names),
                    reverse=True)[:6]
    return (red.kernel_s("linear_state_decode") / reps * 1e3, names,
            red.busy_s / reps * 1e3, beside, pool)


def measure(form, pool, token, table, index, wanted, args):
    """Agreement with the oracle, then the timings, of one form on one
    pool: the fields of its line."""
    want_o, want_new, slack, dst = wanted
    fn = jax.jit(functools.partial(form, page_size=PAGE), donate_argnums=(0,))
    call = token + (table, index)
    o, got = fn(jnp.copy(pool), *call)
    off = np.abs(np.asarray(o[:args.oracle_rows], np.float64) - want_o)
    new = np.asarray(got[jnp.asarray(dst)].astype(f32), np.float64)
    del got
    rounded = np.asarray(jnp.asarray(want_new, f32).astype(pool.dtype)
                         .astype(f32), np.float64)
    room = slack + ulp(rounded, 7 if pool.dtype == bf16 else 23)
    ms, work = timed(fn, jnp.copy(pool), call, args.reps, args.rounds)
    kernel_ms, names, busy_ms, beside, _ = traced(fn, work, call, args.reps)
    return dict(o_err=float(off.max()),
                o_err_rel=float((off / (1e-6 + 1e-5 * np.abs(want_o))).max()),
                entry_err=float((np.abs(new - rounded) / room).max()),
                ms_call_host=ms, ms_kernel=kernel_ms, ms_busy=busy_ms,
                kernels=names, beside=beside)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--forms", nargs="*", default=["columns", "shipped"])
    p.add_argument("--dtypes", nargs="*", default=["bfloat16"],
                   choices=["bfloat16", "float32"])
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--reps", type=int, default=50)
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--oracle_rows", type=int, default=8)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit("state_kernel_sweep measures the chip: no TPU here")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    table_of = forms()
    with open(args.out, "a") as f:
        for name in args.dtypes:
            dtype = jnp.dtype(name)
            pool, token, table, index = case(args.seed, dtype)
            wanted = oracle(pool, token, table, index, args.oracle_rows)
            for form in args.forms:
                rec = {"form": form, "pool": name, "rows": ROWS,
                       "heads": HEADS, "dim": DIM, "pages": POOL,
                       "page": PAGE, "seed": args.seed,
                       "chip": jax.devices()[0].device_kind,
                       "date": datetime.date.today().isoformat(),
                       "least_ms": (2 * ROWS * HEADS * DIM * DIM
                                    * dtype.itemsize / PEAK_BYTES * 1e3)}
                try:
                    rec.update(measure(table_of[form], pool, token, table,
                                       index, wanted, args))
                    rec["of_least"] = rec["least_ms"] / rec["ms_kernel"]
                except Exception as e:  # noqa: BLE001 — a form the
                    # compiler refuses is a row of the table
                    rec.update(error=f"{type(e).__name__}: {str(e)[:300]}")
                f.write(json.dumps(rec) + "\n")
                f.flush()
                print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
