"""Block-sparse attention's CHOICE: which 64-token blocks of a row's cache a
query reads, decided through POOLED keys.

A layer keeps, beside its K and V pools, a pool of pooled keys: ``c_j`` is
the mean of the keys at positions ``[stride * j, stride * j + pool)``
(``pool = 2 * stride``: consecutive pooled keys overlap by half), and it
EXISTS for a query at ``t`` once its last position is written, ``stride * j
+ pool - 1 <= t``.  Block ``b`` is positions ``[block * b, block * (b +
1))``.  For a query at ``t`` past ``dense_len`` (``t + 1 > dense_len``):

    p[h, j] = softmax over the existing j of (scale * q_h . c_j)
    r[g, j] = sum of p[h, j] over the query heads h of KV head g
    R[g, b] = max of r[g, j] over the pooled keys that overlap block b
              (j in n b - 1 .. n b + n - 1, n = block / stride)

and the blocks read are the FORCED ones — the first ``init`` and the
``window / block`` that end at the query's own — and the ``top`` best of
the others by ``R``, ties to the lower ``b``: one choice a (query, KV
head).  At or under ``dense_len`` every block up to the query's is read.

The pooled keys ride the page table: a leaf ``[P, page / stride, Hkv, D]``
whose row ``j % (page / stride)`` of the page that holds position ``stride
* j`` is ``c_j`` (a pooled key that spans two pages lies in the first).

``Sizes``            the seven numbers, checked once;
``write_pooled``     the pooled keys a call completes, ONE scatter;
``scores``           ``r`` in XLA: the CPU oracle and a chunk's form;
``decode_scores``    ``r`` for one query a row as a kernel
                     (``block_select``): the row's pooled pages streamed
                     once, every score kept in VMEM, the softmax taken over
                     all of them at the end — exact, no second read;
``choose``           ``r`` -> the table of blocks ``[.., Hkv, W]`` in
                     ascending order and how many of them count (a decode
                     step's form: a row copies its own blocks);
``physical``         logical blocks -> ids ``page * (page / block) + block
                     % (page / block)`` into a pool seen as blocks;
``members``          ``r`` -> the same choice as membership ``[.., Hkv,
                     blocks]``, no rank and no table (a chunk's form);
``pack_members``     membership -> one small integer a unit of blocks;
``chunk_members``    a chunk's q -> packed membership ``[B, Hkv, S,
                     units]``, a tile of queries' ``r`` at a time: what
                     ``paged_attention.paged_tile_attention`` masks by.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtf_tpu.ops import blockwise as bw
from dtf_tpu.ops.paged_attention import _head_rows

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # [r, d] x [k, d] -> [r, k]


class Sizes(NamedTuple):
    block: int          # tokens a selection block
    pool: int           # tokens a pooled key averages
    stride: int         # tokens between pooled keys
    top: int            # blocks chosen beside the forced ones
    window: int         # tokens always read up to the query's own block
    init: int           # leading blocks always read
    dense_len: int      # a query at t reads everything while t + 1 <= this

    def check(self, page: int):
        if (self.pool != 2 * self.stride or self.block % self.stride
                or self.window % self.block or page % self.block
                or self.dense_len % self.block
                or self.dense_len // self.block
                < self.init + self.top + self.window // self.block):
            raise ValueError(
                f"{self}: pooled keys overlap by half (pool = 2 x stride), "
                f"a block is whole strides, window, page ({page}) and "
                f"dense_len whole blocks, and past dense_len there are at "
                f"least init + top + window / block blocks to choose from")
        return self

    @property
    def read(self) -> int:
        """Blocks a query past ``dense_len`` reads."""
        return self.init + self.top + self.window // self.block

    @property
    def width(self) -> int:
        """Entries of a table of blocks: the most any query reads."""
        return max(self.read, self.dense_len // self.block)


def pooled_exist(t, sizes: Sizes):
    """How many pooled keys exist for a query at ``t``."""
    return jnp.maximum((t - sizes.pool + 1) // sizes.stride + 1, 0)


def write_pooled(pooled, k, k_pool, block_table, index, sizes: Sizes):
    """The pooled keys a call of ``S`` tokens at ``index`` [B] completes,
    written to their rows in ONE scatter.  ``k`` [B, S, H, D] the call's
    keys, already in ``k_pool`` [P, page, H, D] (write-then-pool).

    One token (a decode step): ``c_j`` with ``stride * j + pool - 1 =
    index`` if there is one — the mean of the pool's last ``pool`` rows up
    to ``index``, which may lie in two pages; a row that completes none
    writes the scratch page.  Whole strides from a multiple of ``stride``
    (a chunk): the ``S / stride`` pooled keys whose last position lies in
    the call, the first of which began ``stride`` rows before it (read
    from ``k_pool``; it does not exist at ``index`` 0).  Tail padding
    pools garbage into keys no query can see yet: a later step completes
    and overwrites each before it exists."""
    b, s, h, d = k.shape
    page = k_pool.shape[1]
    per_page, stride = pooled.shape[1], sizes.stride
    m = block_table.shape[1]
    flat_k = k_pool.reshape((-1,) + k_pool.shape[2:])

    def rows_at(pos):               # [B, n] positions -> rows of flat_k
        pos = jnp.clip(pos, 0, m * page - 1)
        return (jnp.take_along_axis(block_table, pos // page, axis=1) * page
                + pos % page)
    if s == 1:
        last = flat_k[rows_at(index[:, None] - jnp.arange(
            sizes.pool - 1, -1, -1, dtype=jnp.int32)[None, :])]
        new = jnp.mean(last.astype(jnp.float32), axis=1, keepdims=True)
        j = (index - sizes.pool + 1) // stride
        done = ((index - sizes.pool + 1) % stride == 0) & (j >= 0)
        j, done = j[:, None], done[:, None]
    else:
        if s % stride:
            raise ValueError(f"a call of {s} tokens is not whole strides "
                             f"of {stride}")
        before = flat_k[rows_at(index[:, None] - stride + jnp.arange(
            stride, dtype=jnp.int32)[None, :])]
        before = jnp.where((index > 0)[:, None, None, None], before, 0)
        halves = jnp.sum(jnp.concatenate([before, k], axis=1).astype(
            jnp.float32).reshape(b, s // stride + 1, stride, h, d), axis=2)
        new = (halves[:, :-1] + halves[:, 1:]) / sizes.pool
        j = (index // stride - 1)[:, None] + jnp.arange(
            s // stride, dtype=jnp.int32)[None, :]
        done = j >= 0
    where = jnp.clip(j * stride // page, 0, m - 1)
    rows = (jnp.take_along_axis(block_table, where, axis=1) * per_page
            + j % per_page)
    rows = jnp.where(done, rows, 0)         # the scratch page's first row
    flat = pooled.reshape((-1,) + pooled.shape[2:])
    flat = flat.at[rows.reshape(-1)].set(
        new.reshape((-1, h, d)).astype(pooled.dtype))
    return flat.reshape(pooled.shape)


def scores(q, pooled, block_table, t, sizes: Sizes, scale: float,
           tile: int = 256):
    """``r`` [B, S, Hkv, J] float32 (``J`` the table's pooled rows; 0 where
    a pooled key does not exist for the query): q [B, S, Hq, D] at
    positions ``t`` [B, S], against the row's pooled keys gathered through
    its table.  Queries go ``tile`` at a time.  The oracle of
    :func:`decode_scores` and a chunk's form."""
    b, s, hq, d = q.shape
    hkv = pooled.shape[2]
    keys = pooled[block_table].reshape(b, -1, hkv, d).astype(jnp.float32)
    jj = jnp.arange(keys.shape[1], dtype=jnp.int32)

    def some(q_, t_):               # [B, T, Hq, D], [B, T]
        n = q_.shape[1]
        sc = jnp.einsum("bthgd,bjhd->bthgj",
                        q_.astype(jnp.float32).reshape(b, n, hkv,
                                                       hq // hkv, d),
                        keys, precision=_HI) * scale
        live = (jj[None, None, :] < pooled_exist(t_, sizes)[..., None]
                )[:, :, None, None, :]
        sc = jnp.where(live, sc, bw.NEG_INF)
        p = jnp.where(live, jnp.exp(sc - jnp.max(sc, -1, keepdims=True)),
                      0.0)
        total = jnp.sum(p, -1, keepdims=True)
        return jnp.sum(p / jnp.where(total > 0, total, 1.0), axis=3)
    if s <= tile or s % tile:
        return some(q, t)
    tiles = s // tile
    r = jax.lax.map(
        lambda xs: some(*xs),
        (jnp.moveaxis(q.reshape(b, tiles, tile, hq, d), 1, 0),
         jnp.moveaxis(t.reshape(b, tiles, tile), 1, 0)))
    return jnp.moveaxis(r, 0, 1).reshape(b, s, hkv, -1)


# pooled pages a step of the kernel scores at once: 4 x 128 rows of two
# heads are 256 KiB a buffer, and a [16, 512] tile of scores a head
_SELECT_PAGES = 4


def _select_kernel(tbl_ref, n_ref, q_ref, pool_hbm, r_ref, buf, sem, s_ref,
                   *, scale: float):
    """Grid (B,): row ``b``'s pooled pages, ``ppb`` a step, are copied in
    (the next step's in flight during this one's products), every KV
    head's ``[G, ppb * rows]`` scores are KEPT in ``s_ref`` with their
    running maximum, and when the row's ``n_ref[b]`` pooled keys are all
    scored the softmax a query head is taken over the whole of it and
    summed over the group's heads: ``r_ref`` [steps, Hkv, ppb * rows].
    Steps past the row's last stay zeros."""
    b = pl.program_id(0)
    _, ppb, rows, hkv, d = buf.shape
    tj = ppb * rows
    n = n_ref[b]
    n_steps = pl.cdiv(n, tj)
    n_pages = pl.cdiv(n, rows)
    group = q_ref.shape[1]
    # float32 queries and pooled keys meet in float32 arithmetic
    precision = _HI if buf.dtype == jnp.float32 else None

    def for_pages(lo, hi, fn):
        def body(p, carry):
            fn(p)
            return carry
        jax.lax.fori_loop(lo, hi, body, 0)

    def live_pages(step):
        return jnp.clip(n_pages - step * ppb, 0, ppb)

    def copy(step, slot, p):
        return pltpu.make_async_copy(
            pool_hbm.at[tbl_ref[b, step * ppb + p]], buf.at[slot, p],
            sem.at[slot])

    def start(step, slot):
        def zero(p):
            buf[slot, p] = jnp.zeros(buf.shape[2:], buf.dtype)
        live = live_pages(step)
        for_pages(0, live, lambda p: copy(step, slot, p).start())
        for_pages(live, ppb, zero)

    r_ref[...] = jnp.zeros_like(r_ref)

    @pl.when(n_steps > 0)
    def _first():
        start(0, 0)

    def score(step, top):
        slot = step % 2

        @pl.when(step + 1 < n_steps)
        def _prefetch():
            start(step + 1, 1 - slot)

        for_pages(0, live_pages(step),
                  lambda p: copy(step, slot, p).wait())
        flat = buf.at[slot].reshape(tj * hkv, d)
        pack = 4 // buf.dtype.itemsize
        heads = [k for w in range(0, hkv, pack)
                 for k in _head_rows(flat, w, hkv, tj)]
        live = (step * tj + jax.lax.broadcasted_iota(jnp.int32, (1, tj), 1)
                < n)
        tops = []
        for g in range(hkv):
            sc = jax.lax.dot_general(
                q_ref[g], heads[g], _NT, precision=precision,
                preferred_element_type=jnp.float32) * scale
            sc = jnp.where(live, sc, bw.NEG_INF)
            s_ref[step, g] = sc
            tops.append(jnp.maximum(top[g], jnp.max(sc, axis=1,
                                                    keepdims=True)))
        return tuple(tops)

    top = jax.lax.fori_loop(
        0, n_steps, score,
        tuple(jnp.full((group, 1), bw.NEG_INF, jnp.float32)
              for _ in range(hkv)))

    def total(step, acc):
        return tuple(a + jnp.sum(jnp.exp(s_ref[step, g] - top[g]), axis=1,
                                 keepdims=True)
                     for g, a in enumerate(acc))
    sums = jax.lax.fori_loop(
        0, n_steps, total,
        tuple(jnp.zeros((group, 1), jnp.float32) for _ in range(hkv)))

    def emit(step, carry):
        for g in range(hkv):
            p = jnp.exp(s_ref[step, g] - top[g]) / sums[g]
            r_ref[step, pl.ds(g, 1), :] = jnp.sum(p, axis=0, keepdims=True)
        return carry
    jax.lax.fori_loop(0, n_steps, emit, 0)


@functools.partial(jax.jit, static_argnames=("sizes", "scale", "interpret"))
def decode_scores(q, pooled, block_table, t, *, sizes: Sizes, scale: float,
                  interpret: bool = False):
    """:func:`scores` for ONE query a row as a kernel: q [B, Hq, D], t [B]
    -> ``r`` [B, Hkv, J].  A row whose query is at or under ``dense_len``
    (it chooses nothing) scores nothing and reads zeros.  Jitted, so that
    a model's layers share one lowering."""
    b, hq, d = q.shape
    _, rows, hkv, _ = pooled.shape
    m = block_table.shape[1]
    ppb = min(_SELECT_PAGES, m)
    steps = -(-m // ppb)
    n = jnp.where(t + 1 > sizes.dense_len, pooled_exist(t, sizes), 0)
    table = jnp.pad(jnp.asarray(block_table, jnp.int32),
                    ((0, 0), (0, steps * ppb - m)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b,),
        in_specs=[pl.BlockSpec((None, hkv, hq // hkv, d),
                               lambda r, tbl, cnt: (r, 0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, steps, hkv, ppb * rows),
                               lambda r, tbl, cnt: (r, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, rows, hkv, d), pooled.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((steps, hkv, hq // hkv, ppb * rows), jnp.float32)])
    r = pl.pallas_call(
        functools.partial(_select_kernel, scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, steps, hkv, ppb * rows),
                                       jnp.float32),
        interpret=interpret, name="block_select",
    )(table, n.astype(jnp.int32),
      q.reshape(b, hkv, hq // hkv, d).astype(pooled.dtype), pooled)
    return jnp.swapaxes(r, 1, 2).reshape(b, hkv, -1)[..., :m * rows]


def top_members(x, k: int):
    """Which ``k`` of ``x`` [..., N] are its largest (non-negative, or -1
    where an entry cannot be chosen; at least ``k`` can), ties to the lower
    index: bool [..., N] with ``k`` True a row — ``top_k`` without the sort
    (the TPU's ``top_k`` sorts all N values a row, 60 ms of a 2,048-token
    chunk's 267 at N = 2,080: my chip run, PR 45).  The k-th largest value
    is found bit by bit (a non-negative float's bits order as an integer:
    31 counts) and ties at it are taken from the left."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    kth = jnp.zeros(x.shape[:-1] + (1,), jnp.int32)
    for bit in range(30, -1, -1):
        trial = kth | (1 << bit)
        enough = jnp.sum(bits >= trial, -1, keepdims=True) >= k
        kth = jnp.where(enough, trial, kth)
    above, at = bits > kth, bits == kth
    spare = k - jnp.sum(above, -1, keepdims=True)
    return above | (at & (jnp.cumsum(at, -1) <= spare))


def top_ids(x, k: int):
    """The indices of :func:`top_members` IN ASCENDING ORDER — what
    ``sort(top_k(x, k)[1])`` gives: the members' indices gathered by their
    rank."""
    member = top_members(x, k)
    rank = jnp.cumsum(member, -1) - 1
    ids = jnp.arange(x.shape[-1], dtype=jnp.int32)
    return jnp.sum(jnp.where(
        member[..., None] & (rank[..., None] == jnp.arange(k)), ids[:, None],
        0), axis=-2, dtype=jnp.int32)


def _free_scores(r, t, sizes: Sizes):
    """``R`` [..., Hkv, blocks] of ``r`` [..., Hkv, J] for queries at ``t``
    [...], -1.0 at the blocks a query does not choose among (the first
    ``init`` and everything from its window's first block on), and that
    first block ``[..., 1, 1]``."""
    n = sizes.block // sizes.stride
    if r.shape[-1] < sizes.width * n:       # a short table: no such keys
        r = jnp.pad(r, [(0, 0)] * (r.ndim - 1)
                    + [(0, sizes.width * n - r.shape[-1])])
    blocks = r.shape[-1] // n
    groups = r[..., :blocks * n].reshape(r.shape[:-1] + (blocks, n))
    before = jnp.pad(groups[..., :-1, -1],              # r at j = n b - 1
                     [(0, 0)] * (r.ndim - 1) + [(1, 0)])
    big_r = jnp.maximum(jnp.max(groups, -1), before)
    ids = jnp.arange(blocks, dtype=jnp.int32)
    first_window = (t // sizes.block - sizes.window // sizes.block
                    + 1)[..., None, None]
    free = (ids >= sizes.init) & (ids < first_window)
    return jnp.where(free, big_r, -1.0), first_window


def choose(r, t, sizes: Sizes):
    """``r`` [..., Hkv, J] and the queries' positions ``t`` [...] -> (blocks
    [..., Hkv, W] int32, ascending, ``W = sizes.width``; count [...]): the
    first ``count`` entries are what the query reads — every block up to
    its own at or under ``dense_len``, else the forced and the chosen —
    and the last of them is the query's own block.  Entries past ``count``
    are 0."""
    own = t // sizes.block                              # b_t
    wb = sizes.window // sizes.block
    free, first_window = _free_scores(r, t, sizes)
    best = top_ids(free, sizes.top)
    lead = r.shape[:-1]
    sparse = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(sizes.init, dtype=jnp.int32),
                         lead + (sizes.init,)),
        best,
        jnp.broadcast_to(first_window + jnp.arange(wb, dtype=jnp.int32),
                         lead + (wb,))], -1)
    sparse = jnp.pad(sparse, [(0, 0)] * (sparse.ndim - 1)
                     + [(0, sizes.width - sizes.read)])
    every = jnp.arange(sizes.width, dtype=jnp.int32)
    dense = t + 1 <= sizes.dense_len
    count = jnp.where(dense, own + 1, sizes.read)
    table = jnp.where(dense[..., None, None], every, sparse)
    table = jnp.where(every < count[..., None, None], table, 0)
    return table, count


def members(r, t, sizes: Sizes):
    """:func:`choose` as MEMBERSHIP: bool [..., Hkv, blocks] (``blocks`` the
    table's, ``J / (block / stride)``, at least ``sizes.width``), True at
    the blocks the query at ``t`` reads — the same set a (query, KV head)
    as the first ``count`` entries of :func:`choose`'s table, with no rank
    gather and no table: what a chunk's tile kernel masks by."""
    free, first_window = _free_scores(r, t, sizes)
    ids = jnp.arange(free.shape[-1], dtype=jnp.int32)
    own = (t // sizes.block)[..., None, None]
    chosen = (top_members(free, sizes.top) | (ids < sizes.init)
              | (ids >= first_window))
    dense = (t + 1 <= sizes.dense_len)[..., None, None]
    return (chosen | dense) & (ids <= own)


def pack_members(member, per: int):
    """bool [..., blocks] -> int32 [..., ceil(blocks / per)]: bit ``i`` of
    entry ``u`` is block ``per * u + i`` (``per`` <= 16, so that an entry
    is a small non-negative integer)."""
    if not 1 <= per <= 16:
        raise ValueError(f"{per} blocks do not pack into a small integer")
    blocks = member.shape[-1]
    member = jnp.pad(member, [(0, 0)] * (member.ndim - 1)
                     + [(0, -blocks % per)])
    units = member.reshape(member.shape[:-1] + (-1, per))
    return jnp.sum(units.astype(jnp.int32)
                   << jnp.arange(per, dtype=jnp.int32), -1, dtype=jnp.int32)


def chunk_members(q, pooled, block_table, t, sizes: Sizes, scale: float,
                  per: int, tile: int = 256):
    """A chunk's choice as packed membership: q [B, S, Hq, D] at positions
    ``t`` [B, S] -> int32 [B, Hkv, S, units] (:func:`scores`,
    :func:`members`, :func:`pack_members` at ``per`` blocks a unit), the
    queries ``tile`` at a time so that the float32 ``r`` of a tile, not of
    the chunk, is what exists (``[S, Hkv, J]`` is 136e6 B at 2,048 tokens
    over 133,120 positions)."""
    b, s, hq, d = q.shape

    def some(xs):                   # [B, T, Hq, D], [B, T]
        q_, t_ = xs
        r = scores(q_, pooled, block_table, t_, sizes, scale, tile=tile)
        return jnp.swapaxes(pack_members(members(r, t_, sizes), per), 1, 2)
    if s <= tile or s % tile:
        return some((q, t))
    tiles = s // tile
    bits = jax.lax.map(
        some, (jnp.moveaxis(q.reshape(b, tiles, tile, hq, d), 1, 0),
               jnp.moveaxis(t.reshape(b, tiles, tile), 1, 0)))
    # [tiles, B, Hkv, T, U] -> [B, Hkv, S, U]
    return jnp.moveaxis(bits, 0, 2).reshape(b, bits.shape[2], s, -1)


def plain_mask(q, k, sizes: Sizes, scale: float):
    """The choice with no cache, as a mask [B, S, Hkv, S] on plain
    attention: q [B, S, Hq, D] and k [B, S, Hkv, D] of one whole sequence
    from position 0 (the model outside decode mode)."""
    b, s, hkv, d = k.shape
    n = sizes.block // sizes.stride
    blocks = -(-s // sizes.block)
    padded = jnp.pad(k.astype(jnp.float32),
                     ((0, 0), (0, (blocks * n + 1) * sizes.stride - s),
                      (0, 0), (0, 0)))
    halves = jnp.sum(padded.reshape(b, blocks * n + 1, sizes.stride, hkv,
                                    d), axis=2)
    pooled = ((halves[:, :-1] + halves[:, 1:]) / sizes.pool)[:, None]
    t = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    r = scores(q, pooled.reshape(b, blocks * n, hkv, d),
               jnp.arange(b, dtype=jnp.int32)[:, None], t, sizes, scale)
    table, count = choose(r, t, sizes)
    listed = jnp.arange(sizes.width, dtype=jnp.int32) < count[..., None,
                                                               None]
    read = jnp.any(jax.nn.one_hot(table, max(blocks, sizes.width),
                                  dtype=bool) & listed[..., None], axis=-2)
    read = read[..., jnp.arange(s, dtype=jnp.int32) // sizes.block]
    return read & (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
                   )[None, :, None, :]


def physical(blocks, block_table, page: int, block: int):
    """Logical blocks [B, ..., W] of rows with tables ``block_table``
    [B, M] -> their ids in a pool seen as ``[P * page / block, block, ...]``
    (a free reshape: a page's blocks are contiguous)."""
    per = page // block
    lead = blocks.shape[1:]
    flat = blocks.reshape(blocks.shape[0], -1)
    pages = jnp.take_along_axis(
        block_table, jnp.minimum(flat // per, block_table.shape[1] - 1), 1)
    return (pages * per + flat % per).reshape((blocks.shape[0],) + lead)
