"""A lightning indexer's CHOICE: which ``k`` TOKENS of a row's cache a query
attends, decided through index keys of the layer's own.

A layer keeps, beside its cache rows, a pool of INDEX KEYS ``[P, page, D]``:
one row ``k_s`` a token.  A query at position ``t`` carries ``H`` index
queries ``q_tj`` [D] and as many weights ``w_tj``:

    I(t, s) = sum_j w_tj * relu(q_tj . k_s)        for s <= t

and attends the ``k`` positions with the largest ``I`` — every ``s <= t``
while ``t < k``; ties go to the lower position.  The products are bfloat16
x bfloat16 summed in float32 (what the pool holds against what the query
is rounded to); the weighting and the sum over heads are float32.

The k-th largest of up to 65,536 scores is found WITHOUT a sort
(``lax.top_k`` lowers to a sort a query on the TPU): a float32's bits,
with the magnitude of a negative flipped, order as a signed integer, and
the largest integer that at least ``k`` scores reach is built bit by bit —
32 counting passes over scores that never leave VMEM; a second
bisection, over positions, takes the ties at that value from the left.

The choice is kept as MEMBERSHIP, not as a list of ids: the TPU lays a
bfloat16 pool out in tiles of 16 rows, so no DMA can fetch ONE token's row
and a gather a query would move the 16 rows around each chosen one —
2,048 x 16 = 32,768 rows, a whole context of that length — where a stream
of the row's pages under a mask moves every row once a tile of queries
(``paged_attention.latent_sparse_*``).

``scores``          ``I`` in XLA, ``-inf`` where ``s > t``: the oracle;
``members``         ``I`` -> bool [.., L], the bisection in XLA;
``chunk_select``    a chunk's queries -> tiled membership, int [B, tiles,
                    blocks, queries a tile, ``MEMBER_BLOCK``], kernel
                    ``index_select``: a tile of queries streams the row's
                    index-key pages once, its scores stay in VMEM;
``decode_select``   the same for one query a row;
``rows_chosen``     how many rows a query's membership names, counted
                    from the membership itself (either form).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtf_tpu.ops.paged_attention import gather_pages, page_stream, tile_rows

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # [r, d] x [k, d] -> [r, k]
_INT_MIN = -2 ** 31

# keys a block of the tiled membership (lanes of one store), and the queries
# a tile: 32 is an int8 tile's sublanes (a chunk), 8 an int32 tile's (a
# decode step's one query a row rides in a tile of its own)
MEMBER_BLOCK = 512
CHUNK_QUERIES = 32
DECODE_QUERIES = 8
_VMEM_BYTES = 64 * 2 ** 20
# keys a step of a decode step's kernel streams: a row's one query makes 49
# passes over its scores, a loop trip a step each, and at 512 keys a step
# the trips were the kernel's time (16 rows at 32k: 5.6 ms a layer, my chip
# run, PR 49).  The membership covers whole steps of it, which are whole
# steps of ``latent_sparse_*`` too
_DECODE_BLOCK = 4096
# ... and a chunk's: 1,024 keys a step against 512 took a 2,048-query
# chunk's choice at 32k from 18.5 to 11.5 ms a layer (my chip run, PR 49)
_CHUNK_BLOCK = 1024


def scores(q, w, keys, t):
    """``I`` [B, S, L] float32, ``-inf`` where a key is not visible: q
    [B, S, H, D], w [B, S, H] float32, keys [B, L, D] (a row's index keys
    in logical order: ``gather_pages`` of the pool), t [B, S] the queries'
    positions.  q and keys meet as
    bfloat16; every sum is float32."""
    sc = jnp.einsum("bshd,bkd->bshk", q.astype(jnp.bfloat16).astype(
        jnp.float32), keys.astype(jnp.bfloat16).astype(jnp.float32),
        precision=_HI)
    total = jnp.sum(jnp.maximum(sc, 0.0) * w.astype(jnp.float32)[..., None],
                    axis=2)
    total = jnp.where(total == 0.0, 0.0, total)          # no -0.0
    seen = jnp.arange(keys.shape[1], dtype=jnp.int32) <= t[..., None]
    return jnp.where(seen, total, -jnp.inf)


def _image(x):
    """float32 -> int32 that orders as the floats do."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(bits >= 0, bits, bits ^ 0x7FFFFFFF)


def members(score, k: int):
    """bool [.., L]: the ``k`` largest of ``score`` [.., L] among its
    visible entries (``-inf``: not visible), all of them where there are
    ``k`` or fewer, ties to the lower position — the kernel's bisection in
    XLA (31 + 1 counts over values, then one cumulative count over ties)."""
    seen = score > -jnp.inf
    img = jnp.where(seen, _image(score), _INT_MIN)

    def reach(trial):
        return jnp.sum(img >= trial, -1, keepdims=True) >= k
    kth = jnp.where(reach(0), 0, _INT_MIN).astype(jnp.int32)
    for bit in range(30, -1, -1):
        trial = kth | (1 << bit)
        kth = jnp.where(reach(trial), trial, kth)
    above, at = img > kth, img == kth
    spare = k - jnp.sum(above, -1, keepdims=True)
    return seen & (above | (at & (jnp.cumsum(at, -1) <= spare)))


def member_blocks(m_pages: int, page: int) -> int:
    """Blocks of the tiled membership of a table ``m_pages`` wide: whole
    steps of these kernels and of the attention's."""
    cover = max(_key_block(page), _DECODE_BLOCK)
    return -(-m_pages * page // cover) * (cover // MEMBER_BLOCK)


def _key_block(page: int) -> int:
    """Keys a step of the kernels streams: whole pages, whole blocks of
    the membership."""
    if MEMBER_BLOCK % page and page % MEMBER_BLOCK:
        raise ValueError(f"pages of {page} tokens and blocks of "
                         f"{MEMBER_BLOCK} keys do not divide each other")
    return max(page, MEMBER_BLOCK)


def rows_chosen(member, t):
    """int32 [B, S]: the rows each query attends by ``member``, COUNTED
    from it — bool [B, S, L] (:func:`members`) or the kernels' tiled form
    (a chunk's ``S`` whole tiles; a decode step's one query a row, the
    tile's first) — for queries at positions ``t`` [B, S].  A tiled block
    past a tile's last visible key was never written: only keys at ``<= t``
    count."""
    if member.ndim == 3:
        return jnp.sum(member, -1, dtype=jnp.int32)
    b, g, blocks, _, mb = member.shape
    mine = member[:, :, :, :t.shape[1] // g]
    kpos = (jnp.arange(blocks, dtype=jnp.int32)[:, None, None] * mb
            + jnp.arange(mb, dtype=jnp.int32))
    named = (mine != 0) & (kpos <= t.reshape(b, g, 1, -1, 1))
    return jnp.sum(named, (2, 4), dtype=jnp.int32).reshape(t.shape)


def _member_dtype(tile: int):
    return jnp.int8 if tile % 32 == 0 else jnp.int32


def _select_kernel(tbl_ref, idx_ref, q_ref, w_ref, pool_hbm, m_ref, buf, sem,
                   img_ref, *, k: int, tile: int, heads: int, chunk: bool):
    """Grid (B, tiles): the ``tile`` queries of a grid point (a chunk's at
    positions ``idx + g * tile + i``; a decode step's all at ``idx``, the
    first of them the row's own) stream the row's index-key pages, ``ppb`` a
    step and the next step's in flight, score them, and keep the scores'
    integer images in ``img_ref`` [steps, tile, T]; then the k-th largest a
    query is built bit by bit, its ties are cut by position, and the
    membership goes out a block of ``MEMBER_BLOCK`` keys at a time:
    ``m_ref`` [blocks, tile, MEMBER_BLOCK].  Blocks past the tile's last
    visible key are not written.

    ``q_ref`` [heads * tile, D] and ``w_ref`` [heads * tile, 1] hold head
    ``j`` of query ``i`` in row ``j * tile + i``."""
    b, g = pl.program_id(0), pl.program_id(1)
    _, ppb, page, d = buf.shape
    t = ppb * page
    m_pages = tbl_ref.shape[1]
    idx = idx_ref[b]
    row = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    qpos = idx + (g * tile + row if chunk else 0 * row)
    n_live = jnp.minimum(idx + ((g + 1) * tile if chunk else 1),
                         m_pages * page)
    n_steps = pl.cdiv(n_live, t)
    start, wait = page_stream(tbl_ref, b, pool_hbm, buf, sem,
                              pl.cdiv(n_live, page))
    start(0, 0)

    def kpos(step):
        return step * t + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)

    def score(step, carry):
        slot = step % 2

        @pl.when(step + 1 < n_steps)
        def _prefetch():
            start(step + 1, 1 - slot)

        wait(step, slot)
        keys = buf.at[slot].reshape(t, d)[...]
        sc = jax.lax.dot_general(q_ref[...].astype(jnp.bfloat16),
                                 keys.astype(jnp.bfloat16), _NT,
                                 preferred_element_type=jnp.float32)
        sc = jnp.maximum(sc, 0.0) * w_ref[...]
        total = sc[0:tile]
        for j in range(1, heads):
            total = total + sc[j * tile:(j + 1) * tile]
        total = jnp.where(total == 0.0, 0.0, total)
        img_ref[step] = jnp.where(kpos(step) <= qpos, _image(total),
                                  _INT_MIN)
        return carry

    jax.lax.fori_loop(0, n_steps, score, 0)

    def count(test):
        """[tile, 1]: how many of a query's images pass ``test(img,
        positions)``."""
        def body(step, acc):
            return acc + jnp.sum(test(img_ref[step], kpos(step)).astype(
                jnp.int32), axis=1, keepdims=True)
        return jax.lax.fori_loop(0, n_steps, body,
                                 jnp.zeros((tile, 1), jnp.int32))

    kth = jnp.where(count(lambda img, _: img >= 0) >= k, 0,
                    _INT_MIN).astype(jnp.int32)

    def value_bit(i, kth):
        trial = kth | jnp.left_shift(1, 30 - i)
        return jnp.where(count(lambda img, _: img >= trial) >= k, trial, kth)
    kth = jax.lax.fori_loop(0, 31, value_bit, kth)
    reach = count(lambda img, _: img >= kth)
    # the ties at ``kth`` from the left: the largest position with fewer
    # than ``spare`` of them before it is the last one taken.  Scores are
    # sums of float32 products: where no query of the tile has more ties
    # than places left (``reach == k``, or fewer than ``k`` keys seen),
    # every tie is taken and the second bisection is skipped
    spare = k - count(lambda img, _: img > kth)
    bits = max(m_pages * page - 1, 1).bit_length()

    def position_bit(i, cut):
        trial = cut | jnp.left_shift(1, bits - 1 - i)
        before = count(lambda img, pos: (img == kth) & (pos < trial))
        return jnp.where(before < spare, trial, cut)

    def cut_ties():
        return jax.lax.fori_loop(0, bits, position_bit,
                                 jnp.zeros((tile, 1), jnp.int32))
    # (a decode step's padding queries tie everywhere: nobody's)
    mine = (kth != _INT_MIN) & (row < (tile if chunk else 1))
    cut = jax.lax.cond(
        jnp.max(jnp.where(mine, reach - k, 0)) > 0, cut_ties,
        lambda: jnp.full((tile, 1), 2 ** 31 - 1, jnp.int32))

    def emit(step, carry):
        img, pos = img_ref[step], kpos(step)
        member = (img != _INT_MIN) & ((img > kth)
                                      | ((img == kth) & (pos <= cut)))
        member = member.astype(jnp.int32)
        per = t // MEMBER_BLOCK
        for piece in range(per):
            m_ref[step * per + piece] = member[
                :, piece * MEMBER_BLOCK:(piece + 1) * MEMBER_BLOCK
            ].astype(m_ref.dtype)
        return carry
    jax.lax.fori_loop(0, n_steps, emit, 0)


@functools.partial(jax.jit, static_argnames=("k", "tile", "chunk",
                                             "interpret"))
def _select(q, w, pool, block_table, index, *, k: int, tile: int,
            chunk: bool, interpret: bool):
    """q [B, G, H * tile, D], w [B, G, H * tile, 1] -> tiled membership
    [B, G, blocks, tile, MEMBER_BLOCK].  Jitted, so that a model's layers
    share one lowering."""
    b, g, rows, d = q.shape
    page = pool.shape[1]
    m_pages = block_table.shape[1]
    t = max(_key_block(page), _CHUNK_BLOCK if chunk else _DECODE_BLOCK)
    ppb = t // page
    steps = -(-m_pages // ppb)
    blocks = member_blocks(m_pages, page)
    table = jnp.pad(jnp.asarray(block_table, jnp.int32),
                    ((0, 0), (0, steps * ppb - m_pages)))

    def spec(lanes):
        return pl.BlockSpec((None, None, rows, lanes),
                            lambda b_, g_, tbl, idx: (b_, g_, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, g),
        in_specs=[spec(d), spec(1), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (None, None, blocks, tile, MEMBER_BLOCK),
            lambda b_, g_, tbl, idx: (b_, g_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, d), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((steps, tile, t), jnp.int32)])
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k, tile=tile,
                          heads=rows // tile, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b, g, blocks, tile, MEMBER_BLOCK), _member_dtype(tile)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret, name="index_select",
    )(table, jnp.asarray(index, jnp.int32), q.astype(pool.dtype),
      w.astype(jnp.float32), pool)


def chunk_select(q, w, pool, block_table, index, *, k: int,
                 interpret: bool = False):
    """A chunk's choice as tiled membership: q [B, S, H, D] and w [B, S, H]
    of the queries at positions ``index[b] + i``, ``S`` whole tiles of
    ``CHUNK_QUERIES``; the chunk's own index keys already in ``pool``."""
    tile = CHUNK_QUERIES
    if q.shape[1] % tile:
        raise ValueError(f"a chunk of {q.shape[1]} queries is not whole "
                         f"tiles of {tile}")
    return _select(tile_rows(q, tile), tile_rows(w[..., None], tile), pool,
                   block_table, index, k=k, tile=tile, chunk=True,
                   interpret=interpret)


def decode_select(q, w, pool, block_table, t, *, k: int,
                  interpret: bool = False):
    """One query a row: q [B, H, D], w [B, H], t [B] -> tiled membership
    [B, 1, blocks, DECODE_QUERIES, MEMBER_BLOCK] whose first query is the
    row's (the others are zeros' and are nobody's)."""
    tile = DECODE_QUERIES
    pad = ((0, 0), (0, tile - 1), (0, 0), (0, 0))
    return _select(
        tile_rows(jnp.pad(q[:, None], pad), tile),
        tile_rows(jnp.pad(w[:, None, :, None], pad), tile), pool,
        block_table, t, k=k, tile=tile, chunk=False, interpret=interpret)
