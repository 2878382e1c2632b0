"""Paged KV-cache primitives: page-pool writes, block-table gathers,
and gather-attention for serving decode.

A serving cache of one [num_slots, max_seq_len, H, Dh] slab per layer
would reserve worst-case HBM for every slot: a 4-token request would
hold the same memory as a max-length one.  The paged layout — the only
one the serving stack builds — is the vLLM/PagedAttention discipline
adapted to fixed-shape XLA:

  page pool    — one [num_pages, page_size, H, Dh] array per layer per
                 K/V, shared by every slot.  Token at logical position
                 ``p`` of a slot lives at pool row
                 ``block_table[slot, p // page_size]``, offset
                 ``p % page_size``.
  block table  — [B, max_pages_per_slot] int32 page ids, maintained
                 host-side by the serving engine's allocator.  Entries
                 for unallocated tail pages are 0 — see the scratch-page
                 invariant below.
  scratch page — pool page 0 is never handed to a request.  Inactive
                 rows of a fixed-shape decode batch still execute the
                 write (XLA has no dynamic batch), and their garbage
                 must land somewhere that no live sequence reads:
                 the engine passes an all-zeros block-table row for
                 such rows, steering both the write and the (ignored)
                 gather at page 0.

Everything here is shape-static: the gather always materializes the
full ``max_pages_per_slot * page_size`` logical window and masks, so
the decode step compiles exactly once regardless of pool occupancy.

``cached_attention`` (dense attention against a fixed-capacity KV
window, f32 softmax) also lives here — it is the score/softmax math
of the gather path and of the routed decoder's teacher-forced forward.

Two formulations of attention-over-pages coexist:

  gather (``paged_attention``)      — materialize the gathered window,
      mask, dense softmax.  Portable, the CPU-default oracle.  Pays a
      gathered copy of the window plus, for prefill chunks, a
      host-side STATIC window trim (one compile per window).
  kernel (``paged_flash_decode``)   — a Pallas kernel that streams, per
      row, only the pages that row HAS.  The pools stay in HBM exactly
      as stored, ``[P, page, H, Dh]``: a page with all its heads is one
      contiguous region (64 KB at 16 × 128 bf16), copied whole by a
      manual DMA whose source page id comes from the scalar-prefetched
      block table.  The grid is the rows; inside a grid point a loop of
      ``ceil((index + S) / (pages_per_block · page))`` trips copies
      blocks of pages into a double buffer (the next block's copies in
      flight during this block's math) and folds each block into the
      online-softmax carry (ops.blockwise math, the same rule the flash
      kernels use).  A page past the row's length costs nothing — no
      grid step, no DMA, no predicate — so the work is the live KV, not
      the table's capacity, and one compile covers every chunk index
      where the gather path needed one per static window.  No
      transposed, gathered or relaid-out copy of a pool exists.

      A block in the stored order is a ``[T · H, Dh]`` matrix whose row
      ``t · H + h`` is token t of head h.  Where a grid point holds few
      query rows (a decode step) they score against the whole matrix
      in one pair of matmuls, the other heads' columns masked; where it
      holds many (a chunk) each head's ``[T, Dh]`` rows are pulled out
      with a sublane-strided load and the heads take turns.  Which of
      the two, how many pages make a block, and how many heads share a
      grid point, follows from the static shapes (``_plan``).

      It serves every decode step, every chunk over ``[k | v]`` rows or
      under a window, and a chunk of whole heads or of few rows a KV
      head.  A CONTINUATION chunk of grouped heads whose rows a KV head
      fill a tile of the flash forward (``chunk_walks``: there the
      kernel would halve a head's rows into blocks that each stream the
      row's pages again) attends through a WALK instead
      (``paged_chunk_attention``): the chunk against itself one causal
      ``flash_forward``, the pages under its start gathered by the row's
      table ``EXPAND_KEYS`` keys a step, laid heads-major and met by the
      same kernel through its carried ``(o, lse)`` — a page is read once
      a 1,024-row tile from a contiguous copy.  Those calls are named
      ``paged_flash_decode_chunk``: on the device's timeline the paged
      kernel's work, whichever kernel does it.

A third kernel with a body of its own (``paged_flash_decode_tiles``,
behind ``paged_tile_attention``) serves the one caller whose CHUNK reads
a subset of the row's blocks, the subset a (query, KV head)'s own
(block-sparse attention): a tile of queries streams the blocks its
queries read once and each query masks what it did not choose.

Over a LATENT pool (one row ``[c_kv | k_rope | 0]`` a token, every query
head over it) both take absorbed queries; a prefill chunk long enough to
repay it (``latent_expands``) attends EXPANDED instead
(``latent_chunk_attention``): its own rows and, in a walk whose length
follows the chunk's start, the pages under it go through ``kv_b`` once and
meet the queries in ``ops.flash_attention.flash_forward`` — under each
query's own choice of rows where the layer has one (``member``: the form a
chunk of chosen-row latent attention takes where it is long enough;
``latent_sparse_chunk`` and ``latent_sparse_decode`` are the absorbed
forms of that attention, a short chunk's and a decode step's).  Both walks
are ONE loop (``_walk_under``); what a step's gathered pages become —
expanded through ``kv_b``, or laid heads-major — is the caller's.

``paged_attention_auto`` dispatches between them: the kernel by default
on TPU, the gather oracle elsewhere; ``use_pallas="interpret"`` runs
the kernel through the Pallas interpreter on CPU (how tier-1 pins
kernel ≡ oracle).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtf_tpu.ops import blockwise as bw
from dtf_tpu.ops.flash_attention import flash_forward


def cached_attention(q, k, v, mask):
    """Dense attention against a fixed-size KV window.

    q [B, S, H, Dh] (S = the chunk being decoded), k/v [B, L, H, Dh]
    (L = the window capacity), mask [B, S, L] True where the query may
    attend.  Scores/softmax run in f32 (the flash kernels' accumulator
    precision); masked positions get a large negative score, and the
    output is cast back to q's dtype.  At decode shapes (S small, L
    fixed) the [S, L] score tile is small — no flash kernel needed."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return o.astype(q.dtype)


def expand_kv_heads(k, v, q_heads: int):
    """K and V [B, L, Hkv, Dh] with every KV head repeated for the
    ``q_heads // Hkv`` query heads that share it (grouped-query heads:
    query head ``i`` reads KV head ``i // group``) — for the attention
    formulations that want one KV head a query head.  Unchanged where
    the counts are equal."""
    group = q_heads // k.shape[2]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def write_pages(pool, new, block_table, index, page_aligned: bool = False):
    """Scatter a [B, S, H, Dh] chunk of K or V into the page pool (or a
    [B, S, W] chunk of latent rows into a ``[P, page_size, W]`` pool).

    ``pool`` [P, page_size, H, Dh]; ``block_table`` [B, M] int32 page
    ids; ``index`` [B] int32 — the chunk's starting logical position
    per row (token i of row b lands at logical position index[b] + i).

    ``page_aligned`` (static) promises index % page_size == 0 and
    S % page_size == 0 for every row — the prefill-chunk case by
    engine construction.  The write then scatters WHOLE pages
    (S/page_size contiguous [page_size, H, Dh] blocks per row) instead
    of S individual token rows: XLA lowers the page-granular scatter to
    block memcpys where the token-granular form degenerates to
    row-at-a-time copies.  Decode steps (S = 1, arbitrary offset) take
    the token path.

    Positions past the block table's logical capacity (M * page_size)
    are clamped to the last logical slot; the engine's invariants make
    such writes garbage-onto-garbage (a padded prefill tail), never a
    live-token overwrite that the mask could later admit unwritten.
    Rows whose block-table entries are all 0 write into the scratch
    page (see module docstring)."""
    num_pages, page_size, *row = pool.shape
    b, s = new.shape[:2]
    capacity = block_table.shape[1] * page_size
    if page_aligned:
        n_pages = s // page_size
        pstart = index // page_size                          # [B]
        pidx = jnp.minimum(
            pstart[:, None] + jnp.arange(n_pages, dtype=jnp.int32)[None, :],
            block_table.shape[1] - 1)
        page = jnp.take_along_axis(block_table, pidx, axis=1)  # [B, n]
        pages = new.reshape(b * n_pages, page_size, *row)
        if len(row) == 2 and row[0] * pool.dtype.itemsize < 32:
            # fewer heads than a (sublane, lane) tile holds (4 bf16 KV
            # heads under grouped queries): XLA lays the scatter out
            # heads-major and then rewrites the WHOLE pool twice a layer
            # to hand the kernel its stored layout back (23 of a 43 ms
            # chunk, v5e, 12 layers of [2049, 64, 4, 128]).  Whole pages
            # written in place, one by one, keep the pool where it is
            for i, pid in enumerate(page.reshape(-1)):
                pool = jax.lax.dynamic_update_slice(
                    pool, pages[i][None], (pid, 0, 0, 0))
            return pool
        return pool.at[page.reshape(-1)].set(pages)
    pos = index[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    pos = jnp.minimum(pos, capacity - 1)                     # [B, S]
    page = jnp.take_along_axis(block_table, pos // page_size, axis=1)
    flat = page * page_size + pos % page_size                # [B, S]
    pool_flat = pool.reshape(num_pages * page_size, *row)
    pool_flat = pool_flat.at[flat.reshape(-1)].set(
        new.reshape(b * s, *row))
    return pool_flat.reshape(pool.shape)


def gather_pages(pool, block_table):
    """Gather each row's full logical KV window from the pool.

    ``pool`` [P, page_size, H, Dh], ``block_table`` [B, M] →
    [B, M * page_size, H, Dh], ordered by logical position (page 0 of
    the row first).  PAGE-granular: the gather moves M whole
    [page_size, H, Dh] blocks per row (contiguous memcpys under XLA),
    never individual tokens.  Unallocated entries gather the scratch
    page — callers mask those positions out (they are always ≥ the
    row's current length)."""
    page_size = pool.shape[1]
    b, m = block_table.shape
    return pool[block_table].reshape(b, m * page_size, *pool.shape[2:])


def paged_attention(q, pool_k, pool_v, block_table, index, *, window=None):
    """Attention of a chunk of queries over a slot's paged KV history.

    q [B, S, H, Dh] — S new queries per row, the row's global positions
    being ``index[b] + i``; pool_k/pool_v [P, page_size, H, Dh];
    block_table [B, M]; index [B] int32.  The chunk's own K/V must
    already be written into the pool (write-then-attend), so query i
    sees logical positions j <= index + i: the just-written chunk
    causally, the prefix fully, and never the unwritten tail (masked).

    Grouped-query heads: q may carry ``G`` times the pools' heads; query
    head ``i`` reads KV head ``i // G``.  ``window`` (static; None = all
    of the history): query at position ``p`` sees keys ``p - window < j
    <= p``, its own position counted.

    ``pool_v`` None: ``pool_k`` [P, page_size, H, 2 * Dh] holds K and V of
    a head in ONE row, ``[k | v]`` (heads exactly half a lane tile wide:
    the routed decoder's grouped-query layers)."""
    k = gather_pages(pool_k, block_table)   # [B, L, H, Dh]
    if pool_v is None:
        k, v = k[..., :q.shape[-1]], k[..., q.shape[-1]:]
    else:
        v = gather_pages(pool_v, block_table)
    k, v = expand_kv_heads(k, v, q.shape[2])
    return cached_attention(q, k, v, _seen(q, k, index, window))


def _seen(q, k, index, window=None):
    """mask [B, S, L]: query ``i`` of row ``b`` (position ``index[b] +
    i``) sees key position ``j`` iff ``j <= index[b] + i`` (and, under a
    window, ``j > index[b] + i - window``)."""
    jpos = jnp.arange(k.shape[1], dtype=jnp.int32)[None, None, :]
    qpos = (index[:, None, None]
            + jnp.arange(q.shape[1], dtype=jnp.int32)[None, :, None])
    mask = jpos <= qpos
    if window is not None:
        mask &= jpos > qpos - window
    return mask


def latent_paged_attention(q, pool, block_table, index, *, value_lanes,
                           scale):
    """The gather oracle over a LATENT pool: one row of ``W`` values a
    token, shared by every query head, whose first ``value_lanes`` lanes
    are also the value.

    q [B, S, H, W] (the absorbed queries: each head's query already
    carried into the row's space); pool [P, page_size, W]; block_table
    [B, M]; index [B].  ``score_h(i, j) = q_h,i . row_j * scale``, causal
    over the whole history, f32 softmax; returns
    ``sum_j p_h(i, j) row_j[:value_lanes]`` as [B, S, H, value_lanes] in
    q's dtype.  Same write-then-attend contract as
    :func:`paged_attention`."""
    rows = gather_pages(pool, block_table).astype(jnp.float32)  # [B, L, W]
    scores = jnp.einsum("bqhw,bkw->bhqk", q.astype(jnp.float32),
                        rows) * scale
    scores = jnp.where(_seen(q, rows, index)[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkv->bqhv", probs, rows[..., :value_lanes])
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# A chunk over a latent pool, EXPANDED
# ---------------------------------------------------------------------------

# cached rows a step of the expanded chunk's walk over its prefix gathers
# and carries through ``kv_b`` at once (whole pages; a row's whole table
# where that is shorter)
EXPAND_KEYS = 2048


def latent_expands(s: int, heads: int, lanes: int, rank: int, nope: int,
                   rope: int, v: int) -> bool:
    """Whether a call of ``s`` queries a row over a latent cache takes the
    EXPANDED form, by its multiply-adds a visible key: absorbed, every
    (query, head) meets the stored row over ``lanes`` and its value over
    ``rank``; expanded, the key goes through ``kv_b`` once (``rank * (nope
    + v)`` a head) and a (query, head) meets it at ``nope + rope + v``.
    One query a row is always absorbed."""
    return (s * heads * (lanes + rank - (nope + rope + v))
            > rank * heads * (nope + v))


def _expand_pages(page_size: int, m_pages: int) -> int:
    return max(1, min(EXPAND_KEYS // page_size, m_pages))


def latent_rows_expanded(index, s: int, page_size: int, m_pages: int):
    """The rows :func:`latent_chunk_attention` carries through ``kv_b`` for
    a row whose chunk of ``s`` starts at ``index``: the cached ones in
    whole steps of its walk, and the chunk's own — of
    :func:`paged_chunk_attention`, the keys its walk's steps gathered and
    the chunk's own."""
    t = _expand_pages(page_size, m_pages) * page_size
    return -(-index // t) * t + s


def _walk_under(index, block_table, pools, ppb: int, carry, attend):
    """The walk of a chunk's queries over the keys UNDER the chunk's start:
    ``carry`` — ``(o, lse)`` of the chunk against itself — continued, ``ppb``
    pages a step, over each row's pages below ``index`` [B].  A step
    gathers its pages of every pool of ``pools`` by the row's table
    ``block_table`` [B, M], each as ``[B, ppb * page, ...]`` in logical
    order, and hands them to ``attend(rows, at, carry, kv_len)``: ``at`` the
    first key's position, ``kv_len`` [B] how many of the step's keys lie
    under the row's ``index`` (all others are not the row's, or not yet:
    masked, whatever the pages hold); it returns the new ``(o, lse)`` —
    what a step's gathered pages BECOME (expanded through ``kv_b``; laid
    heads-major) is the caller's.  The trip count follows the traced
    ``index``, never the table's width: one compile a chunk length, work in
    proportion to what the chunk sees."""
    b, m_pages = block_table.shape
    t = ppb * pools[0].shape[1]
    table = jnp.pad(block_table, ((0, 0), (0, -m_pages % ppb)))

    def step(i, carry):
        pages = jax.lax.dynamic_slice_in_dim(table, i * ppb, ppb, axis=1)
        rows = tuple(p[pages].reshape(b, t, *p.shape[2:]) for p in pools)
        return tuple(attend(rows, i * t, carry,
                            jnp.clip(index - i * t, 0, t)))
    return jax.lax.fori_loop(0, jnp.max(-(-index // t)), step, tuple(carry))


def rope_in_head(nope: int, rope: int) -> bool:
    """Whether an expanded key carries the rotary key IN each head's row
    (one score product over ``nope + rope`` lanes) and not as a second
    product all heads share, padded to a lane tile: where the MXU's
    128-lane passes over ``nope + rope`` are those over ``nope`` alone, the
    second product is a whole pass more and the copy a head costs nothing
    (192 + 64: 512 lane-products a (query, key, head) with 256 of values
    against 640; 128 + 64 keeps the shared product).  By the sweep
    (docs/pr56_latent_sparse_expanded_sweep.jsonl: 2,048 queries x 64
    heads under a membership, v5e, ms a layer at 8k / 32k / 64k keys — in
    the head's row 9.63 / 36.92 / 73.38, shared 10.83 / 41.71 / 82.74)."""
    return -(-(nope + rope) // 128) == -(-nope // 128)


def latent_chunk_attention(q, rows, w_kvb, pool, block_table, index, *,
                           rank: int, nope: int, scale: float,
                           member=None, use_pallas=None):
    """A chunk's attention over a latent pool with its keys EXPANDED: the
    same product as :func:`latent_paged_attention` over absorbed queries,
    taken the other way round — where :func:`latent_expands`.

    q [B, S, H, nope + rope] (each head's query as projected, rotated; NOT
    absorbed); rows [B, S, W] the chunk's own cache rows ``[c_kv | k_rope |
    0]``, already written to ``pool`` [P, page, W] (write-then-attend;
    they are attended from here and not read back); w_kvb [rank, H, nope +
    Dv]; block_table [B, M]; index [B] the chunk's first position, a whole
    number of pages.  Returns [B, S, H, Dv] in q's dtype.

    The chunk against ITSELF is one causal :func:`flash_forward`.  The
    keys under ``index`` — every query sees all of them — are walked in
    steps of ``EXPAND_KEYS``: a step gathers whole pages by the row's
    table, expands them and continues the same online softmax unmasked
    (``kv_len``: the step ``index`` cuts).  The walk's length follows the
    traced ``index``, never the table's: one compile a chunk length, work
    in proportion to what the chunk sees.

    ``member`` (non-zero: attended): the rows each query CHOSE
    (:func:`latent_sparse_attention`'s product) — every call of the walk
    then also takes its keys' part of it, and the kernels run under the
    name ``latent_sparse_chunk_expanded``.  Either a row a query, [B, S, L]
    (``L`` the table's keys or more, by logical position), or the kernels'
    tiled form [B, S / tile, blocks, tile, member_block]
    (``index_select.chunk_select``), which a step of whole blocks reads as
    it lies: only the chunk's own keys, which start at a page and not at a
    block, are laid out a row a query."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    return _latent_chunk_walk(
        q, rows, w_kvb, pool, block_table, index, member, rank=rank,
        nope=nope, scale=scale, use_pallas=use_pallas,
        ppb=_expand_pages(pool.shape[1], block_table.shape[1]),
        in_head=rope_in_head(nope, q.shape[-1] - nope))


@functools.partial(jax.jit, static_argnames=("rank", "nope", "scale",
                                             "use_pallas", "ppb", "in_head"))
def _latent_chunk_walk(q, rows, w_kvb, pool, block_table, index, member, *,
                       rank, nope, scale, use_pallas, ppb, in_head):
    """:func:`latent_chunk_attention` at ``ppb`` pages a step of the walk,
    the rotary key ``in_head`` or shared.  Jitted, so that a model's layers
    share one lowering (two kernels a layer: a second and more of a traced
    body's set-up each)."""
    _, s, h, _ = q.shape
    m_pages = block_table.shape[1]
    t = ppb * pool.shape[1]
    rope = q.shape[-1] - nope
    qh = jnp.swapaxes(q, 1, 2)                       # [B, H, S, nope + rope]
    w_k, w_v = w_kvb[..., :nope], w_kvb[..., nope:]
    # the shared part of the score in whole lane tiles, zeros past the
    # rotary key (the stored row's own pad where its lanes end there)
    pad = -rope % 128
    if in_head:
        attend = functools.partial(flash_forward, qh)
        # ... or the rotary key written INTO each head's key by the product
        # that expands it: the stored row whole against [kv_b[K] | 0] over
        # [0 | I] — a key passes the ones exactly, and no copy a head is
        # made outside the product
        w_k = jnp.zeros((rows.shape[-1], h, nope + rope), w_kvb.dtype)
        w_k = w_k.at[:rank, :, :nope].set(w_kvb[..., :nope])
        w_k = w_k.at[rank:rank + rope, :, nope:].set(
            jnp.eye(rope, dtype=w_kvb.dtype)[:, None])
    else:
        attend = functools.partial(
            flash_forward, qh[..., :nope],
            q_shared=jnp.pad(qh[..., nope:], ((0, 0),) * 3 + ((0, pad),)))
    attend = functools.partial(attend, scale=scale, use_pallas=use_pallas)
    tiled = member is not None and member.ndim == 5
    if tiled and t % member.shape[-1]:
        # pages too small for a step of whole blocks: a row a query
        member, tiled = member_rows(member), False
    if member is not None:
        attend = functools.partial(attend,
                                   name="latent_sparse_chunk_expanded")
        # every step of the walk a slice of its own, whole
        keys = -(-m_pages // ppb) * t
        if tiled and member.shape[2] * member.shape[4] < keys:
            raise ValueError(
                f"a membership of {member.shape[2]} blocks of "
                f"{member.shape[4]} keys does not cover {keys} keys")
        if not tiled:
            member = jnp.pad(member, ((0, 0), (0, 0), (0, max(
                0, keys - member.shape[2]))))

    def expand(rows):
        """``(k, v, the keys' shared part or None)`` of cache rows."""
        def through(x, w):
            return jnp.einsum("btr,rhn->bhtn", x, w,
                              preferred_element_type=jnp.float32
                              ).astype(rows.dtype)
        v = through(rows[..., :rank], w_v)
        if in_head:
            return through(rows, w_k), v, None
        k_rope = rows[..., rank:rank + rope + pad]
        short = rope + pad - k_rope.shape[-1]
        return (through(rows[..., :rank], w_k), v,
                jnp.pad(k_rope, ((0, 0), (0, 0), (0, short))))

    def named(at, keys):
        """``member``'s part for ``keys`` keys from ``at``: a step's [] —
        of the tiled form whole blocks as they lie — or the chunk's own
        [B], which start at a page and not at a block."""
        if member is None:
            return None
        if not tiled:
            if jnp.ndim(at) == 0:
                return jax.lax.dynamic_slice_in_dim(member, at, keys, axis=2)
            return jax.vmap(lambda m, a: jax.lax.dynamic_slice_in_dim(
                m, a, keys, axis=1))(member, at)
        blocks, mb = member.shape[2], member.shape[4]
        if jnp.ndim(at) == 0:
            return jax.lax.dynamic_slice_in_dim(member, at // mb, keys // mb,
                                                axis=2)
        n = min(blocks, -(-keys // mb) + 1)

        def own(m, a):
            first = jnp.clip(a // mb, 0, blocks - n)
            part = jax.lax.dynamic_slice_in_dim(m, first, n, axis=1)
            return jax.lax.dynamic_slice_in_dim(
                member_rows(part[None])[0], a - first * mb, keys, axis=1)
        return jax.vmap(own)(member, at)

    k, v, k_rope = expand(rows)
    carry = attend(k, v, k_shared=k_rope, causal=True,
                   member=named(index, s))

    def step(rows, at, carry, kv_len):
        k, v, k_rope = expand(rows[0])
        return attend(k, v, k_shared=k_rope, carry=carry, kv_len=kv_len,
                      member=named(at, t))
    o, _ = _walk_under(index, block_table, (pool,), ppb, carry, step)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# A chunk over K and V pools, WALKED through the flash forward
# ---------------------------------------------------------------------------

# query rows a KV head meets (the chunk's length times the query heads that
# share the head) from which a continuation chunk walks: a full tile of the
# flash forward (``flash_attention.DEFAULT_BLOCK_Q``) — the count; measured
# (docs/pr58_paged_chunk_walk_sweep.jsonl, `rows` lines: v5e, bf16, 8k keys
# under the chunk, kernel -> walk, ms a layer) the walk breaks even between
# 512 and 1,024 rows: 8 query heads a KV head of 256, pages of 1,024, at 256
# / 512 / 1,024 / 2,048 rows 0.41 -> 0.49, 0.38 -> 0.38, 0.62 -> 0.48, 0.91
# -> 0.55; 7 a head of 128, pages of 64, at 448 / 896 / 1,792 rows 0.44 ->
# 0.63, 0.56 -> 0.51, 0.78 -> 0.64.  At the cells' shapes (16,384 and 7,168
# rows) 6.45 -> 2.70 and 2.58 -> 1.37, at 64k keys 39.0 -> 15.8 and 16.2 ->
# 8.2: a step of 2,048 keys 0.47 ms where the MXU's least is 0.35
CHUNK_WALK_ROWS = 1024


def chunk_walks(s: int, hq: int, h: int, *, window=None,
                pools: int = 2) -> bool:
    """Whether a CONTINUATION chunk of ``s`` queries a row, ``hq`` query
    heads over ``h`` KV heads, attends through
    :func:`paged_chunk_attention` and not :func:`paged_flash_decode` — by
    what the call can see: K and V in ``pools`` = 2 pools (not ``[k | v]``
    rows, not a latent pool), no ``window`` (under one the kernel starts at
    the first visible block and a walk from 0 would not), grouped query
    heads whose rows a KV head, ``hq // h * s``, fill a tile of the flash
    forward.  That is where the kernel's ``_tiling`` halves a head's rows
    into ``row_blocks`` blocks that EACH stream the row's pages again (64
    blocks of 256 rows at 2,048 x 8 rows of 256 lanes), every block a
    strided load a head; the walk meets a page once a 1,024-row tile, from
    a contiguous copy.  One query a row (a decode step) never walks."""
    return (pools == 2 and window is None and s > 1 and hq > h
            and hq // h * s >= CHUNK_WALK_ROWS)


# bytes of ONE pool a gathered slice holds at most.  XLA's gather of whole
# pages relays out the WHOLE pool first where a slice is larger (compiled
# for the v5e, PR 58: two pages of 1,024 x 2 x 256 bf16 a step, 512 KiB a
# slice, 1.36e9 B of temporaries beside a 622-page pool; 33.8e6 at 256 KiB
# and under, the walk's carry — and on the chip 23.0 ms a layer at 8k keys
# where parts of a page take 2.64: the sweep's `walk_whole_pages_ms`): a
# larger page is gathered in equal parts
_GATHER_SLICE_BYTES = 256 * 1024


def _gather_parts(page_size: int, row_bytes: int) -> int:
    """Equal parts a page of ``page_size`` tokens of ``row_bytes`` is
    gathered in (``_GATHER_SLICE_BYTES``): a power of two."""
    parts = 1
    while (page_size % (2 * parts) == 0
           and page_size // parts * row_bytes > _GATHER_SLICE_BYTES):
        parts *= 2
    return parts


def chunk_rows_walked(index, s: int, page_size: int, m_pages: int,
                      row_bytes: int):
    """The keys :func:`paged_chunk_attention` meets for a row whose chunk of
    ``s`` starts at ``index``, a token of one pool ``row_bytes`` wide: those
    its walk's steps gathered, in whole steps, and the chunk's own."""
    parts = _gather_parts(page_size, row_bytes)
    return latent_rows_expanded(index, s, page_size // parts,
                                m_pages * parts)


def paged_chunk_attention(q, k, v, pool_k, pool_v, block_table, index, *,
                          use_pallas=None):
    """A continuation chunk's attention over K and V pools as a WALK of
    :func:`ops.flash_attention.flash_forward` calls: the same product as
    :func:`paged_attention` (every visible key attended, f32 scores and
    carry), where :func:`chunk_walks`.

    q [B, S, Hq, Dh]; k, v [B, S, Hkv, Dh] the chunk's own keys and values,
    already written to the pools [P, page, Hkv, Dh] (write-then-attend;
    they are attended from here and not read back); block_table [B, M];
    index [B] the chunk's first position.  Returns [B, S, Hq, Dh] in q's
    dtype.

    The chunk against ITSELF is one causal ``flash_forward``, a KV head
    repeated for its query heads.  The keys under ``index`` — every query
    sees all of them — are walked in steps of ``EXPAND_KEYS``
    (:func:`_walk_under`): a step's pages laid heads-major, the ``Hq //
    Hkv`` query heads of a KV head ``G * S`` rows of that head, one
    unmasked call continuing the same online softmax.  The kernels run
    under the name ``paged_flash_decode_chunk``: on the device's timeline
    they are the paged kernel's work."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    pages, page_size, h, d = pool_k.shape
    parts = _gather_parts(page_size, h * d * pool_k.dtype.itemsize)
    if parts > 1:
        # the pools and the table in parts of a page (a view: no copy)
        pool_k, pool_v = (p.reshape(pages * parts, page_size // parts, h, d)
                          for p in (pool_k, pool_v))
        block_table = (block_table[:, :, None] * parts + jnp.arange(
            parts, dtype=block_table.dtype)).reshape(len(block_table), -1)
    return _paged_chunk_walk(
        q, k, v, pool_k, pool_v, block_table, index, use_pallas=use_pallas,
        ppb=_expand_pages(pool_k.shape[1], block_table.shape[1]))


@functools.partial(jax.jit, static_argnames=("use_pallas", "ppb"))
def _paged_chunk_walk(q, k, v, pool_k, pool_v, block_table, index, *,
                      use_pallas, ppb):
    """:func:`paged_chunk_attention` at ``ppb`` pages a step of the walk.
    Jitted, so that a model's layers share one lowering."""
    b, s, hq, d = q.shape
    h = k.shape[2]
    group = hq // h
    attend = functools.partial(flash_forward, scale=1.0 / (d ** 0.5),
                               use_pallas=use_pallas,
                               name="paged_flash_decode_chunk")
    qh = jnp.swapaxes(q, 1, 2)                       # [B, Hq, S, D]
    o, lse = attend(qh, *(jnp.swapaxes(x, 1, 2)
                          for x in expand_kv_heads(k, v, hq)), causal=True)
    # the G query heads of a KV head as G * S rows of that head
    qh = qh.reshape(b, h, group * s, d)

    def step(rows, at, carry, kv_len):
        del at
        # a gathered row at or past the row's ``index`` is not the row's
        # (the scratch page, another request's old page): zeros, so that
        # the block ``kv_len`` cuts multiplies finite values under its
        # mask (0 x NaN is NaN); the select rides the relayout's copy
        dead = (jnp.arange(rows[0].shape[1], dtype=jnp.int32)[None, :]
                >= kv_len[:, None])[:, None, :, None]
        return attend(qh, *(jnp.where(dead, 0, jnp.swapaxes(x, 1, 2))
                            for x in rows), carry=carry, kv_len=kv_len)
    o, _ = _walk_under(
        index, block_table, (pool_k, pool_v), ppb,
        (o.reshape(b, h, group * s, d), lse.reshape(b, h, group * s, 1)),
        step)
    return jnp.swapaxes(o.reshape(b, hq, s, d), 1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas paged flash-decode kernel
# ---------------------------------------------------------------------------

# VMEM a call plans its scratch within: two K and two V page blocks (the
# double buffer) in one half, the f32 carry and the q/out blocks of a
# head group in the other.  A quarter of the v5e's 16 MiB default scoped
# limit, so Mosaic's temporaries (a score tile, a head's rows) fit
# beside it.
_VMEM_BUDGET = 4 * 2 ** 20


def _tiled_heads(h, itemsize):
    """The least head count >= ``h`` that a ``[page, H, Dh]`` page holds
    in whole tiles, so that it is one DMA and its ``[page * H, Dh]``
    reading is free: H rows pack ``4 // itemsize`` to a 32-bit sublane
    word; 1, 2, 4 or a multiple of 8 words tile."""
    pack = 4 // itemsize
    words = -(-h // pack)
    words = -(-words // 8) * 8 if words > 4 else 1 << (words - 1).bit_length()
    return words * pack


def _head_bytes(s, d, itemsize):
    """VMEM one head's ``s`` query rows take: f32 o [S, D], m and l
    [S, 1] (a lane tile each), and the pipeline's two q and two out
    blocks."""
    return s * (d * 4 + 2 * 128 * 4 + 4 * d * itemsize)


# when a grid point scores a block all heads at once — one [R, T·H] score
# tile whose other-head columns are masked, H times the FLOPs of the
# head-by-head form on an MXU that a one-row matmul leaves idle: at most
# this many query rows (every head's together) over at least this many
# heads.  Kernel alone on the v5e, bf16, 24 chained calls (PERF.md §6 PR 33),
# head by head -> all at once, ms a call: 16 heads, 48 rows of 20k tokens,
# R = 16 / 128 / 256: 0.721 -> 0.294, 0.719 -> 0.376, 0.744 -> 0.536 (a
# [256, 2048] f32 tile is 2 MiB beside a body's own VMEM: not taken);
# 8 heads R = 8 / 128: 0.379 -> 0.180, 0.389 -> 0.253; but 4 heads
# (28 query heads, pages of 64) R = 28: 0.271 -> 0.286 — a strided load of
# 2-word stride is cheap and a fourfold tile is not.  The head count is the
# measured bound; the row count is a VMEM guard that no caller reaches (a
# decode step is R = H, and a chunk of 16 heads fails ``hg == h`` first),
# timed with the kernel alone only: not a bound to tune for speed
_ALL_HEADS_ROWS = 128
_ALL_HEADS_MIN_HEADS = 8


def _plan(s, h, d, page_size, m_pages, itemsize):
    """Static tiling from what a trace can see: ``(pages_per_block,
    heads_per_group, all_heads)``.  ``s`` is the query rows a KV head
    meets: the chunk's length times the query heads that share the head.

    ``pages_per_block``: the largest power of two whose K+V double
    buffer takes half the budget, at most the table's width.
    ``heads_per_group``: the most heads (a divisor of H, in whole
    32-bit words of packed rows) whose carry and q/out blocks fit the
    other half.  All of them at a decode step; a 256-token chunk of 16
    heads takes 2, and the grid's second axis walks the groups, each
    streaming the row's pages again — a chunk reuses every page S
    times, so the repeat is cheap exactly where a split is needed.
    ``all_heads``: every head is in the one grid point, they are many
    and their rows together few (``_ALL_HEADS_MIN_HEADS``,
    ``_ALL_HEADS_ROWS``), so a block is scored as the one ``[T·H, Dh]``
    matrix it is stored as; otherwise head by head."""
    page_bytes = page_size * h * d * itemsize
    ppb = 1
    while (ppb * 2 <= m_pages
           and 4 * (ppb * 2) * page_bytes <= _VMEM_BUDGET // 2):
        ppb *= 2
    pack = 4 // itemsize
    head_bytes = _head_bytes(s, d, itemsize)
    hg = h
    while hg > pack and (hg * head_bytes > _VMEM_BUDGET // 2
                         or h % hg or hg % pack):
        hg -= 1
    all_heads = (hg == h and h >= _ALL_HEADS_MIN_HEADS
                 and h * s <= _ALL_HEADS_ROWS)
    return ppb, hg, all_heads


def _tiling(s, hq, h, d, page_size, m_pages, itemsize):
    """``(rows, row_blocks) + _plan(rows, ...)`` of a call: S queries of
    ``hq`` query heads over ``h`` KV heads (a count that tiles).  The
    ``hq // h`` query heads of a KV head are ``rows`` rows of that head,
    halved into ``row_blocks`` blocks where they outgrow the budget."""
    rows, row_blocks = hq // h * s, 1
    pack = 4 // itemsize
    while (hq > h and rows % 16 == 0
           and pack * _head_bytes(rows, d, itemsize) > _VMEM_BUDGET // 2):
        rows //= 2
        row_blocks *= 2
    return (rows, row_blocks) + _plan(rows, h, d, page_size, m_pages,
                                      itemsize)


def decode_scores_all_heads(q_heads, kv_heads, head_dim, page_size, m_pages,
                            itemsize) -> bool:
    """Whether :func:`paged_flash_decode` scores a block all heads at
    once (``_plan``) at a decode step — one query a row — of these
    shapes: what the serving engine publishes about its decode body."""
    h = _tiled_heads(kv_heads, itemsize)
    return _tiling(1, q_heads // kv_heads * h, h, head_dim, page_size,
                   m_pages, itemsize)[-1]


def _head_rows(flat_ref, head, h, t):
    """Rows of head ``head`` (32-bit pools) or of the head pair ``head``,
    ``head + 1`` (bf16) out of a ``[t * h, d]`` block whose row
    ``tok * h + hd`` is token ``tok`` of head ``hd`` — the pool's stored
    order.  A sublane-strided load; bf16 packs two rows to a 32-bit
    word, so a pair comes as one strided load of words, split by shift
    and mask (a bf16 is the top half of the f32 with the same bits)."""
    if flat_ref.dtype.itemsize == 4:
        return [flat_ref[pl.ds(head, t, stride=h), :]]
    words = flat_ref.bitcast(jnp.uint32)[pl.ds(head // 2, t, stride=h // 2), :]
    lo = pltpu.bitcast(words << 16, jnp.float32)
    hi = pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32)
    return [lo.astype(flat_ref.dtype), hi.astype(flat_ref.dtype)]


def _paged_decode_kernel(tbl_ref, idx_ref, q_ref, k_hbm, v_hbm, o_ref,
                         kbuf, vbuf, sem, oacc_ref, m_ref, l_ref, *, scale,
                         q_len=None, row_blocks=1, window=None,
                         head_rows=None):
    """Grid (B, head groups): one row streams ITS pages, block by block.

    ``tbl_ref`` [B, M] and ``idx_ref`` [B] are scalar-prefetched (SMEM);
    ``k_hbm``/``v_hbm`` are the whole pools in HBM, as stored
    ``[P, page, H, Dh]``; ``kbuf``/``vbuf`` ``[2, ppb, page, H, Dh]`` are
    the double buffers; ``q_ref``/``o_ref`` [G, S, Dh] the group's
    heads.  The loop runs ``ceil((index + S) / (ppb·page))``
    times: block ``i + 1``'s page copies are started before block
    ``i``'s math, a live page is one contiguous DMA per pool with its id
    read from the table, and a page past the row's length is neither
    copied nor waited for — its buffer slot is zeroed instead, so the
    masked positions multiply finite values (0 × NaN is NaN).  A block
    lies in VMEM in the stored order, a ``[T·H, Dh]`` matrix; each
    head's ``[T, Dh]`` rows are pulled out with a strided load and the
    heads take turns (rolled loops throughout: 24 layers of this compile
    in every serve body).  The online-softmax carry (ops.blockwise:
    un-normalized o in f32, running max m, denominator l) lives in VMEM
    scratch across the blocks.  The causal mask is positional, exactly
    the gather oracle's: key position ``p`` is admitted iff
    ``p <= index + i`` for query ``i``.

    Grouped-query heads (``q_len`` set): the ``G`` query heads that share
    a KV head lie in ``q_ref`` as ``G * q_len`` rows of that head, query
    head after query head, so row ``r`` is the query at ``r % q_len`` and
    one stream of the pages serves all of them; where the rows of a head
    outgrow the budget the grid's second axis also walks ``row_blocks``
    blocks of them.  ``window`` (static): a query sees the last
    ``window`` positions up to its own, and the loop starts at the
    first block the chunk's first query can see, so a row costs
    ``min(len, window + S)`` tokens and not ``len``.  All three unset is
    the kernel of full heads over the whole history, unchanged.

    All heads at once (``head_rows`` set, with ``q_len``): ``q_ref``
    [1, R, Dh] holds every KV head's rows as ONE matrix, ``head_rows`` of
    them a head (row ``r`` is KV head ``r // head_rows``, the query at
    ``r % head_rows % q_len``), and a block is scored as the
    ``[T·H, Dh]`` matrix it is: no strided load, one pair of matmuls.
    Column ``c`` is token ``c // H`` of head ``c % H``; the bias is the
    positional mask at ``c // H`` and ``NEG_INF`` in every other head's
    column, whose probability is then exactly 0 and adds an exact 0 of
    that head's value row.  Contract of this form: the live V rows of a
    page a row may read are finite in EVERY head (0 x NaN is NaN in
    ``p @ vflat``, so one head's non-finite value reaches all heads of
    the row, where head by head it reached that head alone); dead pages
    are zeroed before use, as in every form.

    One pool of ``[k | v]`` rows (``v_hbm`` and ``vbuf`` None; ``k_hbm``
    ``[P, page, H, D]``, ``kbuf`` five-dimensional as ever): a head's row
    holds its key in the first ``D / 2`` lanes and its value in the rest,
    the query's upper lanes are zeros, so ``q . row`` is ``q . k``, and
    the value sum runs over the whole row — its upper lanes are the
    attended value, its lower ones are dropped by the caller.  A page is
    copied once and read once, in every form above.

    Latent pool (``v_hbm`` and ``vbuf`` None; ``k_hbm`` ``[P, page, W]``,
    ``kbuf`` ``[2, ppb, page, W]``): a token is ONE row of ``W`` values
    that every query head scores against, and the row's first lanes (as
    many as ``o_ref`` is wide) are the value, so a page is copied once
    and read once, as a ``[T, W]`` matrix with no strided load.  The
    query heads are the grouped form's rows over the one "KV head"."""
    b = pl.program_id(0)
    g = pl.program_id(1)
    one_pool = v_hbm is None
    latent = one_pool and len(kbuf.shape) == 4
    if latent:
        (_, ppb, page_size, d), h = kbuf.shape, 1
    else:
        _, ppb, page_size, h, d = kbuf.shape
    heads, s, _ = q_ref.shape
    t = ppb * page_size
    m_pages = tbl_ref.shape[1]
    pack = 4 // kbuf.dtype.itemsize
    idx = idx_ref[b]
    row0 = 0
    if row_blocks > 1:
        g, row0 = g // row_blocks, (g % row_blocks) * s
    n_live = jnp.minimum(idx + (s if q_len is None else q_len),
                         m_pages * page_size)
    n_blocks = pl.cdiv(n_live, t)
    first = 0 if window is None else jnp.maximum(idx - window + 1, 0) // t

    def for_pages(lo, hi, fn):
        def body(p, carry):
            fn(p)
            return carry
        jax.lax.fori_loop(lo, hi, body, 0)

    def live_pages(blk):
        return jnp.clip(pl.cdiv(n_live, page_size) - blk * ppb, 0, ppb)

    def copies(blk, slot, p):
        pid = tbl_ref[b, blk * ppb + p]
        k_copy = pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[slot, p],
                                       sem.at[0, slot])
        if one_pool:
            return (k_copy,)
        return (k_copy,
                pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[slot, p],
                                      sem.at[1, slot]))

    def start(blk, slot):
        def copy(p):
            for c in copies(blk, slot, p):
                c.start()

        def zero(p):
            kbuf[slot, p] = jnp.zeros(kbuf.shape[2:], kbuf.dtype)
            if not one_pool:
                vbuf[slot, p] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

        n = live_pages(blk)
        for_pages(0, n, copy)
        for_pages(n, ppb, zero)

    def wait(blk, slot):
        def done(p):
            for c in copies(blk, slot, p):
                c.wait()
        for_pages(0, live_pages(blk), done)

    oacc_ref[...] = jnp.zeros_like(oacc_ref)
    m_ref[...] = jnp.full_like(m_ref, bw.NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    start(first, first % 2)

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _prefetch():
            start(blk + 1, 1 - slot)

        wait(blk, slot)
        kflat = kbuf.at[slot].reshape(t * h, d)
        if not one_pool:
            vflat = vbuf.at[slot].reshape(t * h, d)
        kpos = blk * t
        col = jax.lax.broadcasted_iota(
            jnp.int32, (1, t * h if head_rows else t), 1)
        kpos += jax.lax.div(col, h) if head_rows else col
        qrow = jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0)
        if head_rows:
            own = jax.lax.rem(col, h) == jax.lax.div(qrow, head_rows)
            qrow = jax.lax.rem(qrow, head_rows)
        if q_len is not None:
            qrow = (row0 + qrow) % q_len
        qpos = idx + qrow
        seen = kpos <= qpos
        if window is not None:
            seen &= kpos > qpos - window
        if head_rows:
            seen &= own
        bias = jnp.where(seen, 0.0, bw.NEG_INF)
        if latent or head_rows:
            # the block IS the keys of every row of q (a latent block's
            # first lanes their values too): read once, one pair of
            # matmuls
            rows = kflat[...]
            o, m, l = bw.block_accumulate(
                oacc_ref[0], m_ref[0][:, 0], l_ref[0][:, 0], q_ref[0],
                rows,
                rows[:, :oacc_ref.shape[-1]] if one_pool else vflat[...],
                scale, bias)
            oacc_ref[0] = o
            m_ref[0] = m[:, None]
            l_ref[0] = l[:, None]
            return carry

        def head_words(i, c):
            ks = _head_rows(kflat, g * heads + i * pack, h, t)
            vs = ks if one_pool else _head_rows(vflat, g * heads + i * pack,
                                                h, t)
            for j, (k, v) in enumerate(zip(ks, vs)):
                u = i * pack + j
                o, m, l = bw.block_accumulate(
                    oacc_ref[u], m_ref[u][:, 0], l_ref[u][:, 0],
                    q_ref[u], k, v, scale, bias)
                oacc_ref[u] = o
                m_ref[u] = m[:, None]
                l_ref[u] = l[:, None]
            return c

        return jax.lax.fori_loop(0, heads // pack, head_words, carry)

    jax.lax.fori_loop(first, n_blocks, block, 0)
    o_ref[...] = bw.finalize(
        oacc_ref[...], l_ref[...][..., 0]).astype(o_ref.dtype)


# a latent call's tiling: query rows a grid point holds (its f32 carry is
# [rows, value lanes]), tokens a block streams (one buffer is [tokens, W])
# and the f32 score tile [rows, tokens] the two make, which bounds them
# together: beside a compiled body's own VMEM a (1024, 1024) tile does not
# fit, (1024, 512) and (512, 1024) do.  Measured on the v5e at 32 heads over
# rows of 640 lanes (PERF.md §6 PR 32): a decode step of 24 rows (32 query
# rows each), 254k tokens, 0.87 / 0.65 / 0.58 / 0.61 ms at 256 / 512 / 1024
# / 2048 tokens a block; a 1,024-token chunk over 16,384 cached 8.8 ms at
# (512, 512), 8.5 at (512, 1024), 8.1 at (1024, 512)
_LATENT_ROWS = 1024
_LATENT_BLOCK_TOKENS = 1024
_LATENT_SCORE_ELEMS = 512 * 1024


def _latent_flash_decode(q, pool, block_table, index, *, scale, interpret,
                         value_lanes):
    """:func:`paged_flash_decode` over a latent pool ``[P, page, W]``: q
    [B, S, H, W] -> [B, S, H, value_lanes].  The H query heads are
    ``H * S`` rows over the one row a token; past ``_LATENT_ROWS`` the
    grid's second axis walks blocks of them, each streaming the row's
    pages again."""
    b, s, hq, w = q.shape
    page_size = pool.shape[1]
    m_pages = block_table.shape[1]
    qh = jnp.swapaxes(q, 1, 2).reshape(b, 1, hq * s, w)
    rows = hq * s
    while rows > _LATENT_ROWS and rows % 16 == 0:
        rows //= 2
    block_tokens = min(_LATENT_BLOCK_TOKENS, _LATENT_SCORE_ELEMS // rows)
    # a page is copied whole: one longer than the block IS the block, and
    # the rows a grid point holds give way to keep the score tile
    if page_size > block_tokens:
        while page_size * rows > _LATENT_SCORE_ELEMS and rows % 16 == 0:
            rows //= 2
    row_blocks = hq * s // rows
    ppb = 1
    while ppb * 2 <= m_pages and ppb * 2 * page_size <= block_tokens:
        ppb *= 2

    def spec(lanes):
        return pl.BlockSpec((None, 1, rows, lanes),
                            lambda b_, g_, tbl, idx: (b_, 0, g_, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, row_blocks),
        in_specs=[spec(w), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=spec(value_lanes),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, w), pool.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.VMEM((1, rows, value_lanes), jnp.float32),
            pltpu.VMEM((1, rows, 1), jnp.float32),
            pltpu.VMEM((1, rows, 1), jnp.float32),
        ],
    )

    def kernel(tbl_ref, idx_ref, q_ref, k_hbm, o_ref, kbuf, sem, *carry):
        _paged_decode_kernel(tbl_ref, idx_ref, q_ref, k_hbm, None, o_ref,
                             kbuf, None, sem, *carry, scale=scale, q_len=s,
                             row_blocks=row_blocks)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, hq * s, value_lanes), q.dtype),
        interpret=interpret, name="paged_flash_decode",
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(index, jnp.int32),
      qh, pool)
    return jnp.swapaxes(out.reshape(b, hq, s, value_lanes), 1, 2)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "window",
                                             "value_lanes"))
def paged_flash_decode(q, pool_k, pool_v, block_table, index, *,
                       scale=None, interpret: bool = False, window=None,
                       value_lanes=None):
    """Attention of a chunk of queries over a slot's paged KV history,
    streaming each row's LIVE pages out of the pool as it is stored.

    Same contract as :func:`paged_attention` (write-then-attend; q
    [B, S, H, Dh], pools [P, page_size, H, Dh], block_table [B, M],
    index [B] int32; grouped-query heads where q has ``G`` times the
    pools' heads; ``window`` static).  The pools stay in HBM in their
    stored layout — no transposed or gathered copy exists; the work of
    a row is proportional to its own length (to ``window + S`` under a
    window), not to the table's width; one compile covers every chunk
    index.  Tiling follows the static shapes (:func:`_plan`); where that
    scores a block all heads at once (a decode step of 8 heads or more),
    the written V rows of a page a row reads must be finite in every
    head, not only in the head that reads them.  Jitted,
    so that the layers of a model, which call it at one shape, share
    one trace and one lowering of the kernel: traced per layer it was
    25 s of every serve process's set-up.

    ``value_lanes`` set (and ``pool_v`` None): ``pool_k`` is a latent pool
    ``[P, page_size, W]`` — one row a token for every query head, whose
    first ``value_lanes`` lanes are the value — with
    :func:`latent_paged_attention`'s contract (``scale`` is then required:
    the row's width is not a head's).

    ``pool_v`` None without ``value_lanes``: ``pool_k`` [P, page_size, H,
    2 * Dh] holds ``[k | v]`` of a head in one row (:func:`paged_attention`);
    the pool is streamed once, for scores and values both."""
    if pool_k.dtype not in (jnp.bfloat16, jnp.float32):
        raise ValueError(f"paged_flash_decode reads bf16 or f32 pools, "
                         f"not {pool_k.dtype} (see _head_rows)")
    if value_lanes is not None:
        if window is not None or pool_v is not None:
            raise ValueError("a latent pool is one pool and has no window "
                             "form")
        return _latent_flash_decode(
            q, pool_k, block_table, index, scale=float(scale),
            interpret=interpret, value_lanes=int(value_lanes))
    one_pool = pool_v is None
    if one_pool:
        dh = q.shape[-1]
        if pool_k.shape[-1] != 2 * dh:
            raise ValueError(f"a [k | v] pool's row is twice the head: "
                             f"{pool_k.shape[-1]} lanes for heads of {dh}")
        # zeros meet the row's value half: q . [k | v] is q . k
        scale = float(scale) if scale is not None else 1.0 / (dh ** 0.5)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dh)))
    b, s, hq, d = q.shape
    page_size, h = pool_k.shape[1], pool_k.shape[2]
    m_pages = block_table.shape[1]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    group, rem = divmod(hq, h)
    if rem or group < 1:
        raise ValueError(f"{hq} query heads do not share {h} KV heads")
    hp = _tiled_heads(h, pool_k.dtype.itemsize)
    if hp != h:
        # a head count the TPU's (sublane, lane) tiling cannot hold
        # whole (6 or 3 bf16 heads: ``transformer_tpu``): zero heads
        # fill the tile.  This one case copies the pools, every call
        if one_pool:
            raise ValueError(f"a [k | v] pool of {h} heads does not tile "
                             f"(1, 2, 4 or a multiple of 8 words of heads)")
        zeros = ((0, 0), (0, 0), (0, hp - h), (0, 0))
        qpad = ((0, 0), (0, 0), (0, (hp - h) * group), (0, 0))
        return paged_flash_decode(
            jnp.pad(q, qpad), jnp.pad(pool_k, zeros), jnp.pad(pool_v, zeros),
            block_table, index, scale=scale, interpret=interpret,
            window=window)[:, :, :hq]
    qh = jnp.swapaxes(q, 1, 2)                       # [B, H, S, D], q alone
    if group > 1:
        # the G query heads of a KV head as G * S rows of that head
        qh = qh.reshape(b, h, group * s, d)
    rows, row_blocks, ppb, hg, all_heads = _tiling(
        s, hq, h, d, page_size, m_pages, pool_k.dtype.itemsize)
    kernel_kw = {"scale": scale}
    if all_heads:
        # every head's rows as the one matrix a block is scored against
        kernel_kw.update(q_len=s, head_rows=rows)
        qh = qh.reshape(b, 1, h * rows, d)
        hg, rows, groups = 1, h * rows, 1
    else:
        groups = h // hg * row_blocks
        if group > 1:
            kernel_kw.update(q_len=s, row_blocks=row_blocks)
    if row_blocks > 1:
        qo_spec = pl.BlockSpec(
            (None, hg, rows, d),
            lambda b_, g_, tbl, idx: (b_, g_ // row_blocks,
                                      g_ % row_blocks, 0))
    else:
        qo_spec = pl.BlockSpec((None, hg, rows, d),
                               lambda b_, g_, tbl, idx: (b_, g_, 0, 0))
    pools = (pool_k,) if one_pool else (pool_k, pool_v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, groups),
        in_specs=[qo_spec] + [pl.BlockSpec(memory_space=pl.ANY)
                              for _ in pools],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, h, d), p.dtype) for p in pools
        ] + [
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.VMEM((hg, rows, d), jnp.float32),
            pltpu.VMEM((hg, rows, 1), jnp.float32),
            pltpu.VMEM((hg, rows, 1), jnp.float32),
        ],
    )
    if window is not None:
        kernel_kw["window"] = int(window)
    kernel = functools.partial(_paged_decode_kernel, **kernel_kw)
    if one_pool:
        def kernel(tbl_ref, idx_ref, q_ref, k_hbm, o_ref, kbuf, sem, *carry):
            _paged_decode_kernel(tbl_ref, idx_ref, q_ref, k_hbm, None, o_ref,
                                 kbuf, None, sem, *carry, **kernel_kw)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=interpret,
        name="paged_flash_decode",
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(index, jnp.int32),
      qh, *pools)
    if group > 1 or all_heads:
        out = out.reshape(b, hq, s, d)
    out = jnp.swapaxes(out, 1, 2)
    return out[..., d // 2:] if one_pool else out


def paged_block_attention(q, pool_k, pool_v, blocks, count, last, *,
                          block: int, use_pallas=None):
    """A DECODE STEP's read of chosen blocks (a chunk's is
    :func:`paged_tile_attention`): attention of ONE query a (row, KV head)
    over a table of BLOCKS that are parts of a page, the table a (row, KV
    head)'s own: q [B, Hq, Dh];
    pools [P, page, Hkv, Dh]; ``blocks`` [B, Hkv, W] int32 ids into the
    pools seen as ``[P * page / block, block, Hkv, Dh]`` (a page's blocks
    are contiguous, so the reshape is free), of which the first ``count``
    [B] are read, in order; the last of them is read up to its row
    ``last`` [B] (the query's own block, up to the query), the others
    whole.  Returns [B, Hq, Dh].

    The read blocks are a PREFIX of the table, so a (row, KV head) is a row
    of :func:`paged_flash_decode` at pages of ``block`` tokens whose index
    is ``(count - 1) * block + last``: the kernel copies ``count`` blocks
    and nothing else, whatever the row's length.  A block as stored holds
    every KV head (two bfloat16 heads share a 32-bit word), so a copy
    brings the other heads' keys along and each KV head's queries are a
    row of their own, the other heads' query rows zeros."""
    b, hq, d = q.shape
    p, page, hkv, _ = pool_k.shape
    g = hq // hkv
    pool_k = pool_k.reshape(p * (page // block), block, hkv, d)
    pool_v = pool_v.reshape(p * (page // block), block, hkv, d)
    own = (jnp.arange(hq, dtype=jnp.int32)[None, :] // g
           == jnp.arange(hkv, dtype=jnp.int32)[:, None])        # [Hkv, Hq]
    rows = jnp.where(own[None, :, :, None], q[:, None], 0).reshape(
        b * hkv, 1, hq, d)
    index = jnp.repeat((count - 1) * block + last, hkv)
    o = paged_attention_auto(rows, pool_k, pool_v,
                             blocks.reshape(b * hkv, -1), index,
                             use_pallas=use_pallas)
    o = o.reshape(b, hkv, hkv, g, d)
    return jnp.stack([o[:, h, h] for h in range(hkv)], 1).reshape(b, hq, d)


# ---------------------------------------------------------------------------
# Pallas tile kernel: a chunk whose queries each read a subset of the blocks
# ---------------------------------------------------------------------------

# queries a grid point holds (each with the query heads of its KV head) and
# keys a product scores — also what one DMA copies and what a tile skips by.
# Kernel alone and inside the chunk body on the v5e, 32 query heads over 2 KV
# heads of 128, bf16, pages of 2,048: docs/pr47_sparse_chunk_sweep.jsonl
_TILE_QUERIES = 128
_TILE_KEYS = 256
_TILE_UNITS = 4
_TILE_ROWS = 512
# VMEM the tile kernel asks for, stated: the f32 carry of 16 heads x 128
# queries is 1 MiB of output and 2 MiB of lane-padded max and sum, beside the
# q / out / membership blocks (two each), two K and two V units and a head's
# score tiles — more than the decode kernel's shared 4 MiB plan, well under
# the chip's 128 MiB
_TILE_VMEM_BYTES = 48 * 2 ** 20


def tile_keys(page_size: int, block: int) -> int:
    """Keys a unit of the tile kernel holds at these sizes: ``_TILE_KEYS``
    where a page is whole units of it, else the page; whole blocks, at most
    16 (a unit's membership is one small integer a query)."""
    unit = _TILE_KEYS if page_size % _TILE_KEYS == 0 else page_size
    unit = min(unit, 16 * block)
    if unit % block or page_size % unit:
        raise ValueError(f"a page of {page_size} is not whole units of "
                         f"{unit} keys of whole blocks of {block}")
    return unit


def _kv_head_rows(flat_ref, head, h, t):
    """Rows ``[t, d]`` of the ONE head ``head`` (traced) out of a
    ``[t * h, d]`` block in the pool's stored order (:func:`_head_rows`,
    which gives a bf16 word's two heads at a static parity)."""
    if flat_ref.dtype.itemsize == 4:
        return flat_ref[pl.ds(head, t, stride=h), :]
    words = flat_ref.bitcast(jnp.uint32)[pl.ds(head // 2, t, stride=h // 2), :]
    up = (16 * (1 - head % 2)).astype(jnp.uint32)
    return pltpu.bitcast((words << up) & jnp.uint32(0xFFFF0000),
                         jnp.float32).astype(flat_ref.dtype)


def _paged_tiles_kernel(tbl_ref, idx_ref, list_ref, cnt_ref, q_ref, bits_ref,
                        k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, oacc_ref, m_ref,
                        l_ref, *, scale, block, per_page, tile_q, rows):
    """Grid (B, KV heads, query tiles): a tile of ``Tq`` queries, each with
    the ``G`` query heads of the KV head, streams ONCE the units of keys
    that any of its queries reads, and each query masks what it did not
    choose.

    Scalar-prefetched: ``tbl_ref`` [B, M] the page table, ``idx_ref`` [B]
    the chunk's first position, ``list_ref`` [B * Hkv * tiles, U] the
    logical units a (row, KV head, tile) streams, ascending, of which the
    first ``cnt_ref`` [B * Hkv * tiles] count.  ``q_ref`` / ``o_ref`` [G *
    Tq, D]: row ``x * Tq + i`` is query ``i`` of the tile in the KV head's
    query head ``x`` — the heads share the head's K and V rows, so they are
    rows of ONE product, ``rows`` of them at a time (whole heads: the MXU
    holds a 128 x 128 piece of K while the rows stream past, and a head's
    ``Tq`` alone would be as many rows as the piece takes to load).
    ``bits_ref`` [Tq, U] int32 — bit ``i`` of entry ``u`` of a query's row
    says that it reads block ``i`` of unit ``u``.  ``k_hbm`` / ``v_hbm``
    the pools seen as units ``[P * per_page, unit, Hkv, D]`` (a page's
    units are contiguous); ``kbuf`` / ``vbuf`` [2, W, unit, Hkv, D] the
    double buffers: a step copies the list's next ``W`` units, one DMA
    each, and scores them as ONE product of ``W * unit`` keys (the carry's
    rescale costs a step what it costs whatever the keys, so a step wants
    many; a copy and a skip want few), step ``j + 1``'s copies in flight
    during step ``j``'s products; the last step's missing units are zeroed
    and masked.  A step's bias is built once — membership and ``key
    position <= query position``, NEG_INF where either fails, so a key a
    query did not choose is out of its maximum and adds an exact 0 — and
    the KV head's ``[W * unit, D]`` rows are pulled out of the stored order
    once (full MXU rows, no zero heads).  The carry is the decode kernel's:
    un-normalized f32 o, running max, denominator."""
    b, g, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    d = q_ref.shape[-1]
    _, w, unit, h, _ = kbuf.shape
    row = (b * pl.num_programs(1) + g) * pl.num_programs(2) + i
    n = cnt_ref[row]
    steps = pl.cdiv(n, w)
    qpos = (idx_ref[b] + i * tile_q
            + jax.lax.broadcasted_iota(jnp.int32, (tile_q, 1), 0))
    key = jax.lax.broadcasted_iota(jnp.int32, (1, unit), 1)
    key_bit = jnp.left_shift(1, key // block)
    entry = jax.lax.broadcasted_iota(jnp.int32, (1, bits_ref.shape[1]), 1)

    def for_units(lo, hi, fn):
        def body(p, carry):
            fn(p)
            return carry
        jax.lax.fori_loop(lo, hi, body, 0)

    def live(j):
        return jnp.clip(n - j * w, 0, w)

    def copies(j, slot, p):
        u = list_ref[row, j * w + p]
        pid = tbl_ref[b, u // per_page] * per_page + u % per_page
        return (pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[slot, p],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[slot, p],
                                      sem.at[1, slot]))

    def start(j, slot):
        def copy(p):
            for c in copies(j, slot, p):
                c.start()

        def zero(p):
            kbuf[slot, p] = jnp.zeros(kbuf.shape[2:], kbuf.dtype)
            vbuf[slot, p] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)
        for_units(0, live(j), copy)
        for_units(live(j), w, zero)

    def wait(j, slot):
        def done(p):
            for c in copies(j, slot, p):
                c.wait()
        for_units(0, live(j), done)

    oacc_ref[...] = jnp.zeros_like(oacc_ref)
    m_ref[...] = jnp.full_like(m_ref, bw.NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(steps > 0)
    def _first():
        start(0, 0)

    def stream(j, carry):
        slot = j % 2

        @pl.when(j + 1 < steps)
        def _prefetch():
            start(j + 1, 1 - slot)

        wait(j, slot)
        k = _kv_head_rows(kbuf.at[slot].reshape(w * unit * h, d), g, h,
                          w * unit)
        v = _kv_head_rows(vbuf.at[slot].reshape(w * unit * h, d), g, h,
                          w * unit)
        seen = []
        for p in range(w):
            at = jnp.minimum(j * w + p, list_ref.shape[1] - 1)
            u = list_ref[row, at]
            mine = jnp.sum(jnp.where(entry == u, bits_ref[...], 0), axis=1,
                           keepdims=True)                       # [Tq, 1]
            seen.append(((mine & key_bit) != 0) & (u * unit + key <= qpos)
                        & (j * w + p < n))
        bias = jnp.where(jnp.concatenate(seen, axis=1), 0.0, bw.NEG_INF)
        bias = jnp.concatenate([bias] * (rows // tile_q), axis=0)

        def some(x, c):
            at = pl.ds(pl.multiple_of(x * rows, rows), rows)
            o, m, l = bw.block_accumulate(
                oacc_ref[at, :], m_ref[at, :][:, 0], l_ref[at, :][:, 0],
                q_ref[at, :], k, v, scale, bias)
            oacc_ref[at, :] = o
            m_ref[at, :] = m[:, None]
            l_ref[at, :] = l[:, None]
            return c
        return jax.lax.fori_loop(0, q_ref.shape[0] // rows, some, carry)

    jax.lax.fori_loop(0, steps, stream, 0)
    o_ref[...] = bw.finalize(
        oacc_ref[...], l_ref[...][..., 0]).astype(o_ref.dtype)


def tile_lists(bits, tile_q: int):
    """What each (row, KV head, tile of ``tile_q`` queries) streams: bits
    [B, Hkv, S, U] -> (units [B, Hkv, S / tile_q, U] int32, the union of
    its queries' units in ascending order, then zeros; count [B, Hkv, S /
    tile_q])."""
    b, hkv, s, u = bits.shape
    read = jnp.any(bits.reshape(b, hkv, s // tile_q, tile_q, u) != 0, axis=3)
    ids = jnp.arange(u, dtype=jnp.int32)
    count = jnp.sum(read, -1, dtype=jnp.int32)
    units = jnp.sort(jnp.where(read, ids, u), axis=-1)
    return jnp.where(ids < count[..., None], units, 0), count


@functools.partial(jax.jit, static_argnames=("block", "unit", "tile_q",
                                             "interpret", "rows", "width"))
def paged_flash_decode_tiles(q, pool_k, pool_v, block_table, index, bits,
                             units, count, *, block: int, unit: int,
                             tile_q: int, interpret: bool = False,
                             rows: int = _TILE_ROWS,
                             width: int = _TILE_UNITS):
    """The tile kernel's call: q [B, S, Hq, D]; pools [P, page, Hkv, D];
    ``bits`` [B, Hkv, S, U]; ``units``, ``count`` of :func:`tile_lists` at
    ``tile_q`` -> [B, S, Hq, D].  Jitted, so that a model's layers share
    one lowering."""
    b, s, hq, d = q.shape
    p, page, hkv, _ = pool_k.shape
    if _tiled_heads(hkv, pool_k.dtype.itemsize) != hkv:
        raise ValueError(f"a pool of {hkv} {pool_k.dtype} heads does not "
                         f"tile (1, 2, 4 or a multiple of 8 words of heads)")
    group, tiles, per_page = hq // hkv, s // tile_q, page // unit
    # whole heads a product: the most that divide the group within ``rows``
    heads = max(x for x in range(1, group + 1)
                if group % x == 0 and (x == 1 or x * tile_q <= rows))

    def tiled(x):           # [B, S, Hq, D] -> [B, Hkv, tiles, G * Tq, D]
        x = x.reshape(b, tiles, tile_q, hkv, group, d)
        return jnp.transpose(x, (0, 3, 1, 4, 2, 5)).reshape(
            b, hkv, tiles, group * tile_q, d)
    u_pad = -bits.shape[-1] % 128
    bits = jnp.pad(bits, ((0, 0),) * 3 + ((0, u_pad),))
    units = jnp.pad(units, ((0, 0),) * 3 + ((0, u_pad),))
    qo_spec = pl.BlockSpec((None, None, None, group * tile_q, d),
                           lambda b_, g_, i_, *_: (b_, g_, i_, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hkv, tiles),
        in_specs=[qo_spec,
                  pl.BlockSpec((None, None, tile_q, bits.shape[-1]),
                               lambda b_, g_, i_, *_: (b_, g_, i_, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((2, width, unit, hkv, d), pool_k.dtype),
            pltpu.VMEM((2, width, unit, hkv, d), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((group * tile_q, d), jnp.float32),
            pltpu.VMEM((group * tile_q, 1), jnp.float32),
            pltpu.VMEM((group * tile_q, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_tiles_kernel, scale=1.0 / (d ** 0.5),
                          block=block, per_page=per_page, tile_q=tile_q,
                          rows=heads * tile_q),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, tiles, group * tile_q, d),
                                       q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_TILE_VMEM_BYTES),
        name="paged_flash_decode_tiles",
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(index, jnp.int32),
      units.reshape(-1, units.shape[-1]), count.reshape(-1), tiled(q), bits,
      pool_k.reshape(p * per_page, unit, hkv, d),
      pool_v.reshape(p * per_page, unit, hkv, d))
    out = out.reshape(b, hkv, tiles, group, tile_q, d)
    return jnp.transpose(out, (0, 2, 4, 1, 3, 5)).reshape(b, s, hq, d)


def paged_tile_attention(q, pool_k, pool_v, block_table, index, bits, *,
                         block: int, use_pallas=None, tile_q=None):
    """Attention of a CHUNK whose queries each read a subset of the row's
    blocks, the subset a (query, KV head)'s own: q [B, S, Hq, Dh] at
    positions ``index[b] + i``; pools [P, page, Hkv, Dh], the chunk's own K
    and V already written; ``bits`` [B, Hkv, S, U] int32 the membership
    (``block_select.chunk_members`` at ``tile_keys(page, block) / block``
    blocks a unit): query ``i`` reads the keys of its blocks at positions
    ``<= index[b] + i`` and no other.  Returns (o [B, S, Hq, Dh], the
    blocks the call's tiles copied — units streamed x blocks a unit,
    summed over (row, KV head, tile)).

    On the TPU the tile kernel (:func:`paged_flash_decode_tiles`: a tile of
    queries streams the union of its queries' units once); elsewhere the
    whole window gathered and masked, the oracle."""
    b, s, hq, d = q.shape
    page = pool_k.shape[1]
    unit = tile_keys(page, block)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if tile_q is None:
        tile_q = math.gcd(s, _TILE_QUERIES)
    units, count = tile_lists(bits, tile_q)
    copied = jnp.sum(count) * (unit // block)
    if use_pallas:
        return paged_flash_decode_tiles(
            q, pool_k, pool_v, block_table, index, bits, units, count,
            block=block, unit=unit, tile_q=tile_q,
            interpret=use_pallas == "interpret"), copied
    k = gather_pages(pool_k, block_table)                   # [B, L, Hkv, D]
    v = gather_pages(pool_v, block_table)
    pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    entry = jnp.minimum(pos // unit, bits.shape[-1] - 1)
    read = (bits[..., entry] >> (pos % unit // block)) & 1   # [B, Hkv, S, L]
    mask = (read != 0) & _seen(q, k, index)[:, None]
    qg = q.astype(jnp.float32).reshape(b, s, k.shape[2], -1, d)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                    k.astype(jnp.float32)) * (1.0 / (d ** 0.5))
    sc = jnp.where(mask[:, :, None], sc, -1e30)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(sc, -1),
                   v.astype(jnp.float32))
    return o.reshape(b, s, hq, d).astype(q.dtype), copied


# ---------------------------------------------------------------------------
# latent attention over CHOSEN rows (a lightning indexer's choice)
# ---------------------------------------------------------------------------

# query heads a grid point of a chunk holds (with the 32 queries of a tile:
# 1,024 rows, the dense latent call's), and tokens a step streams
_SPARSE_HEADS = 32
_SPARSE_BLOCK_TOKENS = 1024
_SPARSE_VMEM_BYTES = 64 * 2 ** 20


def latent_sparse_attention(q, pool, block_table, member, *, value_lanes,
                            scale):
    """The oracle of :func:`latent_sparse_chunk` and
    :func:`latent_sparse_decode`: :func:`latent_paged_attention` where a
    query attends the rows ``member`` [B, S, L] (bool) names and no other
    (the causal rule is the membership's: it names no row after the
    query)."""
    rows = gather_pages(pool, block_table).astype(jnp.float32)  # [B, L, W]
    scores = jnp.einsum("bqhw,bkw->bhqk", q.astype(jnp.float32),
                        rows) * scale
    scores = jnp.where(member[:, None, :, :rows.shape[1]], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkv->bqhv", probs, rows[..., :value_lanes])
    return o.astype(q.dtype)


def page_stream(tbl_ref, row, pool_hbm, buf, sem, n_pages):
    """``(start, wait)`` of a double-buffered stream of row ``row``'s first
    ``n_pages`` pages from ``pool_hbm`` ``[P, page, lanes]``: ``buf`` ``[2,
    ppb, page, lanes]``, ``sem`` two DMA semaphores.  ``start(step, slot)``
    starts the copies of step ``step``'s live pages (ids from the table)
    and zeroes the slot's dead ones — a masked position multiplies finite
    values —; ``wait(step, slot)`` waits for those copies."""
    ppb = buf.shape[1]

    def for_pages(lo, hi, fn):
        def body(p, carry):
            fn(p)
            return carry
        jax.lax.fori_loop(lo, hi, body, 0)

    def live(step):
        return jnp.clip(n_pages - step * ppb, 0, ppb)

    def copy(step, slot, p):
        return pltpu.make_async_copy(
            pool_hbm.at[tbl_ref[row, step * ppb + p]], buf.at[slot, p],
            sem.at[slot])

    def start(step, slot):
        def zero(p):
            buf[slot, p] = jnp.zeros(buf.shape[2:], buf.dtype)
        for_pages(0, live(step), lambda p: copy(step, slot, p).start())
        for_pages(live(step), ppb, zero)

    def wait(step, slot):
        for_pages(0, live(step), lambda p: copy(step, slot, p).wait())
    return start, wait


def tile_rows(x, tile: int):
    """[B, S, H, ...] -> [B, S / tile, H * tile, ...]: head ``h`` of a
    tile's query ``i`` in row ``h * tile + i`` — the rows of one grid point
    of the kernels that take a tile of queries."""
    b, s, h = x.shape[:3]
    x = x.reshape((b, s // tile, tile, h) + x.shape[3:])
    return jnp.swapaxes(x, 2, 3).reshape((b, s // tile, h * tile)
                                         + x.shape[4:])


def member_rows(member):
    """The tiled membership [B, tiles, blocks, tile, member_block] as ONE
    row a query, [B, tiles * tile, blocks * member_block]."""
    b, g, blocks, tile, mb = member.shape
    return jnp.swapaxes(member, 2, 3).reshape(b, g * tile, blocks * mb)


def _latent_sparse_kernel(tbl_ref, idx_ref, q_ref, k_hbm, m_ref, o_ref, kbuf,
                          sem, oacc_ref, mx_ref, l_ref, *, scale, tile,
                          member_block):
    """Grid (B, tiles of queries, groups of heads): the latent form of
    :func:`_paged_decode_kernel` — a row's pages streamed block by block
    into a double buffer, each block ``[T, W]`` the keys of every row of
    ``q_ref`` and its first lanes their values — where what a query sees of
    a block is its MEMBERSHIP: ``m_ref`` [blocks, tile rows, member_block]
    (non-zero: attended), a block of ``member_block`` keys an entry, the
    queries of the tile its rows.  ``q_ref`` [heads * tile, W] holds head
    ``h`` of query ``i`` in row ``h * tile + i`` (``tile`` 0: a decode
    step, one query a row, the membership's first row)."""
    b, g = pl.program_id(0), pl.program_id(1)
    _, ppb, page_size, w = kbuf.shape
    rows_n = q_ref.shape[0]
    t = ppb * page_size
    idx = idx_ref[b]
    n_live = jnp.minimum(idx + ((g + 1) * tile if tile else 1),
                         tbl_ref.shape[1] * page_size)
    n_blocks = pl.cdiv(n_live, t)
    start, wait = page_stream(tbl_ref, b, k_hbm, kbuf, sem,
                              pl.cdiv(n_live, page_size))
    oacc_ref[...] = jnp.zeros_like(oacc_ref)
    mx_ref[...] = jnp.full_like(mx_ref, bw.NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    start(0, 0)
    per = t // member_block

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _prefetch():
            start(blk + 1, 1 - slot)

        wait(blk, slot)
        rows = kbuf.at[slot].reshape(t, w)[...]
        pieces = [m_ref[blk * per + i][:max(tile, 1)] for i in range(per)]
        seen = (pieces[0] if per == 1
                else jnp.concatenate(pieces, axis=1)).astype(jnp.int32) != 0
        # the membership's blocks past the tile's last visible key were
        # never written
        seen &= blk * t + jax.lax.broadcasted_iota(
            jnp.int32, (1, t), 1) < n_live
        bias = jnp.where(seen, 0.0, bw.NEG_INF)
        if tile:
            bias = jnp.tile(bias, (rows_n // tile, 1))
        o, m, l = bw.block_accumulate(
            oacc_ref[...], mx_ref[...][:, 0], l_ref[...][:, 0], q_ref[...],
            rows, rows[:, :oacc_ref.shape[-1]], scale, bias)
        oacc_ref[...] = o
        mx_ref[...] = m[:, None]
        l_ref[...] = l[:, None]
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    o_ref[...] = bw.finalize(
        oacc_ref[...], l_ref[...][:, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "value_lanes", "tile",
                                             "interpret"))
def _latent_sparse(q, pool, block_table, index, member, *, scale,
                   value_lanes, tile, interpret):
    """q [B, G, H * max(tile, 1), W] (head ``h`` of a tile's query ``i`` in
    row ``h * tile + i``) -> [B, G, rows, value_lanes]."""
    b, g, rows, w = q.shape
    page_size = pool.shape[1]
    m_pages = block_table.shape[1]
    _, _, blocks, mrows, mb = member.shape
    # whole pages, whole entries of the membership
    hg = min(rows, _SPARSE_HEADS * tile) if tile else rows
    t = max(page_size, mb)
    while (t * 2 <= _SPARSE_BLOCK_TOKENS
           and t * 2 * hg <= _LATENT_SCORE_ELEMS):
        t *= 2
    ppb = t // page_size
    if blocks * mb < -(-m_pages * page_size // t) * t:
        raise ValueError(
            f"a membership of {blocks} blocks of {mb} keys does not cover "
            f"a table of {m_pages} pages of {page_size} in steps of {t}")
    groups = rows // hg
    table = jnp.pad(jnp.asarray(block_table, jnp.int32),
                    ((0, 0), (0, -m_pages % ppb)))

    def spec(lanes):
        return pl.BlockSpec((None, None, hg, lanes),
                            lambda b_, g_, h_, tbl, idx: (b_, g_, h_, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, g, groups),
        in_specs=[spec(w), pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((None, None, blocks, mrows, mb),
                               lambda b_, g_, h_, tbl, idx:
                               (b_, g_, 0, 0, 0))],
        out_specs=spec(value_lanes),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((hg, value_lanes), jnp.float32),
            pltpu.VMEM((hg, 1), jnp.float32),
            pltpu.VMEM((hg, 1), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_latent_sparse_kernel, scale=float(scale),
                          tile=tile, member_block=mb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, rows, value_lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_SPARSE_VMEM_BYTES),
        interpret=interpret,
        name="latent_sparse_chunk" if tile else "latent_sparse_decode",
    )(table, jnp.asarray(index, jnp.int32), q, pool, member)


def latent_sparse_chunk(q, pool, block_table, index, member, *, value_lanes,
                        scale, interpret: bool = False):
    """Latent attention of a CHUNK whose queries each attend rows of their
    own choice: q [B, S, H, W] (absorbed) at positions ``index[b] + i``;
    pool [P, page, W], the chunk's rows already written; ``member`` [B, S /
    tile, blocks, tile, member_block] the tiled membership
    (``index_select.chunk_select``).  A tile of queries streams the row's
    pages once and masks what each query did not choose: the work is the
    visible rows', the result the chosen rows'.  Returns [B, S, H,
    value_lanes]."""
    b, s, h, _ = q.shape
    tile = member.shape[3]
    o = _latent_sparse(tile_rows(q, tile), pool, block_table, index, member,
                       scale=float(scale), value_lanes=int(value_lanes),
                       tile=tile, interpret=interpret)
    o = jnp.swapaxes(o.reshape(b, s // tile, h, tile, value_lanes), 2, 3)
    return o.reshape(b, s, h, value_lanes)


def latent_sparse_decode(q, pool, block_table, index, member, *, value_lanes,
                         scale, interpret: bool = False):
    """The same for ONE query a row: q [B, H, W] at position ``index``;
    ``member`` [B, 1, blocks, rows, member_block] whose first row is the
    query's (``index_select.decode_select``).  Returns [B, H,
    value_lanes]."""
    return _latent_sparse(q[:, None], pool, block_table, index, member,
                          scale=float(scale), value_lanes=int(value_lanes),
                          tile=0, interpret=interpret)[:, 0]


def paged_flash_decode_reference(q, pool_k, pool_v, block_table, index, *,
                                 scale=None):
    """Plain-JAX block-by-block accumulation — the kernel's portable
    oracle, the same role ops.blockwise plays for the flash kernels:
    identical math (bw.block_accumulate per block of the kernel's own
    ``pages_per_block``, in page order).  Dead pages are accumulated
    under a fully-masked bias rather than skipped — numerically inert
    by the NEG_INF construction (p underflows to exactly 0, corr is
    exactly 1) — so the only divergence from the kernel is the order in
    which XLA and the kernel's matmuls sum a row: float-ulp level,
    pinned by the tests at 1e-6 alongside argmax equality."""
    b, s, h, d = q.shape
    page_size = pool_k.shape[1]
    m_pages = block_table.shape[1]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    ppb, _, _ = _plan(s, _tiled_heads(h, pool_k.dtype.itemsize), d, page_size,
                      m_pages, pool_k.dtype.itemsize)
    t = ppb * page_size
    table = jnp.pad(block_table, ((0, 0), (0, -m_pages % ppb)))
    qh = jnp.swapaxes(q, 1, 2)                       # [B, H, S, D]
    o = jnp.zeros(qh.shape, jnp.float32)
    m = jnp.full((b, h, s), bw.NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s), jnp.float32)
    qpos = index[:, None, None, None] + jnp.arange(
        s, dtype=jnp.int32)[None, None, :, None]     # [B, 1, S, 1]
    for j in range(0, table.shape[1], ppb):
        pages = table[:, j:j + ppb]                  # [B, ppb]
        k = jnp.swapaxes(pool_k[pages].reshape(b, t, h, d), 1, 2)
        v = jnp.swapaxes(pool_v[pages].reshape(b, t, h, d), 1, 2)
        kpos = (j * page_size + jnp.arange(t, dtype=jnp.int32)
                )[None, None, None, :]               # [1, 1, 1, T]
        bias = jnp.where(kpos <= qpos, 0.0, bw.NEG_INF)
        o, m, l = bw.block_accumulate(o, m, l, qh, k, v, scale, bias)
    return jnp.swapaxes(bw.finalize(o, l).astype(q.dtype), 1, 2)


def paged_attention_auto(q, pool_k, pool_v, block_table, index, *,
                         window_pages=None, use_pallas=None, window=None,
                         scale=None, value_lanes=None):
    """Dispatch between the kernel and the gather oracle.

    ``use_pallas``: None = auto (kernel on TPU — the default-on flag —
    gather elsewhere); True = kernel; "interpret" = kernel through the
    Pallas interpreter (CPU kernel validation); False = gather.
    ``window_pages`` (static) trims the GATHER path's window exactly as
    before; the kernel ignores it — its loop stops at the row's own
    last page without a per-window recompile.  ``window`` (static) is
    the layer's attention window in tokens, for both.  ``value_lanes``
    set: ``pool_k`` is a latent pool and ``pool_v`` None (``scale`` and
    ``value_lanes`` say what the row is; :func:`latent_paged_attention`);
    ``pool_v`` None without it: a pool of ``[k | v]`` rows
    (:func:`paged_attention`)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if value_lanes is not None:
        if use_pallas:
            return paged_flash_decode(
                q, pool_k, None, block_table, index, scale=scale,
                interpret=use_pallas == "interpret", value_lanes=value_lanes)
        table = (block_table if window_pages is None
                 else block_table[:, :window_pages])
        return latent_paged_attention(q, pool_k, table, index,
                                      value_lanes=value_lanes, scale=scale)
    if use_pallas:
        return paged_flash_decode(q, pool_k, pool_v, block_table, index,
                                  interpret=use_pallas == "interpret",
                                  window=window)
    table = (block_table if window_pages is None
             else block_table[:, :window_pages])
    if window is None:
        return paged_attention(q, pool_k, pool_v, table, index)
    return paged_attention(q, pool_k, pool_v, table, index, window=window)
