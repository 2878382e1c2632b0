"""Paged KV-cache primitives: page-pool writes, block-table gathers,
and gather-attention for serving decode.

The contiguous serving cache (one [num_slots, max_seq_len, H, Dh] slab
per layer) reserves worst-case HBM for every slot: a 4-token request
holds the same memory as a max-length one.  The paged layout is the
vLLM/PagedAttention discipline adapted to fixed-shape XLA:

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
window, f32 softmax) also lives here — it is the shared score/softmax
math for both the contiguous cache path (models/transformer.py) and
the paged gather path.

Two formulations of attention-over-pages coexist:

  gather (``paged_attention``)      — materialize the gathered window,
      mask, dense softmax.  Portable, the CPU-default oracle.  Pays the
      PR-3 gather tax (~3% of contiguous step time) plus, for prefill
      chunks, a host-side STATIC window trim (one compile per window).
  kernel (``paged_flash_decode``)   — a Pallas kernel that reads KV
      pages THROUGH the block table in-kernel (scalar-prefetched, so
      each page's DMA source address is computed before the body runs):
      no gathered window ever materializes, and the window trim is
      FUSED — pages past ``index + S − 1`` are skipped by a dynamic
      ``pl.when`` predicate, so one compile covers every chunk index
      where the gather path needed one per static window.  Online-
      softmax carry in VMEM scratch (ops.blockwise math, the same rule
      the flash kernels use).

``paged_attention_auto`` dispatches between them: the kernel by default
on TPU, the gather oracle elsewhere; ``use_pallas="interpret"`` runs
the kernel through the Pallas interpreter on CPU (how tier-1 pins
kernel ≡ oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtf_tpu.ops import blockwise as bw


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


def write_pages(pool, new, block_table, index, page_aligned: bool = False):
    """Scatter a [B, S, H, Dh] chunk of K or V into the page pool.

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
    num_pages, page_size, h, dh = pool.shape
    b, s = new.shape[:2]
    capacity = block_table.shape[1] * page_size
    if page_aligned:
        n_pages = s // page_size
        pstart = index // page_size                          # [B]
        pidx = jnp.minimum(
            pstart[:, None] + jnp.arange(n_pages, dtype=jnp.int32)[None, :],
            block_table.shape[1] - 1)
        page = jnp.take_along_axis(block_table, pidx, axis=1)  # [B, n]
        return pool.at[page.reshape(-1)].set(
            new.reshape(b * n_pages, page_size, h, dh))
    pos = index[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    pos = jnp.minimum(pos, capacity - 1)                     # [B, S]
    page = jnp.take_along_axis(block_table, pos // page_size, axis=1)
    flat = page * page_size + pos % page_size                # [B, S]
    pool_flat = pool.reshape(num_pages * page_size, h, dh)
    pool_flat = pool_flat.at[flat.reshape(-1)].set(
        new.reshape(b * s, h, dh))
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
    num_pages, page_size, h, dh = pool.shape
    b, m = block_table.shape
    return pool[block_table].reshape(b, m * page_size, h, dh)


def paged_attention(q, pool_k, pool_v, block_table, index):
    """Attention of a chunk of queries over a slot's paged KV history.

    q [B, S, H, Dh] — S new queries per row, the row's global positions
    being ``index[b] + i``; pool_k/pool_v [P, page_size, H, Dh];
    block_table [B, M]; index [B] int32.  The chunk's own K/V must
    already be written into the pool (write-then-attend, exactly the
    contiguous cache path's ordering), so query i sees logical
    positions j <= index + i: the just-written chunk causally, the
    prefix fully, and never the unwritten tail (masked)."""
    k = gather_pages(pool_k, block_table)   # [B, L, H, Dh]
    v = gather_pages(pool_v, block_table)
    s = q.shape[1]
    capacity = k.shape[1]
    jpos = jnp.arange(capacity, dtype=jnp.int32)[None, None, :]
    qpos = (index[:, None, None]
            + jnp.arange(s, dtype=jnp.int32)[None, :, None])
    return cached_attention(q, k, v, jpos <= qpos)


# ---------------------------------------------------------------------------
# Pallas paged flash-decode kernel
# ---------------------------------------------------------------------------

def _paged_decode_kernel(tbl_ref, idx_ref, q_ref, k_ref, v_ref, o_ref,
                         oacc_ref, m_ref, l_ref, *, scale, page_size):
    """Grid (B, H, M): one (row, head) pair streams its pages.

    ``tbl_ref`` [B, M] and ``idx_ref`` [B] are scalar-prefetched: the
    pool in_specs' index maps read ``tbl_ref[b, j]`` to pick the DMA
    source page BEFORE the body runs — the gather never exists as an
    array.  The online-softmax carry (un-normalized o in f32, running
    max m, denominator l — ops.blockwise math, shared with the flash
    kernels) lives in VMEM scratch across the sequential page
    dimension.  Pages whose first position lies past ``index + S − 1``
    are skipped by a DYNAMIC predicate — the window trim the gather
    path did with a static slice, fused, so one compile covers every
    chunk index.  Within a live page the causal mask is positional:
    key position ``j·page + t`` is admitted iff ≤ ``index + i`` (the
    query's global position) — exactly the gather oracle's mask."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    s = q_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        oacc_ref[...] = jnp.zeros_like(oacc_ref)
        m_ref[...] = jnp.full_like(m_ref, bw.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    idx = idx_ref[b]
    live = j * page_size <= idx + s - 1

    @pl.when(live)
    def _accumulate():
        kpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        qpos = idx + jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0)
        bias = jnp.where(kpos <= qpos, 0.0, bw.NEG_INF)
        o, m, l = bw.block_accumulate(
            oacc_ref[...], m_ref[...][:, 0], l_ref[...][:, 0],
            q_ref[...], k_ref[...], v_ref[...], scale, bias)
        oacc_ref[...] = o
        m_ref[...] = m[:, None]
        l_ref[...] = l[:, None]

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = bw.finalize(
            oacc_ref[...], l_ref[...][:, 0]).astype(o_ref.dtype)


def paged_flash_decode(q, pool_k, pool_v, block_table, index, *,
                       scale=None, interpret: bool = False):
    """Attention of a chunk of queries over a slot's paged KV history,
    reading pages through the block table IN-KERNEL.

    Same contract as :func:`paged_attention` (write-then-attend; q
    [B, S, H, Dh], pools [P, page_size, H, Dh], block_table [B, M],
    index [B] int32) — the kernel is the hardware-speed formulation:
    no materialized gathered window, fused window trim (dead pages
    skipped dynamically), one compile per chunk SHAPE instead of one
    per static window."""
    b, s, h, d = q.shape
    page_size = pool_k.shape[1]
    m_pages = block_table.shape[1]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    qh = jnp.swapaxes(q, 1, 2)                       # [B, H, S, D]
    # The kernel sees the pools head-major, [P, H, page, Dh]: a head's
    # page is then a (page, Dh) block over the array's LAST TWO dims —
    # the only placement the TPU lowering accepts for a sub-(8, 128)
    # extent (a (page, None, Dh) block on [P, page, H, Dh] leaves the
    # squeezed head second-to-last and is refused, at any H or Dh).
    # The transpose is logical: XLA's TPU layout for a [P, page, H, Dh]
    # array with small H already stores (page, Dh) minor-most (checked
    # in the compiled HLO at 6 and 3 heads × 128 — a bitcast, no copy);
    # where it does not, XLA inserts the copy and the result is the same.
    pool_spec = pl.BlockSpec(
        (None, None, page_size, d),
        lambda b_, h_, j, tbl, idx: (tbl[b_, j], h_, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, m_pages),
        in_specs=[
            pl.BlockSpec((None, None, s, d),
                         lambda b_, h_, j, tbl, idx: (b_, h_, 0, 0)),
            pool_spec,
            pool_spec,
        ],
        out_specs=pl.BlockSpec((None, None, s, d),
                               lambda b_, h_, j, tbl, idx: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((s, d), jnp.float32),
            pltpu.VMEM((s, 1), jnp.float32),
            pltpu.VMEM((s, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale,
                          page_size=page_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        interpret=interpret,
        name="paged_flash_decode",
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(index, jnp.int32),
      qh, jnp.swapaxes(pool_k, 1, 2), jnp.swapaxes(pool_v, 1, 2))
    return jnp.swapaxes(out, 1, 2)


def paged_flash_decode_reference(q, pool_k, pool_v, block_table, index, *,
                                 scale=None):
    """Plain-JAX page-by-page accumulation — the kernel's portable
    oracle, the same role ops.blockwise plays for the flash kernels:
    identical math (bw.block_accumulate per page, sequential page
    order).  Dead pages are accumulated under a fully-masked bias
    rather than skipped — numerically inert by the NEG_INF
    construction (p underflows to exactly 0, corr is exactly 1) — so
    the only divergence from the kernel is XLA's batched-vs-per-
    program einsum reduction order: float-ulp level, pinned by the
    tests at 1e-6 alongside argmax equality."""
    b, s, h, d = q.shape
    page_size = pool_k.shape[1]
    m_pages = block_table.shape[1]
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    qh = jnp.swapaxes(q, 1, 2)                       # [B, H, S, D]
    o = jnp.zeros(qh.shape, jnp.float32)
    m = jnp.full((b, h, s), bw.NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, s), jnp.float32)
    qpos = index[:, None, None, None] + jnp.arange(
        s, dtype=jnp.int32)[None, None, :, None]     # [B, 1, S, 1]
    for j in range(m_pages):
        k = jnp.swapaxes(pool_k[block_table[:, j]], 1, 2)  # [B, H, P, D]
        v = jnp.swapaxes(pool_v[block_table[:, j]], 1, 2)
        kpos = (j * page_size + jnp.arange(page_size, dtype=jnp.int32)
                )[None, None, None, :]               # [1, 1, 1, P]
        bias = jnp.where(kpos <= qpos, 0.0, bw.NEG_INF)
        o, m, l = bw.block_accumulate(o, m, l, qh, k, v, scale, bias)
    return jnp.swapaxes(bw.finalize(o, l).astype(q.dtype), 1, 2)


def paged_attention_auto(q, pool_k, pool_v, block_table, index, *,
                         window_pages=None, use_pallas=None):
    """Dispatch between the kernel and the gather oracle.

    ``use_pallas``: None = auto (kernel on TPU — the default-on flag —
    gather elsewhere); True = kernel; "interpret" = kernel through the
    Pallas interpreter (CPU kernel validation); False = gather.
    ``window_pages`` (static) trims the GATHER path's window exactly as
    before; the kernel ignores it — its dynamic live predicate skips
    the same pages without a per-window recompile."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return paged_flash_decode(q, pool_k, pool_v, block_table, index,
                                  interpret=use_pallas == "interpret")
    table = (block_table if window_pages is None
             else block_table[:, :window_pages])
    return paged_attention(q, pool_k, pool_v, table, index)
