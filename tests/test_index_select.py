"""The lightning indexer's choice (``dtf_tpu/ops/index_select.py``) and the
attention over it (``ops/paged_attention.py`` ``latent_sparse_*``): the
kernels in interpret mode against ``scores`` + ``lax.top_k`` and against
the gather oracles, at small sizes on the CPU.  Index queries and keys are
small integers and the weights powers of two wherever a test compares a
CHOICE, so that every product and sum is exact in any order and a tie is a
tie."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from dtf_tpu.ops import index_select as ix  # noqa: E402

pa = importlib.import_module("dtf_tpu.ops.paged_attention")
H, D, PAGE, M, POOL = 4, 16, 32, 6, 25


def _pool(rng, dtype=jnp.bfloat16, lanes=D, low=-3, high=4):
    return jnp.asarray(rng.integers(low, high, (POOL, PAGE, lanes)), dtype)


def _tables(rng, rows):
    return jnp.asarray(np.stack([rng.permutation(np.arange(1, POOL))[:M]
                                 for _ in range(rows)]), jnp.int32)


def _queries(rng, b, s):
    q = jnp.asarray(rng.integers(-3, 4, (b, s, H, D)), jnp.bfloat16)
    w = jnp.asarray(2.0 ** rng.integers(-2, 2, (b, s, H))
                    * rng.choice([-1.0, 1.0], (b, s, H)), jnp.float32)
    return q, w


def _tiled(member, tile, blocks):
    """bool [B, S, L] in the kernels' layout, [B, S / tile, blocks, tile,
    MEMBER_BLOCK] (int8 where a tile is 32 queries, else int32)."""
    member = np.asarray(member)
    b, s, n = member.shape
    member = np.pad(member, ((0, 0), (0, -s % tile),
                             (0, blocks * ix.MEMBER_BLOCK - n)))
    tiled = member.reshape(b, -1, tile, blocks, ix.MEMBER_BLOCK)
    return jnp.asarray(np.swapaxes(tiled, 2, 3),
                       jnp.int8 if tile % 32 == 0 else jnp.int32)


def _untiled(tiled, s, n):
    """The inverse: bool [B, S, L]."""
    tiled = np.asarray(tiled)
    b, g, blocks, tile, mb = tiled.shape
    return np.swapaxes(tiled, 2, 3).reshape(
        b, g * tile, blocks * mb)[:, :s, :n] != 0


def _top_k_members(score, k):
    """``lax.top_k``'s choice as membership: the oracle of the oracle."""
    score = np.asarray(score)
    out = np.zeros(score.shape, bool)
    _, best = jax.lax.top_k(jnp.asarray(score), min(k, score.shape[-1]))
    np.put_along_axis(out, np.asarray(best), True, -1)
    return out & (score > -np.inf)


@pytest.mark.parametrize("k", [1, 7, 64, 200])
def test_members_is_top_k_with_ties_to_the_lower_position(k):
    """Counts under and over ``k``, ties everywhere (small integers),
    rows of one visible key."""
    rng = np.random.default_rng(k)
    score = rng.integers(-4, 5, (3, 50, 192)).astype(np.float32)
    t = rng.integers(0, 192, (3, 50))
    t[0, 0], t[0, 1] = 0, 191
    score = np.where(np.arange(192) <= t[..., None], score, -np.inf)
    got = np.asarray(ix.members(jnp.asarray(score), k))
    assert (got == _top_k_members(score, k)).all()
    assert (got.sum(-1) == np.minimum(t + 1, k)).all()


def test_the_image_orders_as_the_floats_do():
    x = np.asarray([-np.inf, -3e38, -1.5, -1e-30, 0.0, 1e-30, 2.0, 3e38,
                    np.inf], np.float32)
    img = np.asarray(ix._image(jnp.asarray(x)))
    assert (np.diff(img.astype(np.int64)) > 0).all()
    assert img.min() > -2 ** 31                 # under every image: unseen


@pytest.mark.parametrize("form", ["bool", "chunk", "decode"])
@pytest.mark.parametrize("chose", ["top", "everything"])
def test_rows_chosen_counts_the_membership_itself(form, chose):
    """What a query attends is COUNTED from its membership, in either
    form: a choice of ``k`` reads ``min(t + 1, k)``, and a membership that
    names every visible row — the selection left out — reads ``t + 1``,
    whatever a reckoning from positions would say.  What lies past a
    query's position (a tiled block the kernel never wrote) and a decode
    tile's seven padding queries do not count."""
    rng = np.random.default_rng(11)
    b, s, n, k = 2, (1 if form == "decode" else 64), M * PAGE, 24
    t = (rng.integers(0, n, (b, 1)) if form == "decode"
         else np.asarray([[96], [40]]) + np.arange(s)[None])
    seen = np.arange(n) <= t[..., None]
    score = np.where(seen, rng.normal(size=(b, s, n)), -np.inf)
    member = np.asarray(ix.members(jnp.asarray(score, jnp.float32),
                                   k if chose == "top" else n))
    want = np.minimum(t + 1, k) if chose == "top" else t + 1
    assert (member.sum(-1) == want).all()
    if form == "bool":
        given = jnp.asarray(member)
    else:
        tile = ix.CHUNK_QUERIES if form == "chunk" else ix.DECODE_QUERIES
        tiled = np.array(_tiled(member, tile, ix.member_blocks(M, PAGE)))
        keys = (np.arange(tiled.shape[2])[:, None, None] * ix.MEMBER_BLOCK
                + np.arange(ix.MEMBER_BLOCK))
        # ones where the kernel never wrote: past the tile's last query
        last = t.reshape(b, tiled.shape[1], -1)[:, :, -1]
        tiled = np.where(keys > last[:, :, None, None, None], 1, tiled)
        if form == "decode":
            tiled[:, :, :, 1:] = 1              # nobody's queries
        given = jnp.asarray(tiled)
    got = np.asarray(ix.rows_chosen(given, jnp.asarray(t, jnp.int32)))
    assert got.dtype == np.int32 and (got == want).all()


def test_the_tiled_membership_covers_whole_steps():
    blocks = ix.member_blocks(M, PAGE)
    assert blocks * ix.MEMBER_BLOCK >= M * PAGE
    assert blocks * ix.MEMBER_BLOCK % ix._DECODE_BLOCK == 0
    member = np.random.default_rng(1).random((2, 64, 192)) < 0.3
    for tile, dtype in ((ix.CHUNK_QUERIES, jnp.int8),
                        (ix.DECODE_QUERIES, jnp.int32)):
        tiled = _tiled(member, tile, blocks)
        assert tiled.shape == (2, 64 // tile, blocks, tile, ix.MEMBER_BLOCK)
        assert tiled.dtype == dtype == ix._member_dtype(tile)
        assert (_untiled(tiled, 64, 192) == member).all()
    with pytest.raises(ValueError, match="do not divide"):
        ix.member_blocks(4, 48)


@pytest.mark.parametrize("start,k", [(0, 24), (32, 24), (96, 24), (128, 64),
                                     (64, 200)])
def test_chunk_select_is_scores_and_top_k(start, k):
    """The kernel against ``scores`` + ``lax.top_k`` for a chunk of 64
    queries (two tiles) at offset ``start``: every query its own visible
    count — under ``k`` (all of them chosen), over it, and across it inside
    the chunk — with ties (integers) cut to the lower position."""
    rng = np.random.default_rng(start + k)
    pool, table = _pool(rng), _tables(rng, 2)
    q, w = _queries(rng, 2, 64)
    index = jnp.asarray([start, max(start - 32, 0)], jnp.int32)
    t = index[:, None] + jnp.arange(64, dtype=jnp.int32)[None]
    score = ix.scores(q, w, pa.gather_pages(pool, table), t)
    want = _top_k_members(score, k)
    assert (want == np.asarray(ix.members(score, k))).all()
    tiled = ix.chunk_select(q, w, pool, table, index, k=k, interpret=True)
    got = _untiled(tiled, 64, M * PAGE)
    # a block past a tile's last visible key is not written: compare what
    # a query can see
    seen = np.arange(M * PAGE) <= np.asarray(t)[..., None]
    assert ((got & seen) == want).all()
    assert (want.sum(-1) == np.minimum(np.asarray(t) + 1, k)).all()
    # ... and the count of what the kernel wrote is the choice's
    assert (np.asarray(ix.rows_chosen(tiled, t)) == want.sum(-1)).all()


def test_decode_select_is_scores_and_top_k():
    """One query a row: a row of ONE visible key, a row under ``k``, a row
    that has just entered a page by one token, a long row; the kernel's
    seven padding queries are nobody's."""
    rng = np.random.default_rng(7)
    pool, table = _pool(rng), _tables(rng, 4)
    q, w = _queries(rng, 4, 1)
    t = jnp.asarray([0, 17, 2 * PAGE, M * PAGE - 1], jnp.int32)
    score = ix.scores(q, w, pa.gather_pages(pool, table), t[:, None])
    want = _top_k_members(score, 24)
    tiled = ix.decode_select(q[:, 0], w[:, 0], pool, table, t, k=24,
                             interpret=True)
    assert tiled.shape[3] == ix.DECODE_QUERIES and tiled.dtype == jnp.int32
    got = _untiled(tiled, 1, M * PAGE)
    seen = np.arange(M * PAGE) <= np.asarray(t)[:, None, None]
    assert ((got & seen) == want).all()
    assert want.sum(-1).ravel().tolist() == [1, 18, 24, 24]
    assert np.asarray(ix.rows_chosen(tiled, t[:, None])).ravel().tolist() \
        == [1, 18, 24, 24]


def test_a_tie_at_the_kth_place_goes_to_the_lower_position():
    """Every key the same: every score ties, and the first ``k`` positions
    are the choice, in the kernel as in ``lax.top_k``."""
    pool = jnp.ones((POOL, PAGE, D), jnp.bfloat16)
    table = jnp.arange(1, M + 1, dtype=jnp.int32)[None]
    q = jnp.ones((1, 32, H, D), jnp.bfloat16)
    w = jnp.ones((1, 32, H), jnp.float32)
    index = jnp.asarray([96], jnp.int32)
    tiled = ix.chunk_select(q, w, pool, table, index, k=40, interpret=True)
    got = _untiled(tiled, 32, M * PAGE)[0]
    assert (got[:, :40]).all() and not got[:, 40:128].any()
    # ... and where only SOME tie: the larger scores first, then the ties
    # from the left
    keys = np.ones((POOL, PAGE, D), np.float32)
    keys[3, 5], keys[3, 9] = 2.0, 2.0           # page 3 = positions 64-95
    pool = jnp.asarray(keys, jnp.bfloat16)
    tiled = ix.chunk_select(q, w, pool, table, index, k=40, interpret=True)
    got = _untiled(tiled, 32, M * PAGE)[0]
    want = sorted([64 + 5, 64 + 9] + list(range(38)))
    assert (np.flatnonzero(got[0]) == want).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_latent_sparse_chunk_is_the_masked_oracle(dtype):
    """The chunk kernel against the gather oracle under the same mask:
    blocks in which a query chose nothing (a fully masked block before the
    first chosen row) are inert."""
    rng = np.random.default_rng(3)
    w_lanes, v_lanes, heads = 128, 64, 4
    pool = jnp.asarray(rng.normal(size=(POOL, PAGE, w_lanes)) * 0.5, dtype)
    table = _tables(rng, 2)
    q = jnp.asarray(rng.normal(size=(2, 64, heads, w_lanes)) * 0.3, dtype)
    index = jnp.asarray([96, 64], jnp.int32)
    t = np.asarray(index)[:, None] + np.arange(64)[None]
    member = (rng.random((2, 64, M * PAGE)) < 0.15)
    member[0, :, :PAGE] = False                 # nothing in the first page
    member &= np.arange(M * PAGE) <= t[..., None]
    member[..., 40] = True
    tiled = _tiled(member, ix.CHUNK_QUERIES, ix.member_blocks(M, PAGE))
    want = pa.latent_sparse_attention(q, pool, table, jnp.asarray(member),
                                      value_lanes=v_lanes, scale=0.2)
    got = pa.latent_sparse_chunk(q, pool, table, index, tiled,
                                 value_lanes=v_lanes, scale=0.2,
                                 interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-6
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_latent_sparse_decode_is_the_masked_oracle():
    rng = np.random.default_rng(4)
    w_lanes, v_lanes, heads = 128, 64, 4
    pool = jnp.asarray(rng.normal(size=(POOL, PAGE, w_lanes)) * 0.5,
                       jnp.float32)
    table = _tables(rng, 3)
    q = jnp.asarray(rng.normal(size=(3, heads, w_lanes)) * 0.3, jnp.float32)
    t = jnp.asarray([0, 2 * PAGE, M * PAGE - 1], jnp.int32)
    member = rng.random((3, 1, M * PAGE)) < 0.2
    member &= np.arange(M * PAGE) <= np.asarray(t)[:, None, None]
    member[:, :, 0] = True
    tiled = _tiled(member, ix.DECODE_QUERIES, ix.member_blocks(M, PAGE))
    want = pa.latent_sparse_attention(q[:, None], pool, table,
                                      jnp.asarray(member),
                                      value_lanes=v_lanes, scale=0.2)[:, 0]
    got = pa.latent_sparse_decode(q, pool, table, t, tiled,
                                  value_lanes=v_lanes, scale=0.2,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def _latent_layer(rng, dtype, heads, rank, nope, rope, dv, rows):
    """A latent pool whose rows are ``[c_kv | k_rope | 0]`` in 128 lanes,
    ``rows`` tables over it, and ``kv_b``."""
    lanes = 128
    pool = np.zeros((POOL, PAGE, lanes), np.float32)
    pool[..., :rank + rope] = rng.normal(size=(POOL, PAGE, rank + rope)) * 0.5
    w_kvb = rng.normal(size=(rank, heads, nope + dv)) * 0.3
    return (jnp.asarray(pool, dtype), _tables(rng, rows),
            jnp.asarray(w_kvb, dtype))


def _absorbed_oracle(q, w_kvb, pool, table, member, nope, rank, scale):
    """``latent_sparse_attention`` over the absorbed image of ``q`` [B, S,
    H, nope + rope], carried back through ``kv_b``'s value half: float32
    throughout, from the inputs as stored."""
    f32 = jnp.float32
    q, w_kvb, pool = q.astype(f32), w_kvb.astype(f32), pool.astype(f32)
    hi = jax.lax.Precision.HIGHEST
    q_abs = jnp.einsum("bshn,rhn->bshr", q[..., :nope], w_kvb[..., :nope],
                       precision=hi)
    pad = pool.shape[-1] - rank - (q.shape[-1] - nope)
    q_abs = jnp.concatenate(
        [q_abs, q[..., nope:], jnp.zeros(q.shape[:3] + (pad,), f32)], -1)
    o = pa.latent_sparse_attention(q_abs, pool, table, jnp.asarray(member),
                                   value_lanes=rank, scale=scale)
    return jnp.einsum("bshr,rhv->bshv", o, w_kvb[..., nope:], precision=hi)


# (the chunks' first positions a row, queries a chunk): with the walk in
# steps of 64 keys, none of it / two whole steps and one / a page and a
# half step / one step a row and none
_EXPANDED_STARTS = {"start_0_past_top": ((0, 0), 64),
                    "deep_in_the_walk": ((128, 64), 64),
                    "a_page_not_a_step": ((96, 32), 64),
                    "rows_apart": ((64, 0), 32)}


@pytest.mark.parametrize("form", ["rope_in_head", "rope_shared"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(_EXPANDED_STARTS))
def test_the_expanded_masked_chunk_is_the_masked_oracle(case, dtype, form,
                                                        monkeypatch):
    """A chunk that attends EXPANDED under its membership
    (``latent_chunk_attention`` with ``member``, the kernel in interpret
    mode and the same walk in plain JAX) against the gather oracle over the
    absorbed queries under the same membership — at both forms of the
    score, the rotary key in each head's row (16 + 8 lanes: one pass of the
    MXU with it or without) or a second product all heads share (128 + 8:
    a pass more), by the widths alone."""
    monkeypatch.setattr(pa, "EXPAND_KEYS", 64)
    rng = np.random.default_rng(11)
    heads, rank, rope, dv = 2, 24, 8, 16
    nope = 16 if form == "rope_in_head" else 128
    assert pa.rope_in_head(nope, rope) == (form == "rope_in_head")
    starts, s = _EXPANDED_STARTS[case]
    pool, table, w_kvb = _latent_layer(rng, dtype, heads, rank, nope, rope,
                                       dv, len(starts))
    index = jnp.asarray(starts, jnp.int32)
    t = np.asarray(starts)[:, None] + np.arange(s)[None]
    at = (np.asarray(table)[:, :, None] * PAGE + np.arange(PAGE)
          ).reshape(len(starts), -1)                    # flat pool rows
    rows = pool.reshape(-1, pool.shape[-1])[
        np.take_along_axis(at, t, axis=1)]              # [B, S, W]
    q = jnp.asarray(rng.normal(size=(len(starts), s, heads, nope + rope))
                    * 0.4, dtype)
    member = rng.random((len(starts), s, M * PAGE)) < 0.2
    member[0, :, 64:128] = False        # a whole step of the walk unnamed
    member[:, 5, :] = False             # a query that names ONE row
    member &= np.arange(M * PAGE) <= t[..., None]
    member[:, :, 0] = True              # nobody attends nothing
    member[np.arange(len(starts))[:, None], np.arange(s)[None], t] |= (
        rng.random(t.shape) < 0.7)      # ... and not everybody itself
    member[:, 5, 1:] = False
    scale = (nope + rope) ** -0.5
    want = np.asarray(_absorbed_oracle(q, w_kvb, pool, table, member, nope,
                                       rank, scale))
    tiled = _tiled(member, ix.CHUNK_QUERIES, ix.member_blocks(M, PAGE))
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    # pages of 32: no step of the walk is whole blocks of 512 keys, and the
    # tiled membership is laid out a row a query inside
    for use_pallas, named in (("interpret", tiled),
                              (False, jnp.asarray(member))):
        got = pa.latent_chunk_attention(
            q, rows, w_kvb, pool, table, index, rank=rank, nope=nope,
            scale=scale, member=named, use_pallas=use_pallas)
        assert got.shape == (len(starts), s, heads, dv)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   atol=tol * np.abs(want).max())


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("starts", [(1152, 640), (1024, 0)],
                         ids=["inside_a_block", "whole_steps"])
def test_the_expanded_walk_reads_the_tiled_membership_as_it_lies(
        starts, dtype, monkeypatch):
    """Pages of 128 and steps of 512 keys: a step of the walk is whole
    blocks of the tiled membership and the kernel takes them as they lie
    (a ``BlockSpec`` over tiles of queries x blocks of keys); the chunk's
    own keys start at a page INSIDE a block (1,152 = 2 x 512 + 128) and
    are laid out a row a query.  Against the gather oracle."""
    monkeypatch.setattr(pa, "EXPAND_KEYS", 512)
    rng = np.random.default_rng(14)
    heads, rank, nope, rope, dv, page, m, s = 2, 24, 16, 8, 16, 128, 12, 64
    pool = np.zeros((25, page, 128), np.float32)
    pool[..., :rank + rope] = rng.normal(size=(25, page, rank + rope)) * 0.5
    pool = jnp.asarray(pool, dtype)
    w_kvb = jnp.asarray(rng.normal(size=(rank, heads, nope + dv)) * 0.3,
                        dtype)
    table = jnp.asarray(np.stack([rng.permutation(np.arange(1, 25))[:m]
                                  for _ in starts]), jnp.int32)
    index = jnp.asarray(starts, jnp.int32)
    t = np.asarray(starts)[:, None] + np.arange(s)[None]
    at = (np.asarray(table)[:, :, None] * page + np.arange(page)
          ).reshape(len(starts), -1)
    rows = pool.reshape(-1, 128)[np.take_along_axis(at, t, axis=1)]
    q = jnp.asarray(rng.normal(size=(len(starts), s, heads, nope + rope))
                    * 0.4, dtype)
    member = rng.random((len(starts), s, m * page)) < 0.1
    member[0, :, 512:1024] = False      # a whole step of the walk unnamed
    member &= np.arange(m * page) <= t[..., None]
    member[np.arange(len(starts))[:, None], np.arange(s)[None], t] = True
    scale = (nope + rope) ** -0.5
    want = np.asarray(_absorbed_oracle(q, w_kvb, pool, table, member, nope,
                                       rank, scale))
    tiled = _tiled(member, ix.CHUNK_QUERIES, ix.member_blocks(m, page))
    got = pa.latent_chunk_attention(
        q, rows, w_kvb, pool, table, index, rank=rank, nope=nope,
        scale=scale, member=tiled, use_pallas="interpret")
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=tol * np.abs(want).max())


@pytest.mark.parametrize("use_pallas", [False, "interpret"],
                         ids=["blockwise", "kernel"])
@pytest.mark.parametrize("carried", [True, False])
def test_a_block_the_membership_names_nothing_in_is_inert(use_pallas,
                                                          carried):
    """A block of keys in which no query's membership names anything leaves
    the carry as it came, to the bit, and a query that has seen nothing yet
    stays at ``(0, NEG_INF)``: no NaN from an all-masked block."""
    from dtf_tpu.ops import blockwise as bw
    from dtf_tpu.ops.flash_attention import flash_forward
    rng = np.random.default_rng(12)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 32, d)), jnp.float32)
               for d in (24, 24, 16))
    carry = None
    if carried:
        carry = (jnp.asarray(rng.normal(size=(1, 2, 32, 16)), jnp.float32),
                 jnp.asarray(rng.normal(size=(1, 2, 32, 1)), jnp.float32))
    o, lse = flash_forward(q, k, v, scale=0.2, carry=carry,
                           member=jnp.zeros((1, 32, 32), jnp.int8),
                           use_pallas=use_pallas)
    if carried:
        assert (np.asarray(o) == np.asarray(carry[0])).all()
        assert (np.asarray(lse) == np.asarray(carry[1])).all()
    else:
        assert np.isfinite(np.asarray(o)).all()
        assert (np.asarray(lse) <= bw.NEG_INF).all()
        # ... and the first key it does see is all it attends
        member = jnp.zeros((1, 32, 32), jnp.int8).at[:, :, 7].set(1)
        o2, _ = flash_forward(q, k, v, scale=0.2, carry=(o, lse),
                              member=member, use_pallas=use_pallas)
        np.testing.assert_allclose(
            np.asarray(o2), np.broadcast_to(np.asarray(v)[:, :, 7:8],
                                            o2.shape), atol=1e-6)


def test_member_rows_is_the_tiled_membership_a_row_a_query():
    rng = np.random.default_rng(13)
    member = rng.random((2, 64, M * PAGE)) < 0.3
    blocks = ix.member_blocks(M, PAGE)
    got = np.asarray(pa.member_rows(_tiled(member, ix.CHUNK_QUERIES, blocks)))
    assert got.shape == (2, 64, blocks * ix.MEMBER_BLOCK)
    assert (got[..., :M * PAGE] != 0).tolist() == member.tolist()
    assert not got[..., M * PAGE:].any()


def test_a_choice_of_everything_is_dense_latent_attention():
    """While a query sees ``k`` rows or fewer it attends all of them: the
    sparse path under that membership is the dense oracle."""
    rng = np.random.default_rng(5)
    pool = jnp.asarray(rng.normal(size=(POOL, PAGE, 128)) * 0.5, jnp.float32)
    table = _tables(rng, 1)
    q = jnp.asarray(rng.normal(size=(1, 32, 4, 128)) * 0.3, jnp.float32)
    index = jnp.asarray([64], jnp.int32)
    t = index[:, None] + jnp.arange(32, dtype=jnp.int32)[None]
    member = ix.members(jnp.where(
        jnp.arange(M * PAGE) <= t[..., None], 0.0, -jnp.inf), 4096)
    want = pa.latent_paged_attention(q, pool, table, index, value_lanes=64,
                                     scale=0.2)
    got = pa.latent_sparse_attention(q, pool, table, member, value_lanes=64,
                                     scale=0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_the_indexed_model_serves_through_the_engine():
    """Through ``ServeEngine`` on the CPU's gather path, at a width of 64:
    prompts under and over ``top`` (24), tokens as the whole-sequence
    forward chooses them; the registry's sibling builds the same kind of
    tree."""
    from dtf_tpu.models import build_model
    from dtf_tpu.serve.engine import ServeEngine
    sibling, _ = build_model("routed_decoder_indexed", num_classes=256,
                             dtype=jnp.float32)
    assert sibling.stats_names[3:] == (
        "index_keys_scored", "latent_rows_visible", "latent_rows_selected",
        "rows_dense_path")
    assert sibling.layer_indexer == ("full", "shared", "shared", "shared",
                                     "full") and not sibling.carries_state
    model, _ = build_model(
        "routed_decoder", num_classes=128, dtype=jnp.float32, num_layers=3,
        d_model=64, num_heads=2, q_lora_rank=32, kv_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_interleave=True, indexer=(2, 16, 24, 8),
        layer_indexer=("full", "shared", "full"), num_dense_layers=3,
        dense_width=96, activation="silu")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, n).tolist() for n in (20, 50)]
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    engine = ServeEngine(model, params, max_batch=2, max_seq_len=64,
                         kv_page_size=16, kv_pool_pages=9, prefill_chunk=32)
    try:
        assert engine.metrics.gauge("serve_index_bytes_per_token",
                                    unit="bytes").value == 2 * 16 * 4
        served = [list(engine.submit(p, max_new_tokens=4).result(
            timeout=100).tokens) for p in prompts]
    finally:
        engine.stop(drain=True, timeout=30)
    forward = jax.jit(lambda tokens: model.apply({"params": params}, tokens))
    for p, got in zip(prompts, served):
        # causal: the whole row's forward holds every step's logits
        logits = forward(jnp.asarray([list(p) + got], jnp.int32))[0]
        chose = jnp.argmax(logits[len(p) - 1:len(p) - 1 + len(got)], -1)
        assert chose.tolist() == got


_CHUNKS = ["first_chunk_past_top", "start_a_whole_step", "one_real_token"]


@pytest.fixture(scope="module")
def indexed_chunks():
    """A prompt of 65 tokens prefilled in chunks of 32 (starts 0 / 32 / 64,
    the last chunk ONE real token and 31 of padding; ``top`` 24, so the
    first chunk is past it), the walk in steps of 32 keys, by a model whose
    second layer is ``full`` or ``shared``: every chunk's logits at its
    sampled position and its call's counts, by (second layer, ``use_pallas``,
    whether the rule was left on), and the whole-sequence forward's."""
    from dtf_tpu.models import build_model
    from dtf_tpu.models import routed_decoder as rd
    from dtf_tpu.serve.decode import Decoder
    prompt = np.random.default_rng(21).integers(0, 128, 65, dtype=np.int32)
    table = np.zeros((8,), np.int32)
    table[:6] = 2 + np.random.default_rng(22).permutation(6)
    out = {"prompt": prompt}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "EXPAND_KEYS", 32)
        for second in ("full", "shared"):
            model, _ = build_model(
                "routed_decoder", num_classes=128, dtype=jnp.float32,
                num_layers=2, d_model=64, num_heads=2, q_lora_rank=32,
                kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, rope_interleave=True, indexer=(2, 16, 24, 8),
                layer_indexer=("full", second), num_dense_layers=2,
                dense_width=96, activation="silu")
            params = model.init(jax.random.key(3),
                                jnp.zeros((1, 16), jnp.int32))["params"]
            out[second] = model
            with jax.default_matmul_precision("highest"):
                out[second, "forward"] = np.asarray(model.apply(
                    {"params": params}, prompt[None]))[0]
            for use_pallas in (False, "interpret"):
                for expanded in (True, False):
                    with mp.context() as off:
                        if not expanded:
                            off.setattr(rd, "latent_expands",
                                        lambda *a: False)
                        dec = Decoder(model.clone(use_pallas=use_pallas),
                                      params, num_slots=1, max_seq_len=128,
                                      kv_page_size=16, kv_pool_pages=9)
                        cache, rows = dec.fresh_cache(), []
                        with jax.default_matmul_precision("highest"):
                            for start in (0, 32, 64):
                                chunk = np.zeros((32,), np.int32)
                                real = prompt[start:start + 32]
                                chunk[:len(real)] = real
                                _, cache, last = dec.prefill_chunk(
                                    cache, chunk, table, start,
                                    len(real) - 1, 0.0, seed=0)
                                rows.append((np.asarray(last), np.asarray(
                                    dec.last_stats["counts"])))
                        out[second, use_pallas, expanded] = rows
    return out


@pytest.mark.parametrize("use_pallas", [False, "interpret"],
                         ids=["blockwise", "kernel"])
@pytest.mark.parametrize("second", ["full", "shared"])
@pytest.mark.parametrize("chunk", range(3), ids=_CHUNKS)
def test_an_indexer_layers_chunk_attends_expanded_under_its_membership(
        indexed_chunks, chunk, second, use_pallas):
    """A chunk of an indexer layer long enough to repay it attends EXPANDED
    (its keys through ``kv_b``, the membership the mask of every block of
    the walk) and serves what the ABSORBED chunk serves and what the
    whole-sequence forward computes — the first chunk already past ``top``,
    a chunk a whole step into the walk, a last chunk entered by one real
    token; a ``shared`` layer by the choice of the ``full`` layer below.
    Its call counts the rows it carried through ``kv_b`` behind the counts
    a step has, which are the absorbed chunk's to the row."""
    model = indexed_chunks[second]
    prompt = indexed_chunks["prompt"]
    got, counts = indexed_chunks[second, use_pallas, True][chunk]
    absorbed, read = indexed_chunks[second, use_pallas, False][chunk]
    start = 32 * chunk
    at = min(start + 31, len(prompt) - 1)
    want = indexed_chunks[second, "forward"][at]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale
    assert np.abs(got - absorbed).max() <= 1e-4 * scale
    dm = model.clone(decode=True)
    assert dm.latent_expanded(32) and not dm.latent_expanded(1)
    names = dm.call_stats_names(32)
    assert names == dm.stats_names + ("latent_tokens_expanded",)
    assert dm.call_stats_names(1) == dm.stats_names
    assert len(counts) == len(names) and len(read) == len(dm.stats_names)
    assert counts[-1] == 2 * (start + 32)
    assert (counts[:-1] == read).all()
