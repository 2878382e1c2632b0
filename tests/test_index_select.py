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
