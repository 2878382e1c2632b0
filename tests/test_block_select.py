"""The routed decoder's block-sparse attention layers (``layer_mixer``
``sparse_block``: a query reads the blocks of its row's cache that IT
chooses through pooled keys, ``ops/block_select.py``) beside lightning
linear-attention layers (``lightning``: a constant decay a head, no write
gate, the no-erase forms of ``ops/linear_state.py``), dense MLPs and muP
scalars — against the plain reference
(``benchmark/families/reference_minicpm_sala.py``) and their own oracles.
The toy keeps the shape of the thing: S L L S, 8 query heads over 2 KV
heads of 16, blocks of 8 tokens, pooled keys over 4 at stride 2, 2 chosen
beside the first block and a window of 2, dense up to 64, pages of 32.
float32 throughout (the pools too), so what is compared is the mathematics
and not a rounding."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from dtf_tpu.models import build_model  # noqa: E402
from dtf_tpu.ops import block_select as bs  # noqa: E402
from dtf_tpu.ops import linear_state as ls  # noqa: E402
from dtf_tpu.ops.paged_attention import (  # noqa: E402
    paged_block_attention, paged_tile_attention, tile_keys)
from dtf_tpu.serve.bridge import serving_memory_plan  # noqa: E402
from dtf_tpu.serve.decode import (KV_POOL, PAGE_STATE, Decoder,  # noqa: E402
                                  cache_leaves)
from dtf_tpu.serve.engine import chunk_plan  # noqa: E402

SIZES = bs.Sizes(block=8, pool=4, stride=2, top=2, window=16, init=1,
                 dense_len=64)
TOY = dict(num_layers=4, d_model=64, num_heads=8, num_kv_heads=2,
           head_dim=16, layer_mixer=["sparse_block", "lightning",
                                     "lightning", "sparse_block"],
           sparse=list(SIZES) + [3.0], lightning=[4, 16, 9, 32],
           mup=[12.0, 1.4, 32, 4.0], rope_theta=1e4, rms_eps=1e-6,
           num_dense_layers=4, dense_width=96, activation="silu",
           max_seq_len=256)
VOCAB, PAGE, CHUNK = 128, 32, 64


@pytest.fixture(scope="module")
def toy():
    model, _ = build_model("routed_decoder", num_classes=VOCAB,
                           dtype=jnp.float32, **TOY)
    params = model.init(jax.random.key(3),
                        jnp.zeros((1, PAGE), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def reference():
    from benchmark import families
    from benchmark.lib.runtime import load_benchmark, load_cell
    cell = load_cell(load_benchmark(), "minicpm-sala-serve-longdoc")
    return families.load_reference(cell.config, ROOT)


def _ref_logits(reference, params, tokens, **controls):
    arch = reference.arch_of_model_kwargs(TOY)
    rows = reference.hidden(params, jnp.asarray(tokens), arch, **controls)
    return np.asarray(reference._head(rows, params["lm_head"]))


# ------------------------------------------------ the no-erase forms ----
def _draw(rng, b, s, h=4, d=8):
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
               for _ in range(3))
    a = jnp.broadcast_to(jnp.asarray(
        -rng.uniform(0.004, 0.9, size=(1, 1, h, 1)), jnp.float32),
        (b, s, h, d))
    return q, k, v, a


@pytest.mark.parametrize("s,block", [(64, 16), (64, 64), (128, 32)])
def test_noerase_blocked_form_equals_the_recurrence(s, block):
    """``chunked`` without the erase term = ``recurrent`` without it, at
    blocks up to the long one lightning's chunk takes, states emitted every
    32 tokens included."""
    q, k, v, a = _draw(np.random.default_rng(0), 2, s)
    want, last = ls.recurrent(q, k, v, a)
    emit = max(block, 32)
    got, states = ls.chunked(q, k, v, a, block=block, emit_every=emit)
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_allclose(states[:, -1], last, atol=2e-4)
    mid, state = ls.recurrent(q[:, :emit], k[:, :emit], v[:, :emit],
                              a[:, :emit])
    np.testing.assert_allclose(states[:, 0], state, atol=2e-4)
    # a carried state: the second half from the first half's
    got2, _ = ls.chunked(q[:, emit:], k[:, emit:], v[:, emit:], a[:, emit:],
                         None, state, block=block) if s > emit else (
                             want[:, emit:], None)
    np.testing.assert_allclose(got2, want[:, emit:], atol=2e-4)


def test_noerase_is_the_delta_rule_at_no_write_gate_only_in_name():
    """The no-erase step is NOT the delta rule with ``beta`` = 1: the
    delta rule erases what the key already reads; this one only adds."""
    q, k, v, a = _draw(np.random.default_rng(1), 1, 8)
    plain, _ = ls.recurrent(q, k, v, a)
    delta, _ = ls.recurrent(q, k, v, a, jnp.ones(q.shape[:3]))
    assert float(jnp.max(jnp.abs(plain - delta))) > 0.1
    # written out: S_t = lambda S_{t-1} + k v^T, o = S^T q
    state = np.zeros((4, 8, 8))
    for t in range(8):
        state = (np.exp(np.asarray(a[0, t]))[:, :, None] * state
                 + np.asarray(k[0, t])[:, :, None]
                 * np.asarray(v[0, t])[:, None, :])
        np.testing.assert_allclose(
            np.einsum("hk,hkv->hv", np.asarray(q[0, t]), state),
            plain[0, t], atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_noerase_kernel_equals_the_step_in_place(dtype):
    """``linear_state_decode`` with ``beta`` None (interpret mode) =
    ``paged_step`` = ``recurrent``, through a pool whose rows sit at a
    page's first token, mid-page, at a boundary and idle."""
    rng = np.random.default_rng(2)
    b, h, d, page, steps = 4, 8, 16, 4, 9
    q, k, v, a = _draw(rng, b, steps, h, d)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 9], [0, 0, 0]],
                        jnp.int32)
    pool_k = pool_s = jnp.zeros((10, h, d, d), dtype)
    outs_k, outs_s = [], []
    for t in range(steps):
        index = jnp.asarray([t, t, t, 0], jnp.int32)
        one = (q[:, t], k[:, t], v[:, t], a[:, t], None, table, index)
        o, pool_k = ls.linear_state_decode(pool_k, *one, page_size=page,
                                           interpret=True)
        outs_k.append(o)
        o, pool_s = ls.paged_step(pool_s, *one, page_size=page)
        outs_s.append(o)
    want, _ = ls.recurrent(q, k, v, a)
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(jnp.stack(outs_k, 1)[:3],
                               jnp.stack(outs_s, 1)[:3], atol=1e-4)
    np.testing.assert_allclose(jnp.stack(outs_s, 1)[:3], want[:3], atol=tol)
    np.testing.assert_allclose(pool_k[1:].astype(jnp.float32),
                               pool_s[1:].astype(jnp.float32), atol=1e-5)


# ------------------------------------------------- the pooled leaf ----
def _pooled_whole(k, sizes):
    """c_j of a whole key sequence k [S, H, D], every j whose last
    position is written."""
    n = (k.shape[0] - sizes.pool) // sizes.stride + 1
    return np.stack([np.mean(k[sizes.stride * j:sizes.stride * j
                               + sizes.pool], axis=0) for j in range(n)])


@pytest.mark.parametrize("plan", [
    [64, 32, 1, 1, 1, 1, 1, 1],         # chunks of two sizes, then steps
    [32] + [1] * 40,                    # a pooled key that spans two pages
    [96, (32, 1), 1, 1, 1, 1],          # a page entered by one real token
    [(32, 7)] + [1] * 30],              # a short prompt, padded
    ids=["chunks", "steps_across_a_page", "one_real_token", "padded"])
def test_the_pooled_leaf_is_the_pooling_of_the_whole_sequence(plan):
    """Whatever mix of chunks (whole strides, tail-padded or not) and single
    steps wrote a row, its pooled-key leaf holds, for every pooled key that
    exists, the mean of the whole key sequence's window."""
    rng = np.random.default_rng(4)
    h, d, pages = 2, 16, 9
    k_pool = jnp.zeros((pages, PAGE, h, d), jnp.float32)
    pooled = jnp.zeros((pages, PAGE // SIZES.stride, h, d), jnp.float32)
    table = jnp.asarray([[3, 1, 7, 5, 2]], jnp.int32)
    from dtf_tpu.ops.paged_attention import write_pages
    keys, at = [], 0
    for call in plan:
        s, real = call if isinstance(call, tuple) else (call, call)
        k = rng.normal(size=(1, s, h, d)).astype(np.float32)
        index = jnp.asarray([at], jnp.int32)
        k_pool = write_pages(k_pool, jnp.asarray(k), table, index,
                             page_aligned=s > 1 and s % PAGE == 0)
        pooled = bs.write_pooled(pooled, jnp.asarray(k), k_pool, table,
                                 index, SIZES)
        keys.append(k[0, :real])
        at += real
    want = _pooled_whole(np.concatenate(keys), SIZES)
    got = np.asarray(pooled[table[0]]).reshape(-1, h, d)[:len(want)]
    assert len(want) == int(bs.pooled_exist(at - 1, SIZES))
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------- the choice ----
def _setup_rows(rng, lengths, hq=8, hkv=2, d=16, pages_per_row=8):
    """Rows of these lengths in a pool: K, V, pooled keys, tables, and the
    newest query of each."""
    b = len(lengths)
    pages = 1 + b * pages_per_row
    table = np.arange(1, pages).reshape(b, pages_per_row).astype(np.int32)
    rng.shuffle(table.reshape(-1))
    k_pool = np.zeros((pages, PAGE, hkv, d), np.float32)
    v_pool = rng.normal(size=k_pool.shape).astype(np.float32)
    pooled = np.zeros((pages, PAGE // SIZES.stride, hkv, d), np.float32)
    keys = []
    for r, n in enumerate(lengths):
        k = rng.normal(size=(n, hkv, d)).astype(np.float32) * 2
        keys.append(k)
        for pos in range(n):
            k_pool[table[r, pos // PAGE], pos % PAGE] = k[pos]
        c = _pooled_whole(k, SIZES) if n >= SIZES.pool else []
        for j, row in enumerate(c):
            pooled[table[r, j * SIZES.stride // PAGE],
                   j % (PAGE // SIZES.stride)] = row
    q = rng.normal(size=(b, hq, d)).astype(np.float32) * 2
    t = np.asarray(lengths, np.int32) - 1
    return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(pooled), jnp.asarray(table), jnp.asarray(t), keys)


def test_the_kernel_scores_what_the_oracle_scores():
    """``decode_scores`` (interpret mode) = ``scores``: rows past
    ``dense_len`` of lengths that end inside a page, at its end and one
    token into the next; a row on the dense path reads zeros."""
    rng = np.random.default_rng(6)
    q, _, _, pooled, table, t, _ = _setup_rows(
        rng, [70, 96, 97, 200, 256, 40])
    want = bs.scores(q[:, None], pooled, table, t[:, None], SIZES, 0.25)
    got = bs.decode_scores(q, pooled, table, t, sizes=SIZES, scale=0.25,
                           interpret=True)
    np.testing.assert_allclose(got[:5], want[:5, 0], atol=1e-6)
    assert float(jnp.max(jnp.abs(got[5]))) == 0.0
    np.testing.assert_allclose(np.asarray(want[:5, 0]).sum(-1), 4.0,
                               atol=1e-5)         # a softmax a head, 4 heads


def test_the_table_is_the_forced_and_the_best_of_the_others():
    """``choose``, written out: the first block, the window's, and the
    ``top`` best of the others by the 5-wide maximum, ties to the lower
    block; at or under ``dense_len`` every block up to the query's."""
    rng = np.random.default_rng(7)
    j_all = 256 // SIZES.stride
    r = rng.uniform(size=(3, 2, j_all)).astype(np.float32)
    r[1, 0, :] = 0.5                                    # ties everywhere
    t = np.asarray([200, 255, 63], np.int32)
    table, count = bs.choose(jnp.asarray(r), jnp.asarray(t), SIZES)
    n = SIZES.block // SIZES.stride
    for row in range(3):
        own = t[row] // SIZES.block
        if t[row] + 1 <= SIZES.dense_len:
            assert int(count[row]) == own + 1
            for g in range(2):
                assert table[row, g, :own + 1].tolist() == list(
                    range(own + 1))
            continue
        assert int(count[row]) == SIZES.read == 5
        first_window = own - SIZES.window // SIZES.block + 1
        for g in range(2):
            big = [max(r[row, g, max(n * b - 1, 0):n * b + n])
                   for b in range(first_window)]
            order = sorted(range(SIZES.init, first_window),
                           key=lambda b: (-big[b], b))[:SIZES.top]
            assert table[row, g, :5].tolist() == (
                [0] + sorted(order) + [first_window, own])
    assert table[1, 0, :5].tolist() == [0, 1, 2, 30, 31]
    assert int(jnp.max(table[:2, :, 5:])) == 0


@pytest.mark.parametrize("k", [1, 8, 64])
def test_top_ids_is_a_sorted_top_k_without_the_sort(k):
    """``top_ids`` = ``sort(top_k(x, k)[1])``: ties to the lower index (a
    row of equal values, a row on a coarse grid), entries at -1 never
    taken."""
    rng = np.random.default_rng(12)
    x = rng.uniform(size=(5, 3, 300)).astype(np.float32)
    x[0, 0, :] = 0.5
    x[1, :, ::3] = 0.25
    x[2, :, :200] = -1.0
    x[3] = np.round(x[3], 1)
    x[4, :, 7] = 0.0
    want = np.sort(np.asarray(jax.lax.top_k(jnp.asarray(x), k)[1]), -1)
    assert (np.asarray(bs.top_ids(jnp.asarray(x), k)) == want).all()


@pytest.mark.parametrize("use_pallas", [False, "interpret"],
                         ids=["gather", "kernel"])
def test_a_row_reads_its_blocks_and_nothing_else(use_pallas):
    """``paged_block_attention`` over a table a (row, KV head) = plain
    softmax attention over exactly those blocks' keys, the last block up
    to the query; the two KV heads read DIFFERENT blocks."""
    rng = np.random.default_rng(8)
    lengths = [70, 131, 200, 33]
    q, k_pool, v_pool, pooled, table, t, keys = _setup_rows(rng, lengths)
    r = bs.scores(q[:, None], pooled, table, t[:, None], SIZES, 0.25)[:, 0]
    blocks, count = bs.choose(r, t, SIZES)
    assert blocks[2, 0].tolist() != blocks[2, 1].tolist()
    ids = bs.physical(blocks, table, PAGE, SIZES.block)
    got = paged_block_attention(q, k_pool, v_pool, ids, count,
                                t % SIZES.block, block=SIZES.block,
                                use_pallas=use_pallas)
    for row, n in enumerate(lengths):
        v = np.stack([np.asarray(v_pool)[table[row, p // PAGE], p % PAGE]
                      for p in range(n)])
        for head in range(8):
            g = head // 4
            read = [p for b in blocks[row, g, :int(count[row])].tolist()
                    for p in range(b * 8, min(b * 8 + 8, n))]
            sc = keys[row][read, g] @ np.asarray(q[row, head]) * 0.25
            w = np.exp(sc - sc.max())
            want = (w / w.sum()) @ v[read, g]
            np.testing.assert_allclose(got[row, head], want, atol=2e-5)


def test_the_membership_is_the_tables_set():
    """``members`` against ``choose``: the same set of blocks a (query, KV
    head) — ``count`` members, each in the table's first ``count`` entries
    — for queries at and under ``dense_len``, one past it, deep past it and
    under ties everywhere; and ``pack_members`` keeps every bit."""
    rng = np.random.default_rng(13)
    j_all = 256 // SIZES.stride
    t = np.asarray([0, 7, 63, 64, 65, 130, 200, 255], np.int32)
    r = rng.uniform(size=(len(t), 2, j_all)).astype(np.float32)
    r[5, 0, :] = 0.5                                    # ties everywhere
    table, count = bs.choose(jnp.asarray(r), jnp.asarray(t), SIZES)
    member = np.asarray(bs.members(jnp.asarray(r), jnp.asarray(t), SIZES))
    assert member.shape == (len(t), 2, 256 // SIZES.block)
    for row in range(len(t)):
        for g in range(2):
            want = sorted(table[row, g, :int(count[row])].tolist())
            assert np.flatnonzero(member[row, g]).tolist() == want
    assert member[5, 0].tolist() != member[5, 1].tolist()
    for per in (1, 4, 16):
        bits = np.asarray(bs.pack_members(jnp.asarray(member), per))
        assert bits.shape[-1] == -(-member.shape[-1] // per)
        back = (bits[..., None] >> np.arange(per)) & 1
        assert (back.reshape(bits.shape[:-1] + (-1,))[..., :member.shape[-1]]
                == member).all()
    with pytest.raises(ValueError, match="pack"):
        bs.pack_members(jnp.asarray(member), 32)


def _sequence(rng, n, dtype, agree=False, hq=8, hkv=2, d=16):
    """One row of ``n`` tokens written into a pool through a shuffled table:
    q [1, n, Hq, D], k, v [1, n, Hkv, D] and the three pools.  ``agree``:
    the pooled keys are PLANTED — blocks 2 and 5 of KV head 0 (3 and 9 of
    head 1) carry a large common direction that every query shares, so
    every query past them chooses the same two."""
    pages = 1 + -(-n // PAGE)
    table = np.arange(1, pages, dtype=np.int32)
    rng.shuffle(table)
    q = rng.normal(size=(1, n, hq, d)).astype(np.float32)
    k = rng.normal(size=(1, n, hkv, d)).astype(np.float32)
    v = rng.normal(size=(1, n, hkv, d)).astype(np.float32)
    if agree:
        q[..., 0] = 6.0
        k[..., 0] = -3.0
        for g, planted in enumerate([(2, 5), (3, 9)]):
            for blk in planted:
                k[0, blk * 8:blk * 8 + 8, g, 0] = 6.0
    q, k, v = (jnp.asarray(x, dtype) for x in (q, k, v))
    pad = (pages - 1) * PAGE - n

    def pool(x):
        rows = jnp.pad(x[0], ((0, pad), (0, 0), (0, 0))).reshape(
            pages - 1, PAGE, hkv, d)
        return jnp.zeros((pages, PAGE, hkv, d), dtype).at[table].set(rows)
    k_pool = pool(k)
    pooled = jnp.zeros((pages, PAGE // SIZES.stride, hkv, d), dtype)
    for start in range(0, (pages - 1) * PAGE, PAGE):
        pooled = bs.write_pooled(
            pooled, jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))[
                :, start:start + PAGE], k_pool, jnp.asarray(table)[None],
            jnp.asarray([start], jnp.int32), SIZES)
    return q, k, v, k_pool, pool(v), pooled, jnp.asarray(table)[None]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case,n,start,s,tile_q,agree", [
    ("past_dense_len", 192, 128, 64, 16, False),
    ("straddles_dense_len", 128, 32, 64, 32, False),
    ("own_block_cut_at_the_query", 104, 96, 8, 8, False),
    ("last_chunk_with_a_padded_tail", 170, 128, 64, 16, False),
    ("queries_that_agree_skip_blocks", 256, 192, 64, 16, True),
    ("queries_that_differ_skip_none", 256, 192, 64, 64, False)])
def test_a_chunks_tiles_read_what_each_query_chose(dtype, case, n, start, s,
                                                   tile_q, agree):
    """The tile kernel (interpret mode) over a chunk's packed membership
    against BOTH other forms of the same read: ``paged_block_attention`` a
    token over ``choose``'s tables (what a chunk ran before PR 47), and
    plain attention under ``plain_mask`` over the whole sequence.  The two
    KV heads choose different blocks; a padded tail (the pool holds zeros
    past ``n``) changes no real query; ``kv_blocks_streamed`` counts whole
    units of 4 blocks and falls under what the tiles could see only where
    the tile's queries agree."""
    rng = np.random.default_rng(14)
    q, k, v, k_pool, v_pool, pooled, table = _sequence(rng, n, dtype, agree)
    scale, real = 0.25, min(s, n - start)
    index = jnp.asarray([start], jnp.int32)
    t = start + jnp.arange(s, dtype=jnp.int32)[None, :]
    q_chunk = jnp.pad(q[:, start:start + s],
                      ((0, 0), (0, s - real), (0, 0), (0, 0)))
    unit = tile_keys(PAGE, SIZES.block)
    assert unit == PAGE
    bits = bs.chunk_members(q_chunk, pooled, table, t, SIZES, scale,
                            unit // SIZES.block, tile=32)
    got, streamed = paged_tile_attention(
        q_chunk, k_pool, v_pool, table, index, bits, block=SIZES.block,
        use_pallas="interpret", tile_q=tile_q)
    oracle, same = paged_tile_attention(
        q_chunk, k_pool, v_pool, table, index, bits, block=SIZES.block,
        use_pallas=False, tile_q=tile_q)
    assert int(same) == int(streamed)
    # a token: the table of blocks a (query, KV head) and a row of the
    # paged kernel's oracle each
    r = bs.scores(q_chunk, pooled, table, t, SIZES, scale)
    blocks, count = bs.choose(r[0], t[0], SIZES)
    past = (np.asarray(t[0]) + 1 > SIZES.dense_len)[:real]
    if past.any():
        chose = np.asarray(blocks)[:real][past]
        assert (chose[:, 0] != chose[:, 1]).any()
    ids = bs.physical(blocks[None], table, PAGE, SIZES.block)[0]
    by_token = paged_block_attention(
        q_chunk[0], k_pool, v_pool, ids, count, t[0] % SIZES.block,
        block=SIZES.block, use_pallas=False)
    # plain attention over the whole sequence under the choice as a mask
    f32 = jnp.float32
    mask = bs.plain_mask(q, k, SIZES, scale)                # [1,n,Hkv,n]
    sc = jnp.einsum("bqhgd,bkhd->bqhgk",
                    q.astype(f32).reshape(1, n, 2, 4, 16),
                    k.astype(f32)) * scale
    sc = jnp.where(mask[:, :, :, None, :], sc, -1e30)
    plain = jnp.einsum("bqhgk,bkhd->bqhgd", jax.nn.softmax(sc, -1),
                       v.astype(f32)).reshape(1, n, 8, 16)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    # (a bfloat16 pooled leaf rounds the pooled keys that ``plain_mask``
    # keeps in float32, and a rounded score flips a choice: float32 only)
    others = (oracle[0, :real], by_token[:real]) + (
        (plain[0, start:start + real],) if dtype == jnp.float32 else ())
    for other in others:
        np.testing.assert_allclose(np.asarray(got[0, :real], np.float32),
                                   np.asarray(other, np.float32), atol=tol)
    per = unit // SIZES.block
    seen = 2 * sum((start + i + tile_q - 1) // SIZES.block + 1
                   for i in range(0, s, tile_q))
    assert int(streamed) % per == 0
    if agree:
        assert int(streamed) < seen
    elif case == "queries_that_differ_skip_none":
        assert int(streamed) >= seen


def test_sizes_that_do_not_fit_a_page_are_refused():
    with pytest.raises(ValueError, match="pool = 2 x stride"):
        bs.Sizes(8, 3, 2, 2, 16, 1, 64).check(32)
    with pytest.raises(ValueError):
        SIZES.check(20)                     # a page of 2.5 blocks
    with pytest.raises(ValueError):
        bs.Sizes(8, 4, 2, 8, 16, 1, 64).check(32)   # 11 blocks of 8 only
    assert SIZES.check(32).width == 8 and SIZES.read == 5


# ------------------------------------- the model against the reference ----
def test_model_equals_reference_at_every_position(toy, reference):
    """The whole-sequence forward (the choice as a mask, the recurrence
    token by token) = the reference, at every position of a prompt that
    crosses ``dense_len``: the chosen table is the reference's wherever a
    wrong block would move a logit, which under gains of 3 is everywhere."""
    model, params = toy
    tokens = np.random.default_rng(9).integers(0, VOCAB, (2, 150),
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, tokens))
    want = _ref_logits(reference, params, tokens)
    # float32 sums in another order
    np.testing.assert_allclose(got, want, atol=2e-4 * want.std() + 1e-6)
    # ... and the choice matters: the forced blocks alone read far off
    off = _ref_logits(reference, params, tokens, faults=("no_chosen",))
    assert np.abs(off[:, 100:] - want[:, 100:]).max() > 0.05 * want.std()
    np.testing.assert_allclose(off[:, :64], want[:, :64], atol=1e-6)


def test_the_chosen_table_is_the_references_at_every_position(toy,
                                                              reference):
    """The program's table of blocks (``choose`` over ``scores``) against
    the reference's own choice (``blocks_read``), for every query of a
    prompt that crosses ``dense_len``, both KV heads."""
    rng = np.random.default_rng(10)
    s, hq, hkv, d = 160, 8, 2, 16
    q = jnp.asarray(rng.normal(size=(1, s, hq, d)) * 2, jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s, hkv, d)) * 2, jnp.float32)
    mask = bs.plain_mask(q, k, SIZES, d ** -0.5)            # [1,S,Hkv,S]
    arch = reference.arch_of_model_kwargs(TOY)
    whole = _pooled_whole(np.asarray(k[0]), SIZES)
    pad = np.zeros((s // SIZES.stride - len(whole), hkv, d), np.float32)
    read = reference.blocks_read(
        q, jnp.asarray(np.concatenate([whole, pad]))[None],
        jnp.arange(s), arch)                                # [1,S,Hkv,nb]
    want = (np.asarray(read)[..., np.arange(s) // SIZES.block]
            & (np.arange(s)[None, :] <= np.arange(s)[:, None])[:, None])
    assert (np.asarray(mask) == want).all()
    per_query = np.asarray(read)[0].sum(-1)
    assert (per_query[64:] == SIZES.read).all()
    assert (per_query[:64, 0] == np.arange(64) // 8 + 1).all()


def _prefill(dec, cache, prompt, table):
    for start, clen in chunk_plan(len(prompt), CHUNK, PAGE):
        chunk = np.zeros((clen,), np.int32)
        real = prompt[start:start + clen]
        chunk[:len(real)] = real
        _, cache, last = dec.prefill_chunk(cache, chunk, table, start,
                                           len(real) - 1, 0.0, seed=0)
    return cache, np.asarray(last)


@pytest.mark.parametrize("lengths,use_pallas", [
    ([40, 97, 129], False),             # dense; past dense_len; one token
    ([64, 161], "interpret")],          # into a third chunk
    ids=["gather", "kernels"])
def test_paged_serving_equals_reference(toy, reference, lengths,
                                        use_pallas):
    """Prefill in chunks and decode through the engine's cache (three kinds
    of leaf in one pool) against the reference's full forward — logits,
    not tokens.  float32 everywhere: 2e-4 of the logits' spread is sums in
    another order, and one wrong block, page or state entry reads 100
    times that."""
    model, params = toy
    new = 10
    dec = Decoder(model.clone(use_pallas=use_pallas), params, num_slots=4,
                  max_seq_len=256, kv_page_size=PAGE, kv_pool_pages=33)
    rng = np.random.default_rng(11)
    rows = [rng.integers(0, VOCAB, n + new, dtype=np.int32)
            for n in lengths]
    cache = dec.fresh_cache()
    tables = np.zeros((4, dec.pages_per_slot), np.int32)
    got = [[] for _ in rows]
    free = list(range(1, 33))
    rng.shuffle(free)
    for r, (n, tokens) in enumerate(zip(lengths, rows)):
        need = -(-(n + new) // PAGE)
        tables[r, :need] = [free.pop() for _ in range(need)]
        cache, last = _prefill(dec, cache, tokens[:n], tables[r])
        got[r].append(last)
    index = np.zeros((4,), np.int32)
    index[:len(rows)] = lengths
    for j in range(new - 1):
        step_tokens = np.zeros((4,), np.int32)
        step_tokens[:len(rows)] = [t[n + j] for n, t in zip(lengths, rows)]
        _, cache, step = dec.decode_step(
            cache, step_tokens, index, np.zeros((4,), np.float32),
            seeds=np.zeros((4,), np.uint32), block_tables=tables)
        for r in range(len(rows)):
            got[r].append(np.asarray(step[r]))
        index[:len(rows)] += 1
    for r, (n, tokens) in enumerate(zip(lengths, rows)):
        want = _ref_logits(reference, params, tokens[None])[0,
                                                            n - 1:n - 1 + new]
        np.testing.assert_allclose(np.stack(got[r]), want,
                                   atol=2e-4 * want.std() + 1e-6)


def test_the_spans_counts_are_what_a_call_reads(toy):
    """``stats_names`` in order, on a chunk under ``dense_len``, one past
    it with a padded tail, and a decode step with an idle row."""
    model, params = toy
    assert model.stats_names == (
        "assignments", "experts_touched", "expert_load_max",
        "kv_blocks_visible", "kv_blocks_read", "pooled_keys_scored",
        "rows_dense_path", "linear_tokens", "state_rows_advanced",
        "kv_blocks_streamed")
    dec = Decoder(model.clone(use_pallas=False), params, num_slots=2,
                  max_seq_len=256, kv_page_size=PAGE, kv_pool_pages=17)
    cache = dec.fresh_cache()
    table = np.arange(1, 9, dtype=np.int32)

    def counts():
        return dict(zip(model.stats_names,
                        np.asarray(dec.last_stats["counts"]).tolist()))
    tokens = np.arange(CHUNK, dtype=np.int32)
    _, cache, _ = dec.prefill_chunk(cache, tokens, table, 0, CHUNK - 1, 0.0,
                                    seed=0)
    blocks = sum(t // 8 + 1 for t in range(64))
    assert counts() == {
        "assignments": 0, "experts_touched": 0, "expert_load_max": 0,
        "kv_blocks_visible": 4 * blocks, "kv_blocks_read": 4 * blocks,
        "pooled_keys_scored": 0, "rows_dense_path": 64,
        "linear_tokens": 128, "state_rows_advanced": 2,
        "kv_blocks_streamed": 0}
    _, cache, _ = dec.prefill_chunk(cache, tokens, table, CHUNK, 9, 0.0,
                                    seed=0)
    got = counts()
    assert got["kv_blocks_read"] == 4 * 10 * SIZES.read
    assert got["kv_blocks_visible"] == 4 * sum(t // 8 + 1
                                               for t in range(64, 74))
    assert got["pooled_keys_scored"] == 2 * sum(
        (t - 3) // 2 + 1 for t in range(64, 74))
    assert (got["rows_dense_path"], got["linear_tokens"]) == (0, 20)
    # one tile a (KV head, layer): the first unit of 4 blocks (the forced
    # block), the chunk's own two, and the one between if any query of the
    # 64 (the padded tail's too: their rows are computed) chose in it
    assert 4 * 12 <= got["kv_blocks_streamed"] <= 4 * 16
    assert got["kv_blocks_streamed"] % 4 == 0
    tables = np.zeros((2, dec.pages_per_slot), np.int32)
    tables[0] = table
    _, cache, _ = dec.decode_step(
        cache, np.zeros((2,), np.int32), np.asarray([74, 0], np.int32),
        np.zeros((2,), np.float32), seeds=np.zeros((2,), np.uint32),
        block_tables=tables)
    got = counts()
    assert (got["kv_blocks_visible"], got["kv_blocks_read"]) == (4 * 10,
                                                                 4 * 5)
    assert (got["linear_tokens"], got["state_rows_advanced"]) == (2, 2)
    assert got["kv_blocks_streamed"] == 0       # a step copies by a table


def test_three_kinds_of_leaf_in_one_pool(toy):
    """K and V rows, pooled keys (a ``kv_pool`` leaf whose rows a page are
    a sixteenth — here a half — of its tokens) and state entries: the
    memory plan counts each by its bytes a page, and equals the pool's
    real bytes."""
    model, _ = toy
    plan = serving_memory_plan(model, num_slots=2, max_seq_len=256,
                               kv_page_size=PAGE, kv_pool_pages=17)
    row = 2 * 16 * 4                                    # Hkv x Dh, float32
    assert plan["per_token_kv_bytes"] == 2 * (2 * row + row // 2)
    assert plan["state_bytes_per_page"] == 2 * 4 * 16 * 16 * 4
    from dtf_tpu.serve.decode import trace_paged_init
    shapes = trace_paged_init(model, PAGE, 17)[0]
    kinds = [k for k, _ in cache_leaves(shapes)]
    assert kinds.count(KV_POOL) == 6 and kinds.count(PAGE_STATE) == 2
    real = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(shapes))
    assert real == 17 * (plan["per_token_kv_bytes"] * PAGE
                         + plan["state_bytes_per_page"])
    assert plan["kv_bytes_paged"] + plan["state_bytes_paged"] \
        == real * 16 // 17


def test_the_cli_reaches_the_sparse_kinds():
    """``--model routed_decoder_sparse``: the registry's small size."""
    model, _ = build_model("routed_decoder_sparse", num_classes=256)
    assert model.layer_mixers() == ["sparse_block", "lightning",
                                    "lightning", "sparse_block"]
    assert model.carries_state
    logits = model.apply(
        {"params": model.init(jax.random.key(0),
                              jnp.zeros((1, 8), jnp.int32))["params"]},
        jnp.zeros((1, 8), jnp.int32))
    assert logits.shape == (1, 8, 256)
