"""A continuation chunk over K and V pools as a WALK through the flash
forward (``ops.paged_attention.paged_chunk_attention``) against the gather
oracle ``paged_attention``, through ``ops.blockwise`` and through the
kernel in interpret mode; the rule that sends a call there
(``chunk_walks``) as a table over the benchmark's serving cells; and what
a model whose layers walk counts and publishes (``kv_tokens_walked``).
float32 throughout, so what is compared is the mathematics and not a
rounding."""

import importlib
import json
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
from dtf_tpu.models import routed_decoder as rd  # noqa: E402

pa = importlib.import_module("dtf_tpu.ops.paged_attention")

BOTH = pytest.mark.parametrize("use_pallas", [False, "interpret"],
                               ids=["blockwise", "kernel"])


def _problem(index, s, group, h, d, page, m, seed=0, poison=False):
    """Rows whose chunks of ``s`` start at ``index`` [B], their pages
    shuffled through a pool; the chunks' own keys written (write-then-
    attend).  ``poison``: NaN in every pool row no query may see — the
    scratch page, the pool's unused pages and each row's pages past its
    chunk."""
    rng = np.random.default_rng(seed)
    b = len(index)
    pages = 1 + b * m + 2
    table = (1 + rng.permutation(b * m)).reshape(b, m).astype(np.int32)
    pool_k, pool_v = (rng.standard_normal((pages, page, h, d)
                                          ).astype(np.float32)
                      for _ in range(2))
    if poison:
        seen = np.zeros((pages, page), bool)
        for r, at in enumerate(index):
            flat = seen[table[r]].reshape(-1)
            flat[:at + s] = True
            seen[table[r]] = flat.reshape(m, page)
        pool_k[~seen] = pool_v[~seen] = np.nan
    q = rng.standard_normal((b, s, group * h, d)).astype(np.float32)
    k, v = (rng.standard_normal((b, s, h, d)).astype(np.float32)
            for _ in range(2))
    index = jnp.asarray(index, jnp.int32)
    pool_k = pa.write_pages(jnp.asarray(pool_k), jnp.asarray(k), table, index)
    pool_v = pa.write_pages(jnp.asarray(pool_v), jnp.asarray(v), table, index)
    return (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), pool_k, pool_v,
            jnp.asarray(table), index)


def _oracle(q, k, v, pool_k, pool_v, table, index):
    """``paged_attention`` over pools whose unseen rows are zeros (the
    dense oracle multiplies a masked probability of 0 by the value)."""
    del k, v
    return np.asarray(pa.paged_attention(
        q, jnp.nan_to_num(pool_k), jnp.nan_to_num(pool_v), table, index))


def _walk(args, use_pallas):
    return np.asarray(pa.paged_chunk_attention(*args, use_pallas=use_pallas))


@BOTH
@pytest.mark.parametrize("group,h,d,page", [
    (1, 2, 128, 64), (7, 4, 128, 64), (8, 2, 256, 64), (8, 2, 256, 1024),
    (7, 1, 128, 1024)], ids=["1x2x128_p64", "7x4x128_p64", "8x2x256_p64",
                             "8x2x256_p1024", "7x1x128_p1024"])
def test_the_walk_equals_the_gather_oracle(group, h, d, page, use_pallas):
    """Groups of 1 / 7 / 8 query heads a KV head, heads of 128 and 256,
    pages of 64 and 1,024 (the larger gathered in parts of a page): a
    chunk of 16 that starts a step and a page into its row — a whole step
    of 2,048 keys, then one the row's length cuts."""
    args = _problem([pa.EXPAND_KEYS + page], 16, group, h, d, page,
                    m=pa.EXPAND_KEYS // page + 3)
    np.testing.assert_allclose(_walk(args, use_pallas), _oracle(*args),
                               atol=2e-5)


@BOTH
@pytest.mark.parametrize("index", [[0], [16], [64], [80], [0, 16, 64, 80],
                                   [48, 16]],
                         ids=["no_page", "one_page", "a_whole_step",
                              "a_step_and_a_page", "rows_at_every_start",
                              "rows_part_way_through_a_step"])
def test_every_start_of_a_chunk(monkeypatch, index, use_pallas):
    """At steps of 64 keys (4 pages of 16): a chunk with nothing under it,
    with one page, a whole step, a step and a page — and the rows of ONE
    call at different starts, the walk as long as the longest needs and
    the others' later steps seeing nothing."""
    monkeypatch.setattr(pa, "EXPAND_KEYS", 64)
    args = _problem(index, 32, 8, 2, 32, 16, m=8)
    np.testing.assert_allclose(_walk(args, use_pallas), _oracle(*args),
                               atol=2e-5)


@BOTH
@pytest.mark.parametrize("m", [7, 8, 13, 32],
                         ids=["the_rows_own", "whole_steps", "odd", "wide"])
def test_a_table_wider_than_the_row_is_long(monkeypatch, m, use_pallas):
    """The walk's length follows ``index``, never the table's width: a
    table of 7 pages (what the row holds), 8, 13 (no whole number of
    steps: padded) and 32 give the same result."""
    monkeypatch.setattr(pa, "EXPAND_KEYS", 64)
    q, k, v, pool_k, pool_v, table, index = _problem([80], 32, 7, 2, 32, 16,
                                                     m=7)
    want = _oracle(q, k, v, pool_k, pool_v, table, index)
    table = jnp.pad(table, ((0, 0), (0, m - 7)))
    got = _walk((q, k, v, pool_k, pool_v, table, index), use_pallas)
    np.testing.assert_allclose(got, want, atol=2e-5)


@BOTH
@pytest.mark.parametrize("index", [[16], [80, 0]],
                         ids=["a_cut_step", "a_row_with_nothing_under_it"])
def test_garbage_past_a_rows_length_is_never_multiplied(monkeypatch, index,
                                                        use_pallas):
    """NaN in every pool row no query of the call may see — the scratch
    page behind the table's unallocated entries, the row's own pages past
    the chunk, the part of a gathered step at and past ``index`` — leaves
    the result finite and the oracle's."""
    monkeypatch.setattr(pa, "EXPAND_KEYS", 64)
    q, k, v, pool_k, pool_v, table, index = _problem(
        index, 32, 8, 2, 32, 16, m=8, poison=True)
    # the entries past the chunk's pages unallocated: the scratch page
    live = (np.asarray(index) + 32) // 16
    table = jnp.where(jnp.arange(8)[None, :] < live[:, None], table, 0)
    assert np.isnan(np.asarray(pool_k[0])).all()
    args = (q, k, v, pool_k, pool_v, table, index)
    got = _walk(args, use_pallas)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _oracle(*args), atol=2e-5)


def test_a_large_page_is_gathered_in_parts():
    """A slice of at most 256 KiB a gather (XLA relays out the WHOLE pool
    before a gather of larger ones: PR 58's v5e compiles): Qwen3-Next's
    page of 1,024 x 2 x 256 bf16 in 4 parts, SmallThinker's of 64 x 4 x
    128 whole, and the count of walked keys in steps of those parts."""
    assert pa._gather_parts(1024, 2 * 256 * 2) == 4
    assert pa._gather_parts(64, 4 * 128 * 2) == 1
    assert pa._gather_parts(1000, 4096) == 8            # 125 is odd
    index = np.array([0, 1024, 2048, 5120])
    np.testing.assert_array_equal(
        pa.chunk_rows_walked(index, 2048, 1024, 66, 1024),
        [2048, 4096, 4096, 8192])
    # a page larger than a step walks in steps of its parts
    np.testing.assert_array_equal(
        pa.chunk_rows_walked(index, 2048, 4096, 16, 1024),
        [2048, 4096, 4096, 8192])


def _cell(name):
    """(the configuration, the engine's settings) of a cell of
    ``BENCHMARK.json``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = next(w["config"] for w in bench["workloads"]
                  if w["name"] == name)
    with open(os.path.join(ROOT, next(
            c["file"] for c in bench["configs"] if c["name"] == config))) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           name + ".json")) as f:
        return cfg, json.load(f)["engine"]


# cell -> layers of a continuation chunk that walk, by kind
CELLS = {
    "qwen3next-serve-hybriddoc": 2,     # the two gated-attention layers
    "smallthinker-serve-mixedctx": 3,   # the GLOBAL layers of twelve
    "lfm2-serve-manyrows": 0,           # [k | v] rows in one pool
    "evabyte-serve-bytedocs": 0,        # whole heads (and a compact table)
    "minicpm-sala-serve-longdoc": 0,    # chosen blocks: the tiles kernel
    "joyai-serve-longctx": 0,           # latent pools: the expanded walk
    "ling-serve-longgen": 0,
    "glm52-serve-sparsectx": 0,
}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_rule_by_serving_cell(name):
    """Each routed serving cell's model at its engine's chunk: how many
    layers of a continuation chunk walk, and that a decode step never
    does."""
    cfg, engine = _cell(name)
    model, _ = build_model(cfg["build_model"]["name"],
                           num_classes=cfg["num_classes"], dtype=jnp.bfloat16,
                           **cfg["build_model"]["kwargs"])
    dm = model.clone(decode=True, kv_page_size=engine["kv_page_size"],
                     kv_pool_pages=engine["kv_pool_pages"])
    assert dm.layers_walking(engine["prefill_chunk"]) == CELLS[name]
    assert dm.layers_walking(1) == 0
    assert model.layers_walking(engine["prefill_chunk"]) == 0  # no cache
    names = dm.call_stats_names(engine["prefill_chunk"])
    assert ("kv_tokens_walked" in names) == bool(CELLS[name])
    assert "kv_tokens_walked" not in dm.call_stats_names(1)


@pytest.mark.parametrize("what,shape,walks", [
    ("qwen3next_gated_layer", (2048, 16, 2, None, 2), True),
    ("smallthinker_global_layer", (1024, 28, 4, None, 2), True),
    ("smallthinker_window_layer", (1024, 28, 4, 4096, 2), False),
    ("qwen3next_decode_step", (1, 16, 2, None, 2), False),
    ("smallthinker_decode_step", (1, 28, 4, None, 2), False),
    ("gpt13b_chunk_of_256", (256, 16, 16, None, 2), False),
    ("gpt13b_decode_step", (1, 16, 16, None, 2), False),
    ("evabyte_whole_heads", (1024, 32, 32, None, 2), False),
    ("lfm2_k_v_rows", (2048, 32, 8, None, 1), False),
    ("a_latent_pool", (2048, 32, 1, None, 1), False),
    ("under_a_tile", (64, 16, 2, None, 2), False),
    ("a_tile", (128, 16, 2, None, 2), True)])
def test_the_rule_by_shape(what, shape, walks):
    """``(s, hq, h, window, pools)``: two pools, no window, grouped heads
    and a full tile of the flash forward a KV head — Qwen3-Next's gated
    layers and SmallThinker's global ones; every other serving shape
    stays in ``paged_flash_decode``."""
    s, hq, h, window, pools = shape
    assert pa.chunk_walks(s, hq, h, window=window, pools=pools) is walks
    if walks:
        # ... which is where the kernel would halve a head's rows into
        # blocks that each stream the row's pages again
        assert pa._tiling(s, hq, h, 128, 64, 256, 2)[1] > 1


TOY = dict(num_layers=4, d_model=64, num_heads=8, num_kv_heads=2, head_dim=16,
           num_experts=8, experts_per_token=2, expert_width=32, window=24,
           layer_window=[False, True], layer_rope=[False, True],
           rope_theta=1e4, rms_eps=1e-6, max_seq_len=256)
VOCAB, PAGE, CHUNK = 128, 8, 16


@pytest.fixture(scope="module")
def toy():
    model, _ = build_model("routed_decoder", num_classes=VOCAB,
                           dtype=jnp.float32, **TOY)
    params = jax.jit(model.init)(jax.random.key(5),
                                 jnp.zeros((1, PAGE), jnp.int32))["params"]
    return model, params


def _chunks(model, params, prompt, use_pallas):
    """Each chunk's sampled logits and counts, through the paged cache."""
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    dm = model.clone(decode=True, kv_page_size=PAGE, kv_pool_pages=9,
                     use_pallas=use_pallas)
    cache = dm.init(jax.random.key(0), jnp.zeros((1, PAGE), jnp.int32),
                    cache_index=jnp.zeros((1,), jnp.int32),
                    block_table=table)["cache"]
    out = []
    for start in range(0, len(prompt), CHUNK):
        logits, mut = dm.apply(
            {"params": params, "cache": cache},
            jnp.asarray(prompt[None, start:start + CHUNK]),
            cache_index=jnp.asarray([start], jnp.int32), block_table=table,
            flash_prefill=start == 0, mutable=["cache", "stats"])
        cache = mut["cache"]
        out.append((np.asarray(logits)[0, -1],
                    np.asarray(mut["stats"]["counts"])))
    return dm, out


@BOTH
def test_a_walking_model_equals_the_kernels_and_counts_its_walk(
        monkeypatch, toy, use_pallas):
    """The toy (8 query heads over 2 KV heads, global and window layers in
    turn) in chunks of 16 with the rule's tile at 64 rows a head: the two
    GLOBAL layers of a continuation chunk walk in steps of 32 keys, the
    window layers and the first chunk do not; the logits are those of the
    same chunks with the rule off, and the chunk counts, last, the keys
    its walks gathered and its own."""
    model, params = toy
    prompt = np.random.default_rng(2).integers(0, VOCAB, 64, dtype=np.int32)
    monkeypatch.setattr(pa, "EXPAND_KEYS", 32)
    monkeypatch.setattr(pa, "CHUNK_WALK_ROWS", 1 << 30)
    dm, kernel = _chunks(model, params, prompt, use_pallas)
    assert dm.layers_walking(CHUNK) == 0
    assert dm.call_stats_names(CHUNK) == rd.STATS
    monkeypatch.setattr(pa, "CHUNK_WALK_ROWS", 64)
    dm, walked = _chunks(model, params, prompt, use_pallas)
    assert dm.layers_walking(CHUNK) == 2 and dm.layers_walking(1) == 0
    assert dm.call_stats_names(CHUNK) == rd.STATS + ("kv_tokens_walked",)
    assert dm.call_stats_names(1) == rd.STATS
    for i, ((got, counts), (want, read)) in enumerate(zip(walked, kernel)):
        np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())
        np.testing.assert_array_equal(counts[:5], read)
        if i == 0:
            assert len(counts) == 5     # a first chunk walks nothing
        else:
            start = CHUNK * i
            assert counts[5] == 2 * (-(-start // 32) * 32 + CHUNK)


def test_only_a_continuation_chunks_span_names_what_it_walked(
        monkeypatch, toy, tmp_path):
    """Tracing on: ``kv_tokens_walked`` is on the ``serve_prefill_chunk``
    spans of the continuation chunks that walked (a prompt of 40 in
    chunks of 16, 16 and 8: the second) and on no other span — not a first
    chunk's, not a chunk's under the rule's tile, not a decode step's —
    beside ``kv_tokens_read_global``, which stays as it was."""
    from dtf_tpu.obs import trace
    from dtf_tpu.serve.engine import ServeEngine
    monkeypatch.setattr(pa, "CHUNK_WALK_ROWS", 64)
    model, params = toy
    rng = np.random.default_rng(4)
    tracer = trace.configure(str(tmp_path))
    try:
        eng = ServeEngine(model, params, max_batch=2, max_seq_len=128,
                          kv_page_size=PAGE, kv_pool_pages=33,
                          prefill_chunk=CHUNK)
        try:
            for h in [eng.submit(rng.integers(0, VOCAB, p, dtype=np.int32),
                                 max_new_tokens=4) for p in (40, 16)]:
                h.result(timeout=300)
        finally:
            eng.stop(drain=True, timeout=30)
    finally:
        trace.disable()
    spans = [r for r in trace.read_records(tracer.path)
             if r.get("kind") == "span"]
    chunks = [r for r in spans if r["name"] == "serve_prefill_chunk"]
    assert len(chunks) == 4
    assert sum("kv_tokens_walked" in r for r in chunks) == 1
    for r in chunks:
        assert r["kv_tokens_read_global"] == 2 * (r["start"] + r["tokens"])
        if r["start"] == 0 or 4 * r["tokens"] < 64:
            # a first chunk; the last one, of 8 tokens: under the tile
            assert "kv_tokens_walked" not in r
        else:
            # a table of 16 pages of 8 is one step of the walk
            assert r["kv_tokens_walked"] == 2 * (
                -(-r["start"] // 128) * 128 + r["tokens"])
    others = [r for r in spans if r["name"] != "serve_prefill_chunk"]
    assert any(r["name"] == "serve_decode" for r in others)
    assert not any("kv_tokens_walked" in r for r in others)
