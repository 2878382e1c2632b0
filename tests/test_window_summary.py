"""The window-and-summaries attention kind (``summary_window``) of the
routed decoder, on the CPU at toy widths (window 32, chunk 4, pages of 8,
float32): the served path through the compact table against the plain
reference and against the program's own whole-sequence forward (every key
kept, summaries computed on the side); the compaction kernel and the paged
kernel in interpret mode against their oracles on a compacted table; the
pages a row holds over a whole request; the memory plan."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import reference_evabyte as reference  # noqa: E402
from dtf_tpu.models import build_model  # noqa: E402
from dtf_tpu.models import routed_decoder as rd  # noqa: E402
from dtf_tpu.ops import window_summary as ws  # noqa: E402
from dtf_tpu.serve.bridge import serving_memory_plan  # noqa: E402
from dtf_tpu.serve.decode import Decoder, teacher_forced_logits  # noqa: E402
from dtf_tpu.serve.engine import ServeEngine, chunk_plan  # noqa: E402

WINDOW, CHUNK, PAGE, VOCAB = 32, 4, 8, 320
KW = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
          layer_window=(False,), layer_rope=(True,), rope_theta=1e5,
          rms_eps=1e-5, num_dense_layers=2, dense_width=96,
          activation="silu", summary_window=WINDOW, summary_chunk=CHUNK,
          norm_unit_offset=True, max_seq_len=256)


@pytest.fixture(scope="module")
def toy():
    model, _ = build_model("routed_decoder", num_classes=VOCAB,
                           dtype=jnp.float32, **KW)
    params = jax.jit(model.clone(use_pallas=False).init)(
        jax.random.key(3), jnp.zeros((1, PAGE), jnp.int32))["params"]
    # offsets that are not zero, so that a forgotten ``1 +`` shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 if "norm" in path[-1].key else a, params)
    return model, params


def _served_logits(model, params, prompt, answer, use_pallas=False,
                   chunk=16):
    """Logits of the served path where each token of ``answer`` is chosen:
    the prompt through ``prefill_chunk`` in the engine's chunk plan, then
    ``answer`` fed back through ``decode_step``, on an UNCOMPACTED
    allocation of ``ceil(len / page)`` pages as the benchmark's replay
    makes it (the program uses the leading entries alone)."""
    dec = Decoder(model.clone(use_pallas=use_pallas), params, num_slots=2,
                  max_seq_len=256, kv_page_size=PAGE, kv_pool_pages=40)
    cache = dec.fresh_cache()
    table = np.zeros((2, dec.pages_per_slot), np.int32)
    need = -(-(len(prompt) + len(answer)) // PAGE)
    table[0, :need] = 1 + np.arange(need)
    for start, clen in chunk_plan(len(prompt), chunk, PAGE):
        piece = np.zeros((clen,), np.int32)
        real = prompt[start:start + clen]
        piece[:len(real)] = real
        _, cache, last = dec.prefill_chunk(cache, piece, table[0], start,
                                           len(real) - 1, 0.0, seed=0)
    out = [np.asarray(last)]
    index = np.array([len(prompt), 0], np.int32)
    for tok in answer[:-1]:
        _, cache, step = dec.decode_step(
            cache, np.array([tok, 0], np.int32), index,
            np.zeros((2,), np.float32), seeds=np.zeros((2,), np.uint32),
            block_tables=table)
        out.append(np.asarray(step[0]))
        index[0] += 1
    return np.stack(out), dec


@pytest.mark.parametrize("plen,new", [(48, 6), (64, 6), (20, 50), (97, 40)],
                         ids=["closed_by_a_chunk", "at_the_first_step",
                              "mid_answer", "three_closed_and_two_more"])
def test_the_served_path_is_the_reference(toy, plen, new):
    """Prefill, then decode through the cache, across a window's close in
    a chunk, at the first decode step and mid-answer: the plain reference's
    logits, and the program's own whole-sequence forward (an uncompacted
    run: every key kept, the summaries computed on the side), to float32
    noise."""
    model, params = toy
    rng = np.random.default_rng(plen)
    prompt = rng.integers(0, VOCAB, plen, dtype=np.int32)
    answer = rng.integers(0, VOCAB, new, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        served, dec = _served_logits(model, params, prompt, answer)
        full = np.concatenate([prompt, answer[:-1]])[None]
        whole = np.asarray(teacher_forced_logits(model, params, full)
                           )[0, plen - 1:]
    plain = np.asarray(reference.forward(params, full))[0, plen - 1:]
    np.testing.assert_allclose(served, plain, atol=3e-5)
    np.testing.assert_allclose(served, whole, atol=3e-5)
    assert dec.windows_closed == (plen + new - 2) // WINDOW
    assert dec.pages_reclaimed == dec.windows_closed * 3


@pytest.mark.parametrize("plen,new", [(64, 8), (41, 30)],
                         ids=["chunks", "steps"])
def test_the_kernels_read_a_compacted_table_as_the_oracles_do(toy, plen,
                                                              new):
    """``paged_flash_decode`` and ``window_compact`` through the Pallas
    interpreter against the gather path and the gathered compaction, over
    closes in chunks and in steps."""
    model, params = toy
    rng = np.random.default_rng(plen)
    prompt = rng.integers(0, VOCAB, plen, dtype=np.int32)
    answer = rng.integers(0, VOCAB, new, dtype=np.int32)
    kernel, _ = _served_logits(model, params, prompt, answer, "interpret")
    oracle, _ = _served_logits(model, params, prompt, answer, False)
    np.testing.assert_allclose(kernel, oracle, atol=2e-5)


@pytest.mark.parametrize("page,chunk,dtype", [
    (8, 4, jnp.float32), (4, 2, jnp.float32), (16, 4, jnp.bfloat16)],
    ids=["one_page_of_summaries", "two_pages_of_summaries", "bfloat16"])
def test_window_compact_is_its_oracle_and_touches_no_other_page(page, chunk,
                                                                dtype):
    pool, h, d, n_pages = 13, 4, 16, 4
    ks = jax.random.split(jax.random.key(page), 4)
    pk = jax.random.normal(ks[0], (pool, page, h, d)).astype(dtype)
    pv = jax.random.normal(ks[1], (pool, page, h, d)).astype(dtype)
    phi = jax.random.normal(ks[2], (h, d)) * 0.25
    mu = jax.random.normal(ks[3], (h, d))
    pages = jnp.array([5, 2, 9, 7], jnp.int32)
    want = ws.compact_window(pk, pv, pages, phi, mu, chunk=chunk,
                             use_pallas=False)
    got = ws.compact_window(pk, pv, pages, phi, mu, chunk=chunk,
                            use_pallas="interpret")
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    written = n_pages * page // chunk // page       # summaries' pages
    for w, g, before in zip(want, got, (pk, pv)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=tol)
        others = np.setdiff1d(np.arange(pool), np.asarray(pages[:written]))
        assert (np.asarray(g)[others] == np.asarray(before)[others]).all()
    # the summaries are chunk_summaries of the window's rows, in order
    k_all = pk[pages].reshape(n_pages * page, h, d)
    v_all = pv[pages].reshape(n_pages * page, h, d)
    ks_, _ = ws.chunk_summaries(k_all, v_all, phi, mu, chunk)
    np.testing.assert_allclose(
        np.asarray(want[0][pages[:written]], np.float32).reshape(-1, h, d),
        np.asarray(ks_, np.float32), atol=tol)


@pytest.mark.parametrize("page", [4, 8])
def test_a_rows_pages_are_the_most_its_positions_ever_touch(page):
    """``pages_for_length`` against the table entries positions 0..L-1
    touch, by brute force; far under ``ceil(L / page)`` past a window."""
    touched = 0
    for length in range(1, 200):
        entry = ws.compact_index(length - 1, WINDOW, CHUNK) // page + 1
        touched = max(touched, entry)
        assert ws.pages_for_length(length, page, WINDOW, CHUNK) == touched
    assert touched == 5 * (8 // page) + WINDOW // page < -(-199 // page)
    with pytest.raises(ValueError, match="whole pages"):
        ws.pages_for_length(10, 16, WINDOW, CHUNK)


@pytest.fixture(scope="module")
def engine(toy):
    model, params = toy
    # 16 usable pages: an uncompacted row of 193 positions alone wants 25
    eng = ServeEngine(model, params, max_batch=3, max_seq_len=256,
                      kv_page_size=PAGE, kv_pool_pages=17, prefill_chunk=16)
    yield eng
    eng.stop(drain=True, timeout=30)


def test_the_engine_grants_the_models_own_page_count(toy, engine):
    """A request of 193 positions holds 9 pages where ``ceil(L / page)`` is
    25 and the pool has 16; rows never hold more than they reserved, the
    served tokens are the whole-sequence forward's, every page comes back
    at retire and no prefix is shared."""
    model, params = toy
    rng = np.random.default_rng(1)
    sizes = [(97, 96), (64, 20), (33, 5), (48, 40)]
    assert [engine.decoder.pages_for(p + n) for p, n in sizes] == [9, 5, 4, 5]
    assert engine.prefix_sharing is False
    prompts = [rng.integers(0, VOCAB, p, dtype=np.int32) for p, _ in sizes]
    prompts[3][:] = prompts[0][:48]             # a shared prefix: not shared
    handles = [engine.submit(p, max_new_tokens=n)
               for p, (_, n) in zip(prompts, sizes)]
    results = [h.result(timeout=300) for h in handles]
    assert engine.error is None
    assert engine.pool.used_pages == 0
    assert engine.pool.high_water <= 16
    assert engine.metrics.get("serve_prefix_hit_pages_total").value == 0
    closed = sum((p + n - 2) // WINDOW for p, n in sizes)
    assert engine.decoder.windows_closed == closed
    assert engine.metrics.get("serve_pages_reclaimed_total").value \
        == 3 * closed
    for prompt, res in zip(prompts, results):
        toks = list(res.tokens)
        full = np.concatenate([prompt, toks[:-1]])[None]
        want = np.asarray(teacher_forced_logits(model, params, full)
                          )[0, len(prompt) - 1:]
        chosen = want[np.arange(len(toks)), toks]
        assert (want.max(-1) - chosen).max() < 1e-4
    # the longest request the engine takes fits the pool it would have
    # outgrown twice over
    assert engine.decoder.pages_for(256) == 11 <= engine.pool.usable_pages


def test_a_traced_turn_names_the_closes_the_decoder_launched(toy, tmp_path):
    """Tracing on: ``windows_closed`` on a ``serve_decode`` or
    ``serve_prefill_chunk`` span is what ``Decoder._close_windows``
    launched before that call's body (the one count of it), each close is
    a ``compact`` lap of the turn, and a lap that launches a program
    (``launch_args``, ``chunk_host``) has begun before the first of
    them."""
    from dtf_tpu.obs import trace
    model, params = toy
    rng = np.random.default_rng(2)
    sizes = [(64, 40), (48, 20), (33, 3)]
    tracer = trace.configure(str(tmp_path))
    try:
        eng = ServeEngine(model, params, max_batch=3, max_seq_len=256,
                          kv_page_size=PAGE, kv_pool_pages=25,
                          prefill_chunk=16)
        try:
            for h in [eng.submit(rng.integers(0, VOCAB, p, dtype=np.int32),
                                 max_new_tokens=n) for p, n in sizes]:
                h.result(timeout=300)
        finally:
            eng.stop(drain=True, timeout=30)
    finally:
        trace.disable()
    spans = [r for r in trace.read_records(tracer.path)
             if r.get("kind") == "span"]
    closed = sum((p + n - 2) // WINDOW for p, n in sizes)
    assert eng.decoder.windows_closed == closed == 6
    calls = [r for r in spans
             if r["name"] in ("serve_decode", "serve_prefill_chunk")]
    assert all("kv_exact_rows_read" in r and "kv_rows_read" not in r
               for r in calls)
    assert sum(r["windows_closed"] for r in calls) == closed
    by_chunk = [r["windows_closed"] for r in calls
                if r["name"] == "serve_prefill_chunk"]
    # a chunk that starts window 1 closes window 0: 64 -> one, 48 -> one,
    # 33 -> one (its third chunk starts at 32)
    assert sum(by_chunk) == 3 and set(by_chunk) == {0, 1}
    turns = [r for r in spans if r["name"] == "serve_iteration"]
    laps = [[n for n, _ in r["laps"]] for r in turns]
    assert sum(names.count("compact") for names in laps) == closed
    for names in laps:
        for i, n in enumerate(names):
            if n == "compact":
                assert names[i - 1] in ("launch_args", "chunk_host",
                                        "compact"), names
    # and one ``serve_close_window`` span a close, round its launch, under
    # the span of the call that needed it
    closes = [r for r in spans if r["name"] == "serve_close_window"]
    assert [r["close"] for r in closes] == list(range(1, closed + 1))
    by_id = {r["span_id"]: r for r in calls}
    for r in closes:
        assert r["program"] == eng.decoder.program("close")
        assert 0 <= r["row"] < 3
        call = by_id[r["parent_span"]]
        assert call["windows_closed"] >= 1
        assert call["ts"] <= r["ts"] and r["ts"] + r["dur_s"] \
            <= call["ts"] + call["dur_s"]
    assert sum(r["parent"] == "serve_prefill_chunk" for r in closes) == 3


def test_what_the_kind_refuses(toy):
    model, params = toy
    with pytest.raises(ValueError, match="must divide the model's summary"):
        ServeEngine(model, params, max_batch=1, kv_page_size=PAGE,
                    prefill_chunk=24)
    dec = Decoder(model, params, num_slots=1, max_seq_len=128,
                  kv_page_size=PAGE, kv_pool_pages=20)
    with pytest.raises(ValueError, match="straddles a window"):
        dec.prefill_chunk(dec.fresh_cache(), np.zeros((16,), np.int32),
                          np.arange(16, dtype=np.int32), 24, 15, 0.0, seed=0)
    # the window's close is not sharded: no model axis under this kind
    from dtf_tpu.runtime.mesh import make_mesh
    mesh = make_mesh(jax.devices()[:2], model=2)
    with pytest.raises(ValueError, match="served on one device"):
        Decoder(model, params, num_slots=1, max_seq_len=128,
                kv_page_size=PAGE, kv_pool_pages=20, mesh=mesh)
    for bad in (dict(layer_window=(True,)), dict(kv_lora_rank=16),
                dict(head_dim=64), dict(summary_chunk=5)):
        other, _ = build_model("routed_decoder", num_classes=VOCAB,
                               dtype=jnp.float32, **dict(KW, **bad))
        with pytest.raises(ValueError, match="summar"):
            other.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


def test_the_memory_plan_is_the_leaves_bytes(toy):
    model, params = toy
    plan = serving_memory_plan(model, num_slots=3, max_seq_len=256,
                               kv_page_size=PAGE, kv_pool_pages=17,
                               params=params)
    dec = Decoder(model, params, num_slots=3, max_seq_len=256,
                  kv_page_size=PAGE, kv_pool_pages=17)
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(dec.fresh_cache))
    assert plan["kv_bytes_paged"] == sum(
        a.size * a.dtype.itemsize for a in leaves) * 16 // 17
    assert plan["per_token_kv_bytes"] == 2 * 2 * 4 * 16 * 4
    assert plan["param_bytes"] == sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    # 7 closed windows of one page and a whole open one of 4, not 32
    assert plan["pages_per_slot"] == dec.pages_for(256) == 7 + 4
    assert serving_memory_plan(model, num_slots=3, max_seq_len=256,
                               kv_page_size=PAGE)["pool_pages"] == 1 + 3 * 11
    assert dec.pages_per_slot == 32         # a table's width, as ever


def test_the_call_counts_rows_of_both_sorts(toy):
    model, params = toy
    dec_model = model.clone(decode=True, kv_page_size=PAGE, kv_pool_pages=9,
                            use_pallas=False)
    assert dec_model.stats_names == rd.SUMMARY_STATS
    cache = jax.eval_shape(lambda: dec_model.init(
        jax.random.key(0), jnp.zeros((1, PAGE), jnp.int32),
        cache_index=jnp.zeros((1,), jnp.int32),
        block_table=jnp.zeros((1, 1), jnp.int32))["cache"])
    cache = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                   cache)
    index = jnp.array([70, 64, 0], jnp.int32)       # 64: a close before it
    _, mut = dec_model.apply(
        {"params": params, "cache": cache}, jnp.zeros((3, 1), jnp.int32),
        cache_index=index, block_table=jnp.zeros((3, 12), jnp.int32),
        mutable=["cache", "stats"])
    counts = dict(zip(rd.SUMMARY_STATS, np.asarray(mut["stats"]["counts"])))
    assert counts["kv_exact_rows_read"] == 2 * (7 + 1 + 1)
    assert counts["kv_summary_rows_read"] == 2 * (16 + 16 + 0)
    # the closes are the decoder's to count: it launches them
    assert set(counts) == set(rd.STATS[:3]) | {"kv_exact_rows_read",
                                               "kv_summary_rows_read"}
    assert counts["assignments"] == counts["experts_touched"] == 0


def test_the_unit_offset_norm_scales_by_one_plus_its_vector():
    x = jax.random.normal(jax.random.key(0), (3, 16))
    g = jax.random.normal(jax.random.key(1), (16,)) * 0.1
    np.testing.assert_allclose(rd.rms_norm(x, g, 1e-5, True),
                               rd.rms_norm(x, 1.0 + g, 1e-5), rtol=1e-6)
