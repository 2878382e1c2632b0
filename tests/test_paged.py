"""Paged KV cache + chunked flash prefill: exactness, reclamation,
admission, and scheduling.

The two invariants that make paging shippable:

  1. EXACTNESS — the paged/chunked decode path computes the same
     function as the teacher-forced forward, token for token, at prompt
     lengths that exercise every page-geometry edge: 1 (sub-page),
     page_size − 1 (page boundary minus one), page_size (exactly one
     page), 3·page_size + 7 (multi-page, non-aligned, multi-chunk).
  2. RECLAMATION — pages freed by a retiring slot are reused by the
     next admit (pool high-water mark bounded by the CONCURRENT need,
     not the total traffic), and admission waits for pages instead of
     overcommitting.

All tier-1 (tiny model, CPU).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dtf_tpu.models.transformer import TransformerLM
from dtf_tpu.serve import Decoder, PagePool, ServeEngine
from dtf_tpu.serve.decode import teacher_forced_logits

VOCAB, SEQ = 64, 32
PAGE = 4                                 # tiny page so 32 tokens = 8 pages
CHUNK = 8                                # 2 pages per prefill chunk
PROMPT_LENS = (1, PAGE - 1, PAGE, 3 * PAGE + 7)   # 1, 3, 4, 19


def tiny_model(**kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("num_layers", 2)
    kw.setdefault("d_model", 32)
    kw.setdefault("num_heads", 2)
    kw.setdefault("d_ff", 64)
    kw.setdefault("max_seq_len", SEQ)
    return TransformerLM(**kw)


@pytest.fixture(scope="module")
def model_and_params():
    model = tiny_model()
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, SEQ), jnp.int32))["params"]
    return model, params


def paged_engine(model, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", SEQ)
    kw.setdefault("max_delay_s", 0.0)
    kw.setdefault("queue_size", 16)
    kw.setdefault("kv_page_size", PAGE)
    kw.setdefault("prefill_chunk", CHUNK)
    return ServeEngine(model, params, **kw)


def _oracle(model, params, prompt, n_new):
    """Greedy generation via padded full forwards (one compile)."""
    fwd = jax.jit(lambda p, t: model.apply({"params": p}, t))
    toks = list(map(int, prompt))
    out = []
    for _ in range(n_new):
        padded = np.zeros((1, SEQ), np.int32)
        padded[0, : len(toks)] = toks
        logits = fwd(params, jnp.asarray(padded))
        nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def prefill_in_chunks(dec, cache, prompt, block_row, chunk=CHUNK):
    """Write ``prompt`` into the pages of ``block_row`` the way the
    engine does — full ``chunk``-token chunks, then a page-padded
    remainder (mirrors ServeEngine._chunk_plan) — greedy.  Returns
    (cache, logits at the prompt's last real position)."""
    page, plen = dec.page_size, len(prompt)
    plan, start = [], 0
    while plen - start > chunk:
        plan.append((start, chunk))
        start += chunk
    plan.append((start, -(-(plen - start) // page) * page))
    padded = np.zeros((plan[-1][0] + plan[-1][1],), np.int32)
    padded[:plen] = prompt
    for ci, (start, clen) in enumerate(plan):
        last = ci == len(plan) - 1
        _, cache, logits = dec.prefill_chunk(
            cache, padded[start:start + clen], block_row, start,
            plen - 1 - start if last else 0, 0.0, seed=0)
    return cache, logits


# ---------------------------------------------------------------------------
# decoder-level exactness across page geometries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plen", PROMPT_LENS)
def test_paged_chunked_prefill_token_exact(model_and_params, plen):
    """Chunked prefill through the pages + paged decode reproduce the
    teacher-forced argmax at EVERY position — prefill next-token
    included — for prompts spanning sub-page to multi-page,
    non-page-aligned lengths."""
    model, params = model_and_params
    dec = Decoder(model, params, num_slots=2, max_seq_len=SEQ,
                  kv_page_size=PAGE)
    cache = dec.fresh_cache()
    rng = np.random.default_rng(plen)
    total = min(SEQ, plen + 6)
    toks = rng.integers(0, VOCAB, (1, total)).astype(np.int32)
    ref = np.argmax(np.asarray(
        teacher_forced_logits(model, params, toks)), -1)

    # slot 0 owns pages 1..pages_per_slot (engine normally allocates;
    # here we drive the decoder directly)
    block_row = np.arange(1, dec.pages_per_slot + 1, dtype=np.int32)
    cache, logits = prefill_in_chunks(dec, cache, toks[0, :plen], block_row)
    assert int(np.argmax(np.asarray(logits))) == ref[0, plen - 1]

    # teacher-forced stepwise decode over the remaining positions; the
    # second (empty) slot exercises the scratch-page write path
    index = np.array([plen, 0], np.int32)
    tables = np.zeros((2, dec.pages_per_slot), np.int32)
    tables[0] = block_row
    temps = np.zeros((2,), np.float32)
    for t in range(plen, total):
        step = np.array([toks[0, t], 0], np.int32)
        _, cache, logits = dec.decode_step(
            cache, step, index, temps, np.zeros((2,), np.uint32), tables)
        assert int(np.argmax(np.asarray(logits)[0])) == ref[0, t], t
        index[0] += 1


@pytest.mark.parametrize("plen", PROMPT_LENS)
def test_paged_engine_greedy_matches_oracle(model_and_params, plen):
    """End-to-end through the paged engine (50%-sized pool, chunked
    prefill): greedy output equals the full-forward oracle at every
    page-geometry edge length."""
    model, params = model_and_params
    # 50% of one full reservation per slot
    full = 4 * (SEQ // PAGE)
    eng = paged_engine(model, params, kv_pool_pages=1 + full // 2)
    try:
        n_new = min(6, SEQ - plen)
        prompt = np.random.default_rng(7 + plen).integers(
            0, VOCAB, (plen,)).astype(np.int32)
        r = eng.generate(prompt, max_new_tokens=n_new)
        assert r.tokens == _oracle(model, params, prompt, n_new)
    finally:
        eng.stop(drain=False)


# ---------------------------------------------------------------------------
# page pool: reclamation, admission, high-water
# ---------------------------------------------------------------------------

def test_page_pool_alloc_free_high_water():
    pool = PagePool(9)                    # 8 usable + scratch
    assert pool.usable_pages == 8 and pool.free_pages == 8
    a = pool.alloc(5)
    assert a is not None and 0 not in a   # scratch page never granted
    assert pool.used_pages == 5 and pool.high_water == 5
    assert pool.alloc(4) is None          # never a partial grant
    assert pool.used_pages == 5           # failed alloc takes nothing
    pool.free(a)
    b = pool.alloc(8)
    assert b is not None and pool.high_water == 8
    pool.free(b)
    assert pool.used_pages == 0


def test_pages_reclaimed_across_requests(model_and_params):
    """Sequential requests through a pool sized for ~2 concurrent: all
    complete, pages return to the pool, and the high-water mark stays
    at the CONCURRENT need — proof retired pages were reused, not
    leaked."""
    model, params = model_and_params
    # each request: prompt 4 + budget 4 = 8 tokens = 2 pages.  Sharing
    # off: this test pins pure reclamation (pool drains to ZERO at
    # retire); the owning prefix registry deliberately keeps cached
    # prompt pages alive — that behavior is tests/test_prefix_sharing.py
    eng = paged_engine(model, params, max_batch=2, prefix_sharing=False,
                       kv_pool_pages=1 + 4)   # room for exactly 2
    try:
        rng = np.random.default_rng(0)
        handles = [eng.submit(
            rng.integers(0, VOCAB, (4,)).astype(np.int32),
            max_new_tokens=4) for _ in range(6)]
        for h in handles:
            assert len(h.result(timeout=300).tokens) == 4
        assert eng.pool.used_pages == 0            # everything reclaimed
        # 6 requests x 2 pages ran through a 4-page pool: reuse is the
        # only way that completes; high-water == the concurrent need
        assert eng.pool.high_water <= 4
    finally:
        eng.stop(drain=False)


def test_admission_waits_for_pages_fifo(model_and_params):
    """A pool that fits ONE long request at a time: the second waits
    for the first's retire (no overcommit, no deadlock), and both
    outputs stay oracle-exact."""
    model, params = model_and_params
    plen, n_new = 12, 4                    # 16 tokens = 4 pages
    eng = paged_engine(model, params, max_batch=2,
                       kv_pool_pages=1 + 4)
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, VOCAB, (plen,)).astype(np.int32)
                   for _ in range(2)]
        handles = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
        results = [h.result(timeout=300) for h in handles]
        for p, r in zip(prompts, results):
            assert r.tokens == _oracle(model, params, p, n_new)
        assert eng.pool.high_water <= 4    # never both in flight
        assert eng.max_concurrent == 1
    finally:
        eng.stop(drain=False)


def test_submit_rejects_pool_infeasible_request(model_and_params):
    """A request whose worst-case page need exceeds the whole pool can
    never be admitted — rejected loudly at submit, not queued forever."""
    model, params = model_and_params
    eng = paged_engine(model, params, kv_pool_pages=1 + 2)  # 8 tokens
    try:
        with pytest.raises(ValueError, match="page pool"):
            eng.submit(np.arange(12, dtype=np.int32) % VOCAB,
                       max_new_tokens=4)
        # an in-bounds request still works afterwards
        r = eng.submit(np.array([1, 2], np.int32),
                       max_new_tokens=2).result(timeout=120)
        assert len(r.tokens) == 2
    finally:
        eng.stop(drain=False)


# ---------------------------------------------------------------------------
# chunked-prefill scheduling
# ---------------------------------------------------------------------------

def test_long_prompt_prefills_in_chunks_while_decoding(model_and_params):
    """A max-length prompt admitted next to a running decode goes
    through multiple prefill chunks (counter-asserted) and BOTH results
    stay oracle-exact — the interleaving changes scheduling, never
    math."""
    model, params = model_and_params
    eng = paged_engine(model, params, max_batch=2)
    try:
        rng = np.random.default_rng(11)
        short = rng.integers(0, VOCAB, (2,)).astype(np.int32)
        long_p = rng.integers(0, VOCAB, (SEQ - 4,)).astype(np.int32)
        h1 = eng.submit(short, max_new_tokens=12)
        h2 = eng.submit(long_p, max_new_tokens=4)
        r1, r2 = h1.result(timeout=300), h2.result(timeout=300)
        assert r1.tokens == _oracle(model, params, short, 12)
        assert r2.tokens == _oracle(model, params, long_p, 4)
        # 28-token prompt at 8-token chunks = 4 chunks for the long one
        chunks = eng.metrics.get("serve_prefill_chunks_total").value
        assert chunks >= 4 + 1            # long's 4 + short's 1
    finally:
        eng.stop(drain=False)


def test_decode_steps_record_their_live_pages(model_and_params):
    """``serve_decode_live_pages``: one sample per decode step, the
    pages the step's rows hold — ceil((index + 1) / page) over all
    ``max_batch`` rows, an idle row counting its scratch page.  One
    request alone in a 4-row batch: a 5-token prompt (PAGE 4) decodes 5
    tokens at index 5..9, i.e. 2, 2, 2, 3, 3 pages, plus 3 idle rows."""
    model, params = model_and_params
    eng = paged_engine(model, params, max_batch=4)
    try:
        prompt = np.arange(1, 6, dtype=np.int32)
        eng.submit(prompt, max_new_tokens=6).result(timeout=300)
        h = eng.metrics.get("serve_decode_live_pages")
        assert h.count == eng.metrics.get("serve_decode_step_s").count == 5
        assert (h.percentile(0), h.percentile(100)) == (2 + 3, 3 + 3)
        assert h.mean == pytest.approx((2 + 2 + 2 + 3 + 3) / 5 + 3)
    finally:
        eng.stop(drain=False)


SERVED = ((19, 0.0), (3, 0.8), (4, 0.0), (1, 0.0), (19, 0.8), (7, 0.0))


def _served(model, params, trace_dir=None, **engine_kw):
    """Six requests through a 4-slot engine (so two wait for a slot and
    chunks run beside decode steps): the tokens served, the decoder and,
    traced, the span records."""
    from dtf_tpu.obs import trace
    tracer = trace.configure(trace_dir) if trace_dir else None
    try:
        with paged_engine(model, params, **engine_kw) as eng:
            handles = [eng.submit(np.arange(1, n + 1, dtype=np.int32) % 60,
                                  max_new_tokens=6, temperature=t)
                       for n, t in SERVED]
            tokens = [h.result(timeout=300).tokens for h in handles]
    finally:
        trace.disable()
    records = trace.read_records(tracer.path) if tracer else []
    return tokens, [r for r in records
                    if r.get("kind") == "span"], eng.decoder


def test_an_iteration_is_one_record_of_named_laps(model_and_params,
                                                  tmp_path):
    """Tracing on: one ``serve_iteration`` span a turn of the engine
    thread, cut into laps in loop order with the turn's counts, the three
    older spans its children and otherwise as they were — and the tokens
    served those of an untraced engine."""
    model, params = model_and_params
    untraced, none, _ = _served(model, params)
    tokens, spans, _ = _served(model, params, str(tmp_path))
    assert tokens == untraced and not none
    turns = [r for r in spans if r["name"] == "serve_iteration"]
    by_id = {r["span_id"]: r for r in turns}
    for r in turns:
        assert sum(s for _, s in r["laps"]) == pytest.approx(r["dur_s"],
                                                             abs=1e-9)
    stepped = [r for r in turns if "step" in r]
    assert [r["step"] for r in stepped] == list(range(1, len(stepped) + 1))
    order = ["sweep", "admit", "sweep", "gauges", "build", "launch_args",
             "launch_call", "ready", "emit", "rest"]
    for r in stepped:
        laps = [n for n, _ in r["laps"] if not n.startswith("chunk_")]
        assert laps == order, laps
    assert sum(r["decoding"] for r in stepped) == sum(
        len(t) for t in tokens) - 6      # a prompt's first token: its chunk's
    chunked = [r for r in turns if "chunk" in r]
    assert [r["chunk"] for r in chunked] == list(range(1, len(chunked) + 1))
    for r in chunked:                   # after the second sweep, before gauges
        laps = [n for n, _ in r["laps"]]
        assert laps[3] == "chunk_host" and laps[laps.index("gauges") - 1] \
            == "chunk_host"
        assert set(laps[3:laps.index("gauges")]) <= {"chunk_host",
                                                     "chunk_sync"}
    # a turn that only waited
    assert any([n for n, _ in r["laps"]] == ["sweep", "wait", "rest"]
               for r in turns)
    assert sum(r.get("admitted", 0) for r in turns) == 6
    assert sum(r["retired"] for r in turns if "retired" in r) == 6
    assert all(r["cancelled"] == 0 and r["pages_used"] >= 0
               and r["pending"] >= 0 for r in turns if "pending" in r)
    # the three spans that were there: names, attributes, one a launch,
    # each the child of the turn that made it
    plain = {"kind", "name", "ts", "dur_s", "span_id", "parent",
             "parent_span", "rank"}
    # (``allheads`` went with PR 52: no reader; the gauge
    # ``serve_paged_decode_allheads`` says it once an engine)
    attrs = {"serve_decode": {"traces", "step", "program", "rows",
                              "context_tokens"},
             "serve_prefill_chunk": {"slot", "start", "tokens", "last",
                                     "trace", "chunk", "program",
                                     "real_tokens"},
             "serve_batch_form": {"admitted", "traces"}}
    for name, want in attrs.items():
        mine = [r for r in spans if r["name"] == name]
        assert mine and all(set(r) - plain == want for r in mine), name
        assert all(r["parent"] == "serve_iteration" and "laps" not in r
                   and by_id[r["parent_span"]]["ts"] <= r["ts"]
                   for r in mine)
    assert len([r for r in spans if r["name"] == "serve_decode"]) \
        == len(stepped)
    assert len([r for r in spans if r["name"] == "serve_prefill_chunk"]) \
        == len(chunked)


@pytest.mark.parametrize("sharing", [True, False],
                         ids=["prefixes_shared", "every_prompt_prefilled"])
def test_a_traced_engine_anchors_its_clock_and_names_its_launches(
        model_and_params, tmp_path, monkeypatch, sharing):
    """Tracing ON: before every ANCHOR_TURNS-th decode launch a
    ``clock_anchor`` span (consecutive ``n``) inside the turn's
    ``launch_args`` lap, which adds no lap name of its own; a
    ``serve_decode`` span carries its turn's ``step``, the body's name,
    the rows in phase decode and the positions their queries see; a
    ``serve_prefill_chunk`` span its turn's ``chunk`` and the prompt's
    remainder on a last chunk.  Tracing OFF: the decoder builds no anchor
    and names no program, and serves the same tokens."""
    from dtf_tpu.serve import engine
    model, params = model_and_params
    monkeypatch.setattr(engine, "ANCHOR_TURNS", 3)
    untraced, none, off = _served(model, params, prefix_sharing=sharing)
    assert off._anchor is None and off.anchors_run == 0 and not none
    assert off._program_names == {}
    tokens, spans, dec = _served(model, params, str(tmp_path),
                                 prefix_sharing=sharing)
    assert tokens == untraced
    turns = {r["span_id"]: r for r in spans if r["name"] == "serve_iteration"}
    steps = [r for r in spans if r["name"] == "serve_decode"]
    chunks = [r for r in spans if r["name"] == "serve_prefill_chunk"]
    anchors = [r for r in spans if r["name"] == "clock_anchor"]
    # the anchors: one built with the decode body (its run has no span),
    # then one before every third launch
    assert dec.anchors_run == 1 + len(anchors) == 1 + len(steps) // 3
    assert [r["n"] for r in anchors] == list(range(2, 2 + len(anchors)))
    order = ["sweep", "admit", "sweep", "gauges", "build", "launch_args",
             "launch_call", "ready", "emit", "rest"]
    for r in anchors:
        turn = turns[r["parent_span"]]
        assert turn["step"] % 3 == 0 and r["program"] == "jit__clock_anchor"
        laps = turn["laps"]
        assert [n for n, _ in laps if not n.startswith("chunk_")] == order
        at = [n for n, _ in laps].index("launch_args")
        began = turn["ts"] + sum(s for _, s in laps[:at])
        assert began <= r["ts"]
        assert r["ts"] + r["dur_s"] <= began + laps[at][1]
    # the steps
    for r in steps:
        turn = turns[r["parent_span"]]
        assert r["step"] == turn["step"] and r["rows"] == turn["decoding"]
        assert r["program"] == dec.program("decode") \
            == "jit__decode_paged_impl"
        assert r["rows"] <= r["context_tokens"]
    # a request of p prompt tokens and m new ones feeds token j at
    # position p + j - 1, whose query sees p + j positions, j = 1 .. m - 1
    assert sum(r["context_tokens"] for r in steps) == sum(
        p + j for p, _ in SERVED for j in range(1, 6))
    assert sum(r["rows"] for r in steps) == 6 * 5
    # the chunks
    lens = sorted(p for p, _ in SERVED)
    for r in chunks:
        assert r["chunk"] == turns[r["parent_span"]]["chunk"]
        assert r["program"] == dec.program("chunk") == "jit__chunk_impl"
        if r["last"]:
            assert r["start"] + r["real_tokens"] in lens
            assert 0 <= r["tokens"] - r["real_tokens"] < PAGE
        else:
            assert r["real_tokens"] == r["tokens"]
    if not sharing:
        assert sum(r["real_tokens"] for r in chunks) == sum(lens)
        assert sum(r["last"] for r in chunks) == 6


def test_begin_drain_racing_inflight_prefill_chunk(model_and_params):
    """begin_drain() landing BETWEEN a request's prefill chunks (the
    SIGTERM-mid-prefill race): the drain must finish that request —
    remaining chunks run, decode completes, tokens stream — not strand
    its pages or drop it, while NEW submits shed.  Pinned against the
    no-drain oracle and a fully-reclaimed pool."""
    import time as _time

    from dtf_tpu.serve import Backpressure
    model, params = model_and_params
    # sharing off so full reclamation is exactly used_pages == 0 (the
    # owning registry would intentionally keep prompt pages alive)
    eng = paged_engine(model, params, max_batch=2, prefix_sharing=False)
    try:
        rng = np.random.default_rng(23)
        long_p = rng.integers(0, VOCAB, (SEQ - 4,)).astype(np.int32)
        h = eng.submit(long_p, max_new_tokens=4)   # 28 tokens = 4 chunks
        streamed = []
        # the race: drain the moment the FIRST chunk has run, while
        # chunks 2-4 are still pending in the slot's chunk plan
        deadline = _time.time() + 120
        while (eng.metrics.get("serve_prefill_chunks_total").value < 1
               and _time.time() < deadline):
            _time.sleep(0.001)
        assert eng.metrics.get("serve_prefill_chunks_total").value >= 1
        eng.begin_drain()
        with pytest.raises(Backpressure):
            eng.submit(np.array([1], np.int32), max_new_tokens=1)
        streamed = list(h.stream(timeout=300))
        r = h.result(timeout=300)
        assert not r.cancelled
        assert r.tokens == _oracle(model, params, long_p, 4)
        assert streamed == r.tokens, "drain dropped streamed tokens"
        assert eng.metrics.get("serve_prefill_chunks_total").value >= 4
        eng.stop(drain=True)
        assert eng.pool.used_pages == 0, (
            f"drain stranded {eng.pool.used_pages} pages")
    finally:
        eng.stop(drain=False)


def test_unchunked_and_chunked_prefill_agree(model_and_params):
    """prefill_chunk=0 (whole-prompt single chunk) and chunked prefill
    produce identical greedy output — chunking is pure scheduling."""
    model, params = model_and_params
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, VOCAB, (19,)).astype(np.int32)
    outs = []
    for chunk in (0, CHUNK):
        eng = paged_engine(model, params, prefill_chunk=chunk)
        try:
            outs.append(eng.generate(prompt, max_new_tokens=6).tokens)
        finally:
            eng.stop(drain=False)
    assert outs[0] == outs[1] == _oracle(model, params, prompt, 6)
