"""Prefix-sharing / copy-on-write pages + token streaming: the engine
edge cases that make sharing safe to ship.

The invariants (serve/engine.py module docs):

  - a shared system prompt costs ONE physical copy (pool high-water);
  - refcounts release on retire, and a page physically frees only when
    its LAST holder leaves;
  - COW protects the one write that can target a shared page (a prompt
    that is entirely a registered prefix) — the original page stays
    pristine for its other holders;
  - a hash collision degrades to a MISS (stored token ids are
    verified), never to serving another prompt's KV;
  - cached (registry-only) prefixes are EVICTED under pool pressure —
    they never starve live traffic — but pages live slots hold are
    untouchable;
  - drain finishes in-flight work that holds shared pages.

Everything greedy + tiny model ⇒ token streams are deterministic, so
each scenario also pins TOKEN EXACTNESS vs a sharing-off engine — the
proof that sharing changed the memory story, not the math.
"""

import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import dtf_tpu.serve.engine as engine_mod
from dtf_tpu.models.transformer import TransformerLM
from dtf_tpu.serve import Backpressure, PagePool, ServeEngine
from dtf_tpu.serve.engine import PrefixRegistry

VOCAB, SEQ, PS = 64, 64, 8
PREFIX = np.arange(1, 2 * PS + 1, dtype=np.int32)     # 2 full pages


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


def make_engine(model_and_params, **kw):
    model, params = model_and_params
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", SEQ)
    kw.setdefault("max_delay_s", 0.0)
    kw.setdefault("kv_page_size", PS)
    return ServeEngine(model, params, **kw)


def _settle(eng, timeout=5.0):
    """Wait until the engine thread has retired everything it is going
    to (slots empty) — registry/pool state is then quiescent."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        with eng._cond:
            if not eng._pending and all(s is None for s in eng._slots):
                return
        time.sleep(0.01)
    raise TimeoutError("engine did not go idle")


# ---------------------------------------------------------------------------
# pool refcounts
# ---------------------------------------------------------------------------

def test_pool_share_free_refcount_lifecycle():
    pool = PagePool(6)                      # pages 1..5 usable
    pages = pool.alloc(2)
    assert pool.used_pages == 2 and pool.refcount(pages[0]) == 1
    pool.share(pages)                       # second holder
    assert pool.shared_refs == 2
    assert pool.free(pages) == []           # first release: still live
    assert pool.used_pages == 2
    assert sorted(pool.free(pages)) == sorted(pages)   # last holder
    assert pool.used_pages == 0 and pool.shared_refs == 0
    with pytest.raises(ValueError, match="double free"):
        pool.free([pages[0]])
    with pytest.raises(ValueError, match="not allocated"):
        pool.share([pages[0]])


def test_pool_high_water_counts_physical_pages_once():
    """Ten holders of one page are one physical page — the high-water
    mark is the sharing win, quantified."""
    pool = PagePool(6)
    (p,) = pool.alloc(1)
    for _ in range(9):
        pool.share([p])
    assert pool.high_water == 1 and pool.shared_refs == 9


# ---------------------------------------------------------------------------
# sharing: one physical copy, release on retire
# ---------------------------------------------------------------------------

def test_shared_prefix_single_physical_copy_and_release(model_and_params):
    """Three sequential same-prefix requests: the 2 prefix pages are
    written once, hit twice; after all retire the ONLY live pages are
    the registry's cached prefix."""
    eng = make_engine(model_and_params, kv_pool_pages=25)
    try:
        tails = [np.array([t], np.int32) for t in (5, 9, 13)]
        ref = {}
        for t in tails:
            prompt = np.concatenate([PREFIX, t])
            ref[t[0]] = eng.submit(prompt, max_new_tokens=3).result(
                timeout=120).tokens
            _settle(eng)
        assert eng.metrics.get("serve_prefix_hit_pages_total").value == 4
        # retired: registry holds exactly the 2 prefix pages, refcount 1
        assert len(eng.registry) == 2
        assert eng.pool.used_pages == 2
        # exactness vs a sharing-off engine
        eng2 = make_engine(model_and_params, kv_pool_pages=25,
                           prefix_sharing=False)
        try:
            for t in tails:
                prompt = np.concatenate([PREFIX, t])
                assert eng2.generate(
                    prompt, max_new_tokens=3).tokens == ref[t[0]]
        finally:
            eng2.stop(drain=False)
    finally:
        eng.stop(drain=False)


def test_refcount_high_water_concurrent_burst(model_and_params):
    """Four CONCURRENT same-prefix requests after a warm-up: high-water
    stays at one prefix copy + per-request tails, far below four full
    copies."""
    eng = make_engine(model_and_params, kv_pool_pages=33)
    try:
        eng.submit(PREFIX, max_new_tokens=2).result(timeout=120)
        _settle(eng)
        eng.reset_measurement()
        tails = [np.array([t, t + 1], np.int32) for t in (3, 7, 11, 15)]
        handles = [eng.submit(np.concatenate([PREFIX, t]),
                              max_new_tokens=4) for t in tails]
        for h in handles:
            h.result(timeout=120)
        # per request: ceil((18 + 4)/8) = 3 total pages, 2 shared →
        # 1 fresh each; high-water ≤ 2 prefix + 4 tails (+1 for the
        # warm request's still-cached tail page, freed at its retire)
        assert eng.pool.high_water <= 2 + 4 + 1
        assert eng.metrics.get("serve_prefix_hit_pages_total").value == 8
    finally:
        eng.stop(drain=False)


def test_sharing_fits_twice_the_concurrent_sequences_at_equal_pages(
        model_and_params):
    """At ONE page budget too small for N unshared copies of a system
    prompt, the peak of concurrently admitted sequences with
    ``prefix_sharing`` on is at least twice that with it off: admission
    is then bounded by a request's TAIL pages, not its whole prompt."""
    n, sys_pages, budget = 4, 4, 16
    sys_prompt = np.arange(1, sys_pages * PS + 1, dtype=np.int32) % VOCAB
    tails = [np.array([t, t + 1, t + 2, t + 3], np.int32)
             for t in (3, 7, 11, 15)]
    total = -(-(sys_pages * PS + 4 + budget) // PS)       # pages a request
    tail_pages = total - sys_pages
    # scratch + one whole copy + the other requests' tails: every
    # request fits when the prompt is shared, two whole copies otherwise
    pool = 1 + total + (n - 1) * tail_pages
    assert n * total > pool - 1 >= 2 * total
    peak = {}
    for sharing in (True, False):
        eng = make_engine(model_and_params, max_batch=n, kv_pool_pages=pool,
                          prefix_sharing=sharing)
        try:
            eng.submit(sys_prompt, max_new_tokens=2).result(timeout=120)
            _settle(eng)
            eng.reset_measurement()
            handles = [eng.submit(np.concatenate([sys_prompt, t]),
                                  max_new_tokens=budget) for t in tails]
            for h in handles:
                h.result(timeout=120)
            peak[sharing] = eng.max_concurrent
        finally:
            eng.stop(drain=False)
    assert peak[False] >= 1
    assert peak[True] >= 2 * peak[False], peak


# ---------------------------------------------------------------------------
# copy-on-write
# ---------------------------------------------------------------------------

def test_cow_on_fully_shared_prompt_exact_and_pristine(model_and_params):
    """A prompt that IS a registered prefix re-decodes its last token
    into a COPIED page.  Its tokens are exact, and the original page
    stays pristine — a THIRD request sharing the same prefix still
    decodes exactly."""
    eng = make_engine(model_and_params, kv_pool_pages=25)
    try:
        tail = np.array([33], np.int32)
        eng.submit(np.concatenate([PREFIX, tail]),
                   max_new_tokens=2).result(timeout=120)
        _settle(eng)
        r_cow = eng.submit(PREFIX, max_new_tokens=4).result(timeout=120)
        assert eng.metrics.get("serve_prefix_cow_total").value == 1
        _settle(eng)
        # original pages pristine: the next sharer is still exact
        r_share = eng.submit(np.concatenate([PREFIX, tail]),
                             max_new_tokens=4).result(timeout=120)
        eng2 = make_engine(model_and_params, prefix_sharing=False)
        try:
            assert eng2.generate(PREFIX,
                                 max_new_tokens=4).tokens == r_cow.tokens
            assert eng2.generate(np.concatenate([PREFIX, tail]),
                                 max_new_tokens=4).tokens == r_share.tokens
        finally:
            eng2.stop(drain=False)
    finally:
        eng.stop(drain=False)


def test_divergent_tail_never_cows(model_and_params):
    """A prompt extending PAST the registered prefix writes only fresh
    pages — divergence happens where the share ends, no COW needed."""
    eng = make_engine(model_and_params, kv_pool_pages=25)
    try:
        eng.submit(PREFIX, max_new_tokens=2).result(timeout=120)
        _settle(eng)
        eng.submit(np.concatenate([PREFIX, [1, 2, 3]]),
                   max_new_tokens=3).result(timeout=120)
        assert eng.metrics.get("serve_prefix_cow_total").value == 0
        assert eng.metrics.get("serve_prefix_hit_pages_total").value == 2
    finally:
        eng.stop(drain=False)


# ---------------------------------------------------------------------------
# hash-collision guard
# ---------------------------------------------------------------------------

def test_hash_collision_degrades_to_miss(model_and_params, monkeypatch):
    """With a pathological digest (every prefix collides), the stored
    token ids catch the mismatch: zero false hits, exact tokens."""
    monkeypatch.setattr(engine_mod, "_page_digest",
                        lambda prev, tokens: "collide")
    eng = make_engine(model_and_params, kv_pool_pages=25)
    try:
        a = np.concatenate([PREFIX, [5]])
        b_prefix = PREFIX[::-1].copy()       # different ids, same digest
        b = np.concatenate([b_prefix, [5]])
        ra = eng.submit(a, max_new_tokens=3).result(timeout=120)
        _settle(eng)
        rb = eng.submit(b, max_new_tokens=3).result(timeout=120)
        assert eng.metrics.get("serve_prefix_hit_pages_total").value == 0
        eng2 = make_engine(model_and_params, prefix_sharing=False)
        try:
            assert eng2.generate(a, max_new_tokens=3).tokens == ra.tokens
            assert eng2.generate(b, max_new_tokens=3).tokens == rb.tokens
        finally:
            eng2.stop(drain=False)
    finally:
        eng.stop(drain=False)


def test_registry_lookup_verifies_stored_tokens():
    """Unit-level collision pin: two prefixes with a forced-equal
    digest — lookup returns the registered one's pages and MISSES the
    impostor."""
    reg = PrefixRegistry(4)
    a = np.arange(4, dtype=np.int32)
    b = a[::-1].copy()
    reg.register(a, [7])
    import unittest.mock as um
    with um.patch.object(engine_mod, "_page_digest",
                         lambda prev, t: "same"):
        reg2 = PrefixRegistry(4)
        reg2.register(a, [7])
        assert reg2.lookup(a) == [7]
        assert reg2.lookup(b) == []          # digest hits, tokens differ
    assert reg.lookup(b) == []


# ---------------------------------------------------------------------------
# pool exhaustion with shared pages held
# ---------------------------------------------------------------------------

def test_cached_prefix_evicted_under_pool_pressure(model_and_params):
    """Pool too small for a new request + the cached prefix: the
    registry-only pages are evicted (deepest first) and the request
    admits instead of deadlocking behind a cold cache."""
    # usable 7: prefix request uses 2 prefix + 1 tail-ish page
    eng = make_engine(model_and_params, kv_pool_pages=8)
    try:
        eng.submit(PREFIX, max_new_tokens=2).result(timeout=120)
        _settle(eng)
        assert len(eng.registry) == 2 and eng.pool.used_pages == 2
        # needs 6 pages: only 5 free until the cached prefix yields.
        # (Distinct tokens from PREFIX — a shared head would dodge the
        # starvation this test exists to create.)
        big = (np.arange(1, 40, dtype=np.int32) * 3 + 1) % VOCAB
        r = eng.submit(big.astype(np.int32),
                       max_new_tokens=8).result(timeout=120)
        assert len(r.tokens) == 8
        assert eng.metrics.get("serve_prefix_evicted_total").value >= 1
        # the cached chain lost (at least) its deepest page — the big
        # request's own pages may have re-registered afterwards, but
        # the ORIGINAL prefix no longer resolves in full
        assert len(eng.registry.lookup(PREFIX)) < 2
    finally:
        eng.stop(drain=False)


def test_live_shared_pages_survive_pressure_then_admit(model_and_params):
    """Pages a LIVE slot holds are never evicted: a starved admit
    waits FIFO for the retire, then proceeds — and the holder's tokens
    are unaffected."""
    eng = make_engine(model_and_params, kv_pool_pages=8, max_batch=2)
    try:
        # holder: 2 prefix pages + 1 page of budget, long generation
        holder = eng.submit(PREFIX, max_new_tokens=7)
        time.sleep(0.2)                      # prefill done, decoding
        big = (np.arange(1, 40, dtype=np.int32) * 3 + 1) % VOCAB
        starved = eng.submit(big.astype(np.int32), max_new_tokens=8)
        rh = holder.result(timeout=120)
        rs = starved.result(timeout=120)
        assert len(rh.tokens) == 7 and len(rs.tokens) == 8
        eng2 = make_engine(model_and_params, prefix_sharing=False)
        try:
            assert eng2.generate(PREFIX,
                                 max_new_tokens=7).tokens == rh.tokens
        finally:
            eng2.stop(drain=False)
    finally:
        eng.stop(drain=False)


def test_pool_sized_request_with_cached_prompt_no_livelock(
        model_and_params):
    """A request sized EXACTLY to the pool whose full prompt is a
    registered prefix: the COW target would make physical demand
    usable+1, which can never be satisfied — admission must degrade
    the hit (prefill the last page instead of COW) and complete, not
    livelock the FIFO head forever."""
    model, params = model_and_params
    # usable 4; prompt 2 pages + budget 2 pages = exactly 4
    eng = ServeEngine(model, params, max_batch=2, max_seq_len=SEQ,
                      max_delay_s=0.0, kv_page_size=PS, kv_pool_pages=5)
    try:
        ra = eng.submit(PREFIX, max_new_tokens=2 * PS).result(timeout=120)
        _settle(eng)
        assert len(eng.registry) == 2        # prompt pages cached
        rb = eng.submit(PREFIX, max_new_tokens=2 * PS).result(timeout=120)
        assert rb.tokens == ra.tokens        # same prompt, greedy
        assert eng.metrics.get("serve_prefix_cow_total").value == 0
    finally:
        eng.stop(drain=False)


# ---------------------------------------------------------------------------
# drain with live shared prefixes
# ---------------------------------------------------------------------------

def test_drain_finishes_inflight_shared_prefixes(model_and_params):
    """begin_drain with same-prefix requests in flight: they finish
    (exact), new submits shed, stop() joins cleanly."""
    eng = make_engine(model_and_params, kv_pool_pages=33)
    try:
        eng.submit(PREFIX, max_new_tokens=2).result(timeout=120)
        _settle(eng)
        handles = [eng.submit(np.concatenate([PREFIX, [t]]),
                              max_new_tokens=6) for t in (3, 9)]
        eng.begin_drain()
        with pytest.raises(Backpressure):
            eng.submit(np.array([1], np.int32), max_new_tokens=2)
        results = [h.result(timeout=120) for h in handles]
        assert all(len(r.tokens) == 6 and not r.cancelled
                   for r in results)
        eng.stop(drain=True)
        eng2 = make_engine(model_and_params, prefix_sharing=False)
        try:
            for t, r in zip((3, 9), results):
                assert eng2.generate(np.concatenate([PREFIX, [t]]),
                                     max_new_tokens=6).tokens == r.tokens
        finally:
            eng2.stop(drain=False)
    finally:
        eng.stop(drain=False)


# ---------------------------------------------------------------------------
# token streaming
# ---------------------------------------------------------------------------

def test_stream_yields_every_token_in_order(model_and_params):
    """stream() and result() see the same tokens; the callback fires
    from the engine thread per retired token."""
    eng = make_engine(model_and_params)
    try:
        seen = []
        h = eng.submit(np.array([2, 4, 6], np.int32), max_new_tokens=5,
                       on_token=seen.append)
        streamed = list(h.stream(timeout=60))
        r = h.result(timeout=60)
        assert streamed == r.tokens == seen
        assert len(streamed) == 5
    finally:
        eng.stop(drain=False)


def test_stream_first_token_before_retire(model_and_params):
    """The streaming consumer receives token 1 while the request is
    still decoding — first-token latency, not full-retire latency."""
    eng = make_engine(model_and_params)
    try:
        got_first = threading.Event()
        done_at_first = []

        def on_token(_):
            if not got_first.is_set():
                done_at_first.append(False)
                got_first.set()

        h = eng.submit(np.array([3], np.int32), max_new_tokens=16,
                       on_token=on_token)
        assert got_first.wait(timeout=60)
        assert not h.done()                  # still generating
        r = h.result(timeout=60)
        assert len(r.tokens) == 16
        lag = eng.metrics.get("serve_stream_lag_s")
        assert lag is not None               # histogram registered
    finally:
        eng.stop(drain=False)


def test_stream_timeout_raises(model_and_params):
    """A consumer polling a handle whose engine is wedged behind a
    long queue gets TimeoutError, not a silent hang."""
    eng = make_engine(model_and_params)
    try:
        h = eng.submit(np.array([1], np.int32), max_new_tokens=2)
        h.result(timeout=60)
        it = h.stream(timeout=0.05)
        # stream after completion yields the buffered tokens then ends
        assert len(list(it)) == 2
        h2 = eng.submit(np.array([1], np.int32), max_new_tokens=2)
        h2.result(timeout=60)
        list(h2.stream(timeout=60))
        with pytest.raises(TimeoutError):
            # fresh handle, nothing ever submitted for it
            next(iter(engine_mod._Handle(
                engine_mod.ServeRequest(
                    prompt=np.array([1], np.int32))).stream(timeout=0.05)))
    finally:
        eng.stop(drain=False)


def test_on_token_exception_does_not_kill_engine(model_and_params):
    """A raising client callback is logged and contained — the request
    still completes and the engine serves the next one."""
    eng = make_engine(model_and_params)
    try:
        def bad(_tok):
            raise RuntimeError("client bug")

        r = eng.submit(np.array([7], np.int32), max_new_tokens=3,
                       on_token=bad).result(timeout=60)
        assert len(r.tokens) == 3
        r2 = eng.submit(np.array([8], np.int32),
                        max_new_tokens=2).result(timeout=60)
        assert len(r2.tokens) == 2
    finally:
        eng.stop(drain=False)
