"""KV-page wire migration (serve/migrate.py + the engine's export/
import surface): the contracts disaggregated serving stands on.

The invariants (serve/migrate.py module docs):

  - a migrated page is BIT-IDENTICAL to a locally-prefilled one —
    decode after import is token-exact vs a colocated oracle, at every
    awkward prompt length (1, page-1, page, 3*page+7);
  - a page under a migration hold can NEVER be evicted, even when the
    pool is starving — refcount >= 2 by construction;
  - holds balance: fetch, push, abort and a dead peer all release
    exactly what they took (serve_migration_holds returns to 0);
  - verification is layered: a torn payload raises TornTransfer, a
    colliding digest with different tokens is rejected (the wire form
    of the registry's stored-token collision guard), a chain-digest
    mismatch aborts.
"""

import socket
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dtf_tpu import chaos
from dtf_tpu.models.transformer import TransformerLM
from dtf_tpu.serve import ServeEngine
from dtf_tpu.serve import migrate
from dtf_tpu.serve.replica import ReplicaServer

VOCAB, SEQ, PS = 64, 64, 8


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


@pytest.fixture(autouse=True)
def _no_chaos_leak():
    yield
    chaos.disable()


@pytest.fixture(scope="module")
def engine_pair(model_and_params):
    """One (src, dst) pair shared by the read-mostly tests here.
    Building an engine costs seconds of compile; these tests only ever
    ADD registry chains to a 25-page pool, and each uses a prompt with
    its own salt so their chains never alias."""
    src = make_engine(model_and_params)
    dst = make_engine(model_and_params)
    yield src, dst
    src.stop()
    dst.stop()


def make_engine(model_and_params, **kw):
    model, params = model_and_params
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", SEQ)
    kw.setdefault("max_delay_s", 0.0)
    kw.setdefault("kv_page_size", PS)
    kw.setdefault("kv_pool_pages", 25)
    kw.setdefault("seed", 3)
    return ServeEngine(model, params, **kw)


def _prompt(n, salt=0):
    return ((np.arange(1, n + 1, dtype=np.int32) + salt) % 63) + 1


def _export_all(eng, prompt):
    """Pull a whole chain out of ``eng`` through the export surface
    (holds taken and released), as wire-decoded payloads."""
    pages, digests = eng.export_chain_begin(prompt)
    try:
        leaves = eng.export_chain_read(pages, 0, len(pages))
        return ([migrate.decode_page(migrate.encode_page(l))
                 for l in leaves], digests)
    finally:
        eng.export_chain_end(pages)


# ---------------------------------------------------------------------------
# serialization + verification layers (no engine)
# ---------------------------------------------------------------------------

def test_encode_decode_roundtrip_and_torn_detection():
    leaves = [np.arange(24, dtype=np.float32).reshape(2, 3, 4),
              np.arange(6, dtype=np.int32).reshape(3, 2)]
    out = migrate.decode_page(migrate.encode_page(leaves))
    for a, b in zip(leaves, out):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    # a payload whose bytes do not hash to the claimed digest is TORN
    enc = migrate.encode_page(leaves)
    other = migrate.encode_page([l + 1 for l in leaves])
    enc["leaves"][0]["data"] = other["leaves"][0]["data"]
    with pytest.raises(migrate.TornTransfer):
        migrate.decode_page(enc)
    # layout is content too: same bytes, different shape = torn
    enc2 = migrate.encode_page(leaves)
    enc2["leaves"][0]["shape"] = [4, 3, 2]
    with pytest.raises(migrate.TornTransfer):
        migrate.decode_page(enc2)


def test_verify_page_rejects_collision_and_foreign_chain(monkeypatch):
    prompt = _prompt(2 * PS)
    expect = migrate.expected_chain(prompt, PS)
    leaves = [np.zeros((PS, 2, 4), np.float32)]
    good = {"depth": 1, "digest": expect[1],
            "tokens": [int(t) for t in prompt[PS:2 * PS]],
            "payload": migrate.encode_page(leaves)}
    assert migrate._verify_page(good, prompt, PS, expect)
    # COLLISION GUARD: force every chain digest to collide — a page
    # whose digest "matches" but whose tokens differ must still be
    # rejected (the wire form of the registry's stored-token check)
    monkeypatch.setattr(migrate, "_page_digest",
                        lambda prev, toks: "collide")
    collide = migrate.expected_chain(prompt, PS)
    assert collide == ["collide", "collide"]
    bad = dict(good, digest="collide",
               tokens=[int(t) for t in prompt[:PS]])
    with pytest.raises(migrate.MigrationError, match="tokens differ"):
        migrate._verify_page(bad, prompt, PS, collide)
    monkeypatch.undo()
    # chain-digest mismatch (two sides disagree what prefix this is)
    with pytest.raises(migrate.MigrationError, match="chain digest"):
        migrate._verify_page(dict(good, digest="deadbeef"),
                             prompt, PS, expect)
    # depth past the receiver's own chain
    with pytest.raises(migrate.MigrationError, match="only"):
        migrate._verify_page(dict(good, depth=7), prompt, PS, expect)


# ---------------------------------------------------------------------------
# decoder + engine level: bit identity and token exactness
# ---------------------------------------------------------------------------

def test_decoder_page_roundtrip_bit_identity(engine_pair):
    """Decoder level: write_page(read_page(p)) reproduces the page's
    exact bytes — the primitive the bit-identity contract rests on."""
    eng = engine_pair[0]
    prompt = _prompt(2 * PS)
    eng.generate(prompt, max_new_tokens=2)
    pages, _ = eng.export_chain_begin(prompt)
    assert len(pages) == 2

    def roundtrip():
        src = pages[0]
        leaves = eng.decoder.read_page(eng._cache, src)
        dst = eng.pool.alloc(1)[0]
        eng._cache = eng.decoder.write_page(eng._cache, dst, leaves)
        back = eng.decoder.read_page(eng._cache, dst)
        eng.pool.free([dst])
        return leaves, back

    leaves, back = eng.run_on_engine(roundtrip)
    eng.export_chain_end(pages)
    for a, b in zip(leaves, back):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_engine_without_prefix_sharing_has_no_migration_surface(
        model_and_params):
    eng = make_engine(model_and_params, prefix_sharing=False)
    try:
        prompt = _prompt(2 * PS)
        assert eng.export_chain_begin(prompt) == ([], [])
        with pytest.raises(RuntimeError, match="prefix sharing"):
            eng.import_chain(prompt, [[np.zeros(1, np.float32)]])
    finally:
        eng.stop()


@pytest.mark.parametrize("plen", [1, PS - 1, PS, 3 * PS + 7])
def test_migrated_chain_token_exact_vs_colocated(engine_pair, plen):
    """Engine level, the acceptance contract: decode after migration
    is token-exact vs the colocated oracle at every awkward prompt
    length.  Sub-page prompts have no full pages — the transfer is a
    clean no-op and exactness still holds.  Salting by plen keeps each
    length's chain disjoint on the shared pair, so the importer is
    genuinely cold for every case."""
    src, dst = engine_pair
    prompt = _prompt(plen, salt=1000 + plen)
    want = src.generate(prompt, max_new_tokens=8).tokens
    payloads, digests = _export_all(src, prompt)
    assert len(payloads) == plen // PS
    assert digests == migrate.expected_chain(prompt, PS)
    assert dst.import_chain(prompt, payloads) == len(payloads)
    got = dst.generate(prompt, max_new_tokens=8).tokens
    assert got == want
    if payloads:
        hits = dst.metrics.get("serve_prefix_hit_pages_total").value
        assert hits >= len(payloads)
        # bit identity end to end: re-export from the importer and
        # compare raw bytes against what crossed the wire
        back, _ = _export_all(dst, prompt)
        for pa, pb in zip(payloads, back):
            for la, lb in zip(pa, pb):
                assert la.tobytes() == lb.tobytes()
    assert src.metrics.get("serve_migration_holds").value == 0
    assert dst.metrics.get("serve_migration_holds").value == 0


# ---------------------------------------------------------------------------
# migration holds vs eviction / refcount balance
# ---------------------------------------------------------------------------

def test_hold_survives_starvation_eviction(model_and_params):
    """A mid-transfer chain outlives pool starvation: the flood evicts
    every refcount-1 registry page it can, but held pages (refcount
    >= 2) are untouchable — their bytes after the flood are identical
    to before."""
    eng = make_engine(model_and_params, kv_pool_pages=12)
    try:
        prompt = _prompt(3 * PS)
        eng.generate(prompt, max_new_tokens=2)
        pages, _ = eng.export_chain_begin(prompt)
        assert len(pages) == 3
        before = eng.export_chain_read(pages, 0, 3)
        # flood: distinct 2-page prompts whose registered pages exceed
        # the free pool — _evict_for must starve-evict cached prefixes
        for i in range(8):
            eng.generate(_prompt(2 * PS, salt=100 + 7 * i),
                         max_new_tokens=2)
        assert eng.registry.lookup(prompt) == pages
        after = eng.export_chain_read(pages, 0, 3)
        for pa, pb in zip(before, after):
            for la, lb in zip(pa, pb):
                assert la.tobytes() == lb.tobytes()
        eng.export_chain_end(pages)
        assert eng.metrics.get("serve_migration_holds").value == 0
        # the hold was the only shield: the same flood now evicts it
        for i in range(8):
            eng.generate(_prompt(2 * PS, salt=200 + 7 * i),
                         max_new_tokens=2)
        assert len(eng.registry.lookup(prompt)) < 3
    finally:
        eng.stop()


def test_refcounts_balance_after_export_abort(engine_pair):
    """An aborted transfer (begin, maybe some reads, end) leaves the
    pool exactly where it started."""
    eng = engine_pair[0]
    prompt = _prompt(3 * PS)
    eng.generate(prompt, max_new_tokens=2)
    used0 = eng.pool.used_pages
    shared0 = eng.pool.shared_refs
    for reads in (0, 2):
        pages, _ = eng.export_chain_begin(prompt)
        assert eng.pool.shared_refs == shared0 + 3
        assert eng.metrics.get("serve_migration_holds").value == 3
        if reads:
            eng.export_chain_read(pages, 0, reads)
        eng.export_chain_end(pages)          # abort: no import ever
        assert eng.pool.used_pages == used0
        assert eng.pool.shared_refs == shared0
        assert eng.metrics.get("serve_migration_holds").value == 0
    # double-end of the same chain must not double-free
    pages, _ = eng.export_chain_begin(prompt)
    eng.export_chain_end(pages)
    eng.export_chain_end([])
    assert eng.pool.shared_refs == shared0


@pytest.mark.slow
def test_dead_peer_connection_releases_holds(model_and_params):
    """A migration client that vanishes mid-transfer cannot pin pages:
    the replica connection's teardown drops its transfers' holds."""
    import tempfile
    eng = make_engine(model_and_params)
    rdv = tempfile.mkdtemp()
    srv = ReplicaServer(eng, 0, rdv).start()
    try:
        prompt = _prompt(3 * PS)
        eng.generate(prompt, max_new_tokens=2)
        conn = socket.create_connection((srv.host, srv.port), timeout=5)
        rf, wf = conn.makefile("rb"), conn.makefile("wb")
        import json
        wf.write((json.dumps(
            {"op": "page_fetch", "xfer": "t1",
             "prompt": [int(t) for t in prompt], "lo": 0, "n": 1})
            + "\n").encode())
        wf.flush()
        # one page + end marker arrive; the hold is now live
        msgs = [json.loads(rf.readline()) for _ in range(2)]
        assert msgs[0]["op"] == "page_push" and msgs[1].get("end")
        assert eng.metrics.get("serve_migration_holds").value == 3
        for c in (rf, wf, conn):             # vanish without release
            c.close()
        deadline = time.monotonic() + 5
        while (eng.metrics.get("serve_migration_holds").value
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert eng.metrics.get("serve_migration_holds").value == 0
    finally:
        srv.stop()
        eng.stop()


# ---------------------------------------------------------------------------
# the wire client end to end
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fetch_chain_wire_token_exact_and_stall_chaos(model_and_params):
    """fetch_chain over a real socket: windowed pull, import, token
    exactness — with a page_fetch_stall chaos arm proving the stall
    delays but never corrupts the transfer.  (slow: the wire path is
    also pinned every CI run by tools/disagg_smoke.py, stage 15.)"""
    import tempfile
    src = make_engine(model_and_params)
    dst = make_engine(model_and_params)
    rdv = tempfile.mkdtemp()
    srv = ReplicaServer(src, 0, rdv).start()
    try:
        prompt = _prompt(3 * PS + 7)
        want = src.generate(prompt, max_new_tokens=8).tokens
        chaos.configure("page_fetch_stall@replica0:0.01", rank=0)
        t0 = time.monotonic()
        stats = migrate.fetch_chain(dst, srv.host, srv.port, prompt,
                                    window=2)
        assert stats == {"pages": 3, "chain_len": 3, "torn": 0}
        assert time.monotonic() - t0 >= 0.02     # 2 windows stalled
        assert dst.generate(prompt, max_new_tokens=8).tokens == want
        assert src.metrics.get("serve_migration_holds").value == 0
        # a chain the peer never saw: clean no-op, never an error
        other = _prompt(2 * PS, salt=500)
        assert migrate.fetch_chain(dst, srv.host, srv.port, other) == \
            {"pages": 0, "chain_len": 0, "torn": 0}
    finally:
        srv.stop()
        src.stop()
        dst.stop()


# ---------------------------------------------------------------------------
# the disaggregated tier end to end (router orchestration)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_router_disagg_migrates_and_rehomes(model_and_params):
    """prefill_replicas=1 over real engines: a cold paged prompt
    prefills in the prefill pool, its chain migrates to the decode
    pool, and the repeat prompt decodes THERE on warm pages — token-
    exact against the first answer at every step.  (slow: the same
    re-home contract runs every CI as disagg_smoke, stage 15.)"""
    import tempfile
    from dtf_tpu.obs.watchdog import Heartbeat, heartbeat_path
    from dtf_tpu.serve.router import Router

    rdv = tempfile.mkdtemp()
    engines, servers, stops = [], [], []
    for rid in range(2):
        eng = make_engine(model_and_params)
        srv = ReplicaServer(eng, rid, rdv).start()
        stop = threading.Event()
        hb = Heartbeat(heartbeat_path(rdv, rid), interval_s=0.04)

        def beat(stop=stop, hb=hb):
            while not stop.wait(0.04):
                hb.beat(step=0)

        threading.Thread(target=beat, daemon=True).start()
        engines.append(eng)
        servers.append(srv)
        stops.append(stop)
    router = Router(2, rdv, probe_interval_s=0.05,
                    health_timeout_s=0.5, deadline_s=30.0,
                    replica_inflight=32, page_size=PS,
                    prefill_replicas=1, migrate_timeout_s=10.0)
    router.start(wait_s=10)
    try:
        prompt = _prompt(3 * PS + 7)
        r1 = router.generate(prompt, max_new_tokens=8)
        assert r1.replica == 0                   # cold → prefill pool
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            ms = router.migration_stats()
            if ms["migrated"]:
                break
            time.sleep(0.05)
        assert ms == {"migrated": 1, "failed": 0, "pending": 0}
        r2 = router.generate(prompt, max_new_tokens=8)
        assert r2.tokens == r1.tokens            # token-exact re-home
        assert r2.replica == 1                   # …in the decode pool
        hits = engines[1].metrics.get(
            "serve_prefix_hit_pages_total").value
        assert hits >= 3
        assert engines[0].metrics.get(
            "serve_migration_holds").value == 0
    finally:
        router.stop(drain=False)
        for s in stops:
            s.set()
        for s in servers:
            s.stop()
        for e in engines:
            e.stop()


@pytest.mark.slow
def test_prefetch_on_heal_warms_replica_token_exact(model_and_params):
    """A healed replica gets the hottest tracked prompt chain
    prefetched from its owner (router HA satellite: the failover/heal
    handoff) — counted by router_prefetch_pages_total, and decode on
    the prefetched pages is token-exact against the original answer."""
    import tempfile
    from dtf_tpu.obs.watchdog import Heartbeat, heartbeat_path
    from dtf_tpu.serve.router import Router

    rdv = tempfile.mkdtemp()
    engines, servers, stops = [], [], []
    hbs = []
    for rid in range(2):
        eng = make_engine(model_and_params)
        srv = ReplicaServer(eng, rid, rdv).start()
        stop = threading.Event()
        pause = threading.Event()
        hb = Heartbeat(heartbeat_path(rdv, rid), interval_s=0.04)

        def beat(stop=stop, pause=pause, hb=hb):
            while not stop.wait(0.04):
                if not pause.is_set():
                    hb.beat(step=0)

        threading.Thread(target=beat, daemon=True).start()
        engines.append(eng)
        servers.append(srv)
        stops.append(stop)
        hbs.append(pause)
    router = Router(2, rdv, probe_interval_s=0.05,
                    health_timeout_s=0.5, deadline_s=30.0,
                    replica_inflight=32, page_size=PS,
                    migrate_timeout_s=10.0)
    router.start(wait_s=10)
    try:
        # heat a paged chain on its affinity home
        prompt = _prompt(3 * PS + 7, salt=29)
        r1 = router.generate(prompt, max_new_tokens=8)
        for _ in range(2):
            assert router.generate(
                prompt, max_new_tokens=8).tokens == r1.tokens
        home = r1.replica
        other = 1 - home
        # the OTHER replica blips (heartbeat pause past the timeout)…
        hbs[other].set()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and router.replica_healthy(other):
            time.sleep(0.02)
        assert not router.replica_healthy(other)
        # …and heals: the heal handoff prefetches the hot chain
        hbs[other].clear()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if (router.replica_healthy(other)
                    and router.migration_stats()["migrated"] >= 1):
                break
            time.sleep(0.05)
        assert router.migration_stats()["migrated"] >= 1
        pages = router.metrics.get("router_prefetch_pages_total").value
        assert pages >= 3
        # force traffic onto the healed replica: the chain's owner goes
        # down, affinity re-homes, and decode runs on PREFETCHED pages
        hbs[home].set()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and router.replica_healthy(home):
            time.sleep(0.02)
        r2 = router.generate(prompt, max_new_tokens=8)
        assert r2.replica == other
        assert r2.tokens == r1.tokens            # token-exact on warm pages
        hits = engines[other].metrics.get(
            "serve_prefix_hit_pages_total").value
        assert hits >= 3
    finally:
        router.stop(drain=False)
        for s in stops:
            s.set()
        for s in servers:
            s.stop()
        for e in engines:
            e.stop()
