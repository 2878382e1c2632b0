"""Serving subsystem tests: checkpoint→inference bridge, KV-cache
decode (token-exact vs the teacher-forced forward), and the dynamic
batching engine's edge cases.

All tier-1 (no `slow` marks): tiny models, CPU mesh.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtf_tpu.models.transformer import TransformerLM
from dtf_tpu.serve import (Backpressure, Decoder, ServeEngine,
                           collect_stats, load_inference_variables,
                           place_for_serving)
from dtf_tpu.serve.decode import teacher_forced_logits
from test_paged import prefill_in_chunks

VOCAB, SEQ = 64, 16
PAGE = 4


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


# ---------------------------------------------------------------------------
# decode: token-exact vs teacher-forced
# ---------------------------------------------------------------------------

def _rows_of_pages(dec, batch):
    """Block tables giving row i its own pages (page 0 is scratch)."""
    m = dec.pages_per_slot
    return 1 + np.arange(batch * m, dtype=np.int32).reshape(batch, m)


@pytest.mark.parametrize("batch", [1, 4, 8])
def test_decode_token_exact_vs_teacher_forced(model_and_params, batch):
    """Feeding the SAME token sequence through the cache path one token
    at a time must reproduce the teacher-forced forward's argmax at
    every position, for every row — the decode path computes the same
    function, incrementally."""
    model, params = model_and_params
    rng = np.random.default_rng(batch)
    toks = rng.integers(0, VOCAB, (batch, 12)).astype(np.int32)
    ref = np.argmax(np.asarray(
        teacher_forced_logits(model, params, toks)), -1)

    dec = Decoder(model, params, num_slots=batch, max_seq_len=SEQ,
                  kv_page_size=PAGE)
    cache = dec.fresh_cache()
    tables = _rows_of_pages(dec, batch)
    got = np.zeros_like(ref)
    # prefill each row's first token into its pages
    for i in range(batch):
        cache, logits = prefill_in_chunks(dec, cache, toks[i, :1],
                                          tables[i])
        got[i, 0] = int(np.argmax(np.asarray(logits)))
    index = np.ones((batch,), np.int32)
    temps = np.zeros((batch,), np.float32)
    seeds = np.zeros((batch,), np.uint32)
    for t in range(1, toks.shape[1]):
        _, cache, logits = dec.decode_step(cache, toks[:, t], index,
                                           temps, seeds, tables)
        got[:, t] = np.argmax(np.asarray(logits), -1)
        index += 1
    np.testing.assert_array_equal(ref, got)


def test_decode_prefill_chunk_matches_stepwise(model_and_params):
    """Writing a prompt as page-aligned chunks must leave the pages in
    the same state as feeding it token by token through decode_step:
    the logits at its last position agree."""
    model, params = model_and_params
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, VOCAB, (9,)).astype(np.int32)

    dec = Decoder(model, params, num_slots=1, max_seq_len=SEQ,
                  kv_page_size=PAGE)
    tables = _rows_of_pages(dec, 1)
    # chunked prefill: 4 + 4 + a page-padded 1
    _, chunk_logits = prefill_in_chunks(dec, dec.fresh_cache(), prompt,
                                        tables[0], chunk=PAGE)
    # stepwise
    c2 = dec.fresh_cache()
    for t in range(len(prompt)):
        _, c2, step_logits = dec.decode_step(
            c2, prompt[t:t + 1], np.array([t], np.int32),
            np.zeros((1,), np.float32), np.zeros((1,), np.uint32), tables)
    np.testing.assert_allclose(np.asarray(chunk_logits),
                               np.asarray(step_logits[0]),
                               rtol=1e-5, atol=1e-5)


def test_decode_rejects_seq_sharded_config():
    """seq_axis (ring attention) still refuses decode; model_axis now
    composes — that path is tests/test_serve_tp.py's subject."""
    model = tiny_model(seq_axis="seq", decode=True, kv_page_size=PAGE,
                       kv_pool_pages=5)
    with pytest.raises(ValueError, match="seq_axis"):
        model.init(jax.random.key(0), jnp.zeros((1, SEQ), jnp.int32),
                   cache_index=jnp.zeros((1,), jnp.int32),
                   block_table=jnp.zeros((1, 4), jnp.int32))


def test_decode_model_needs_its_page_pool_shape():
    """decode=True without kv_page_size/kv_pool_pages has no cache to
    build: refused when the model is first traced, not served from
    some other layout."""
    model = tiny_model(decode=True)
    with pytest.raises(ValueError, match="kv_page_size"):
        model.init(jax.random.key(0), jnp.zeros((1, SEQ), jnp.int32),
                   cache_index=jnp.zeros((1,), jnp.int32))


@pytest.mark.parametrize("build, page", [
    (Decoder, 0), (ServeEngine, 0), (ServeEngine, None)],
    ids=["decoder-0", "engine-0", "engine-none"])
def test_kv_page_size_must_be_a_page(model_and_params, build, page):
    """There is one KV cache: a page size of 0/None selects nothing and
    is refused by name."""
    model, params = model_and_params
    kw = ({"num_slots": 2} if build is Decoder else {"max_batch": 2})
    with pytest.raises(ValueError, match="kv_page_size"):
        build(model, params, max_seq_len=SEQ, kv_page_size=page, **kw)


# ---------------------------------------------------------------------------
# engine: correctness + batcher edge cases
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(model_and_params):
    model, params = model_and_params
    eng = ServeEngine(model, params, max_batch=4, max_seq_len=SEQ,
                      max_delay_s=0.005, queue_size=8)
    yield eng
    eng.stop(drain=False)


def _oracle(model, params, prompt, n_new):
    """Greedy generation via padded full forwards (one compile)."""
    fwd = jax.jit(lambda p, t: model.apply({"params": p}, t))
    toks = list(map(int, prompt))
    out = []
    for _ in range(n_new):
        padded = np.zeros((1, SEQ), np.int32)
        padded[0, :len(toks)] = toks
        logits = fwd(params, jnp.asarray(padded))
        nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def test_engine_greedy_matches_oracle_across_lengths(engine,
                                                     model_and_params):
    """Six staggered varied-length requests through 4 slots (forces
    continuous batching: retire + re-admit mid-flight) all reproduce
    the full-forward greedy oracle exactly."""
    model, params = model_and_params
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, (n,)).astype(np.int32)
               for n in (3, 5, 2, 7, 4, 6)]
    handles = [engine.submit(p, max_new_tokens=SEQ - len(p))
               for p in prompts]
    results = [h.result(timeout=300) for h in handles]
    for p, r in zip(prompts, results):
        assert r.tokens == _oracle(model, params, p, SEQ - len(p))
        assert r.latency_s >= 0 and not r.cancelled
    stats = collect_stats(engine.completed, engine.shed_count)
    assert stats.num_requests >= len(prompts)
    assert stats.tokens_per_s > 0


def test_engine_empty_queue_timeout_then_serves(engine):
    """An idle engine (empty queue) must neither busy-crash nor wedge:
    after sitting idle it still serves the next request."""
    time.sleep(0.3)  # idle: several empty-queue wait timeouts elapse
    r = engine.submit(np.array([1, 2], np.int32),
                      max_new_tokens=3).result(timeout=120)
    assert len(r.tokens) == 3


def test_engine_single_oversized_request_rejected_loudly(engine):
    with pytest.raises(ValueError, match="oversized"):
        engine.submit(np.arange(SEQ, dtype=np.int32), max_new_tokens=1)
    with pytest.raises(ValueError, match="oversized"):
        engine.submit(np.array([1], np.int32), max_new_tokens=SEQ)
    # an in-bounds request still works afterwards
    r = engine.submit(np.array([1], np.int32),
                      max_new_tokens=2).result(timeout=120)
    assert len(r.tokens) == 2


def test_engine_heartbeat_from_engine_loop(model_and_params, tmp_path):
    """Serve processes emit obs heartbeat files like train ranks do:
    the ENGINE LOOP rewrites heartbeat_rank{N}.json (step = completed
    count), so launch.py's hang watchdog — and the serving router's
    health probe — cover serving.  Beating from the loop is the
    contract: a deadlocked engine thread stops beating."""
    from dtf_tpu.obs.watchdog import Heartbeat, heartbeat_path, \
        read_heartbeat
    model, params = model_and_params
    path = heartbeat_path(str(tmp_path), 0)
    eng = ServeEngine(model, params, max_batch=2, max_seq_len=SEQ,
                      max_delay_s=0.0,
                      heartbeat=Heartbeat(path, interval_s=0.01))
    try:
        assert read_heartbeat(path) is not None, \
            "heartbeat file must exist before the first request"
        eng.submit(np.array([1, 2], np.int32),
                   max_new_tokens=2).result(timeout=120)
        deadline = time.time() + 30
        while time.time() < deadline:
            hb = read_heartbeat(path)
            if hb and hb.get("step") == 1:
                break
            time.sleep(0.02)
        assert read_heartbeat(path)["step"] == 1, (
            "engine loop never beat with the completed count")
        assert read_heartbeat(path)["pid"] == os.getpid()
    finally:
        eng.stop(drain=False)


def test_engine_sheds_under_backpressure(model_and_params):
    """Queue full ⇒ Backpressure with a positive retry_after; accepted
    requests still complete, and the shed is counted."""
    model, params = model_and_params
    eng = ServeEngine(model, params, max_batch=1, max_seq_len=SEQ,
                      max_delay_s=0.2, queue_size=2)
    try:
        handles = [eng.submit(np.array([i + 1], np.int32),
                              max_new_tokens=2) for i in range(2)]
        shed = 0
        with pytest.raises(Backpressure) as ei:
            for i in range(50):  # the queue only drains 1/slot at a time
                handles.append(eng.submit(np.array([1], np.int32),
                                          max_new_tokens=2))
        assert ei.value.retry_after > 0
        assert eng.shed_count >= 1
        for h in handles:
            assert len(h.result(timeout=300).tokens) == 2
    finally:
        eng.stop(drain=False)


def test_engine_eos_stops_early(model_and_params):
    """A request whose eos_id appears stops before max_new_tokens."""
    model, params = model_and_params
    prompt = np.array([5, 9], np.int32)
    ref = _oracle(model, params, prompt, 8)
    eos = ref[2]  # stops at the FIRST occurrence, wherever that is
    expect = ref[:ref.index(eos) + 1]
    assert len(expect) < 8  # the test only means something if it stops early
    eng = ServeEngine(model, params, max_batch=1, max_seq_len=SEQ,
                      max_delay_s=0.0, queue_size=4)
    try:
        r = eng.submit(prompt, max_new_tokens=8,
                       eos_id=eos).result(timeout=120)
        assert r.tokens == expect
    finally:
        eng.stop(drain=False)


def test_engine_temperature_sampling_in_vocab(model_and_params):
    """Temperature > 0 samples valid token ids (and the engine mixes
    greedy and sampled rows in one batch without error)."""
    model, params = model_and_params
    eng = ServeEngine(model, params, max_batch=2, max_seq_len=SEQ,
                      max_delay_s=0.05, queue_size=4, seed=1)
    try:
        h1 = eng.submit(np.array([3], np.int32), max_new_tokens=6,
                        temperature=1.0)
        h2 = eng.submit(np.array([3], np.int32), max_new_tokens=6,
                        temperature=0.0)
        r1, r2 = h1.result(timeout=120), h2.result(timeout=120)
        assert all(0 <= t < VOCAB for t in r1.tokens)
        assert r2.tokens == _oracle(model, params,
                                    np.array([3], np.int32), 6)
    finally:
        eng.stop(drain=False)


# ---------------------------------------------------------------------------
# bridge: checkpoint → inference variables
# ---------------------------------------------------------------------------

def test_bridge_loads_train_checkpoint(tmp_path, model_and_params):
    """A train-format checkpoint (full TrainState incl. optimizer
    state) round-trips through the structure-free bridge restore; the
    reloaded params serve the same logits."""
    optax = pytest.importorskip("optax")
    from dtf_tpu.train.checkpoint import Checkpointer
    from dtf_tpu.train.loop import TrainState

    model, params = model_and_params
    tx = optax.sgd(0.1)
    state = TrainState(step=jnp.asarray(7, jnp.int32), params=params,
                       batch_stats={}, opt_state=tx.init(params))
    ck = Checkpointer(str(tmp_path))
    ck.save(state, step=7)
    ck.wait()
    ck.close()

    variables = load_inference_variables(model_dir=str(tmp_path))
    assert set(variables) == {"params", "batch_stats"}
    variables = place_for_serving(variables)
    toks = np.arange(8, dtype=np.int32).reshape(1, 8) % VOCAB
    np.testing.assert_allclose(
        np.asarray(teacher_forced_logits(model, params, toks)),
        np.asarray(teacher_forced_logits(model, variables["params"],
                                         toks)),
        rtol=1e-6, atol=1e-6)


def test_bridge_loads_export_format(tmp_path, model_and_params):
    import types

    from dtf_tpu.train.checkpoint import export_model

    model, params = model_and_params
    export_model(str(tmp_path), types.SimpleNamespace(
        params=params, batch_stats={}))
    variables = load_inference_variables(export_dir=str(tmp_path))
    leaves_a = jax.tree_util.tree_leaves(params)
    leaves_b = jax.tree_util.tree_leaves(variables["params"])
    assert len(leaves_a) == len(leaves_b)
    for a, b in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bridge_missing_checkpoint_fails_loudly(tmp_path):
    with pytest.raises(FileNotFoundError, match="no checkpoint"):
        load_inference_variables(model_dir=str(tmp_path / "nope"))


def test_serve_fails_when_the_engine_thread_dies(model_and_params,
                                                 monkeypatch):
    """A decode failure kills the engine thread, which hands every
    client a cancelled, empty result so nobody hangs — serve() must
    turn that into a failed run carrying the cause, not into stats of
    zeros and exit code 0."""
    from dtf_tpu.cli import serve_main
    from dtf_tpu.config import parse_flags

    model, params = model_and_params

    def rigged_engine(cfg, random_init=False, replica_rank=None):
        engine = ServeEngine(model, params, max_batch=2, max_seq_len=SEQ,
                             kv_page_size=4, max_delay_s=0.0)

        def boom(*a, **kw):
            raise FloatingPointError("injected decode failure")
        engine.decoder.decode_step = boom
        return model, engine

    monkeypatch.setattr(serve_main, "build_serving_engine", rigged_engine)
    cfg = parse_flags(["--serve_requests", "3", "--serve_prompt_len", "4",
                       "--serve_max_new_tokens", "4"],
                      defaults=serve_main.SERVE_DEFAULTS)
    with pytest.raises(RuntimeError, match="engine thread died") as exc:
        serve_main.serve(cfg)
    assert isinstance(exc.value.__cause__, FloatingPointError)


@pytest.mark.slow
def test_serve_main_random_init_demo(tmp_path, monkeypatch):
    """The CLI entry end-to-end on a tiny config: synthetic traffic
    through the engine, BenchmarkMetric-format metric.log written."""
    import json
    import os

    from dtf_tpu.cli.serve_main import main

    blog = str(tmp_path / "blog")
    out = main(["--serve_random_init", "--model", "transformer_small",
                "--num_classes", "64",
                "--serve_max_seq_len", "32", "--serve_requests", "3",
                "--serve_max_new_tokens", "4", "--serve_prompt_len", "4",
                "--serve_max_batch", "2", "--benchmark_log_dir", blog])
    assert out["requests"] == 3 and out["shed"] == 0
    assert out["tokens_per_second"] > 0
    metric_log = os.path.join(blog, "metric.log")
    names = [json.loads(line)["name"]
             for line in open(metric_log)]
    assert "serve_tokens_per_second" in names
    assert "serve_latency_p99" in names
