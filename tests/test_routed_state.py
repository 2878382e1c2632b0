"""The routed decoder's layers WITHOUT attention (``layer_mixer``
``short_conv``: a double-gated short convolution whose running state rides
the page table) beside grouped-query layers with per-head norms and
``[k | v]`` pool rows, two leading dense layers, sigmoid-plus-bias routing
and a tied head, against the plain reference
(``benchmark/families/reference_lfm2.py``) and its own oracles.  The toy
keeps the shape of the thing: two dense + six routed layers in the order
c c a c c c a c, 4 query / 2 KV heads of 64 (the width at which K and V
share one pool row, as at the published size), 8 experts top-2, a non-zero
bias.  float32 throughout, so what is compared is the mathematics and not
a rounding."""

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

from dtf_tpu.models import build_model  # noqa: E402
from dtf_tpu.models import routed_decoder as rd  # noqa: E402
from dtf_tpu.serve import migrate  # noqa: E402
from dtf_tpu.serve.bridge import serving_memory_plan  # noqa: E402
from dtf_tpu.serve.decode import Decoder  # noqa: E402
from dtf_tpu.serve.engine import ServeEngine, chunk_plan  # noqa: E402

MIXERS = ["short_conv", "short_conv", "attention", "short_conv",
          "short_conv", "short_conv", "attention", "short_conv"]
TOY = dict(num_layers=8, d_model=64, num_heads=4, num_kv_heads=2, head_dim=64,
           layer_mixer=MIXERS, conv_taps=3, qk_norm=True,
           tie_head=True, layer_window=[False], layer_rope=[True],
           rope_theta=10000.0, rms_eps=1e-5, num_dense_layers=2,
           dense_width=96, num_experts=8, experts_per_token=2,
           expert_width=32, routing="sigmoid_bias", routed_scale=1.0,
           routing_sum_eps=1e-6, router_bias_stddev=0.05, activation="silu",
           router_input="post_attention", max_seq_len=256)
# the module: ``dtf_tpu.ops`` exports a function of the same name
pa = importlib.import_module("dtf_tpu.ops.paged_attention")
VOCAB, PAGE, CHUNK = 128, 8, 16
N_CONV = MIXERS.count("short_conv")


@pytest.fixture(scope="module")
def toy():
    model, _ = build_model("routed_decoder", num_classes=VOCAB,
                           dtype=jnp.float32, **TOY)
    params = model.init(jax.random.key(3),
                        jnp.zeros((1, PAGE), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def reference():
    ref = importlib.import_module("benchmark.families.reference_lfm2")
    return ref, ref.arch_of_model_kwargs(TOY)


@pytest.fixture(scope="module")
def decoders(toy):
    """One decoder a path, shared by the tests (each starts from a fresh
    cache): a body compiles once a chunk shape, not once a test."""
    model, params = toy
    return {up: Decoder(model.clone(use_pallas=up), params, num_slots=4,
                        max_seq_len=128, kv_page_size=PAGE, kv_pool_pages=65)
            for up in (False, "interpret")}


_REF_LEN = 128


def _ref_logits(reference, params, tokens):
    """The reference's logits of ``tokens`` [B, S]: causal, so the
    sequences are padded to one length and one program serves every
    test."""
    ref, arch = reference
    if "fn" not in _ref_logits.__dict__:
        _ref_logits.fn = jax.jit(lambda p, t: ref._head(
            ref.hidden(p, t, arch), p["embed"]))
    tokens = np.asarray(tokens)
    b, s = tokens.shape
    padded = np.zeros((b, _REF_LEN), np.int32)
    padded[:, :s] = tokens
    return np.asarray(_ref_logits.fn(params, jnp.asarray(padded)))[:, :s]


def _close(got, want):
    """1e-4 of the logit scale: both sides are float32 and differ in the
    order of their sums (grouped expert rows against dense masked experts,
    a carried state against the whole sequence's pad) — a wrong carry,
    mask, position, norm or routing weight moves logits by their whole
    spread."""
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_model_equals_reference(toy, reference):
    """(a) Teacher-forced logits of the program's full forward against the
    plain reference's: both gates and all three taps, the per-head norms,
    the bias in the choice and not in the weight, 1e-6 in the sum, the
    final norm, the tied head."""
    model, params = toy
    assert model.carries_state and "lm_head" not in params
    assert params["layer2"]["attn"]["q_norm"].shape == (64,)
    assert params["layer0"]["conv"]["taps"].shape == (64, 3)
    # the toy's bias is not zero, so it can move a choice
    assert float(jnp.abs(params["layer2"]["router_bias"]).max()) > 0.01
    tokens = np.random.default_rng(0).integers(0, VOCAB, (2, 60),
                                               dtype=np.int32)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(tokens)))
    _close(got, _ref_logits(reference, params, tokens))


def _prefill(dec, cache, prompt, table, start=0):
    for s, clen in chunk_plan(len(prompt), CHUNK, PAGE, start):
        chunk = np.zeros((clen,), np.int32)
        real = prompt[s:s + clen]
        chunk[:len(real)] = real
        _, cache, last = dec.prefill_chunk(
            cache, chunk, table, s, len(real) - 1, 0.0, seed=0)
    return cache, np.asarray(last)


def _serve(dec, rows, new_tokens, slots_of=None):
    """Chunked prefill of every row (the engine's chunk plan), then
    ``new_tokens`` lockstep decode steps feeding the given continuation
    back: the logits at every position that would choose a token.
    ``slots_of``: the decode row of each request (idle rows between)."""
    slots = dec.num_slots
    slots_of = slots_of or list(range(len(rows)))
    cache = dec.fresh_cache()
    tables = np.zeros((slots, dec.pages_per_slot), np.int32)
    nxt = 1
    out = [[] for _ in rows]
    for r, (prompt, _) in enumerate(rows):
        need = -(-(len(prompt) + new_tokens) // PAGE)
        tables[slots_of[r], :need] = np.arange(nxt, nxt + need)
        nxt += need
        cache, last = _prefill(dec, cache, prompt, tables[slots_of[r]])
        out[r].append(last)
    index = np.zeros((slots,), np.int32)
    for r, (p, _) in enumerate(rows):
        index[slots_of[r]] = len(p)
    for j in range(new_tokens - 1):
        tokens = np.zeros((slots,), np.int32)
        for r, (_, cont) in enumerate(rows):
            tokens[slots_of[r]] = cont[j]
        _, cache, step = dec.decode_step(
            cache, tokens, index, np.zeros((slots,), np.float32),
            seeds=np.zeros((slots,), np.uint32), block_tables=tables)
        for r in range(len(rows)):
            out[r].append(np.asarray(step[slots_of[r]]))
            index[slots_of[r]] += 1
    return [np.stack(o) for o in out], cache


# the gather path compiles a body a visible window, so it takes the two
# cases that cross every kind of boundary; the kernel path (one body a
# chunk shape, the Pallas interpreter) takes them all
@pytest.mark.parametrize("lengths,slots_of,use_pallas", [
    ((1,), None, "interpret"), ((PAGE - 1,), None, "interpret"),
    ((PAGE,), None, "interpret"), ((PAGE + 1,), None, "interpret"),
    ((CHUNK - 1,), None, "interpret"), ((CHUNK,), None, "interpret"),
    ((CHUNK + 1,), None, "interpret"), ((CHUNK + 1,), None, False),
    ((5, 40, 61, 100), None, "interpret"), ((33, 70), [1, 3], "interpret"),
    ((9, 33), [0, 2], False),
], ids=["one", "page-1", "page", "page+1", "chunk-1", "chunk", "chunk+1",
        "chunk+1-gather", "batch4", "idle_rows_between",
        "idle_rows_between-gather"])
def test_paged_serving_equals_reference(toy, reference, decoders, lengths,
                                        slots_of, use_pallas):
    """(b) Chunked prefill then decode through pages and state entries
    (chunks of 16, pages of 8) against the reference's full forward over
    prompt + continuation: carries across chunk boundaries, a final chunk
    whose entry is taken at its last real token, steps that cross page
    boundaries, rows of different lengths in one decode batch, idle rows
    whose entries go to the scratch page."""
    model, params = toy
    rng = np.random.default_rng(1)
    new = 2 * PAGE + 2          # a step on either side of two page ends
    rows = [(rng.integers(0, VOCAB, n, dtype=np.int32),
             rng.integers(0, VOCAB, new, dtype=np.int32)) for n in lengths]
    dec = decoders[use_pallas]
    got, _ = _serve(dec, rows, new, slots_of)
    for (prompt, cont), g in zip(rows, got):
        seq = np.concatenate([prompt, cont])[None]
        want = _ref_logits(reference, params, seq)[0][
            len(prompt) - 1:len(prompt) - 1 + new]
        _close(g, want)
    counts = dict(zip(model.stats_names,
                      (int(c) for c in dec.last_stats["counts"])))
    assert counts["conv_tokens"] == 4 * N_CONV
    # only the live rows' entries went to pages of their own
    assert counts["state_rows_advanced"] == len(rows) * N_CONV
    assert counts["kv_tokens_read_window"] == 0


def test_idle_rows_move_nobodys_state(decoders):
    """(b) A decode step in which a row is idle (index 0, an all-zero
    table) changes no state entry but the scratch page's and the live
    rows' own."""
    dec = decoders["interpret"]
    rng = np.random.default_rng(2)
    rows = [(rng.integers(0, VOCAB, 19, dtype=np.int32),
             rng.integers(0, VOCAB, 2, dtype=np.int32))]
    _, cache = _serve(dec, rows, 1, [2])
    before = np.asarray(cache["layer0"]["conv"]["conv_state"])
    tables = np.zeros((4, dec.pages_per_slot), np.int32)
    tables[2, :3] = [1, 2, 3]
    index = np.asarray([0, 0, 19, 0], np.int32)
    _, cache, _ = dec.decode_step(
        cache, np.asarray([5, 6, 7, 8], np.int32), index,
        np.zeros((4,), np.float32), seeds=np.zeros((4,), np.uint32),
        block_tables=tables)
    after = np.asarray(cache["layer0"]["conv"]["conv_state"])
    changed = sorted(np.flatnonzero((before != after).any(-1)).tolist())
    assert changed == [0, 3]        # scratch, and the page that holds 19


# ------------------------------------- (c) the state rides the pages ----
def test_a_shared_prefix_and_a_copied_page_carry_the_state(toy, reference,
                                                           decoders):
    """A row whose table names another row's first pages and prefills only
    the rest, and a row that continues on a COPY of the last shared page
    (``copy_page``), both read the logits of the prompt prefilled whole:
    a full page's entry is the snapshot at its end."""
    _, params = toy
    dec = decoders["interpret"]
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, VOCAB, 3 * PAGE + 5, dtype=np.int32)
    want = _ref_logits(reference, params, prompt[None])[0, -1]
    cache = dec.fresh_cache()
    owner = np.zeros((dec.pages_per_slot,), np.int32)
    owner[:4] = [1, 2, 3, 4]
    cache, last = _prefill(dec, cache, prompt, owner)
    _close(last, want)
    # shares pages 1-3 (24 tokens), prefills the last 5 into its own page
    sharer = owner.copy()
    sharer[3] = 9
    cache, last = _prefill(dec, cache, prompt, sharer, start=3 * PAGE)
    _close(last, want)
    # copy-on-write of the last shared page: the copy brings the entry
    cache = dec.copy_page(cache, 3, 11)
    copier = np.zeros_like(owner)
    copier[:4] = [1, 2, 11, 12]
    cache, last = _prefill(dec, cache, prompt, copier, start=3 * PAGE)
    _close(last, want)
    # and a zeroed carry at that boundary is NOT the same logits
    wrong = np.zeros_like(owner)
    wrong[:4] = [1, 2, 20, 13]      # page 20 was never written
    _, bad = _prefill(dec, cache, prompt, wrong, start=3 * PAGE)
    assert np.abs(bad - want).max() > 1e-2 * np.abs(want).max()


def test_exported_pages_carry_the_state(toy, reference, decoders):
    """``read_page`` / ``write_page`` (what ``serve/migrate.py`` moves,
    through its wire form) bring a page's state entry with its K and V:
    another decoder's cache continues the prompt to the same logits."""
    _, params = toy
    src = dst = decoders["interpret"]       # two caches of one decoder
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, VOCAB, 2 * PAGE + 3, dtype=np.int32)
    want = _ref_logits(reference, params, prompt[None])[0, -1]
    table = np.zeros((src.pages_per_slot,), np.int32)
    table[:3] = [4, 5, 6]
    cache, _ = _prefill(src, src.fresh_cache(), prompt, table)
    there = dst.fresh_cache()
    moved = np.zeros_like(table)
    moved[:3] = [7, 8, 9]
    for page, to in ((4, 7), (5, 8)):
        leaves = migrate.decode_page(migrate.encode_page(
            src.read_page(cache, page)))
        assert sorted(a.ndim for a in leaves) == [1] * N_CONV + [3] * 2
        there = dst.write_page(there, to, leaves)
    _, last = _prefill(dst, there, prompt, moved, start=2 * PAGE)
    _close(last, want)


@pytest.mark.parametrize("plen", [3 * PAGE, 3 * PAGE + 5],
                         ids=["whole_prompt_registered", "prefix_registered"])
def test_the_engine_serves_a_registered_prefix_as_an_unshared_run(toy, plen):
    """Through ``ServeEngine`` with prefix sharing on (the default): the
    second admit of a prompt hits the ``PrefixRegistry`` and serves the
    tokens of an engine that shares nothing.  Where the WHOLE prompt is
    registered, an attention-only model replays its last token on a copy
    of the last page; a cache that carries state cannot (the copy's entry
    is past that token), so the engine prefills the last page again from
    the carry of the page before it, and copies nothing."""
    model, params = toy
    kw = dict(max_batch=2, max_seq_len=128, max_delay_s=0.0,
              kv_page_size=PAGE, kv_pool_pages=33, prefill_chunk=CHUNK,
              seed=3)
    prompt = np.random.default_rng(plen).integers(1, VOCAB, plen,
                                                  dtype=np.int32)
    plain = ServeEngine(model, params, prefix_sharing=False, **kw)
    shared = ServeEngine(model, params, **kw)
    try:
        want = plain.generate(prompt, max_new_tokens=PAGE + 2).tokens
        assert shared.generate(prompt, max_new_tokens=PAGE + 2).tokens == want
        before = shared.metrics.get("serve_prefix_hit_pages_total").value
        assert shared.generate(prompt, max_new_tokens=PAGE + 2).tokens == want
        hits = shared.metrics.get("serve_prefix_hit_pages_total").value
        assert hits - before == (plen - 1) // PAGE
        assert shared.metrics.get("serve_prefix_cow_total").value == 0
        gauge = shared.metrics.get("serve_state_bytes_per_page").value
        assert gauge == N_CONV * 2 * 64 * 4
    finally:
        plain.stop()
        shared.stop()


def test_a_migrated_row_serves_what_the_source_served(toy):
    """Through ``serve/migrate.py``'s engine surface: a chain exported
    from one engine and imported into a cold one is a prefix hit there,
    and the row serves the same tokens."""
    model, params = toy
    kw = dict(max_batch=2, max_seq_len=128, max_delay_s=0.0,
              kv_page_size=PAGE, kv_pool_pages=33, prefill_chunk=CHUNK,
              seed=3)
    src, dst = ServeEngine(model, params, **kw), ServeEngine(model, params,
                                                             **kw)
    try:
        prompt = np.random.default_rng(7).integers(1, VOCAB, 3 * PAGE + 4,
                                                   dtype=np.int32)
        want = src.generate(prompt, max_new_tokens=PAGE).tokens
        pages, digests = src.export_chain_begin(prompt)
        try:
            payloads = [migrate.decode_page(migrate.encode_page(leaves))
                        for leaves in src.export_chain_read(pages, 0,
                                                            len(pages))]
        finally:
            src.export_chain_end(pages)
        assert digests == migrate.expected_chain(prompt, PAGE)
        assert dst.import_chain(prompt, payloads) == 3
        assert dst.generate(prompt, max_new_tokens=PAGE).tokens == want
        assert dst.metrics.get("serve_prefix_hit_pages_total").value == 3
    finally:
        src.stop()
        dst.stop()


# --------------------------------------------- (d) the kernel at 64 ----
@pytest.mark.parametrize("s,index", [(1, [37, 64, 5, 0]), (64, [0, 64, 128,
                                                                 192])],
                         ids=["decode", "chunk"])
def test_kernel_equals_oracle_at_heads_of_64_in_one_row(s, index):
    """The paged kernel in interpret mode against the gather oracle over
    ONE pool of ``[k | v]`` rows: 32 query heads over 8 KV heads of 64
    (group 4: a decode step scores a block all heads at once, a chunk head
    by head), pages of 64, rows of different lengths."""
    rng = np.random.default_rng(5)
    b, hq, hkv, dh, page, m = 4, 32, 8, 64, 64, 5
    pool = jnp.asarray(rng.normal(size=(1 + b * m, page, hkv, 2 * dh)),
                       jnp.float32)
    table = jnp.asarray(1 + np.arange(b * m).reshape(b, m), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, s, hq, dh)), jnp.float32)
    idx = jnp.asarray(index, jnp.int32)
    want = pa.paged_attention(q, pool, None, table, idx)
    got = pa.paged_flash_decode(q, pool, None, table, idx, interpret=True)
    assert got.shape == (b, s, hq, dh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the oracle reads the row's halves as K and as V
    split = pa.paged_attention(q, pool[..., :dh], pool[..., dh:], table, idx)
    np.testing.assert_allclose(np.asarray(want), np.asarray(split),
                               rtol=1e-6, atol=1e-6)
    assert pa.decode_scores_all_heads(hq, hkv, 2 * dh, page, m, 2)


# --------------------------------------------------- (e) routing ----
def test_the_bias_moves_the_choice_and_never_the_weight():
    """32 experts top-4 with scale 1: an expert lifted by the bias is
    chosen, its weight is its score without the bias over the chosen
    scores' sum plus 1e-6; an expert pushed down gets no token."""
    rng = np.random.default_rng(6)
    t, d, e, k = 48, 16, 32, 4
    h = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, e)) * 0.3, jnp.float32)
    bias = np.zeros((e,), np.float32)
    bias[7], bias[11] = 5.0, -5.0
    idx, weights = rd.route(h, w, k, jnp.asarray(bias), 1.0, 1e-6)
    idx0, weights0 = rd.route(h, w, k, jnp.zeros((e,)), 1.0, 1e-6)
    assert bool((idx == 7).any(-1).all()) and not bool((idx == 11).any())
    assert not bool((idx0 == 7).any(-1).all())      # the bias moved it
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", h, w, precision=jax.lax.Precision.HIGHEST))
    chosen = jnp.take_along_axis(scores, idx, -1)
    want = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(weights), np.asarray(want),
                               rtol=1e-6)
    assert float(weights.sum(-1).max()) < 1.0       # 1e-6 is in the sum
    # an expert with no token costs nothing and breaks nothing
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    gu = jnp.asarray(rng.normal(size=(e, d, 24)) * 0.1, jnp.float32)
    dn = jnp.asarray(rng.normal(size=(e, 12, d)) * 0.1, jnp.float32)
    y, sizes = rd.routed_experts(x, idx, weights, gu, dn, use_pallas=False,
                                 activation="silu")
    assert int(sizes[11]) == 0 and int(sizes[7]) == t
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(rd.routed_experts_dense(
            x, idx, weights, gu, dn, activation="silu")),
        rtol=1e-5, atol=1e-5)


# ------------------------------------------------ (f) memory plan ----
def test_serving_memory_plan_counts_the_state_entry():
    """At the published widths (shapes only): 4 attention layers of one
    [k | v] row of 8 x 128 bf16 a token, 12 state entries of 2 x 2048 bf16
    a page, and the tied head's one matrix."""
    kw = dict(num_layers=16, d_model=2048, num_heads=32, num_kv_heads=8,
              head_dim=64, layer_mixer=(MIXERS * 2)[:16], qk_norm=True,
              tie_head=True, layer_window=[False],
              layer_rope=[True], num_dense_layers=2, dense_width=7168,
              num_experts=32, experts_per_token=4, expert_width=1792,
              routing="sigmoid_bias", activation="silu",
              router_input="post_attention", max_seq_len=128000,
              param_dtype="bfloat16")
    kw["layer_mixer"] = ["short_conv", "short_conv", "attention",
                         "short_conv"] + ["short_conv", "short_conv",
                                          "attention", "short_conv"] * 3
    model, _ = build_model("routed_decoder", num_classes=65536,
                           dtype=jnp.bfloat16, **kw)
    assert model.layer_mixers().count("attention") == 4
    plan = serving_memory_plan(model, num_slots=96, max_seq_len=8704,
                               kv_page_size=64, kv_pool_pages=5121)
    assert plan["kv_heads"] == 8 and plan["head_dim"] == 128
    assert plan["per_token_kv_bytes"] == 4 * 8 * 128 * 2 == 8192
    assert plan["state_bytes_per_page"] == 12 * 2 * 2048 * 2 == 98304
    assert plan["kv_bytes_paged"] == 5120 * 64 * 8192
    assert plan["state_bytes_paged"] == 5120 * 98304
    assert plan["param_bytes"] == 10_798_258_944


@pytest.mark.parametrize("head_dim,names", [
    (64, {"paged_kv": 2, "conv_state": 6}),
    (16, {"paged_key": 2, "paged_value": 2, "conv_state": 6})],
    ids=["heads_of_64_one_row", "narrower_heads_two_pools"])
def test_every_cache_leaf_is_of_a_named_kind(decoders, head_dim, names):
    """What a cache leaf is comes from the name its layer declared it
    under, once (``serve/decode.py`` ``CACHE_LEAF_KINDS``), never from its
    rank: the bytes plan, ``carries_state`` and ``decode_all_heads`` read
    that.  K and V share a pool row at heads of exactly half a lane tile —
    the layout follows from ``head_dim`` — and a leaf of a name nobody
    declared is refused."""
    from dtf_tpu.serve import decode as sd
    model, _ = build_model("routed_decoder", num_classes=VOCAB,
                           dtype=jnp.float32, **dict(TOY, head_dim=head_dim))
    shapes, _ = sd.trace_paged_init(model, PAGE, 9)
    found = {}
    for path, _ in jax.tree_util.tree_leaves_with_path(shapes):
        found[path[-1].key] = found.get(path[-1].key, 0) + 1
    assert found == names
    kinds = [kind for kind, _ in sd.cache_leaves(shapes)]
    assert kinds.count(sd.PAGE_STATE) == N_CONV
    assert kinds.count(sd.KV_POOL) == len(kinds) - N_CONV
    assert [leaf.ndim for _, leaf in sd.cache_leaves(shapes, sd.PAGE_STATE)
            ] == [2] * N_CONV
    assert sd.state_bytes_per_page(shapes) == N_CONV * 2 * 64 * 4
    assert decoders[False].carries_state
    with pytest.raises(ValueError, match="CACHE_LEAF_KINDS"):
        sd.cache_leaves({"layer0": {"head_state": shapes["layer0"]["conv"][
            "conv_state"]}})


def test_a_state_carrying_cache_refuses_tensor_parallel_calls(decoders):
    """``last_pos`` has no way through the tensor-parallel ``shard_map``
    yet: dropping it silently would take a final chunk's entry at its
    padded end."""
    dec = decoders[False]
    tp = dec.tp
    try:
        dec.tp = 2
        with pytest.raises(NotImplementedError, match="tensor-parallel"):
            dec._apply_model(dec.params, None, None, None, None, False,
                             None, last_pos=jnp.zeros((1,), jnp.int32))
    finally:
        dec.tp = tp


def test_the_cli_reaches_the_state_carrying_kinds():
    """``cli/serve_main.py --model routed_decoder_state``: the registry's
    sibling name builds the same module with short-convolution layers, the
    memory plan logs the state entry, and the demo serves every request
    through ``ServeEngine``."""
    import subprocess
    done = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.cli.serve_main",
         "--serve_random_init", "--model", "routed_decoder_state",
         "--num_classes", "256", "--serve_max_seq_len", "128",
         "--serve_requests", "4", "--serve_max_new_tokens", "4"],
        cwd=ROOT, capture_output=True, text=True, timeout=110,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    assert "state 24576 B/page" in done.stderr      # 6 layers x 2 x 512 x 4
    assert "'requests': 4, 'shed': 0" in done.stderr


def test_a_call_that_crosses_pages_unaligned_is_refused(toy):
    """A call of several tokens that is not whole pages would leave a
    crossed page's entry stale: the layer refuses it."""
    model, params = toy
    dec_model = model.clone(decode=True, kv_page_size=PAGE, kv_pool_pages=9)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: dec_model.init(
            jax.random.key(0), jnp.zeros((1, PAGE), jnp.int32),
            cache_index=jnp.zeros((1,), jnp.int32),
            block_table=jnp.zeros((1, 2), jnp.int32))["cache"]))
    with pytest.raises(ValueError, match="neither one token nor whole"):
        dec_model.apply({"params": params, "cache": cache},
                        jnp.zeros((1, 3), jnp.int32),
                        cache_index=jnp.zeros((1,), jnp.int32),
                        block_table=jnp.ones((1, 2), jnp.int32),
                        mutable=["cache", "stats"])
