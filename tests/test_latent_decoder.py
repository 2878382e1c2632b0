"""The routed decoder's latent-attention / shared-expert / sigmoid-routed
kinds (``models/routed_decoder.py``) against their plain reference and
their own oracles, at a toy that keeps the shape of the thing: one dense
layer then three routed ones, 4 heads of nope/rope/v 16/8/16 over latents
of rank 32 (q) and 24 (kv), 16 experts of which a token takes 4 beside a
shared one, a score bias that is not zero.  float32 throughout, so what is
compared is the mathematics and not a rounding."""

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
from dtf_tpu.serve.bridge import serving_memory_plan  # noqa: E402
from dtf_tpu.serve.decode import Decoder  # noqa: E402
from tests.test_routed_decoder import _serve  # noqa: E402

pa = importlib.import_module("dtf_tpu.ops.paged_attention")

TOY = dict(num_layers=4, d_model=64, num_heads=4, q_lora_rank=32,
           kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, rope_theta=32e6, rope_interleave=True,
           num_dense_layers=1, dense_width=96, num_experts=16,
           experts_per_token=4, expert_width=32, shared_expert_width=32,
           routing="sigmoid_bias", routed_scale=2.5, router_bias_stddev=0.05,
           activation="silu", router_input="post_attention", rms_eps=1e-6,
           max_seq_len=256)
VOCAB, PAGE = 128, 8


@pytest.fixture(scope="module")
def toy():
    model, _ = build_model("routed_decoder", num_classes=VOCAB,
                           dtype=jnp.float32, **TOY)
    params = model.init(jax.random.key(3),
                        jnp.zeros((1, PAGE), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def reference():
    ref = importlib.import_module("benchmark.families.reference_joyai")
    return ref, ref.arch_of_model_kwargs(TOY)


def _ref_logits(reference, params, tokens, **controls):
    ref, arch = reference
    return np.asarray(ref._head(
        ref.hidden(params, jnp.asarray(tokens), arch, **controls),
        params["lm_head"]))


def test_the_tree_is_the_layer_description(toy):
    """A dense layer has no router and no expert; a routed layer has the
    router, its float32 score bias (drawn, not zero), 16 experts and the
    shared one; attention holds the two latents' projections and norms."""
    _, params = toy
    assert set(params["layer0"]) == {"norm1", "norm2", "attn",
                                     "dense_gate_up", "dense_down"}
    assert set(params["layer1"]) == {
        "norm1", "norm2", "attn", "router", "router_bias", "gate_up",
        "down", "shared_gate_up", "shared_down"}
    assert set(params["layer1"]["attn"]) == {
        "q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "out"}
    bias = np.asarray(params["layer1"]["router_bias"])
    assert bias.dtype == np.float32 and 0.01 < bias.std() < 0.1
    assert params["layer1"]["attn"]["kv_a"].shape == (64, 24 + 8)
    assert params["layer1"]["attn"]["kv_b"].shape == (24, 4 * (16 + 16))


@pytest.mark.parametrize("control,moves", [
    ({}, False), ({"zero_bias": True}, True)], ids=["as_built", "no_bias"])
def test_model_equals_reference(toy, reference, control, moves):
    """Teacher-forced logits of the program's full forward (attention
    expanded from the definition) against the plain reference's, 60
    positions.  1e-4 of the logit scale: both are float32 and differ in
    the order of their sums (grouped expert rows against dense masked
    experts) — a wrong pairing of the rotary lanes, a bias that reached
    the weights, a missing shared expert or scale moves logits by their
    whole spread, as the reference WITHOUT the bias shows."""
    model, params = toy
    tokens = np.random.default_rng(0).integers(0, VOCAB, (2, 60),
                                               dtype=np.int32)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(tokens)))
    want = _ref_logits(reference, params, tokens, **control)
    off = np.abs(got - want).max() / np.abs(want).max()
    assert (off > 1e-2) if moves else (off <= 1e-4), off


@pytest.mark.parametrize("use_pallas", [False, "interpret"],
                         ids=["gather", "kernel"])
@pytest.mark.parametrize("lengths", [(61,), (5, 40, 61, 100)],
                         ids=["batch1", "batch4"])
def test_latent_paged_serving_equals_reference(toy, reference, lengths,
                                               use_pallas):
    """Chunked prefill then decode ABSORBED through latent pages (chunks
    of 16, pages of 8: contexts run across chunk and page boundaries; rows
    of different lengths in one decode batch) against the reference's full
    forward over prompt + continuation, which never absorbs."""
    model, params = toy
    rng = np.random.default_rng(1)
    new = 6
    rows = [(rng.integers(0, VOCAB, n, dtype=np.int32),
             rng.integers(0, VOCAB, new, dtype=np.int32)) for n in lengths]
    dec = Decoder(model.clone(use_pallas=use_pallas), params, num_slots=4,
                  max_seq_len=128, kv_page_size=PAGE, kv_pool_pages=65)
    got = _serve(dec, rows, new)
    for (prompt, cont), g in zip(rows, got):
        seq = np.concatenate([prompt, cont])[None]
        want = _ref_logits(reference, params, seq)[0][
            len(prompt) - 1:len(prompt) - 1 + new]
        assert np.abs(g - want).max() <= 1e-4 * np.abs(want).max()
    # the last call was a decode step of 4 rows: every layer reads every
    # row's history, the token just written included
    counts = dict(zip(model.stats_names,
                      np.asarray(dec.last_stats["counts"])))
    assert model.stats_names == rd.LATENT_STATS
    assert counts["latent_tokens_read"] == 4 * sum(
        n + new - 1 if i < len(lengths) else 1
        for i, n in enumerate(list(lengths) + [0] * (4 - len(lengths))))
    assert counts["assignments"] == 4 * 4 * 3      # rows x top-4 x 3 layers
    # one row of 24 + 8 values a token a layer, stored in whole lane tiles
    cache = dec.fresh_cache()
    assert {k: v["attn"]["paged_latent"].shape for k, v in cache.items()
            } == {f"layer{i}": (65, PAGE, 128) for i in range(4)}


def test_absorbed_equals_expanded_attention():
    """One attention module, the same parameters and inputs: decode mode
    (the cache row ``[c_kv | k_rope]``, queries carried through ``kv_b``'s
    key half, its value half applied after) against the full-sequence mode
    (K and V of every head expanded from the latent)."""
    attn = rd.LatentAttention(4, 32, 24, 16, 8, 16, 32e6, True, 1e-6,
                              jnp.float32, jnp.float32, use_pallas=False)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(2, 16, 64)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    params = attn.init(jax.random.key(0), h, pos)["params"]
    want = attn.apply({"params": params}, h, pos)
    paged = attn.clone(decode=True, kv_page_size=8, kv_pool_pages=5)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    index = jnp.zeros((2,), jnp.int32)
    cache = jax.tree_util.tree_map(
        jnp.zeros_like, paged.init(jax.random.key(0), h, pos, index,
                                   table)["cache"])
    got, _ = paged.apply({"params": params, "cache": cache}, h, pos, index,
                         table, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.fixture(scope="module")
def chunk_logits(toy):
    """A prompt of 61 tokens prefilled in chunks of 16 (starts 0 / 16 / 32
    / 48, the last chunk 13 real tokens and 3 of padding) with the walk
    over the prefix in steps of 32 keys: the logits at every chunk's
    sampled position and the counts of its call, by (``use_pallas``, whether
    the rule was left on)."""
    model, params = toy
    prompt = np.random.default_rng(7).integers(0, VOCAB, 61, dtype=np.int32)
    table = np.zeros((16,), np.int32)
    table[:8] = 3 + np.random.default_rng(8).permutation(8)
    out = {"prompt": prompt}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "EXPAND_KEYS", 32)
        for use_pallas in (False, "interpret"):
            for expanded in (True, False):
                with mp.context() as off:
                    if not expanded:
                        off.setattr(rd, "latent_expands", lambda *a: False)
                    dec = Decoder(model.clone(use_pallas=use_pallas), params,
                                  num_slots=1, max_seq_len=128,
                                  kv_page_size=PAGE, kv_pool_pages=12)
                    cache, rows = dec.fresh_cache(), []
                    for start in range(0, 64, 16):
                        chunk = np.zeros((16,), np.int32)
                        real = prompt[start:start + 16]
                        chunk[:len(real)] = real
                        _, cache, last = dec.prefill_chunk(
                            cache, chunk, table, start, len(real) - 1, 0.0,
                            seed=0)
                        rows.append((np.asarray(last), np.asarray(
                            dec.last_stats["counts"])))
                    out[use_pallas, expanded] = rows
    return out


@pytest.mark.parametrize("use_pallas", [False, "interpret"],
                         ids=["blockwise", "kernel"])
@pytest.mark.parametrize("chunk", range(4), ids=[
    "first_chunk", "start_half_a_step", "start_whole_steps", "padded_tail"])
def test_an_expanded_chunk_equals_the_absorbed_one(toy, reference,
                                                   chunk_logits, chunk,
                                                   use_pallas):
    """The chunk whose keys go through ``kv_b`` (its own causally, the
    pages under its start in steps of 32 keys: none, half a step, one
    whole step, one and a half) against the chunk whose queries do, and
    both against the reference's full forward; through ``ops.blockwise``
    and through the kernel in interpret mode.  The expanded call counts the
    rows it expanded — the walk's whole steps and its own 16 — in the
    place where the absorbed one counts the rows it read."""
    model, _ = toy
    prompt = chunk_logits["prompt"]
    got, counts = chunk_logits[use_pallas, True][chunk]
    absorbed, read = chunk_logits[use_pallas, False][chunk]
    start = 16 * chunk
    at = min(start + 15, len(prompt) - 1)
    want = _ref_logits(reference, toy[1], prompt[None])[0, at]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale
    assert np.abs(got - absorbed).max() <= 1e-4 * scale
    names = model.clone(decode=True).call_stats_names(16)
    assert names[3] == "latent_tokens_expanded"
    assert "latent_tokens_read" not in names
    assert counts[3] == 4 * (-(-start // 32) * 32 + 16)
    assert read[3] == 4 * (start + 16)
    assert (counts[:3] == read[:3]).all()


@pytest.mark.parametrize("name,widths,first", [
    ("joyai", (32, 640, 512, 128, 64, 128), 158),
    ("ling", (32, 640, 512, 128, 64, 128), 158),
    ("toy", (4, 128, 24, 16, 8, 16), 7)])
def test_the_form_follows_the_calls_shape(name, widths, first):
    """Expanded from the first chunk length at which a key through ``kv_b``
    once costs less than every query meeting the stored row: about 160
    queries at JoyAI's and Ling's widths, 7 at the toy's; one query a row
    (a decode step) is absorbed at any widths."""
    if name != "toy":
        import json
        cfg = json.load(open(os.path.join(
            ROOT, "benchmark", "configs",
            {"joyai": "joyai-llm-flash.json",
             "ling": "ling-3.0-flash-vl.json"}[name])))
        assert widths == (
            cfg["num_attention_heads"],
            rd.latent_row_lanes(cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]),
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    assert not pa.latent_expands(1, *widths)
    assert not pa.latent_expands(first - 1, *widths)
    assert pa.latent_expands(first, *widths)
    assert pa.latent_expands(2048, *widths)


def test_a_chunk_span_names_what_it_expanded_and_a_step_what_it_read(
        toy, tmp_path):
    """Tracing on: a ``serve_prefill_chunk`` span of a chunk that attended
    expanded carries ``latent_tokens_expanded`` and no ``latent_tokens_read``
    (``latent_attention_roofline`` prices every span with that name at the
    absorbed cost over the paged kernel's time); a ``serve_decode`` span the
    reverse."""
    from dtf_tpu.obs import trace
    from dtf_tpu.serve.engine import ServeEngine
    model, params = toy
    rng = np.random.default_rng(4)
    tracer = trace.configure(str(tmp_path))
    try:
        eng = ServeEngine(model, params, max_batch=2, max_seq_len=128,
                          kv_page_size=PAGE, kv_pool_pages=33,
                          prefill_chunk=16)
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
    steps = [r for r in spans if r["name"] == "serve_decode"]
    assert len(chunks) == 4 and steps
    for r in chunks:
        # a table of 16 pages of 8 is one step of the walk
        assert r["latent_tokens_expanded"] == 4 * (
            -(-r["start"] // 128) * 128 + r["tokens"])
        assert "latent_tokens_read" not in r
    for r in steps:
        assert r["latent_tokens_read"] > 0
        assert "latent_tokens_expanded" not in r


@pytest.mark.parametrize("heads", [32, 4], ids=["group32", "group4"])
@pytest.mark.parametrize("batch,s,lens", [(3, 1, [5, 17, 40]),
                                          (2, 16, [8, 32])],
                         ids=["decode", "chunk"])
def test_latent_kernel_equals_the_gather_oracle(heads, batch, s, lens):
    """The paged kernel in interpret mode over a latent pool — one row of
    40 values a token, the first 32 the value, every query head over it —
    against the gather oracle of the same contract; 32 heads of a 16-token
    chunk are 512 query rows, 4 heads 64."""
    rng = np.random.default_rng(heads + s)
    w, lanes, page, m = 40, 32, 8, 8
    pool = jnp.asarray(rng.normal(size=(1 + batch * m, page, w)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, 1 + batch * m)
                                        ).reshape(batch, m), jnp.int32)
    index = jnp.asarray(lens, jnp.int32)
    q = jnp.asarray(rng.normal(size=(batch, s, heads, w)), jnp.float32)
    want = pa.latent_paged_attention(q, pool, table, index,
                                     value_lanes=lanes, scale=0.2)
    got = pa.paged_attention_auto(q, pool, None, table, index,
                                  use_pallas="interpret", scale=0.2,
                                  value_lanes=lanes)
    assert got.shape == (batch, s, heads, lanes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_latent_kernel_walks_row_blocks(monkeypatch):
    """Past ``_LATENT_ROWS`` query rows the grid's second axis walks
    blocks of them; the answer does not change."""
    rng = np.random.default_rng(9)
    pool = jnp.asarray(rng.normal(size=(9, 8, 40)), jnp.float32)
    table = jnp.asarray(np.arange(1, 9)[None], jnp.int32)
    index = jnp.asarray([16], jnp.int32)
    q = jnp.asarray(rng.normal(size=(1, 32, 8, 40)), jnp.float32)
    want = pa.latent_paged_attention(q, pool, table, index, value_lanes=32,
                                     scale=0.2)
    monkeypatch.setattr(pa, "_LATENT_ROWS", 64)
    monkeypatch.setattr(pa, "_LATENT_BLOCK_TOKENS", 16)
    got = pa.paged_flash_decode.__wrapped__(
        q, pool, None, table, index, scale=0.2, interpret=True,
        value_lanes=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_the_bias_moves_the_choice_and_never_the_weight():
    """``sigmoid_bias``: a bias large enough to force expert 3 into every
    token's choice changes who is chosen; the weight of every chosen
    expert is still its own sigmoid score over the chosen scores' sum,
    times the scale, whatever the bias."""
    rng = np.random.default_rng(4)
    t, d, e, k = 12, 16, 8, 3
    h = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, e)), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(h @ w))
    zero = jnp.zeros((e,), jnp.float32)
    push = zero.at[3].set(10.0)
    for bias in (zero, push):
        idx, weights = rd.route(h, w, k, bias, 2.5)
        idx, weights = np.asarray(idx), np.asarray(weights)
        np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
        chosen = np.take_along_axis(scores, idx, -1)
        np.testing.assert_allclose(
            weights, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    free = np.asarray(rd.route(h, w, k, zero, 2.5)[0])
    forced = np.asarray(rd.route(h, w, k, push, 2.5)[0])
    assert (forced == 3).any(-1).all() and not (free == 3).any(-1).all()
    # softmax_topk is untouched by the new arguments' defaults
    idx, weights = rd.route(h, w, k)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("use_pallas", [False, "interpret"],
                         ids=["ragged_dot", "pallas_gmm"])
@pytest.mark.parametrize("case", ["random", "all_to_one", "one_empty"])
def test_silu_experts_with_a_shared_one_equal_the_dense_oracle(case,
                                                               use_pallas):
    """The grouped path (gated SiLU) plus the shared
    expert against every expert on every token masked by the weights plus
    the shared expert — also when one expert takes a row of every token
    and when one takes none."""
    rng = np.random.default_rng(7)
    t, e, d, f, k = 24, 8, 64, 128, 3
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    wgu = jnp.asarray(rng.normal(size=(e, d, 2 * f)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(e, f, d)) * 0.1, jnp.float32)
    sgu = jnp.asarray(rng.normal(size=(d, 2 * f)) * 0.1, jnp.float32)
    sd = jnp.asarray(rng.normal(size=(f, d)) * 0.1, jnp.float32)
    bias = np.zeros((e,), np.float32)
    if case == "all_to_one":
        bias[2] = 10.0
    if case == "one_empty":
        bias[5] = -10.0
    idx, w = rd.route(x, jnp.asarray(rng.normal(size=(d, e)), jnp.float32),
                      k, jnp.asarray(bias), 2.5)
    got, sizes = rd.routed_experts(x, idx, w, wgu, wd, use_pallas=use_pallas,
                                   activation="silu")
    got = got + rd.gated_mlp(x, sgu, sd, "silu")
    want = (rd.routed_experts_dense(x, idx, w, wgu, wd, "silu")
            + (jax.nn.silu(x @ sgu[:, :f]) * (x @ sgu[:, f:])) @ sd)
    assert int(sizes.sum()) == t * k
    if case == "all_to_one":
        assert int(sizes[2]) == t
    if case == "one_empty":
        assert int(sizes[5]) == 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name,kwargs,vocab,want", [
    ("transformer", dict(num_layers=24, d_model=2048, num_heads=16,
                         d_ff=8192, max_seq_len=2048), 50257,
     dict(kv_heads=16, head_dim=128,
          per_token_kv_bytes=24 * 2 * 16 * 128 * 2)),
    ("routed_decoder", dict(num_layers=12, d_model=2560, num_heads=28,
                            num_kv_heads=4, head_dim=128, num_experts=64,
                            experts_per_token=6, expert_width=768,
                            max_seq_len=16384, param_dtype="bfloat16"),
     151936, dict(kv_heads=4, head_dim=128,
                  per_token_kv_bytes=12 * 2 * 4 * 128 * 2)),
    ("routed_decoder", dict(
        num_layers=5, d_model=2048, num_heads=32, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, num_dense_layers=1, dense_width=7168,
        num_experts=256, experts_per_token=8, expert_width=768,
        shared_expert_width=768, routing="sigmoid_bias",
        max_seq_len=131072, param_dtype="bfloat16"), 129280,
     # 576 values a token a layer in 640 stored lanes of bf16
     dict(kv_heads=1, head_dim=640, per_token_kv_bytes=5 * 1280,
          param_bytes=2 * 5_558_140_928 + 4 * 4 * 256)),
    # the registry's sibling name (what ``cli/serve_main.py --model``
    # reaches): 128 + 16 values a token a layer in 256 lanes
    ("routed_decoder_latent", {}, 256,
     dict(kv_heads=1, head_dim=256, per_token_kv_bytes=4 * 256 * 2)),
], ids=["full_heads", "grouped_heads", "latent_row", "sibling_name"])
def test_serving_memory_plan_counts_what_the_cache_stores(name, kwargs,
                                                          vocab, want):
    """Cache bytes a token are read off the pools the model's own paged
    init makes — K and V of 16 heads, of 4 of 28 heads, or one latent row
    (shapes only; nothing is materialised)."""
    model, _ = build_model(name, num_classes=vocab, dtype=jnp.bfloat16,
                           **kwargs)
    plan = serving_memory_plan(model, num_slots=16, max_seq_len=2048,
                               kv_page_size=16, kv_pool_pages=129)
    for key, value in want.items():
        assert plan[key] == value, key
    assert plan["kv_bytes_paged"] == 128 * 16 * want["per_token_kv_bytes"]


def test_a_page_payload_of_another_shape_is_refused(toy):
    """Migration import: the payload's leaves are held to the cache's own
    pages, one latent row pool a layer here."""
    model, params = toy
    dec = Decoder(model.clone(use_pallas=False), params, num_slots=2,
                  max_seq_len=64, kv_page_size=PAGE, kv_pool_pages=17)
    cache = dec.fresh_cache()
    leaves = dec.read_page(cache, 3)
    assert [a.shape for a in leaves] == [(PAGE, 128)] * 4
    cache = dec.write_page(cache, 5, leaves)
    with pytest.raises(ValueError, match="does not fit"):
        dec.write_page(cache, 5, [np.zeros((PAGE, 2, 64), np.float32)] * 4)
