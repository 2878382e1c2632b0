"""Checks of what the family ``glm_dsa`` and its cell add to the benchmark:
the configuration against the catalog's row key by key, the bytes the file
states against what the program stores (counted by hand here), the cell by
name through the serve driver at the toy size, the reference against the
program's whole-sequence forward and through the cache (chunks, then decode
steps) on logits, the share test, IndexShare, the reference's injected
faults, the cost functions and readers of the new per-layer metrics.  CPU
only; under BENCHMARK.json's ``paths``.

The toy's limit (``families/glm_dsa.py`` ``TOY``, 0.034 = the root of
0.0271 x 0.0426): the program, bf16 matmuls and a bf16 cache on an f32
stream whose choice of rows is made from bf16 index keys, reads a
``logit_rms`` of 0.0218-0.0271 against the float32 reference at the toy's
three prompts of 24 new tokens (``control.py --toy``, seeds 11-16; the
rehearsal's seed 0.0257), the reference with every matrix at 8 bits
0.0426-0.0518 (seeds 11, 12; 4 bits 0.25-0.26).  On two prompts past
``top`` of 4 new tokens (a float32 tree, this file's fixture) the
reference with the selection left out reads 0.365, a shared layer on the
first 32 positions 0.435, index keys a page stale 0.310, the ReLU left out
0.212, the heads' weights left out 0.417, 8-bit weights 0.059, and with
nothing but the choice's inputs rounded to bfloat16 0.002 (CPU, PR 49)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import families  # noqa: E402
from benchmark.lib import agreement, peaks, traffic  # noqa: E402
from benchmark.lib.runtime import (load_benchmark, load_cell,  # noqa: E402
                                   load_json)
from benchmark.lib.xplane import Reduction  # noqa: E402
from benchmark.readers import ReaderInput, read_metric  # noqa: E402

import structure  # noqa: E402  (beside this file)

CELL = "glm52-serve-sparsectx"
BENCH = load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
_F, _S = "full", "shared"
# https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json as the
# catalog of architectures holds it
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "head_dim": 192, "hidden_act": "silu", "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32,
    "index_share_for_mtp_iteration": True, "index_skip_topk_offset": 3,
    "index_topk": 2048, "index_topk_freq": 4, "index_topk_pattern": None,
    "indexer_rope_interleave": True,
    "indexer_types": [_F] * 3 + [_S, _S, _S, _F] * 18 + [_S] * 3,
    "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 1048576,
    "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 75,
    "model_type": "glm_moe_dsa", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 78, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 256, "vocab_size": 154880}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
# the per-layer metrics the cell needs, each under the entry's own name (a
# suffix says how an entry differs, never which cell reads it)
NEEDS = {CELL: [
    "serve_mfu", "decode_step_ms", "prefill_chunk_ms", "device_idle_pct",
    "host_launch_ms", "idle_host_pct", "index_select_kernel_ms.sparsectx",
    "index_select_roofline.sparsectx", "latent_sparse_kernel_ms.sparsectx",
    "latent_sparse_roofline.sparsectx", "keys_selected_share.sparsectx",
    "moe_experts_ms", "moe_experts_roofline",
    "expert_load_max_over_mean.n_routed_experts"]}
# the parameters, counted by hand: a layer's attention, a full layer's
# indexer, the dense MLP, one expert, the router
ATTENTION = (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448
             + 64 * 256 * 6144)
INDEXER = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
DENSE_MLP, EXPERT, ROUTER = 3 * 6144 * 12288, 3 * 6144 * 2048, 6144 * 256


@pytest.fixture(scope="module")
def cell():
    return load_cell(BENCH, CELL)


def test_the_published_keys_are_the_catalogs_row():
    """Where the catalog is at hand, ``PUBLISHED`` above is its row."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    assert row["config"] == PUBLISHED
    entry = next(c for c in BENCH["configs"] if c["name"] == "glm-5.2")
    assert entry["source"] == row["source_url"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_key(cell, key):
    """Every published key unchanged — the 78-entry lists whole — but the
    depth, the experts HELD and the vocabulary rows held, which ``reduced``
    names and ``published`` keeps."""
    cfg = cell.config
    assert cfg["reduced"] == REDUCED
    if key in REDUCED:
        assert cfg["published"][key] == PUBLISHED[key]
        assert cfg[key] < PUBLISHED[key]
    else:
        assert cfg[key] == PUBLISHED[key]


def test_the_cut_is_one_period_of_the_indexer_types(cell):
    """Published layers 2-6: the last leading dense layer and the four
    after it, F S S S F — two choosers for five attention layers — 16 of
    256 experts, an eighth of the vocabulary; no width is touched."""
    cfg, fam = cell.config, cell.family
    assert cfg["published"]["first_layer"] == 2
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 16, 154880 // 8)
    assert fam.indexer_types(cfg) == PUBLISHED["indexer_types"][2:7] \
        == [_F, _S, _S, _S, _F]
    assert fam.mlp_types(cfg) == PUBLISHED["mlp_layer_types"][2:7] \
        == ["dense"] + ["sparse"] * 4
    entry = next(c for c in BENCH["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmark/configs/glm-5.2.json"
    kinds = PUBLISHED["indexer_types"]
    assert (kinds.count(_F), len(kinds)) == (21, 78)
    assert set(cfg["not_built"]) >= {"multi_token_prediction",
                                     "index_share_for_mtp_iteration"}


def test_the_build_call_is_the_configuration(cell):
    """What ``build_model`` is given is what the published keys say: no
    width comes from anywhere else."""
    cfg, kw = cell.config, cell.config["build_model"]["kwargs"]
    assert cfg["build_model"]["name"] == "routed_decoder"
    assert kw["num_layers"] == cfg["num_hidden_layers"]
    assert kw["d_model"] == cfg["hidden_size"] == 6144
    assert kw["num_heads"] == cfg["num_attention_heads"] == 64
    assert (kw["q_lora_rank"], kw["kv_lora_rank"]) \
        == (cfg["q_lora_rank"], cfg["kv_lora_rank"]) == (2048, 512)
    assert (kw["qk_nope_head_dim"], kw["qk_rope_head_dim"],
            kw["v_head_dim"]) == (cfg["qk_nope_head_dim"],
                                  cfg["qk_rope_head_dim"],
                                  cfg["v_head_dim"]) == (192, 64, 256)
    assert kw["indexer"] == [cfg["index_n_heads"], cfg["index_head_dim"],
                             cfg["index_topk"], cfg["qk_rope_head_dim"]] \
        == [32, 128, 2048, 64]
    assert kw["layer_indexer"] == cell.family.indexer_types(cfg)
    assert kw["num_dense_layers"] == cell.family.mlp_types(cfg).count(
        "dense") == 1
    assert kw["dense_width"] == cfg["intermediate_size"] == 12288
    assert kw["num_experts"] == cfg["published"]["n_routed_experts"] == 256
    assert kw["experts_held"] == [0, cfg["n_routed_experts"]]
    assert kw["experts_per_token"] == cfg["num_experts_per_tok"] == 8
    assert kw["expert_width"] == kw["shared_expert_width"] \
        == cfg["moe_intermediate_size"] == 2048
    assert kw["routing"] == "sigmoid_bias"
    assert kw["routed_scale"] == cfg["routed_scaling_factor"]
    assert kw["rope_theta"] == cfg["rope_parameters"]["rope_theta"]
    assert kw["rope_interleave"] == cfg["rope_interleave"] \
        == cfg["indexer_rope_interleave"]
    assert kw["rms_eps"] == cfg["rms_norm_eps"]
    assert kw["activation"] == cfg["hidden_act"]
    assert kw["max_seq_len"] == cfg["max_position_embeddings"]
    assert cfg["num_classes"] == cfg["vocab_size"]
    for key in ("assumed", "not_built", "deployment", "stored", "published"):
        assert cfg[key]


def test_the_program_counts_the_bytes_the_file_states(cell):
    """``serving_memory_plan`` over the configuration's own build call: the
    matmul parameters' 7,762,870,272 B and the vectors' own (two norms of
    6,144 a layer and the final one, the latents' norms, a full layer's
    LayerNorm scale and bias, in bf16; a routed layer's score bias, 256
    float32), and 6,912 B a token as stored: five latent rows of 1,280 B
    and two index keys of 256 B."""
    import jax
    import jax.numpy as jnp
    from dtf_tpu.models import build_model
    from dtf_tpu.serve.bridge import serving_memory_plan
    from dtf_tpu.serve.decode import trace_paged_init
    cfg, engine, fam = cell.config, cell.workload["engine"], cell.family
    model, _ = build_model("routed_decoder", num_classes=cfg["vocab_size"],
                           dtype=jnp.bfloat16,
                           **cfg["build_model"]["kwargs"])
    plan = serving_memory_plan(
        model, num_slots=engine["max_batch"],
        max_seq_len=engine["max_seq_len"],
        kv_page_size=engine["kv_page_size"],
        kv_pool_pages=engine["kv_pool_pages"])
    shared_layer = ATTENTION + ROUTER + 17 * EXPERT
    matmul = ((ATTENTION + INDEXER + DENSE_MLP) + 3 * shared_layer
              + (shared_layer + INDEXER) + 2 * 19360 * 6144)
    assert (ATTENTION, INDEXER, DENSE_MLP, EXPERT, ROUTER) == (
        165_019_648, 9_371_648, 226_492_416, 37_748_736, 1_572_864)
    assert matmul == 3_881_435_136 == fam.held_matmul_params(cfg)
    assert 2 * matmul == 7_762_870_272 \
        == cfg["stored"]["matmul_param_bytes"]
    vectors = 5 * (2 * 6144 + 2048 + 512) + 6144 + 2 * 2 * 128
    assert plan["param_bytes"] == 2 * matmul + 2 * vectors + 4 * 4 * 256
    stored = cfg["stored"]
    assert plan["per_token_kv_bytes"] == stored["cache_bytes_per_token"] \
        == fam.cache_bytes_per_token(cfg) == 5 * 1280 + 2 * 256 == 6912
    assert (plan["kv_heads"], plan["head_dim"]) == (1, 640)
    assert plan["state_bytes_per_page"] == 0
    page, pages = engine["kv_page_size"], engine["kv_pool_pages"]
    assert (page, pages) == (stored["page_tokens"], stored["pool_pages"])
    assert plan["per_token_kv_bytes"] * page == stored["bytes_per_page"]
    shapes = trace_paged_init(model, page, pages)[0]
    real = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(shapes))
    assert real == pages * stored["bytes_per_page"]
    assert plan["param_bytes"] + real >= 13.5e9
    # 16 rows hold at most 131 pages each, 2,096 of the 3,280: the others
    # keep retired prompts' pages warm in the prefix registry (every run of
    # the cell reads ``pool_pages_high_water`` 3,280; PERF.md section 4)
    assert plan["pages_per_slot"] == 131
    assert engine["max_batch"] * plan["pages_per_slot"] == 2096 < pages - 1


def test_the_traffic_is_the_mix_the_cell_was_asked_for(cell):
    mix, engine = cell.traffic, cell.workload["engine"]
    assert (mix["arrivals"], mix["clients"]) == ("closed", 16)
    assert mix["clients"] == engine["max_batch"]
    assert (mix["ramp_s"], mix["drain_s"]) == (40, 10)
    assert mix["prepare_per_s"] == mix["prepare_block_per_s"] == 8
    assert mix["prompt_len"]["min"] > cell.config["index_topk"]
    assert mix["output_len"] == {"median": 256, "sigma": 0.5, "min": 96,
                                 "max": 768}
    assert engine["prefill_chunk"] == 2048
    assert engine["max_seq_len"] % engine["kv_page_size"] == 0
    assert engine["max_seq_len"] >= mix["prompt_len"]["max"] \
        + mix["output_len"]["max"]
    bases = [load_json(os.path.join(ROOT, "benchmark", "traffic", f))
             .get("base_seed") for f in os.listdir(
                 os.path.join(ROOT, "benchmark", "traffic"))]
    assert bases.count(mix["base_seed"]) == 1
    # ISSUE 49's fallback, taken (PERF.md section 4 has both readings of
    # the mix to 65,536): the two largest lengths are dropped, and the
    # engine admits what the longest request left needs, whole pages
    assert engine["max_seq_len"] == 32768 + 768 == 131 * 256
    assert mix["prompt_len"] == {
        "median": 24576, "sigma": 0.7, "min": 4096, "max": 32768,
        "snap_to": [4096, 8192, 12288, 16384, 16385, 24576, 32768]}
    drawn = traffic.request_sizes(
        mix, 200_000, np.random.default_rng(mix["base_seed"]))[:, 0]
    assert set(np.unique(drawn)) <= set(mix["prompt_len"]["snap_to"])
    assert 23_000 < drawn.mean() < 23_600                # 23,276
    assert 0.40 < np.mean(drawn == 32768) < 0.43         # 0.413
    # 16 rows hold their prompt + budget: the slots bind before the pool
    sizes = np.concatenate([traffic.phase_draw(mix, k, s)[0]
                            for k, s in enumerate((40, 51, 15))])
    pages = -(-(sizes[:, 0] + sizes[:, 1]) // engine["kv_page_size"])
    assert 16 * pages.max() < engine["kv_pool_pages"] - 1


def test_the_sample_reads_the_choice_and_a_page_of_one_token(cell):
    """8,192: four chunks, three of them of choice; 16,385: eight chunks
    and ONE token of the ninth; 64 new tokens each, 128 compared.  The
    serve driver samples the agreement's prompts from the lengths the MIX
    holds (``snap_to``), and this mix holds none at or under ``index_topk``
    (every request crosses it: its first chunk is the dense path, and what
    that chunk writes is what every later query attends) — so ISSUE 49's
    2,048-token prompt is not listed: it would never be compared.  The toy
    mix does hold one (32 = the toy's ``top``)."""
    agree, engine = cell.workload["agreement"], cell.workload["engine"]
    assert agree["prompt_lens"] == [8192, 16385]
    assert agree["new_tokens"] == 64
    snap = cell.traffic["prompt_len"]["snap_to"]
    assert set(agree["prompt_lens"]) <= set(snap)
    assert min(snap) > cell.config["index_topk"]
    assert 16385 % engine["kv_page_size"] == 1
    assert 16385 % engine["prefill_chunk"] == 1
    toy = cell.family.TOY["serve"]
    top = toy["model_kwargs"]["indexer"][2]
    lens = toy["agreement"]["prompt_lens"]
    assert lens[0] == top < lens[1]
    assert set(lens) <= set(toy["traffic"]["prompt_len"]["snap_to"])
    assert lens[2] % toy["engine"]["prefill_chunk"] == 1


def test_serve_tok_s_is_judged_in_the_new_cell():
    """The cell's own claims on BENCHMARK.json (``structure.py``): nothing
    about its place in a list, or about what else lists an entry."""
    cell, mine = structure.check_cell(
        BENCH, ROOT, CELL, NEEDS[CELL], config="glm-5.2",
        traffic="sparsectx-closed-16")
    assert cell.family.SPAN_COSTS["model_flops"] is cell.family.model_flops
    assert all(m["moves"] == "serve_tok_s" for m in mine.values())


@pytest.mark.parametrize("trace", ["0", "1"], ids=["trace0", "trace1"])
def test_the_cell_rehearses_through_the_serve_driver(trace):
    """Loaded by name, at the family's toy size, on the CPU; the traced
    rehearsal also walks the spans' counts into the readers."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.rehearse", "--workload", CELL,
         "--trace", trace, "--seconds", "2"],
        cwd=ROOT, capture_output=True, text=True, timeout=115,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    said = json.loads(last[last.index("{"):])
    assert said["line"]["correct"] is True and said["reasons"] == []
    assert said["contract_refuses_it_for"]      # never a result
    if trace == "1":
        read = done.stdout[done.stdout.index("readers without"):]
        for name in ("keys_selected_share.sparsectx",
                     "expert_load_max_over_mean.n_routed_experts"):
            assert f"'{name}': None" not in read


# ------------------------------------------------------ the reference ----
def _toy_model(cell, dtype="float32", **changes):
    import jax.numpy as jnp
    from dtf_tpu.models import build_model
    toy = cell.family.TOY["serve"]
    kw = dict(cell.config["build_model"]["kwargs"], **toy["model_kwargs"])
    kw.update(param_dtype=dtype, **changes)
    model, _ = build_model("routed_decoder", num_classes=toy["vocab_size"],
                           dtype=jnp.dtype(dtype), **kw)
    return model


@pytest.fixture(scope="module")
def toy_sample(cell):
    """The toy's weights (a float32 tree), two prompts past ``top`` (one a
    page entered by one real token) and what the reference would serve for
    them."""
    import jax
    import jax.numpy as jnp
    reference = families.load_reference(cell.config, ROOT)
    model = _toy_model(cell)
    params = model.init(jax.random.key(5),
                        jnp.zeros((1, 32), jnp.int32))["params"]
    rng = np.random.default_rng(5)
    vocab = cell.family.TOY["serve"]["vocab_size"]
    prompts = [rng.integers(0, vocab, n, dtype=np.int32) for n in (97, 129)]
    served = reference.greedy_tokens(params, prompts, 4)
    return reference, model, params, prompts, served


def test_the_reference_is_the_programs_whole_sequence_forward(toy_sample):
    """Two writings of the equations — the reference's expanded keys under
    ``lax.top_k``'s mask, the program's bisection — give the same logits in
    float32 at every position of a prompt that crosses ``top``.  The index
    scores differ by design (the program rounds index queries and keys to
    bfloat16, the reference scores in float32), so a row at the 32nd place
    may flip, and every later position reads the flipped one's keys: up to
    ``top`` the two agree to float32's sums, past it no further than a
    flipped row moves a logit, and with the reference's choice made from
    the same bfloat16 inputs they agree everywhere."""
    import jax
    reference, model, params, prompts, served = toy_sample
    tokens = np.concatenate([prompts[1], served[1]])[None]
    with jax.default_matmul_precision("highest"):
        program = np.asarray(model.apply({"params": params}, tokens))
    ref = np.asarray(reference.forward(params, tokens))
    off = np.abs(ref - program).max(-1)[0]
    # float32 sums in another order: 1e-4 of the logits' spread
    exact = off <= 1e-4 * ref.std() + 1e-6
    assert exact[:32].all()             # nothing is chosen up to ``top``
    assert off.max() < 0.5 * ref.std()
    # with the reference's choice made from the same bfloat16 inputs, the
    # two agree everywhere
    same = np.asarray(reference._head(reference.hidden(
        params, tokens, router_input=lambda x: x.astype("bfloat16").astype(
            "float32")), params["lm_head"]))
    np.testing.assert_allclose(same, program,
                               atol=1e-4 * ref.std() + 1e-6)


def test_prefill_in_chunks_then_decode_is_the_references_forward(
        cell, toy_sample):
    """Through the cache, as the engine runs it: two chunks of 64 tokens,
    then a decode step a token, each step's logits against the reference's
    full forward at that position — float32 weights and cache, the choice
    made from the same bfloat16 inputs, so what is left is float32's sums in
    another order: 1e-4 of the logits' spread."""
    import jax
    import jax.numpy as jnp
    reference, model, params, prompts, _ = toy_sample
    tokens = jnp.asarray(prompts[1][None])                  # 129 tokens
    page, chunk = 32, 64
    dm = model.clone(decode=True, kv_page_size=page, kv_pool_pages=9,
                     use_pallas="interpret")
    table = jnp.arange(1, 6, dtype=jnp.int32)[None]
    cache = dm.init(jax.random.key(0), tokens[:, :page],
                    cache_index=jnp.zeros((1,), jnp.int32),
                    block_table=table)["cache"]
    names = {jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(cache)}
    assert sum("index_key" in n for n in names) == 2        # full layers
    assert sum("paged_latent" in n for n in names) == 5
    got, selected = [], []

    @jax.jit            # one program a shape: a chunk's and a step's
    def step(cache, piece, index):
        return dm.apply({"params": params, "cache": cache}, piece,
                        cache_index=index, block_table=table,
                        mutable=["cache", "stats"])
    with jax.default_matmul_precision("highest"):
        for start in list(range(0, 128, chunk)) + [128]:
            piece = tokens[:, start:start + (chunk if start < 128 else 1)]
            logits, mut = step(cache, piece, jnp.asarray([start], jnp.int32))
            cache = mut["cache"]
            got.append(np.asarray(logits))
            selected.append(int(mut["stats"]["counts"][
                dm.stats_names.index("latent_rows_selected")]))
        ref = np.asarray(reference._head(reference.hidden(
            params, np.asarray(tokens), router_input=lambda x: x.astype(
                "bfloat16").astype("float32")), params["lm_head"]))
    np.testing.assert_allclose(np.concatenate(got, 1), ref,
                               atol=1e-4 * ref.std() + 1e-6)
    counts = dict(zip(dm.stats_names, np.asarray(mut["stats"]["counts"])))
    # the one decode step at position 128: 129 rows visible, 32 attended a
    # layer, both full layers score every visible key
    assert counts["latent_rows_visible"] == 5 * 129
    assert counts["latent_rows_selected"] == 5 * 32
    assert counts["index_keys_scored"] == 2 * 129
    assert counts["rows_dense_path"] == 0
    # the chunks', counted from the membership the kernel wrote: 32 queries
    # that attend all they see and 32 that attend 32; then 64 that attend 32
    assert selected == [5 * (32 * 33 // 2 + 32 * 32), 5 * 64 * 32, 5 * 32]


@pytest.mark.parametrize("path", ["gather", "kernels"])
def test_the_rows_selected_are_what_the_membership_names(
        cell, toy_sample, path, monkeypatch):
    """``latent_rows_selected`` is COUNTED from the membership the layers
    attended by: with the selection left out — a choice that names every
    visible row — it reads ``latent_rows_visible``, where a reckoning from
    positions (``min(seen, top)``) would read what the sound program
    reads."""
    import jax
    import jax.numpy as jnp
    from dtf_tpu.ops import index_select as ix
    _, model, params, prompts, _ = toy_sample
    tokens = jnp.asarray(prompts[1][None, :128])
    dm = model.clone(decode=True, kv_page_size=32, kv_pool_pages=9,
                     use_pallas="interpret" if path == "kernels" else False)
    table = jnp.arange(1, 6, dtype=jnp.int32)[None]
    cache = dm.init(jax.random.key(0), tokens[:, :32],
                    cache_index=jnp.zeros((1,), jnp.int32),
                    block_table=table)["cache"]

    def counts_of_the_second_chunk():
        # a program a call of this: what is patched below is traced anew
        chunk = jax.jit(lambda held, start: dm.apply(
            {"params": params, "cache": held},
            jax.lax.dynamic_slice_in_dim(tokens, start, 64, 1),
            cache_index=start[None], block_table=table,
            mutable=["cache", "stats"]))
        held = cache
        for start in (0, 64):
            _, mut = chunk(held, jnp.int32(start))
            held = mut["cache"]
        return dict(zip(dm.stats_names,
                        np.asarray(mut["stats"]["counts"]).tolist()))
    sound = counts_of_the_second_chunk()
    visible = 5 * sum(range(65, 129))
    assert sound["latent_rows_visible"] == visible
    assert sound["latent_rows_selected"] == 5 * 64 * 32
    if path == "gather":
        monkeypatch.setattr(ix, "members", lambda score, k: score > -jnp.inf)
    else:
        choose = ix.chunk_select
        monkeypatch.setattr(
            ix, "chunk_select", lambda *a, k, interpret: choose(
                *a, k=10 ** 6, interpret=interpret))
    left_out = counts_of_the_second_chunk()
    assert left_out["latent_rows_visible"] == visible
    assert left_out["latent_rows_selected"] == visible


def test_bf16_in_the_programs_place_fails_the_float32_tolerance(
        cell, toy_sample):
    """The control of the test above: the same program with bfloat16
    weights and cache is two orders past that tolerance, so the tolerance
    does separate a sound float32 path from a rounded one."""
    import jax
    import jax.numpy as jnp
    reference, model, params, prompts, _ = toy_sample
    tokens = np.asarray(prompts[0][None])
    bf = _toy_model(cell, "bfloat16")
    with jax.default_matmul_precision("highest"):
        got = np.asarray(bf.apply({"params": jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 and a.ndim >= 1 and a.shape != (16,)
            else a, params)}, tokens))
    ref = np.asarray(reference.forward(params, tokens))
    assert np.abs(got - ref).max() > 30 * (1e-4 * ref.std() + 1e-6)


def test_the_sixteen_shares_make_the_uncut_layer(cell, toy_sample):
    """The share test: an expert layer of the reference with ALL experts
    held is the sum, over the shares of a layer group, of each share's
    expert part (its held experts' weighted outputs alone) plus the shared
    expert counted ONCE.  At the toy: 16 experts in 2 shares of 8."""
    import jax
    import jax.numpy as jnp
    reference = toy_sample[0]
    model = _toy_model(cell, experts_held=None)
    params = model.init(jax.random.key(7),
                        jnp.zeros((1, 32), jnp.int32))["params"]
    p = params["layer1"]
    rng = np.random.default_rng(7)
    h2 = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    arch = dict(reference.arch_of(toy_sample[2]))
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(h2 @ p["router"])
        full = reference.routing_weights(scores, p["router_bias"],
                                         arch["top_k"],
                                         arch["routed_scale"])
        shared = reference._gated(h2, p["shared_gate_up"], p["shared_down"])
        whole = reference._experts(h2, full, p["gate_up"], p["down"],
                                   None) + shared
        parts = [reference._experts(h2, full[:, lo:lo + 8],
                                    p["gate_up"][lo:lo + 8],
                                    p["down"][lo:lo + 8], None)
                 for lo in (0, 8)]
    assert all(float(jnp.abs(part).max()) > 0 for part in parts)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-6)
    # and the program's layer with one share held is that share's part
    # beside the shared expert
    from dtf_tpu.models.routed_decoder import route, routed_experts
    idx, w = route(h2, p["router"], arch["top_k"], p["router_bias"],
                   arch["routed_scale"])
    y, sizes = routed_experts(h2, idx, w, p["gate_up"][8:], p["down"][8:],
                              use_pallas=False, activation="silu",
                              held=(8, 8))
    np.testing.assert_allclose(np.asarray(y), np.asarray(parts[1]),
                               rtol=2e-4, atol=2e-5)
    assert int(sizes.sum()) == int((np.asarray(idx) >= 8).sum())


def test_a_shared_layer_attends_the_choice_of_the_full_layer_below(cell):
    """IndexShare: the value that crosses layers.  A ``shared`` layer hands
    up the choice it was handed, bit for bit — the ``full`` layer's below —
    and has no indexer of its own; a model of only ``full`` layers chooses
    again in every layer and differs."""
    import jax
    import jax.numpy as jnp
    from dtf_tpu.models import routed_decoder as rd
    model = _toy_model(cell)
    tokens = jax.random.randint(jax.random.key(3), (1, 96), 0, 384)
    params = model.init(jax.random.key(3), tokens)["params"]
    assert ["indexer" in params[f"layer{i}"]["attn"] for i in range(5)] \
        == [True, False, False, False, True]
    seen = []

    def note(next_fun, args, kwargs, context):
        out = next_fun(*args, **kwargs)
        if isinstance(context.module, rd.RoutedBlock) \
                and context.method_name == "__call__":
            seen.append((args[-1] if len(args) > 7 else None, out[-1]))
        return out
    import flax.linen as nn
    with nn.intercept_methods(note):
        logits = model.apply({"params": params}, tokens)
    taken, handed = zip(*seen)
    assert taken[0] is None
    for layer in (1, 2, 3):             # shared: in is out is layer 0's
        assert taken[layer] is handed[0] and handed[layer] is handed[0]
    assert handed[4] is not handed[0]
    assert not bool(jnp.array_equal(handed[4], handed[0]))
    first = np.asarray(handed[0])[0]
    assert first.dtype == bool and first.shape == (96, 96)
    assert (first.sum(-1) == np.minimum(np.arange(96) + 1, 32)).all()
    every = _toy_model(cell, layer_indexer=["full"] * 5)
    params_every = every.init(jax.random.key(3), tokens)["params"]
    assert all("indexer" in params_every[f"layer{i}"]["attn"]
               for i in range(5))
    # the same weights where both have them: the shared layers' borrowed
    # choice is not what their own indexer would choose
    merged = jax.tree_util.tree_map(lambda a: a, params_every)
    for i in range(5):
        for k, v in params[f"layer{i}"].items():
            if k != "attn":
                merged[f"layer{i}"][k] = v
        for k, v in params[f"layer{i}"]["attn"].items():
            merged[f"layer{i}"]["attn"][k] = v
    for k in ("embed", "lm_head", "norm_f"):
        merged[k] = params[k]
    other = every.apply({"params": merged}, tokens)
    assert float(jnp.abs(other[:, :32] - logits[:, :32]).max()) < 1e-5
    assert float(jnp.abs(other[:, 32:] - logits[:, 32:]).max()) > 1e-3


def test_the_references_own_comparison_is_lib_agreements(toy_sample):
    reference, _, params, prompts, served = toy_sample
    rows = reference.rows_that_chose(params, prompts, served)
    mine = reference.served_tokens_agree(params, prompts, served, 0.1, rows,
                                         0.01)
    assert mine["ok"] and mine["logit_rms"] == 0.0
    assert mine["greedy_identical"] == mine["tokens_compared"] == 8
    theirs = agreement.tokens_agree(reference.forward, params, prompts[:1],
                                    served[:1], 0.1, rows[:1], 0.01)
    alone = reference.served_tokens_agree(params, prompts[:1], served[:1],
                                          0.1, rows[:1], 0.01)
    for key in ("worst_gap", "logit_scale", "allowed_gap"):
        assert alone[key] == pytest.approx(theirs[key], rel=1e-4, abs=1e-6)


@pytest.mark.parametrize("fault", ["dense", "shared_first", "stale_index",
                                   "no_relu", "no_weights", "w8"])
def test_the_controls_read_worse_than_the_reference_itself(cell, toy_sample,
                                                           fault,
                                                           monkeypatch):
    """Each injected fault of the reference (the selection left out, a
    shared layer on the first ``top`` positions, index keys a page stale,
    the ReLU left out, the heads' weights left out) and its 8-bit weights
    change one thing and read a ``logit_rms`` above the toy's limit, on the
    tokens the sound reference serves."""
    reference, _, params, prompts, served = toy_sample
    monkeypatch.setattr(reference, "FAULT_PAGE",
                        cell.family.TOY["serve"]["engine"]["kv_page_size"])
    kw = ({"weights": reference.rounded_to(8)} if fault == "w8"
          else {"faults": (fault,)})
    rows = reference.rows_that_chose(params, prompts, served, **kw)
    limit = cell.family.TOY["serve"]["agreement"]["logit_rms_limit"]
    said = reference.served_tokens_agree(params, prompts, served, 0.1, rows,
                                         limit)
    assert not said["ok"] and said["logit_rms"] > 1.3 * limit


# ------------------------------------------------- costs and readers ----
def _span(name, ts=0.5, **attrs):
    return dict(kind="span", name=name, ts=ts, dur_s=0.01, **attrs)


def _spec(name):
    return load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  name + ".json"))


def _run(cell, records, kernels, decode_steps=2):
    reduction = Reduction(window_s=1.0, busy_s=0.5, self_s=dict(kernels),
                          calls={k: 1 for k in kernels}, idle_gaps=[])
    return ReaderInput(
        cell=cell, device_kind="TPU v5 lite", reduction=reduction,
        driver={"records": records, "window_wall": (0.0, 1.0),
                "decode_steps": decode_steps, "histograms": {},
                "window_s": 1.0,
                "engine": {"max_batch": 16, "page_size": 256}})


READINGS = os.path.join(ROOT, "docs", "pr49_control_readings.jsonl")


@pytest.mark.parametrize("who,refused,least", [
    ("program", False, 8), ("index_bf16", False, 2),
    ("router_bf16", False, 2), ("w8", True, 8), ("dense", True, 2),
    ("shared_first", True, 8), ("stale_index", True, 2),
    ("no_relu", True, 2), ("no_weights", True, 2)])
def test_the_committed_limits_part_the_kept_readings(cell, who, refused,
                                                     least):
    """The chip's readings at the published widths (``benchmark.control``
    and ``tools/glm_faults.py``, kept line by line in ``docs/``), each
    judged HERE by the limits the workload file commits — whatever limit
    the line itself was printed under: every sound reading passes both,
    every fault and the 8-bit tree is refused by ``logit_rms``, on at
    least ``least`` seeds."""
    agree = cell.workload["agreement"]
    limit, gap_allowed = agree["logit_rms_limit"], 2 * agree["logit_rtol"]
    with open(READINGS) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    mine = [ln for ln in lines if ln["who"] == who]
    assert len({ln["seed"] for ln in mine}) >= least
    for ln in mine:
        assert ln["control"] == CELL
        assert (ln["logit_rms"] > limit) == refused, ln
        if who == "program":
            assert ln["gap"] < gap_allowed and ln["tokens_compared"] == 128
    sound = max(ln["logit_rms"] for ln in lines if ln["who"] == "program")
    eight = min(ln["logit_rms"] for ln in lines if ln["who"] == "w8")
    assert sound < limit < eight
    assert 1.2 < limit / sound and 1.2 < eight / limit


def test_model_flops_against_a_hand_count(cell):
    """A chunk of 2,048 real tokens at position 8,192 and a decode step of
    10 rows at 30,000, counted by hand."""
    cfg, flops = cell.config, cell.family.model_flops
    body = 5 * ATTENTION + 2 * INDEXER + DENSE_MLP + 4 * (ROUTER + EXPERT)
    assert cell.family.body_params(cfg) == body
    head = 6144 * 19360
    per_key, per_index = 2 * 64 * (2 * 512 + 64), 2 * 32 * 128
    assert per_key == 139_264
    visible = sum(range(8193, 8193 + 2048))
    got = flops(cfg, {"tokens": 2048, "real_tokens": 2048, "start": 8192,
                      "assignments": 4000})
    assert got == pytest.approx(
        2.0 * body * 2048 + 2.0 * head + 2.0 * EXPERT * 4000
        + 5 * per_key * 2048 * 2048 + 2 * per_index * visible)
    # a first chunk chooses nothing and attends what it sees
    got = flops(cfg, {"tokens": 2048, "real_tokens": 2048, "start": 0,
                      "assignments": 0})
    assert got == pytest.approx(2.0 * body * 2048 + 2.0 * head
                                + 5 * per_key * 2048 * 2049 / 2)
    # a last chunk of one real token: its padding is no work
    got = flops(cfg, {"tokens": 2048, "real_tokens": 1, "start": 16384,
                      "assignments": 2048})
    assert got == pytest.approx(
        2.0 * body + 2.0 * head + 2.0 * EXPERT * 1
        + 5 * per_key * 2048 + 2 * per_index * 16385)
    step = {"rows": 10, "slots": 16, "assignments": 32,
            "latent_rows_selected": 5 * 10 * 2048,
            "index_keys_scored": 2 * 10 * 30_001}
    assert flops(cfg, step) == pytest.approx(
        2.0 * (body + head) * 10 + 2.0 * EXPERT * 20
        + 5 * per_key * 10 * 2048 + 2 * per_index * 10 * 30_001)
    assert flops(cfg, {"slots": 16}) is None
    assert flops(cfg, {"rows": 3, "slots": 16}) is None


def test_the_costs_count_the_least_work_as_stored(cell):
    cfg, costs = cell.config, cell.family.SPAN_COSTS
    rows = 5 * 10 * 2048
    flops, nbytes = costs["latent_sparse_reads"](
        cfg, {"latent_rows_selected": rows})
    assert (flops, nbytes) == (rows * 2.0 * 64 * (576 + 512), rows * 1280.0)
    # a chunk past 2,048: its visible rows once a layer, never the reads a
    # (query, row) nor the copies the masked stream makes
    flops, nbytes = costs["latent_sparse_reads"](
        cfg, {"latent_rows_selected": 5 * 2048 * 2048, "tokens": 2048,
              "start": 8192, "rows_dense_path": 0})
    assert nbytes == 5 * 10240 * 1280.0
    assert flops == 5 * 2048 * 2048 * 2.0 * 64 * 1088
    assert costs["latent_sparse_reads"](
        cfg, {"latent_rows_selected": 7, "tokens": 2048, "start": 0,
              "rows_dense_path": 2048}) is None
    assert costs["latent_sparse_reads"](cfg, {}) is None
    flops, nbytes = costs["index_select_scores"](
        cfg, {"index_keys_scored": 1000})
    assert (flops, nbytes) == (1000 * 2.0 * 32 * 128, 1000 * 256.0)
    flops, nbytes = costs["index_select_scores"](
        cfg, {"index_keys_scored": 2 * 2048 * 9000, "tokens": 2048,
              "start": 8192})
    assert nbytes == 2 * 10240 * 256.0
    assert costs["index_select_scores"](cfg, {"index_keys_scored": 0}) \
        is None
    assert costs["expert_matmuls"](
        cfg, {"assignments": 10, "experts_touched": 4}) \
        == (2.0 * 10 * EXPERT, 2.0 * 4 * EXPERT)


def test_the_readers_read_the_spans(cell):
    records = [_span("serve_decode", latent_rows_selected=5 * 10 * 2048,
                     latent_rows_visible=5 * 10 * 30_000,
                     index_keys_scored=2 * 10 * 30_000, rows_dense_path=0,
                     assignments=20, experts_touched=12, expert_load_max=9),
               _span("serve_prefill_chunk",
                     latent_rows_selected=5 * 2048 * 2048,
                     latent_rows_visible=5 * 2048 * 9000,
                     index_keys_scored=2 * 2048 * 9000, tokens=2048,
                     start=8192, rows_dense_path=0, assignments=4000,
                     experts_touched=64, expert_load_max=1200),
               _span("serve_decode", ts=2.0, latent_rows_selected=5,
                     latent_rows_visible=5)]
    run = _run(cell, records, {"latent_sparse_decode.3": 0.004,
                               "latent_sparse_chunk.9": 0.3,
                               "index_select.1": 0.05, "gmm.2": 0.02})
    cfg, costs = cell.config, cell.family.SPAN_COSTS
    for metric, cost, total in (
            ("latent_sparse_roofline.sparsectx", "latent_sparse_reads",
             0.304),
            ("index_select_roofline.sparsectx", "index_select_scores", 0.05),
            ("moe_experts_roofline", "expert_matmuls", 0.02)):
        least = sum(peaks.least_seconds("TPU v5 lite", *costs[cost](cfg, r))
                    for r in records[:2])
        got = read_metric(_spec(metric), run)
        assert got == pytest.approx(100 * least / total) and 0 < got < 100
    assert read_metric(_spec("keys_selected_share.sparsectx"), run) \
        == pytest.approx(2048 / 30_000)
    assert read_metric(_spec("expert_load_max_over_mean.n_routed_experts"),
                       run) == pytest.approx(9 * 16 / 20)
    assert read_metric(_spec("latent_sparse_kernel_ms.sparsectx"), run) \
        == pytest.approx(152.0)
    assert read_metric(_spec("index_select_kernel_ms.sparsectx"), run) \
        == pytest.approx(25.0)
    assert read_metric(_spec("moe_experts_ms"), run) == pytest.approx(10.0)
    # a program that counts none of it (the parent): nothing, and no error
    bare = _run(cell, [_span("serve_decode"), _span("serve_prefill_chunk",
                                                   tokens=2048, start=0)],
                {"paged_flash_decode.1": 0.1})
    for name in NEEDS[CELL]:
        spec = _spec(name)
        if spec["reader"] in ("trace_kernel", "trace_kernel_spans",
                              "span_ratio"):
            assert read_metric(spec, bare) is None
