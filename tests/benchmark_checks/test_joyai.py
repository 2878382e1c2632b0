"""Checks of what the family ``joyai`` adds to the benchmark: the
configuration against the published one, its cell through the serve driver
at the toy size, the reference's own comparison against ``lib/agreement``, the
readers and cost functions of the new per-layer metrics, and that the
serve bodies of the configurations the benchmark already had lower to the
text they lowered to before.  CPU only; under BENCHMARK.json's ``paths``."""

import hashlib
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
from benchmark.lib import agreement, peaks  # noqa: E402
from benchmark.lib.runtime import (load_benchmark, load_cell,  # noqa: E402
                                   load_json)
from benchmark.lib.xplane import Reduction  # noqa: E402
from benchmark.readers import ReaderInput, read_metric  # noqa: E402

import structure  # noqa: E402  (beside this file)

CELL = "joyai-serve-longctx"
BENCH = load_benchmark()
# the per-layer metrics the cell needs, each under the entry's own name (a
# suffix says how an entry differs, never which cell reads it)
NEEDS = {
    CELL: [
        "decode_step_ms", "device_idle_pct", "prefill_chunk_ms",
        "moe_experts_ms", "moe_experts_roofline",
        "latent_attention_kernel_ms", "latent_attention_roofline",
        "expert_load_max_over_mean.n_routed_experts", "host_admit_ms",
        "host_chunk_ms", "host_launch_ms", "host_emit_ms", "idle_host_pct",
        "idle_wait_pct", "serve_mfu"],
}
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json
# as the catalog of architectures holds it
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}


@pytest.fixture(scope="module")
def cell():
    return load_cell(BENCH, CELL)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_key(cell, key):
    """Every published key unchanged, but the depth, which ``reduced``
    names and the file states beside the published count."""
    if key == "num_hidden_layers":
        assert cell.config["reduced"] == ["num_hidden_layers"]
        assert cell.config[key] == 5
        assert cell.config["published"][key] == PUBLISHED[key]
    else:
        assert cell.config[key] == PUBLISHED[key]


def test_the_build_call_is_the_configuration(cell):
    """What ``build_model`` is given is what the published keys say: no
    width, rank, expert count, scale or theta of its own; the prediction
    module is named as not built; the entry names the config.json."""
    c, kw = cell.config, cell.config["build_model"]["kwargs"]
    assert kw["num_layers"] == c["num_hidden_layers"] == 5
    # the dense layer and the four expert layers after it: the floor
    assert kw["num_layers"] - kw["num_dense_layers"] == 4
    for ours, theirs in [("d_model", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("q_lora_rank", "q_lora_rank"),
                         ("kv_lora_rank", "kv_lora_rank"),
                         ("qk_nope_head_dim", "qk_nope_head_dim"),
                         ("qk_rope_head_dim", "qk_rope_head_dim"),
                         ("v_head_dim", "v_head_dim"),
                         ("rope_theta", "rope_theta"),
                         ("rope_interleave", "rope_interleave"),
                         ("num_dense_layers", "first_k_dense_replace"),
                         ("dense_width", "intermediate_size"),
                         ("num_experts", "n_routed_experts"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("expert_width", "moe_intermediate_size"),
                         ("routed_scale", "routed_scaling_factor"),
                         ("activation", "hidden_act"),
                         ("rms_eps", "rms_norm_eps"),
                         ("max_seq_len", "max_position_embeddings")]:
        assert kw[ours] == c[theirs], ours
    assert kw["shared_expert_width"] == (c["n_shared_experts"]
                                         * c["moe_intermediate_size"])
    assert kw["routing"] == "sigmoid_bias" and c["scoring_func"] == "sigmoid"
    assert kw["router_input"] == "post_attention"
    assert kw["router_bias_stddev"] == 0.05
    assert c["qk_head_dim"] == c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    assert c["num_classes"] == c["vocab_size"]
    assert kw["param_dtype"] == "bfloat16" and c["dtype"] == "bf16"
    assert list(c["not_built"]) == ["multi_token_prediction"]
    for key in ("norm", "rope", "softmax_scale", "head_dim",
                "num_key_value_heads", "routing", "cache", "weights",
                "router_bias"):
        assert key in c["assumed"], key
    entry = next(x for x in BENCH["configs"] if x["name"] == cell.config_name)
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200
    assert entry["source"].endswith("config.json")
    assert entry["reduced"] == c["reduced"]


def test_the_traffic_is_the_mix_the_cell_was_asked_for(cell):
    assert cell.traffic == {
        "kind": "requests", "arrivals": "closed", "clients": 24,
        "prepare_per_s": 8.0, "prepare_block_per_s": 8.0,
        "base_seed": 20261101, "ramp_s": 20, "drain_s": 10,
        "prompt_len": {"median": 8192, "sigma": 0.7, "min": 2048,
                       "max": 32768,
                       "snap_to": [2048, 4096, 8192, 12288, 16384, 24576,
                                   32768]},
        "output_len": {"median": 256, "sigma": 0.5, "min": 96, "max": 768}}
    eng = cell.workload["engine"]
    assert eng["max_batch"] == cell.traffic["clients"] == 24
    assert eng["max_seq_len"] == 34816 and eng["queue_size"] == 256
    # the longest row fits: 32,768 + 768
    assert eng["max_seq_len"] >= 32768 + 768
    assert (eng["kv_pool_pages"] - 1) * eng["kv_page_size"] == POOL_TOKENS
    agree = cell.workload["agreement"]
    assert agree["prompt_lens"] == [2048, 12288] and agree["new_tokens"] == 64
    # limits from readings at the published widths (PERF.md §2): above the
    # largest sound reading of 17 runs, under the smallest 8-bit one; a
    # flipped top-8 choice put a served token up to 0.156 of the scale under
    assert 0.0953 < agree["logit_rms_limit"] < 0.1411
    assert 2 * agree["logit_rtol"] > 0.156
    assert eng["kv_page_size"] == 64 and eng["prefill_chunk"] == 2048
    assert cell.chips == 1


@pytest.mark.parametrize("trace", ["0", "1"], ids=["trace0", "trace1"])
def test_the_cell_rehearses_through_the_serve_driver(trace):
    """Loaded by name, at the family's toy size, on the CPU; the traced
    rehearsal also walks the spans' counts into the readers."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.rehearse", "--workload", CELL,
         "--trace", trace,
         "--seconds", "2"],
        cwd=ROOT, capture_output=True, text=True, timeout=110,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    said = json.loads(last[last.index("{"):])
    assert said["line"]["correct"] is True and said["reasons"] == []
    assert said["line"]["device"]["platform"] == "cpu"
    assert said["contract_refuses_it_for"]      # never a result


@pytest.fixture(scope="module")
def toy_sample(cell):
    """The toy's weights (a bfloat16 tree), two prompts and what the
    reference would serve for them."""
    import jax
    import jax.numpy as jnp
    from dtf_tpu.models import build_model
    reference = families.load_reference(cell.config, ROOT)
    toy = cell.family.TOY["serve"]
    kw = dict(cell.config["build_model"]["kwargs"], **toy["model_kwargs"])
    model, _ = build_model("routed_decoder", num_classes=toy["vocab_size"],
                           dtype=jnp.bfloat16, **kw)
    params = model.init(jax.random.key(5),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, toy["vocab_size"], n, dtype=np.int32)
               for n in (20, 45)]
    served = agreement.greedy_tokens(reference.forward, params, prompts, 4)
    return reference, params, prompts, served


@pytest.mark.parametrize("noise", [0.0, 0.05], ids=["exact", "off"])
def test_the_references_own_comparison_is_lib_agreements(toy_sample, noise):
    """``served_tokens_agree`` gathers the hidden rows before the head, a
    prompt at a time; ``lib/agreement.tokens_agree`` gathers them after,
    in one padded batch.  Same dictionary, same numbers — for logits that
    agree and for logits that do not."""
    reference, params, prompts, served = toy_sample
    rows = reference.rows_that_chose(params, prompts, served)
    rng = np.random.default_rng(0)
    program = [r + noise * rng.normal(size=r.shape).astype(np.float32)
               for r in rows]
    ours = reference.served_tokens_agree(params, prompts, served, 0.01,
                                         program, 0.02)
    theirs = agreement.tokens_agree(reference.forward, params, prompts,
                                    served, 0.01, program, 0.02)
    assert set(ours) == set(theirs)
    assert ours["ok"] is theirs["ok"] is (noise == 0.0)
    for key in ours:
        if isinstance(ours[key], float):
            assert ours[key] == pytest.approx(theirs[key], rel=1e-4,
                                              abs=1e-6), key
        else:
            assert ours[key] == theirs[key], key


@pytest.mark.parametrize("control", ["w8", "router_bf16", "latent_bf16",
                                     "zero_bias"])
def test_the_controls_read_worse_than_the_reference_itself(toy_sample,
                                                           control):
    """Each control of the reference changes one thing and reads a
    ``logit_rms`` above 0: 8-bit weights; the router's input alone in
    bfloat16 (flipped top-k choices); the cached latents alone in bfloat16;
    the score-correction bias left out (a part of the mathematics: it
    reads an order of magnitude above any rounding)."""
    import jax.numpy as jnp
    reference, params, prompts, served = toy_sample

    def bf16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    kw = {"w8": {"weights": reference.rounded_to(8)},
          "router_bf16": {"router_input": bf16},
          "latent_bf16": {"latent": bf16},
          "zero_bias": {"zero_bias": True}}[control]
    said = reference.served_tokens_agree(
        params, prompts, served, 0.01,
        reference.rows_that_chose(params, prompts, served, **kw), 1.0)
    low, high = {"w8": (1e-3, 0.1), "router_bf16": (0.0, 0.1),
                 "latent_bf16": (1e-4, 0.05),
                 "zero_bias": (0.05, 2.0)}[control]
    assert low <= said["logit_rms"] < high, said["logit_rms"]


# ------------------------------------------------- costs and readers ----
def _span(name, ts=0.5, **attrs):
    return dict(kind="span", name=name, ts=ts, dur_s=0.01, **attrs)


def test_flops_count_the_active_parameters(cell):
    cfg, fam = cell.config, cell.family
    attn = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
            + 32 * 128 * 2048)
    assert attn == 26_345_472
    routed = 2048 * 256 + 9 * 3 * 2048 * 768
    assert fam.active_matmul_params(cfg) == (
        5 * attn + 3 * 2048 * 7168 + 4 * routed + 2048 * 129280)
    assert fam.row_lanes(cfg) == 640
    flops = fam.train_flops_per_sample(cfg, {"seq_len": 1024})
    assert flops == pytest.approx(3 * (2 * fam.active_matmul_params(cfg)
                                       + 5 * 2 * 32 * 320 * 1025 / 2) * 1024)


def test_expert_cost_counts_pairs_and_touched_experts(cell):
    cost = cell.family.SPAN_COSTS["expert_matmuls"]
    flops, nbytes = cost(cell.config, {"assignments": 192 * 4,
                                       "experts_touched": 135 * 4})
    assert flops == 2.0 * 192 * 4 * 3 * 2048 * 768
    assert nbytes == 2.0 * 135 * 4 * 3 * 2048 * 768
    assert cost(cell.config, {}) is None


def test_latent_cost_counts_the_row_as_stored_and_a_chunks_keys_at_half(cell):
    cost = cell.family.SPAN_COSTS["latent_attention_reads"]
    # a decode step: 5 layers read 254,000 cached rows, one query a row
    flops, nbytes = cost(cell.config, {"latent_tokens_read": 5 * 254000})
    assert nbytes == 5 * 254000 * 1280
    assert flops == 2.0 * 32 * (576 + 512) * 5 * 254000
    # a 1,024-token chunk at 5,120: every layer attends 6,144 rows, its own
    # 1,024 at half; the first chunk goes through the same kernel
    span = {"latent_tokens_read": 5 * 6144, "tokens": 1024, "start": 5120}
    flops, nbytes = cost(cell.config, span)
    assert nbytes == 5 * 6144 * 1280
    assert flops == 2.0 * 32 * 1088 * 1024 * 5 * (6144 - 1023 / 2)
    assert cost(cell.config, {"latent_tokens_read": 5 * 1024, "tokens": 1024,
                              "start": 0}) is not None
    assert cost(cell.config, {"tokens": 1024, "start": 1024}) is None


def _run(cell, records, kernel_s):
    red = Reduction(window_s=1.0, busy_s=0.5, self_s=dict(kernel_s),
                    calls={k: 1 for k in kernel_s}, idle_gaps=[])
    return ReaderInput(cell=cell, device_kind="TPU v5 lite", reduction=red,
                       driver={"window_wall": (0.0, 1.0), "records": records,
                               "decode_steps": 2})


def _spec(name):
    return load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  name + ".json"))


@pytest.mark.parametrize("metric,kernel,attrs", [
    ("moe_experts_roofline", "gmm.3",
     {"assignments": 768, "experts_touched": 540}),
    ("latent_attention_roofline", "paged_flash_decode.7",
     {"latent_tokens_read": 1270000}),
])
def test_span_roofline_reads_100_at_the_floor_and_none_without(
        cell, metric, kernel, attrs):
    spec = _spec(metric)
    cost = cell.family.SPAN_COSTS[spec["args"]["cost"]]
    least = peaks.least_seconds("TPU v5 lite", *cost(cell.config, attrs))
    records = [_span("serve_decode", **attrs), _span("serve_decode", **attrs),
               _span("serve_decode", ts=2.0, **attrs),      # outside
               _span("serve_decode")]                        # counts nothing
    run = _run(cell, records, {kernel: 2 * least, "fusion.1": 0.1})
    assert read_metric(spec, run) == pytest.approx(100.0)
    run = _run(cell, records, {kernel: 4 * least})
    assert read_metric(spec, run) == pytest.approx(50.0)
    # a program that counts nothing on its spans: nothing, and no error
    run = _run(cell, [_span("serve_decode")], {kernel: 1.0})
    assert read_metric(spec, run) is None
    run = _run(cell, records, {"fusion.1": 1.0})
    assert read_metric(spec, run) is None


def test_expert_load_reads_max_over_mean_of_256(cell):
    spec = _spec("expert_load_max_over_mean.n_routed_experts")
    # 4 routed layers of 192 pairs; the busiest expert of each holds 3
    # rows: 3 / (192 / 256) = 4
    records = [_span("serve_decode", assignments=192 * 4,
                     expert_load_max=3 * 4)] * 5
    assert read_metric(spec, _run(cell, records, {})) == pytest.approx(4.0)
    assert read_metric(spec, _run(cell, [_span("serve_decode")], {})) is None


@pytest.mark.parametrize("metric,kernel", [
    ("moe_experts_ms", "gmm.12"),
    ("latent_attention_kernel_ms", "paged_flash_decode.4")])
def test_kernel_time_is_per_decode_step(cell, metric, kernel):
    run = _run(cell, [], {kernel: 0.030, "gmm_like_fusion": 1.0})
    assert read_metric(_spec(metric), run) == pytest.approx(15.0)


def test_serve_tok_s_is_judged_in_the_new_cell():
    """The cell's own claims on BENCHMARK.json (``structure.py``): nothing
    about its place in a list, or about what else lists an entry."""
    _, mine = structure.check_cell(BENCH, ROOT, CELL, NEEDS[CELL])
    assert all(m["moves"] == "serve_tok_s" for m in mine.values())


# ---------------------------- the bodies the benchmark already had ----
def body_hashes() -> dict:
    """sha256 of the lowered text (CPU: the gather path) of the first
    chunk, a continuation chunk and the decode step, at the toy sizes of
    the two serving families the benchmark had before this one."""
    import jax
    import jax.numpy as jnp
    from dtf_tpu.models import build_model
    from dtf_tpu.serve.decode import Decoder, _seed_row_keys, position_key
    out = {}
    for family, name, vocab in (("smallthinker", "routed_decoder", 512),
                                ("gpt2", "transformer", 256)):
        kw = dict(families.load(family, ROOT).TOY["serve"]["model_kwargs"])
        if family == "smallthinker":
            kw["param_dtype"] = "bfloat16"
        model, _ = build_model(name, num_classes=vocab, dtype=jnp.bfloat16,
                               **kw)
        params = jax.jit(model.clone(use_pallas=False).init)(
            jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]
        page, chunk, slots = 8, 32, 4
        dec = Decoder(model, params, num_slots=slots, max_seq_len=128,
                      kv_page_size=page, kv_pool_pages=65)
        cache = jax.eval_shape(dec.fresh_cache)
        m = dec.pages_per_slot

        def sha(lowered):
            return hashlib.sha256(lowered.as_text().encode()).hexdigest()
        for tag, start in (("chunk_first", 0), ("chunk_cont", chunk)):
            out[f"{family}.{tag}"] = sha(dec._chunk.lower(
                dec.params, cache, jnp.zeros((1, chunk), jnp.int32),
                jnp.zeros((1, m), jnp.int32), jnp.asarray(0, jnp.int32),
                jnp.asarray(0.0, jnp.float32), position_key(0, 0),
                jnp.asarray(start, jnp.int32), (start + chunk) // page,
                start == 0))
        zeros = jnp.zeros((slots,), jnp.int32)
        out[f"{family}.decode"] = sha(dec._decode.lower(
            dec.params, cache, jnp.zeros((slots, 1), jnp.int32), zeros,
            jnp.zeros((slots, m), jnp.int32),
            jnp.zeros((slots,), jnp.float32),
            _seed_row_keys(jnp.zeros((slots,), jnp.uint32), zeros)))
    return out


def test_the_bodies_the_benchmark_had_lower_as_before():
    """The routed decoder gained layer kinds and the paged attention a
    latent pool; the SmallThinker and the dense configurations' build
    calls are not edited and their two compiled bodies lower, on the CPU,
    to the text the parent commit's lowered to (recorded from it in
    ``data/serve_bodies_lowered.json``)."""
    assert body_hashes() == load_json(
        os.path.join(DATA, "serve_bodies_lowered.json"))


POOL_TOKENS = 393216

if __name__ == "__main__":      # record: PYTHONPATH=<a checkout> python <this>
    print(json.dumps(body_hashes(), indent=1, sort_keys=True))
