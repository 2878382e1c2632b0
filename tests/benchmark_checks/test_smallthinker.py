"""Checks of what the family ``smallthinker`` adds to the benchmark: its
configuration against the published one, its cell through the serve
driver at the toy size, its reference's own comparison against
``lib/agreement``, and the readers and cost functions of its per-layer
metrics.  CPU only; under BENCHMARK.json's ``paths``."""

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
from benchmark.lib.runtime import load_benchmark, load_cell  # noqa: E402
from benchmark.lib.xplane import Reduction  # noqa: E402
from benchmark.readers import ReaderInput, read_metric  # noqa: E402
from benchmark.lib.runtime import load_json  # noqa: E402

import structure  # noqa: E402  (beside this file)

CELL = "smallthinker-serve-mixedctx"
BENCH = load_benchmark()
# the per-layer metrics the cell needs, each under the entry's own name (a
# suffix says how an entry differs, never which cell reads it)
NEEDS = {CELL: [
    "serve_mfu", "decode_step_ms", "device_idle_pct", "prefill_chunk_ms",
    "paged_decode_kernel_ms", "paged_decode_roofline.by_span",
    "moe_experts_ms", "moe_experts_roofline", "expert_load_max_over_mean",
    "host_admit_ms", "host_chunk_ms", "host_launch_ms", "host_emit_ms",
    "idle_host_pct", "idle_wait_pct", "moe_experts_step_ms",
    "moe_experts_chunk_ms"]}
PERIOD = [0, 1, 1, 1]
# https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json
# as the catalog of architectures holds it
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": PERIOD * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": PERIOD * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def cell():
    return load_cell(BENCH, CELL)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_key(cell, key):
    """Every published key unchanged, but the depth, which ``reduced``
    names and the file states beside the published count."""
    if key == "num_hidden_layers":
        assert cell.config["reduced"] == ["num_hidden_layers"]
        assert cell.config[key] == 12
        assert cell.config["published"][key] == PUBLISHED[key]
    else:
        assert cell.config[key] == PUBLISHED[key]


def test_the_build_call_is_the_configuration(cell):
    """What ``build_model`` is given is what the published keys say: no
    width, expert count, window or theta of its own."""
    c, kw = cell.config, cell.config["build_model"]["kwargs"]
    assert kw["num_layers"] == c["num_hidden_layers"] == 12
    assert 12 % len(kw["layer_window"]) == 0        # whole periods
    n = len(kw["layer_window"])
    assert [int(v) for v in kw["layer_window"]] == c["sliding_window_layout"][:n]
    assert [int(v) for v in kw["layer_rope"]] == c["rope_layout"][:n]
    assert c["sliding_window_layout"] == c["sliding_window_layout"][:n] * 13
    for ours, theirs in [("d_model", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("num_experts", "moe_num_primary_experts"),
                         ("experts_per_token", "moe_num_active_primary_experts"),
                         ("expert_width", "moe_ffn_hidden_size"),
                         ("window", "sliding_window_size"),
                         ("rope_theta", "rope_theta"),
                         ("rms_eps", "rms_norm_eps"),
                         ("max_seq_len", "max_position_embeddings")]:
        assert kw[ours] == c[theirs], ours
    assert c["num_classes"] == c["vocab_size"]
    assert kw["param_dtype"] == "bfloat16" and c["dtype"] == "bf16"
    entry = next(x for x in BENCH["configs"] if x["name"] == cell.config_name)
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200
    assert entry["source"].endswith("config.json")


def test_the_traffic_is_the_mix_the_cell_was_asked_for(cell):
    assert cell.traffic == {
        "kind": "requests", "arrivals": "closed", "clients": 16,
        "prepare_per_s": 8.0, "prepare_block_per_s": 8.0,
        "base_seed": 20261027, "ramp_s": 20, "drain_s": 10,
        "prompt_len": {"median": 3072, "sigma": 0.6, "min": 1024,
                       "max": 12288,
                       "snap_to": [1024, 2048, 3072, 4096, 6144, 8192, 12288]},
        "output_len": {"median": 192, "sigma": 0.5, "min": 64, "max": 512}}
    eng = cell.workload["engine"]
    assert eng["max_batch"] == cell.traffic["clients"] == 16
    assert eng["max_seq_len"] == 16384 and eng["queue_size"] == 256
    # 131,072 tokens and the scratch page
    assert (eng["kv_pool_pages"] - 1) * eng["kv_page_size"] == 131072
    agree = cell.workload["agreement"]
    # 64 new tokens, not the 8 first asked for: 16 compared tokens read
    # 0.008-0.024 over 12 seeds, a flipped top-6 choice or two deciding
    assert agree["prompt_lens"] == [1024, 6144] and agree["new_tokens"] == 64
    # limits from control readings at the published widths (PERF.md §2)
    assert 0.0204 < agree["logit_rms_limit"] < 0.039
    assert agree["logit_rtol"] == 0.04
    assert cell.chips == 1


def test_serve_tok_s_is_judged_in_the_cell():
    """The cell's own claims on BENCHMARK.json (``structure.py``): nothing
    about its place in a list, or about what else lists an entry."""
    _, mine = structure.check_cell(
        BENCH, ROOT, CELL, NEEDS[CELL], config="smallthinker-21b-a3b",
        traffic="mixedctx-closed-16")
    assert all(m["moves"] == "serve_tok_s" for m in mine.values())


def test_the_cell_rehearses_through_the_serve_driver():
    """Loaded by name, at the family's toy size, on the CPU."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.rehearse", "--workload", CELL,
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
    """The toy's weights (float32 and bfloat16 trees), two prompts and
    what the reference would serve for them."""
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
    """``served_tokens_agree`` gathers the hidden rows before the head;
    ``lib/agreement.tokens_agree`` gathers them after.  Same dictionary,
    same numbers — for logits that agree and for logits that do not."""
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


def test_the_controls_round_and_read_worse(toy_sample):
    """8-bit weights put in the reference's place read a larger
    ``logit_rms`` than the reference against itself (0)."""
    reference, params, prompts, served = toy_sample
    w8 = reference.rounded_to(8)
    tokens = reference.greedy_tokens(params, prompts, 4, w8)
    said = reference.served_tokens_agree(
        params, prompts, tokens, 0.01,
        reference.rows_that_chose(params, prompts, tokens, w8), 1.0)
    assert 0.001 < said["logit_rms"] < 0.1


# ------------------------------------------------- costs and readers ----
def _span(name, ts=0.5, **attrs):
    return dict(kind="span", name=name, ts=ts, dur_s=0.01, **attrs)


def test_train_flops_count_the_active_parameters_and_the_window(cell):
    cfg = cell.config
    fam = cell.family
    per_layer = (2560 * (28 + 8) * 128 + 28 * 128 * 2560 + 2560 * 64
                 + 6 * 3 * 2560 * 768)
    assert fam.active_matmul_params(cfg) == 12 * per_layer + 2560 * 151936
    short = fam.train_flops_per_sample(cfg, {"seq_len": 1024})
    attn = 12 * 4 * 28 * 128 * (1024 + 1) / 2
    assert short == pytest.approx(
        3 * (2 * fam.active_matmul_params(cfg) + attn) * 1024)
    # past the window the nine window layers stop growing
    long = fam.train_flops_per_sample(cfg, {"seq_len": 16384}) / 16384
    full = 3 * (2 * fam.active_matmul_params(cfg)
                + 12 * 4 * 28 * 128 * (16384 + 1) / 2)
    assert long < full


def test_expert_cost_counts_pairs_and_touched_experts(cell):
    flops, nbytes = cell.family.SPAN_COSTS["expert_matmuls"](
        cell.config, {"assignments": 96 * 12, "experts_touched": 51 * 12})
    assert flops == 2.0 * 96 * 12 * 3 * 2560 * 768
    assert nbytes == 2.0 * 51 * 12 * 3 * 2560 * 768
    assert cell.family.SPAN_COSTS["expert_matmuls"](cell.config, {}) is None


def test_paged_cost_counts_what_each_layer_kind_reads(cell):
    cost = cell.family.SPAN_COSTS["paged_attention_reads"]
    # a decode step: 3 global layers read 10,000 tokens, 9 window layers
    # 4,096 each
    span = {"kv_tokens_read_global": 3 * 10000,
            "kv_tokens_read_window": 9 * 4096}
    flops, nbytes = cost(cell.config, span)
    tokens = 3 * 10000 + 9 * 4096
    assert nbytes == 2 * 2.0 * tokens * 4 * 128
    assert flops == 2 * 2.0 * tokens * 28 * 128
    # a first chunk reads no page; a span without counts says nothing
    assert cost(cell.config, dict(span, start=0, tokens=512)) is None
    assert cost(cell.config, {"tokens": 512, "start": 512}) is None


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
     {"assignments": 1152, "experts_touched": 612}),
    ("paged_decode_roofline.by_span", "paged_flash_decode.7",
     {"kv_tokens_read_global": 30000, "kv_tokens_read_window": 36864}),
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
    # a program that counts nothing on its spans (the parent's): nothing
    run = _run(cell, [_span("serve_decode")], {kernel: 1.0})
    assert read_metric(spec, run) is None
    # no such kernel in the trace: nothing
    run = _run(cell, records, {"fusion.1": 1.0})
    assert read_metric(spec, run) is None


def test_expert_load_reads_max_over_mean(cell):
    spec = _spec("expert_load_max_over_mean")
    # 12 layers of 96 pairs; the busiest expert of each layer holds 3 rows:
    # 3 / (96 / 64) = 2
    records = [_span("serve_decode", assignments=96 * 12,
                     expert_load_max=3 * 12)] * 5
    assert read_metric(spec, _run(cell, records, {})) == pytest.approx(2.0)
    assert read_metric(spec, _run(cell, [_span("serve_decode")], {})) is None


@pytest.mark.parametrize("metric,kernel", [
    ("moe_experts_ms", "gmm.12"),
    ("paged_decode_kernel_ms", "paged_flash_decode.4")])
def test_kernel_time_is_per_decode_step(cell, metric, kernel):
    run = _run(cell, [], {kernel: 0.030, "gmm_like_fusion": 1.0})
    assert read_metric(_spec(metric), run) == pytest.approx(15.0)
