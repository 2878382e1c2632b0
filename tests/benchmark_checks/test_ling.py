"""Checks of what the family ``ling`` and its cell add to the benchmark: the
configuration against the published one, the cell by name through the serve
driver at the toy size, the reference's own comparison against
``lib/agreement``, the reference's controls, the cost functions and readers
of the new per-layer metrics, and that the serve bodies of the FOURTH
serving configuration the benchmark already had (the short-convolution
one; ``test_lfm2.py`` holds the other three to their records) lower to the
text they lowered to at the parent commit.  CPU only; under
BENCHMARK.json's ``paths``.

The toy's limit (``families/ling.py`` ``TOY``, 0.08): the program, bf16
matmuls and a bf16 state pool on an f32 stream, reads a ``logit_rms`` of
0.0399-0.0449 over seeds 11-12 (a float32 state pool read 0.036-0.040 where
the bf16 one read 0.046, on an earlier, longer sample), the reference with
every matrix at 8 bits 0.146-0.161 (``control_rows.py --toy --tokens
random``), CPU, PR 39, at 4 linear heads of 8 and the sample of four prompts
of 24 new tokens.  The toy's state lives long against its width of 64, so the
decays' rounding shows more than at the published size."""

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

CELL = "ling-serve-longgen"
BENCH = load_benchmark()
# the per-layer metrics the cell needs, each under the entry's own name (a
# suffix says how an entry differs, never which cell reads it)
NEEDS = {
    CELL: [
        "decode_step_ms", "device_idle_pct", "prefill_chunk_ms",
        "moe_experts_ms", "moe_experts_roofline",
        "latent_attention_kernel_ms", "latent_attention_roofline",
        "expert_load_max_over_mean.num_experts", "host_admit_ms",
        "host_chunk_ms", "host_launch_ms", "host_emit_ms", "idle_host_pct",
        "idle_wait_pct", "decode_rows_per_step.longgen",
        "linear_state_kernel_ms", "linear_state_roofline", "serve_mfu"],
}
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/config.json
# as the catalog of architectures holds it (the language model's keys)
PUBLISHED = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}
REDUCED = {"num_hidden_layers": 8, "num_experts": 128, "vocab_size": 39296}
MIXER = {"linear": "linear_delta", "full": "attention"}


@pytest.fixture(scope="module")
def cell():
    return load_cell(BENCH, CELL)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_key(cell, key):
    """Every published key unchanged, but the three that ``reduced`` names
    — the depth, the experts this chip holds, the vocabulary rows it holds
    — each with its published value beside it."""
    if key in REDUCED:
        assert cell.config["reduced"] == list(REDUCED)
        assert cell.config[key] == REDUCED[key]
        assert cell.config["published"][key] == PUBLISHED[key]
    else:
        assert cell.config[key] == PUBLISHED[key]


def test_the_derived_layer_types_follow_the_group_size(cell):
    c = cell.config
    assert len(c["layer_types"]) == 42
    assert c["layer_types"] == ["full" if (l + 1) % c["layer_group_size"] == 0
                                else "linear" for l in range(42)]
    assert c["layer_types"].count("full") == 7
    assert cell.family.layer_types(c) == ["linear"] * 5 + ["full"] + [
        "linear"] * 2
    assert "layer_order" in c["assumed"]
    # the clamp the cut leaves out is inactive in its layers
    assert not any(c["expert_swiglu_limit_list"][:8])
    assert not any(c["share_expert_swiglu_limit_list"][:8])
    assert set(c["not_built"]) == {"vision_tower", "multi_token_prediction",
                                   "swiglu_clamp"}
    # no request carries an id of the vision tower's: they lie outside the
    # vocabulary rows held
    assert min(c[k] for k in PUBLISHED if k.endswith("_token")) \
        >= c["vocab_size"]


def test_the_build_call_is_the_configuration(cell):
    """What ``build_model`` is given is what the published keys say: no
    width, count, scale or theta of its own; the share is 128 of the 512
    the router chooses among; the stored bytes are stated."""
    c, kw = cell.config, cell.config["build_model"]["kwargs"]
    fam = cell.family
    assert kw["num_layers"] == c["num_hidden_layers"] == 8
    assert kw["layer_mixer"] == [MIXER[k] for k in c["layer_types"][:8]]
    for ours, theirs in [("d_model", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("linear_heads", "num_attention_heads"),
                         ("linear_head_dim", "head_dim"),
                         ("linear_conv_taps", "short_conv_kernel_size"),
                         ("linear_decay_floor", "kda_lower_bound"),
                         ("q_lora_rank", "q_lora_rank"),
                         ("kv_lora_rank", "kv_lora_rank"),
                         ("qk_nope_head_dim", "qk_nope_head_dim"),
                         ("qk_rope_head_dim", "qk_rope_head_dim"),
                         ("v_head_dim", "v_head_dim"),
                         ("q_head_norm", "use_qk_norm"),
                         ("rope_theta", "rope_theta"),
                         ("num_dense_layers", "first_k_dense_replace"),
                         ("dense_width", "intermediate_size"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("expert_width", "moe_intermediate_size"),
                         ("shared_expert_width",
                          "moe_shared_expert_intermediate_size"),
                         ("routed_scale", "routed_scaling_factor"),
                         ("route_groups", "n_group"),
                         ("route_groups_kept", "topk_group"),
                         ("rms_eps", "rms_norm_eps"),
                         ("max_seq_len", "max_position_embeddings")]:
        assert kw[ours] == c[theirs], ours
    # the router's width is the published count; the chip holds a block
    assert kw["num_experts"] == c["published"]["num_experts"] == 512
    assert kw["experts_held"] == [0, c["num_experts"]] == [0, 128]
    assert c["num_classes"] == c["vocab_size"] == 39296 == 157184 // 4
    assert c["vocab_size"] % 128 == 0
    assert kw["attention_head_gate"] and kw["routing"] == "sigmoid_bias"
    assert kw["rope_interleave"] is False
    assert kw["router_input"] == "post_attention"
    assert kw["router_bias_stddev"] == 0.05 and kw["activation"] == "silu"
    assert kw["param_dtype"] == "bfloat16" and c["dtype"] == "bf16"
    for key in ("layer_order", "depth", "linear_heads", "linear_projections",
                "linear_conv", "linear_norms", "decay_gate",
                "write_strength", "linear_output", "linear_positions",
                "latent_attention", "rope", "head_gate", "norm", "routing",
                "share", "experts", "state", "precision", "weights",
                "router_bias", "parameters"):
        assert key in c["assumed"], key
    assert c["stored"]["latent_bytes_per_token"] \
        == fam.latent_bytes_per_token(c) == 1280
    assert c["stored"]["state_bytes_per_page"] \
        == fam.state_bytes_per_page(c) == 7 * (1048576 + 73728)
    assert "2x2 host" in c["deployment"] and "39,296" in c["deployment"]
    entry = next(x for x in BENCH["configs"] if x["name"] == cell.config_name)
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200
    assert entry["source"].endswith("Ling-3.0-flash-VL/blob/main/config.json")
    assert entry["reduced"] == c["reduced"]
    assert len(entry["why"]) <= 200


def test_the_program_counts_the_bytes_the_file_states(cell):
    """``serving_memory_plan`` over the configuration's own build call:
    the state entry a page and the latent row a token as the file and
    ``PERF.md`` state them, 128 experts' weights a layer, 39,296 rows."""
    import jax.numpy as jnp
    from dtf_tpu.models import build_model
    from dtf_tpu.serve.bridge import serving_memory_plan
    c, eng = cell.config, cell.workload["engine"]
    model, _ = build_model(c["build_model"]["name"],
                           num_classes=c["vocab_size"], dtype=jnp.bfloat16,
                           **c["build_model"]["kwargs"])
    plan = serving_memory_plan(
        model, num_slots=eng["max_batch"], max_seq_len=eng["max_seq_len"],
        kv_page_size=eng["kv_page_size"], kv_pool_pages=eng["kv_pool_pages"])
    assert plan["state_bytes_per_page"] == c["stored"]["state_bytes_per_page"]
    assert plan["per_token_kv_bytes"] == c["stored"]["latent_bytes_per_token"]
    assert plan["kv_tokens_capacity"] == 393216 * 1024 // eng["kv_page_size"] \
        * eng["kv_page_size"] // 1024
    assert 10.6e9 < plan["param_bytes"] < 10.75e9
    resident = (plan["param_bytes"] + plan["kv_bytes_paged"]
                + plan["state_bytes_paged"])
    assert 13e9 < resident < 14.6e9


def test_the_traffic_is_the_mix_the_cell_was_asked_for(cell):
    assert cell.traffic == {
        "kind": "requests", "arrivals": "closed", "clients": 96,
        "prepare_per_s": 16.0, "prepare_block_per_s": 16.0,
        "base_seed": 20261301, "ramp_s": 20, "drain_s": 10,
        "prompt_len": {"median": 1024, "sigma": 0.9, "min": 256,
                       "max": 8192,
                       "snap_to": [256, 512, 1024, 2048, 3072, 4096, 6144,
                                   8192, 8193]},
        "output_len": {"median": 768, "sigma": 0.7, "min": 128,
                       "max": 4096}}
    eng = cell.workload["engine"]
    assert eng["max_batch"] == cell.traffic["clients"] == 96
    assert eng["max_seq_len"] == 12288 == 8192 + 4096
    assert eng["queue_size"] == 256
    assert eng["kv_page_size"] in (512, 1024, 2048)
    assert (eng["kv_pool_pages"] - 1) * eng["kv_page_size"] == 393216
    assert eng["prefill_chunk"] in (1024, 2048)
    assert eng["prefill_chunk"] % eng["kv_page_size"] == 0
    agree = cell.workload["agreement"]
    assert agree["prompt_lens"] == [1024, 4096, 8193]
    assert agree["new_tokens"] == 64
    assert cell.chips == 1


def test_the_sample_reads_a_carried_state_and_the_mix_never_draws_it(cell):
    """``snap_to`` holds 8,193, one past ``max``: no draw snaps to it (a
    length is clipped to 8,192 first), and the sample gains a prompt whose
    final chunk holds ONE real token on a page of its own — its first
    compared position reads the matrices carried across a chunk and a page
    boundary, and its entries are taken at ``last_pos`` 0."""
    from benchmark.lib import traffic
    from dtf_tpu.serve.engine import chunk_plan
    mix, eng = cell.traffic, cell.workload["engine"]
    asked = dict(mix, prompt_len=dict(mix["prompt_len"],
                                      snap_to=mix["prompt_len"]["snap_to"][:-1]))
    for phase, length_s in enumerate((mix["ramp_s"], 51.0, 15.0)):
        ours, _ = traffic.phase_draw(mix, phase, length_s)
        theirs, _ = traffic.phase_draw(asked, phase, length_s)
        assert (ours == theirs).all() and ours[:, 0].max() <= 8192
    plan = chunk_plan(8193, eng["prefill_chunk"], eng["kv_page_size"])
    assert plan[-1] == (8192, eng["kv_page_size"])
    assert 8193 + cell.workload["agreement"]["new_tokens"] \
        <= eng["max_seq_len"]
    toy = cell.family.TOY["serve"]
    assert max(toy["agreement"]["prompt_lens"]) \
        == toy["traffic"]["prompt_len"]["max"] + 1 \
        == toy["engine"]["prefill_chunk"] * 3 + 1


def test_serve_tok_s_is_judged_in_the_new_cell():
    """The cell's own claims on BENCHMARK.json (``structure.py``): nothing
    about its place in a list, or about what else lists an entry."""
    _, mine = structure.check_cell(BENCH, ROOT, CELL, NEEDS[CELL])
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
        assert "'decode_rows_per_step.longgen': None" not in read
        assert "'expert_load_max_over_mean.num_experts': None" not in read


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
                        jnp.zeros((1, 16), jnp.int32))["params"]
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
            # float32 sums in another order (a prompt alone, its queries
            # in blocks, against the padded batch): 4e-6 of the spread
            assert ours[key] == pytest.approx(theirs[key], rel=1e-4,
                                              abs=2e-5), key
        else:
            assert ours[key] == theirs[key], key


@pytest.mark.parametrize("control", ["w8", "router_bf16", "state_bf16",
                                     "latent_bf16", "zero_bias",
                                     "other_share"])
def test_the_controls_read_worse_than_the_reference_itself(toy_sample,
                                                           control):
    """Each control of the reference changes one thing and reads a
    ``logit_rms`` above 0: 8-bit weights; the router's input alone in
    bfloat16 (flipped top-k choices); the matrices alone rounded to
    bfloat16 after every token (what the pool holds); the latent rows
    alone in bfloat16; the router's bias left out, and ANOTHER block of
    the experts taken for the held one (parts of the mathematics: they
    read far above any rounding)."""
    import jax.numpy as jnp
    reference, params, prompts, served = toy_sample

    def bf16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    kw = {"w8": {"weights": reference.rounded_to(8)},
          "router_bf16": {"router_input": bf16},
          "state_bf16": {"state": bf16}, "latent_bf16": {"latent": bf16},
          "zero_bias": {"zero_bias": True},
          "other_share": {"held": (8, 8)}}[control]
    said = reference.served_tokens_agree(
        params, prompts, served, 0.01,
        reference.rows_that_chose(params, prompts, served, **kw), 1.0)
    low, high = {"w8": (1e-3, 0.5), "router_bf16": (0.0, 0.1),
                 "state_bf16": (1e-7, 0.1), "latent_bf16": (1e-7, 0.05),
                 "zero_bias": (0.01, 2.0),
                 "other_share": (0.01, 2.0)}[control]
    assert low <= said["logit_rms"] < high, said["logit_rms"]


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
                "decode_steps": decode_steps, "histograms": {}})


def test_flops_and_bytes_count_the_layers_by_kind(cell):
    cfg, fam = cell.config, cell.family
    linear = 5 * 2560 * 4096 + 2560 * 32 + 4096 * 2560
    latent = (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
              + 4096 * 2560)
    routed = 2560 * 512 + 9 * 3 * 2560 * 768
    assert fam.active_matmul_params(cfg) == (
        7 * linear + latent + 2 * 3 * 2560 * 6144 + 6 * routed
        + 2560 * 39296)
    assert fam.row_lanes(cfg) == 640
    assert fam.matrix_bytes_per_page(cfg) == 32 * 128 * 128 * 2
    flops = fam.train_flops_per_sample(cfg, {"seq_len": 1024})
    assert flops == pytest.approx(3 * (
        2 * fam.active_matmul_params(cfg) + 7 * 6 * 32 * 128 * 128
        + 2 * 32 * (192 + 128) * 1025 / 2) * 1024)


def test_expert_cost_counts_the_pairs_computed_here(cell):
    cost = cell.family.SPAN_COSTS["expert_matmuls"]
    flops, nbytes = cost(cell.config, {"assignments": 190 * 6,
                                       "experts_touched": 100 * 6})
    assert flops == 2.0 * 190 * 6 * 3 * 2560 * 768
    assert nbytes == 2.0 * 100 * 6 * 3 * 2560 * 768
    assert cost(cell.config, {}) is None


def test_latent_cost_counts_the_one_latent_layer(cell):
    cost = cell.family.SPAN_COSTS["latent_attention_reads"]
    flops, nbytes = cost(cell.config, {"latent_tokens_read": 230000})
    assert nbytes == 230000 * 640 * 2
    assert flops == 2.0 * 32 * (2 * 512 + 64) * 230000
    # a chunk of 1,024 queries over 3,072 cached + its own keys at half
    flops, nbytes = cost(cell.config, {"latent_tokens_read": 4096,
                                       "tokens": 1024, "start": 3072})
    assert nbytes == 4096 * 640 * 2
    assert flops == 2.0 * 32 * (2 * 512 + 64) * (4096 - 1023 / 2) * 1024
    assert cost(cell.config, {}) is None


def test_state_cost_counts_the_matrices_as_stored(cell):
    cost = cell.family.SPAN_COSTS["linear_state_steps"]
    flops, nbytes = cost(cell.config, {"state_rows_advanced": 90 * 7})
    assert nbytes == 2.0 * 1048576 * 90 * 7
    assert flops == 2.0 * 3 * 32 * 128 * 128 * 90 * 7
    assert cost(cell.config, {}) is None


def test_state_roofline_reads_the_decode_spans_alone(cell):
    """90 live rows a step in 7 layers, two steps: 2.64e9 stored bytes
    against the kernel's time; a chunk's span adds nothing (its state goes
    through the blocked form, not the kernel)."""
    records = [_span("serve_decode", state_rows_advanced=630),
               _span("serve_decode", state_rows_advanced=630),
               _span("serve_prefill_chunk", state_rows_advanced=7,
                     tokens=1024, start=0),
               _span("serve_decode", ts=2.0, state_rows_advanced=630)]
    run = _run(cell, records, {"linear_state_decode.3": 0.004,
                               "linear_state_decode.9": 0.004})
    least = 2 * peaks.least_seconds(
        "TPU v5 lite", 2.0 * 3 * 32 * 128 * 128 * 630, 2.0 * 1048576 * 630)
    got = read_metric(_spec("linear_state_roofline"), run)
    assert got == pytest.approx(100 * least / 0.008)
    assert 0 < got < 100
    assert read_metric(_spec("linear_state_kernel_ms"), run) \
        == pytest.approx(4.0)
    assert read_metric(_spec("linear_state_roofline"),
                       _run(cell, [_span("serve_decode")],
                            {"linear_state_decode": 0.004})) is None


def test_rows_per_step_and_load_read_the_spans(cell):
    records = [_span("serve_decode", state_rows_advanced=7 * 88,
                     assignments=6 * 190, expert_load_max=6 * 5),
               _span("serve_decode", state_rows_advanced=7 * 92,
                     assignments=6 * 194, expert_load_max=6 * 7)]
    run = _run(cell, records, {})
    assert read_metric(_spec("decode_rows_per_step.longgen"), run) \
        == pytest.approx(90.0)
    # the busiest held expert over the mean of the 128 held
    assert read_metric(_spec("expert_load_max_over_mean.num_experts"), run) \
        == pytest.approx(128 * 72 / (6 * 384))


@pytest.mark.parametrize("metric,kernel", [
    ("moe_experts_ms", "gmm.12"),
    ("latent_attention_kernel_ms", "paged_flash_decode.4"),
    ("linear_state_kernel_ms", "linear_state_decode.2")])
def test_kernel_time_is_per_decode_step(cell, metric, kernel):
    run = _run(cell, [], {kernel: 0.030, "gmm_like_fusion": 1.0})
    assert read_metric(_spec(metric), run) == pytest.approx(15.0)


# ----------------- the fourth serving configuration's compiled bodies ----
def state_body_hashes() -> dict:
    """sha256 of the lowered text (CPU: the gather path) of the first
    chunk, a continuation chunk and the decode step at the toy size of the
    short-convolution family — the serving configuration ``test_lfm2.py``'s
    records (``serve_bodies_lowered*.json``: the other three) do not
    hold."""
    import jax
    import jax.numpy as jnp
    from dtf_tpu.models import build_model
    from dtf_tpu.serve.decode import Decoder, _seed_row_keys, position_key
    cfg = load_json(os.path.join(ROOT, "benchmark", "configs",
                                 "lfm2-8b-a1b.json"))
    kw = dict(cfg["build_model"]["kwargs"],
              **families.load("lfm2", ROOT).TOY["serve"]["model_kwargs"])
    model, _ = build_model("routed_decoder", num_classes=512,
                           dtype=jnp.bfloat16, **kw)
    params = jax.jit(model.clone(use_pallas=False).init)(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]
    page, chunk, slots = 8, 32, 4
    dec = Decoder(model, params, num_slots=slots, max_seq_len=128,
                  kv_page_size=page, kv_pool_pages=65)
    cache = jax.eval_shape(dec.fresh_cache)
    m = dec.pages_per_slot
    out = {}

    def sha(lowered):
        return hashlib.sha256(lowered.as_text().encode()).hexdigest()
    for tag, start in (("chunk_first", 0), ("chunk_cont", chunk)):
        out[f"lfm2.{tag}"] = sha(dec._chunk.lower(
            dec.params, cache, jnp.zeros((1, chunk), jnp.int32),
            jnp.zeros((1, m), jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray(0.0, jnp.float32), position_key(0, 0),
            jnp.asarray(start, jnp.int32), (start + chunk) // page,
            start == 0))
    zeros = jnp.zeros((slots,), jnp.int32)
    out["lfm2.decode"] = sha(dec._decode.lower(
        dec.params, cache, jnp.zeros((slots, 1), jnp.int32), zeros,
        jnp.zeros((slots, m), jnp.int32), jnp.zeros((slots,), jnp.float32),
        _seed_row_keys(jnp.zeros((slots,), jnp.uint32), zeros)))
    return out


def test_the_state_carrying_bodies_the_benchmark_had_lower_as_before():
    """The routed decoder gained a third mixer kind, mixers beside the
    latent cache, a group limit and a held share; the short-convolution
    configuration's build call is not edited and its compiled bodies
    lower, on the CPU, to the text the parent commit's lowered to
    (recorded from it in ``data/serve_bodies_lowered_pr37.json``).  The
    other three serving configurations are held to
    ``serve_bodies_lowered.json`` and ``serve_bodies_lowered_pr34.json`` by
    ``test_lfm2.py``, which this PR leaves as it is."""
    assert state_body_hashes() == load_json(
        os.path.join(DATA, "serve_bodies_lowered_pr37.json"))
    for name in ("serve_bodies_lowered.json", "serve_bodies_lowered_pr34.json"):
        assert os.path.exists(os.path.join(DATA, name))


if __name__ == "__main__":      # record: run the copy in a parent checkout
    print(json.dumps(state_body_hashes(), indent=1, sort_keys=True))
