"""Checks of what the family ``qwen3_next`` and its cell add to the
benchmark: the configuration against the published one, its bytes against
the program's plan, the traffic, the cell by name through the serve driver
at the toy size, the cost functions and the readers of the new per-layer
metrics, and the chip's kept readings against the committed limits.  The
reference's own checks (its controls, the injected faults, the shares) are
``test_qwen3_next_reference.py``'s.  CPU only; under BENCHMARK.json's
``paths``.

The toy's limit (``families/qwen3_next.py`` ``TOY``, 0.017): the program,
bf16 matmuls and a bf16 state pool on an f32 stream, reads a ``logit_rms``
of 0.0079-0.0090 (``benchmark.control --toy``, seeds 11-13), the reference
with every matrix at 8 bits 0.0317-0.0324 (``tools/qwen3_next_faults.py
--toy --controls w8``, seeds 11-13), the four injected faults 0.26-0.59
(seed 11); CPU, PR 57, at one period L L L A of width 64 and the sample
of four prompts of 24 new tokens."""

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

from benchmark.lib import peaks, traffic  # noqa: E402
from benchmark.lib.runtime import (load_benchmark, load_cell,  # noqa: E402
                                   load_json)
from benchmark.lib.xplane import Reduction  # noqa: E402
from benchmark.readers import ReaderInput, read_metric  # noqa: E402

import structure  # noqa: E402  (beside this file)

CELL = "qwen3next-serve-hybriddoc"
BENCH = load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json
# as the catalog of architectures holds it
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
# the per-layer metrics the cell needs, each under the entry's own name:
# ONE measurement lists many cells, and a suffix says how an entry differs
# (another cost, another configuration key), never which cell reads it
NEEDS = {CELL: [
    "serve_mfu", "decode_step_ms", "prefill_chunk_ms", "device_idle_pct",
    "host_launch_ms", "idle_host_pct", "decode_rows_per_step.hybriddoc",
    "moe_experts_ms", "moe_experts_roofline",
    "expert_load_max_over_mean.num_experts",
    "experts_touched_share.hybriddoc", "linear_state_kernel_ms",
    "linear_state_roofline", "paged_decode_kernel_ms",
    "paged_decode_roofline.by_span", "flash_attention_roofline.hybriddoc"]}
LINEAR = 2048 * (2 * 2048 + 2 * 4096) + 2048 * 64 + 4096 * 2048
FULL = 2048 * (2 * 16 + 2 * 2) * 256 + 4096 * 2048
EXPERT, ROUTER = 3 * 2048 * 512, 2048 * 512
HEAD = 2048 * 37984


@pytest.fixture(scope="module")
def cell():
    return load_cell(BENCH, CELL)


def test_the_published_keys_are_the_catalogs_row():
    """Where the catalog is at hand, ``PUBLISHED`` above is its row."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert row["config"] == PUBLISHED
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert entry["source"] == row["source_url"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_key(cell, key):
    """Every published key unchanged, but the depth, the experts HELD and
    the vocabulary rows held, which ``reduced`` names and ``published``
    keeps: no width, head count, ``num_experts_per_tok``, filter length or
    rotary part is touched."""
    cfg = cell.config
    assert cfg["reduced"] == REDUCED
    if key in REDUCED:
        assert cfg["published"][key] == PUBLISHED[key]
        assert cfg[key] < PUBLISHED[key]
    else:
        assert cfg[key] == PUBLISHED[key]


def test_the_cut_is_two_periods_of_one_chip_of_four(cell):
    """Layers 0-7, L L L A L L L A: 6 linear to 2 full, the published
    3 : 1; a quarter of the experts (EP 4, rank 0) and of the vocabulary."""
    cfg, fam = cell.config, cell.family
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (8, 512 // 4, 151936 // 4)
    assert cfg["layer_types"] == [
        "full_attention" if (l + 1) % cfg["full_attention_interval"] == 0
        else "linear_attention" for l in range(48)]
    kinds = fam.layer_types(cfg)
    assert kinds == (["linear_attention"] * 3 + ["full_attention"]) * 2
    entry = next(c for c in BENCH["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmark/configs/qwen3-next-80b-a3b.json"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert set(cfg["not_built"]) >= {"multi_token_prediction"}
    assert set(cfg["assumed"]) >= {"column_order", "decay", "state",
                                   "routing", "qk_gain", "share"}
    for words in ("rank 0", "EP 4", "6 hosts", "layers 0-7"):
        assert words in cfg["deployment"]


def test_the_build_call_is_the_configuration(cell):
    """What ``build_model`` is given is what the published keys say: no
    width comes from anywhere else, and no model's name is tested."""
    cfg, kw = cell.config, cell.config["build_model"]["kwargs"]
    assert cfg["build_model"]["name"] == "routed_decoder"
    assert kw["num_layers"] == cfg["num_hidden_layers"]
    assert kw["d_model"] == cfg["hidden_size"] == 2048
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]) == (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"]) == (16, 2, 256)
    assert kw["rotary_dim"] == cfg["partial_rotary_factor"] * cfg["head_dim"]
    assert kw["rope_theta"] == cfg["rope_theta"]
    assert kw["qk_norm"] and kw["attention_output_gate"]
    assert kw["qk_norm_gain"] == 2.0 and "qk_gain" in cfg["assumed"]
    assert kw["norm_unit_offset"] and kw["shared_expert_gate"]
    period = cfg["full_attention_interval"]
    assert kw["layer_mixer"] == ["linear_delta"] * (period - 1) \
        + ["attention"]
    assert (kw["layer_window"], kw["layer_rope"]) == ([False], [True])
    assert not cfg["use_sliding_window"]
    assert (kw["linear_heads"], kw["linear_key_heads"]) == (
        cfg["linear_num_value_heads"], cfg["linear_num_key_heads"]) \
        == (32, 16)
    assert kw["linear_head_dim"] == cfg["linear_key_head_dim"] \
        == cfg["linear_value_head_dim"] == 128
    assert kw["linear_conv_taps"] == cfg["linear_conv_kernel_dim"]
    assert (kw["linear_decay"], kw["linear_gate"]) == ("head", "silu")
    assert kw["num_dense_layers"] == len(cfg["mlp_only_layers"]) == 0
    assert cfg["decoder_sparse_step"] == 1
    assert kw["num_experts"] == cfg["published"]["num_experts"] == 512
    assert kw["experts_held"] == [0, cfg["num_experts"]]
    assert kw["experts_per_token"] == cfg["num_experts_per_tok"] == 10
    assert kw["expert_width"] == cfg["moe_intermediate_size"] == 512
    assert kw["shared_expert_width"] \
        == cfg["shared_expert_intermediate_size"] == 512
    assert kw["routing"] == "softmax_topk" and cfg["norm_topk_prob"]
    assert kw["rms_eps"] == cfg["rms_norm_eps"]
    assert kw["activation"] == cfg["hidden_act"]
    assert kw["max_seq_len"] == cfg["max_position_embeddings"]
    assert "tie_head" not in kw and not cfg["tie_word_embeddings"]
    assert cfg["num_classes"] == cfg["vocab_size"]
    for key in ("assumed", "not_built", "deployment", "stored", "published"):
        assert cfg[key]


def test_the_program_counts_the_bytes_the_file_states(cell):
    """``serving_memory_plan`` over the configuration's own build call
    (shapes only): the matmul parameters and the vectors in bf16, ``a_log``
    and ``dt_bias`` in float32; 4,096 B a token of K and V in two layers;
    a state entry of 6,586,368 B a page in six; and with the cell's pool
    at least 12.5e9 B resident."""
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
    assert (LINEAR, FULL, EXPERT) == (33_685_504, 27_262_976, 3_145_728)
    assert fam.mixer_params(cfg) == (LINEAR, FULL)
    layer = ROUTER + EXPERT + 2048               # router, shared, its gate
    assert layer == 4_196_352
    matmul = 6 * LINEAR + 2 * FULL + 8 * (128 * EXPERT + layer) + 2 * HEAD
    # filters, out_norm, q/k norms, two norms a layer and the final one
    vectors = 6 * (8192 * 4 + 128) + 2 * 2 * 256 + 8 * 2 * 2048 + 2048
    assert 6 * (LINEAR + 8192 * 4 + 128 + 64) == 6 * 33_718_464
    assert 2 * (FULL + 512) == 2 * 27_263_488
    assert matmul + vectors + 6 * 64 == 3_667_251_328
    assert plan["param_bytes"] == 2 * (matmul + vectors) + 4 * 6 * 64 \
        == cfg["stored"]["param_bytes"] == 7_334_503_424
    stored = cfg["stored"]
    assert plan["per_token_kv_bytes"] == stored["kv_bytes_per_token"] \
        == fam.kv_bytes_per_token(cfg) == 4096
    assert (plan["kv_heads"], plan["head_dim"]) == (2, 256)
    assert plan["state_bytes_per_page"] == stored["state_bytes_per_page"] \
        == fam.state_bytes_per_page(cfg) == 6 * (1_048_576 + 49_152)
    page, pages = engine["kv_page_size"], engine["kv_pool_pages"]
    shapes = trace_paged_init(model, page, pages)[0]
    real = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(shapes))
    assert real == pages * (page * 4096 + 6_586_368)
    assert 12.5e9 <= plan["param_bytes"] + real <= 14.5e9
    assert plan["pages_per_slot"] == -(-engine["max_seq_len"] // page) == 66


def test_the_traffic_is_the_mix_the_cell_was_asked_for(cell):
    mix, engine = cell.traffic, cell.workload["engine"]
    assert (mix["arrivals"], mix["clients"]) == ("closed", 32)
    assert mix["clients"] == engine["max_batch"]
    assert 20 <= mix["ramp_s"] <= 40 and mix["drain_s"] == 10
    assert mix["prepare_per_s"] == mix["prepare_block_per_s"]
    assert mix["prompt_len"] == {
        "median": 12288, "sigma": 0.8, "min": 2048, "max": 65536,
        "snap_to": [2048, 4096, 8192, 12288, 16384, 16385, 24576, 32768,
                    49152, 65536]}
    assert mix["output_len"] == {"median": 384, "sigma": 0.6, "min": 128,
                                 "max": 1536}
    assert engine["max_seq_len"] == 65536 + 1536 == 67072
    assert engine["prefill_chunk"] % engine["kv_page_size"] == 0
    bases = [load_json(os.path.join(ROOT, "benchmark", "traffic", f))
             .get("base_seed") for f in os.listdir(
                 os.path.join(ROOT, "benchmark", "traffic"))]
    assert bases.count(mix["base_seed"]) == 1
    drawn = traffic.request_sizes(
        mix, 200_000, np.random.default_rng(mix["base_seed"]))
    assert set(np.unique(drawn[:, 0])) <= set(mix["prompt_len"]["snap_to"])
    assert 15_500 < drawn[:, 0].mean() < 17_500
    assert 430 < drawn[:, 1].mean() < 480
    # a row reserves prompt + budget at admission: 32 rows of the run's own
    # draw fit the pool in the mean several times over, so the slots bind
    sizes = np.concatenate([traffic.phase_draw(mix, k, s)[0]
                            for k, s in enumerate((mix["ramp_s"], 51, 15))])
    pages = -(-(sizes[:, 0] + sizes[:, 1]) // engine["kv_page_size"])
    assert 32 * pages.mean() < engine["kv_pool_pages"] - 1
    assert pages.max() <= -(-engine["max_seq_len"]
                            // engine["kv_page_size"])


def test_the_sample_reads_a_carried_state_and_a_page_of_one_token(cell):
    """2,048: a first chunk alone; 8,192: several carries of the state and
    of the filter; 16,385: a page entered by ONE token, the last chunk one
    real token; 64 new tokens each.  All three are lengths the mix holds:
    the serve driver samples the agreement's prompts from ``snap_to``."""
    agree, engine = cell.workload["agreement"], cell.workload["engine"]
    assert agree["prompt_lens"] == [2048, 8192, 16385]
    assert agree["new_tokens"] == 64
    assert set(agree["prompt_lens"]) <= set(
        cell.traffic["prompt_len"]["snap_to"])
    assert 16385 % engine["kv_page_size"] == 1
    assert 16385 % engine["prefill_chunk"] == 1
    assert 2048 <= engine["prefill_chunk"] < 8192
    toy = cell.family.TOY["serve"]
    lens = toy["agreement"]["prompt_lens"]
    assert set(lens) <= set(toy["traffic"]["prompt_len"]["snap_to"])
    assert lens[-1] % toy["engine"]["prefill_chunk"] == 1
    assert lens[-1] % toy["engine"]["kv_page_size"] == 1


def _spec(name):
    return load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  name + ".json"))


def test_serve_tok_s_is_judged_in_the_new_cell():
    """The cell's own claims on BENCHMARK.json (``structure.py``): nothing
    about its place in a list, or about what else lists an entry."""
    cell, mine = structure.check_cell(
        BENCH, ROOT, CELL, NEEDS[CELL], config="qwen3-next-80b-a3b",
        traffic="hybriddoc-closed-32")
    assert cell.family.SPAN_COSTS["model_flops"] is cell.family.model_flops
    assert all(m["moves"] == "serve_tok_s" for m in mine.values())


def test_the_cell_rehearses_through_the_serve_driver():
    """Loaded by name, at the family's toy size, on the CPU, traced: the
    served tokens are held to the plain reference under the toy's limit and
    the spans' counts walk into the readers."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.rehearse", "--workload", CELL,
         "--trace", "1", "--seconds", "2"],
        cwd=ROOT, capture_output=True, text=True, timeout=115,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    said = json.loads(last[last.index("{"):])
    assert said["line"]["correct"] is True and said["reasons"] == []
    assert said["contract_refuses_it_for"]      # never a result
    read = done.stdout[done.stdout.index("readers without"):]
    for name in ("decode_rows_per_step.hybriddoc",
                 "expert_load_max_over_mean.num_experts",
                 "experts_touched_share.hybriddoc", "decode_step_ms",
                 "prefill_chunk_ms", "host_launch_ms"):
        assert f"'{name}': None" not in read
        assert f"'{name}'" in read


# ------------------------------------------------- costs and readers ----
def _span(name, ts=0.5, **attrs):
    return dict(kind="span", name=name, ts=ts, dur_s=0.01, **attrs)


def _run(cell, records, kernels, decode_steps=2):
    reduction = Reduction(window_s=1.0, busy_s=0.5, self_s=dict(kernels),
                          calls={k: 1 for k in kernels}, idle_gaps=[])
    return ReaderInput(
        cell=cell, device_kind="TPU v5 lite", reduction=reduction,
        driver={"records": records, "window_wall": (0.0, 1.0),
                "decode_steps": decode_steps, "histograms": {},
                "window_s": 1.0,
                "engine": {"max_batch": 32, "page_size": 1024}})


def test_flops_and_bytes_count_the_layers_by_kind(cell):
    cfg, fam = cell.config, cell.family
    routed = ROUTER + EXPERT + 2048 + 10 * EXPERT
    active = 6 * LINEAR + 2 * FULL + 8 * routed + HEAD
    assert fam.active_matmul_params(cfg) == active
    # ISSUE 57's 431e6 a token HERE: 2.5 of a token's 10 experts are held
    assert 430e6 < active - 8 * 7.5 * EXPERT < 432e6
    assert fam.matrix_bytes_per_page(cfg) == 32 * 128 * 128 * 2
    flops = fam.train_flops_per_sample(cfg, {"seq_len": 1024})
    assert flops == pytest.approx(3 * (
        2 * active + 6 * 6 * 32 * 128 * 128
        + 2 * 4 * 16 * 256 * 1025 / 2) * 1024)


def test_model_flops_against_a_hand_count(cell):
    """A chunk of 2,048 real tokens at position 8,192, a last chunk of one
    real token, and a decode step of 20 rows of 32, counted by hand."""
    cfg, flops = cell.config, cell.family.model_flops
    body = 6 * LINEAR + 2 * FULL + 8 * (ROUTER + EXPERT + 2048)
    state, per_key = 6 * 6 * 32 * 128 * 128, 4 * 16 * 256
    got = flops(cfg, {"tokens": 2048, "real_tokens": 2048, "start": 8192,
                      "assignments": 40_000})
    keys = 2048 * 8192 + 2048 * 2049 // 2
    assert got == pytest.approx((2.0 * body + state) * 2048 + 2.0 * HEAD
                                + 2.0 * EXPERT * 40_000 + 2 * per_key * keys)
    got = flops(cfg, {"tokens": 1024, "real_tokens": 1, "start": 16384,
                      "assignments": 20_480})
    assert got == pytest.approx(2.0 * body + state + 2.0 * HEAD
                                + 2.0 * EXPERT * 20 + 2 * per_key * 16385)
    step = {"rows": 20, "slots": 32, "assignments": 640,
            "kv_tokens_read_global": 2 * (20 * 17_000 + 12)}
    assert flops(cfg, step) == pytest.approx(
        (2.0 * (body + HEAD) + state) * 20 + 2.0 * EXPERT * 400
        + per_key * 2 * 20 * 17_000)
    assert flops(cfg, {"slots": 32}) is None
    assert flops(cfg, {"rows": 0, "slots": 32}) is None


def test_the_costs_count_the_least_work_as_stored(cell):
    cfg, costs = cell.config, cell.family.SPAN_COSTS
    assert costs["expert_matmuls"](
        cfg, {"assignments": 640, "experts_touched": 480}) \
        == (2.0 * 640 * EXPERT, 2.0 * 480 * EXPERT)
    assert 2 * EXPERT == 6_291_456              # a touched expert's bytes
    assert costs["expert_matmuls"](cfg, {}) is None
    # the state: 2 x 1,048,576 stored bytes a (live row, linear layer); one
    # decay a head and one key row a pair of value heads: nothing more
    flops, nbytes = costs["linear_state_steps"](
        cfg, {"state_rows_advanced": 30 * 6})
    assert nbytes == 2.0 * 1_048_576 * 30 * 6
    assert flops == 2.0 * 3 * 32 * 128 * 128 * 30 * 6
    assert costs["linear_state_steps"](cfg, {}) is None
    # the paged kernel: the live rows' K and V once, 2 KV heads of 256
    flops, nbytes = costs["paged_attention_reads"](
        cfg, {"kv_tokens_read_global": 2 * 500_000})
    assert nbytes == 2 * 500_000 * 2 * 2 * 256 * 2.0
    assert flops == 4.0 * 16 * 256 * 2 * 500_000
    flops, nbytes = costs["paged_attention_reads"](
        cfg, {"kv_tokens_read_global": 2 * 10_240, "tokens": 2048,
              "start": 8192})
    assert nbytes == 2 * 10_240 * 2048.0
    assert flops == 4.0 * 16 * 256 * (2 * 10_240 - 2047 / 2 * 2) * 2048
    assert costs["paged_attention_reads"](
        cfg, {"kv_tokens_read_global": 4096, "tokens": 2048, "start": 0}) \
        is None
    assert costs["paged_attention_reads"](cfg, {}) is None
    # the flash forward: a FIRST chunk's real queries alone
    flops, nbytes = costs["flash_first_chunks"](
        cfg, {"tokens": 2048, "real_tokens": 2000, "start": 0})
    assert flops == 2 * 4.0 * 16 * 256 * 2000 * 2001 / 2
    assert nbytes == 2.0 * 2 * 2000 * (2 * 16 + 2 * 2) * 256
    assert costs["flash_first_chunks"](
        cfg, {"tokens": 2048, "start": 2048}) is None
    assert costs["flash_first_chunks"](cfg, {"rows": 3}) is None


def test_the_readers_read_the_spans(cell):
    step = dict(state_rows_advanced=6 * 30, assignments=600,
                experts_touched=450, expert_load_max=8 * 4,
                kv_tokens_read_global=2 * 500_000)
    first = dict(tokens=2048, real_tokens=2048, start=0, assignments=40_000,
                 experts_touched=1024, expert_load_max=8 * 90,
                 kv_tokens_read_global=2 * 2048, state_rows_advanced=6)
    later = dict(first, start=8192, kv_tokens_read_global=2 * 10_240)
    records = [_span("serve_decode", **step),
               _span("serve_prefill_chunk", **first),
               _span("serve_prefill_chunk", **later),
               _span("serve_decode", ts=2.0, **step)]
    run = _run(cell, records, {
        "linear_state_decode_mxu1x3.3": 0.0006, "gmm.2": 0.02,
        "paged_flash_decode.1": 0.01, "flash_fwd.7": 0.002,
        "gmm_like_fusion": 1.0})
    cfg, costs = cell.config, cell.family.SPAN_COSTS
    for metric, cost, total, spans in (
            ("moe_experts_roofline", "expert_matmuls", 0.02, records[:3]),
            ("linear_state_roofline", "linear_state_steps", 0.0006,
             records[:1]),
            ("paged_decode_roofline.by_span", "paged_attention_reads", 0.01,
             [records[0], records[2]]),
            ("flash_attention_roofline.hybriddoc", "flash_first_chunks",
             0.002, records[1:2])):
        least = sum(peaks.least_seconds("TPU v5 lite", *costs[cost](cfg, r))
                    for r in spans)
        got = read_metric(_spec(metric), run)
        assert got == pytest.approx(100 * least / total), metric
        assert 0 < got < 100, metric
    for metric, ms in (("moe_experts_ms", 10.0),
                       ("linear_state_kernel_ms", 0.3),
                       ("paged_decode_kernel_ms", 5.0)):
        assert read_metric(_spec(metric), run) == pytest.approx(ms)
    assert read_metric(_spec("decode_rows_per_step.hybriddoc"), run) \
        == pytest.approx(30.0)
    assert read_metric(_spec("experts_touched_share.hybriddoc"), run) \
        == pytest.approx(100 * 450 / (128 * 8))
    assert read_metric(_spec("expert_load_max_over_mean.num_experts"), run) \
        == pytest.approx(128 * 32 / 600)
    # a program without the counters: nothing to read, and no raise
    bare = _run(cell, [_span("serve_decode"),
                       _span("serve_prefill_chunk", tokens=2048, start=0)],
                {"gmm.2": 0.02})
    for name in ("moe_experts_roofline", "linear_state_roofline",
                 "paged_decode_roofline.by_span",
                 "decode_rows_per_step.hybriddoc",
                 "experts_touched_share.hybriddoc",
                 "expert_load_max_over_mean.num_experts",
                 "linear_state_kernel_ms"):
        assert read_metric(_spec(name), bare) is None


READINGS = os.path.join(ROOT, "docs", "pr57_control_readings.jsonl")


@pytest.mark.parametrize("who,refused,least", [
    ("program", False, 8), ("w8", True, 8), ("router_bf16", False, 2),
    ("state_bf16", False, 2), ("zero_state_carry", True, 2),
    ("zero_filter_carry", True, 2), ("ungated", True, 2),
    ("rope_all", True, 2)])
def test_the_committed_limits_part_the_kept_readings(cell, who, refused,
                                                     least):
    """The chip's readings at the published widths (``benchmark.control``
    and ``tools/qwen3_next_faults.py``, kept line by line in ``docs/``),
    each judged HERE by the limits the workload file commits — whatever
    limit the line itself was printed under: every sound reading passes,
    every fault and the 8-bit tree is refused by ``logit_rms``, on at
    least ``least`` seeds; the limit is the geometric mean of the sound
    maximum and the 8-bit minimum."""
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
            assert ln["gap"] < gap_allowed and ln["tokens_compared"] == 192
    sound = max(ln["logit_rms"] for ln in lines if ln["who"] == "program")
    eight = min(ln["logit_rms"] for ln in lines if ln["who"] == "w8")
    assert limit == pytest.approx((sound * eight) ** 0.5, rel=0.1)
    assert 1.2 < limit / sound and 1.2 < eight / limit
