"""Checks of what the family ``evabyte`` and its cell add to the benchmark:
the configuration against the published one, the cell by name through the
serve driver at the toy size, the reference against the whole-sequence
forward of the program and against its own injected faults, the cost
functions and readers of the new per-layer metrics.  CPU only; under
BENCHMARK.json's ``paths``.

The toy's limit (``families/evabyte.py`` ``TOY``, 0.006): the program, bf16
matmuls and a bf16 cache on an f32 stream, reads a ``logit_rms`` of 0.0026
against the reference at the toy's three prompts of 40 new tokens (the
rehearsal's seed); the reference with every matrix at 8 bits reads 0.0121,
with mean pooling 0.0223, with the newest closed window's summaries missing
0.637 and with none 1.105 (a float32 tree, two prompts of 4 new tokens:
``test_the_controls_read_worse_than_the_reference_itself``), CPU, PR 41."""

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

CELL = "evabyte-serve-bytedocs"
BENCH = load_benchmark()
# the per-layer metrics the cell needs, each under the entry's own name (a
# suffix says how an entry differs, never which cell reads it)
NEEDS = {
    CELL: [
        "decode_step_ms", "device_idle_pct", "prefill_chunk_ms",
        "paged_decode_kernel_ms", "paged_decode_roofline.by_span",
        "host_launch_ms", "idle_host_pct", "idle_wait_pct",
        "summary_rows_share.bytedocs", "window_compact_ms.bytedocs",
        "window_compact_roofline.bytedocs", "host_compact_ms.bytedocs",
        "serve_mfu"],
}
# https://huggingface.co/EvaByte/EvaByte/blob/main/config.json as the
# catalog of architectures holds it
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}


@pytest.fixture(scope="module")
def cell():
    return load_cell(BENCH, CELL)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_key(cell, key):
    """Every published key unchanged but the depth, which ``reduced`` names
    and ``published`` keeps."""
    cfg = cell.config
    if key in cfg["reduced"]:
        assert cfg["reduced"] == ["num_hidden_layers"]
        assert cfg["published"][key] == PUBLISHED[key]
        assert cfg[key] == 8
    else:
        assert cfg[key] == PUBLISHED[key]


def test_the_build_call_is_the_configuration(cell):
    """What ``build_model`` is given is what the published keys say: no
    width comes from anywhere else."""
    cfg, kw = cell.config, cell.config["build_model"]["kwargs"]
    assert cfg["build_model"]["name"] == "routed_decoder"
    assert kw["num_layers"] == kw["num_dense_layers"] \
        == cfg["num_hidden_layers"]
    assert kw["d_model"] == cfg["hidden_size"]
    assert kw["num_heads"] == cfg["num_attention_heads"]
    assert kw["num_kv_heads"] == cfg["num_key_value_heads"]
    assert kw["head_dim"] * kw["num_heads"] == cfg["hidden_size"]
    assert kw["dense_width"] == cfg["intermediate_size"]
    assert kw["activation"] == cfg["hidden_act"]
    assert kw["summary_window"] == cfg["window_size"]
    assert kw["summary_chunk"] == cfg["chunk_size"]
    assert kw["norm_unit_offset"] is cfg["norm_add_unit_offset"]
    assert kw["rope_theta"] == cfg["rope_theta"]
    assert kw["rms_eps"] == cfg["rms_norm_eps"]
    assert kw["max_seq_len"] == cfg["max_position_embeddings"]
    assert kw["layer_window"] == [False] and kw["layer_rope"] == [True]
    assert cfg["num_classes"] == cfg["vocab_size"]
    for key in ("assumed", "not_built", "deployment", "stored"):
        assert cfg[key]


def test_the_program_counts_the_bytes_the_file_states(cell):
    """``serving_memory_plan`` over the configuration's own build call:
    the parameters of ``assumed.parameters``, 16,384 B a row a layer, and
    a full-length row's pages by the model's own count."""
    import jax.numpy as jnp
    from dtf_tpu.models import build_model
    from dtf_tpu.serve.bridge import serving_memory_plan
    cfg, engine = cell.config, cell.workload["engine"]
    model, _ = build_model("routed_decoder", num_classes=cfg["vocab_size"],
                           dtype=jnp.bfloat16,
                           **cfg["build_model"]["kwargs"])
    plan = serving_memory_plan(
        model, num_slots=engine["max_batch"],
        max_seq_len=engine["max_seq_len"],
        kv_page_size=engine["kv_page_size"],
        kv_pool_pages=engine["kv_pool_pages"])
    assert plan["param_bytes"] == 2 * 1_621_757_952
    assert plan["per_token_kv_bytes"] == cfg["stored"]["kv_bytes_per_token"] \
        == 8 * cell.family.kv_bytes_per_row(cfg)
    assert plan["kv_bytes_paged"] == 660 * 16_777_216
    # 15 closed windows of one page and a whole open one, not 256
    assert plan["pages_per_slot"] == 15 + 16
    assert plan["state_bytes_per_page"] == 0


def test_the_traffic_is_the_mix_the_cell_was_asked_for(cell):
    mix, engine = cell.traffic, cell.workload["engine"]
    assert (mix["arrivals"], mix["clients"]) == ("closed", 28)
    assert mix["clients"] == engine["max_batch"]
    assert (mix["ramp_s"], mix["drain_s"]) == (20, 10)
    assert mix["prompt_len"]["median"] == 6144
    assert mix["output_len"] == {"median": 1024, "sigma": 0.6, "min": 256,
                                 "max": 4096}
    snap = mix["prompt_len"]["snap_to"]
    assert snap == sorted(list(range(2048, 24577, 1024)) + [16385])
    assert snap[-1] + mix["output_len"]["max"] == 28672 \
        <= engine["max_seq_len"]
    assert 2048 % engine["prefill_chunk"] == 0
    assert 128 % engine["kv_page_size"] == 0
    bases = [load_json(os.path.join(ROOT, "benchmark", "traffic", f))
             .get("base_seed") for f in os.listdir(
                 os.path.join(ROOT, "benchmark", "traffic"))]
    assert bases.count(mix["base_seed"]) == 1
    # half of the drawn prompts end mid-window, half close one at their
    # first decode step; the pool holds what 28 rows reserve
    sizes = np.concatenate([traffic.phase_draw(mix, k, s)[0]
                            for k, s in enumerate((20, 51, 15))])
    at_boundary = np.mean(sizes[:, 0] % 2048 == 0)
    assert 0.4 < at_boundary < 0.6
    from dtf_tpu.ops.window_summary import pages_for_length
    pages = np.array([pages_for_length(p + o, engine["kv_page_size"], 2048,
                                       16) for p, o in sizes])
    assert 28 * pages.max() > engine["kv_pool_pages"] - 1 \
        > np.convolve(pages, np.ones(28), "valid").max()


def test_the_sample_reads_three_kinds_of_close(cell):
    """3,072: window 0 closed by the chunk that starts at 2,048; 4,096:
    its second window closed by the first decode step; 16,385: eight closed
    windows and ONE byte of the ninth (a length the mix draws too: a
    draw of 16,385-16,896 snaps to it)."""
    agree, mix = cell.workload["agreement"], cell.traffic
    assert agree["prompt_lens"] == [3072, 4096, 16385]
    assert agree["new_tokens"] == 64
    assert set(agree["prompt_lens"]) <= set(mix["prompt_len"]["snap_to"])
    toy = cell.family.TOY["serve"]
    window = toy["model_kwargs"]["summary_window"]
    lens = toy["agreement"]["prompt_lens"]
    assert [n % window for n in lens] == [16, 0, 1]
    assert lens[1] + toy["agreement"]["new_tokens"] > 3 * window


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
        assert "'summary_rows_share.bytedocs': None" not in read


# ------------------------------------------------------ the reference ----
@pytest.fixture(scope="module")
def toy_sample(cell):
    """The toy's weights (a float32 tree), two prompts past a window's
    close and what the reference would serve for them."""
    import jax
    import jax.numpy as jnp
    from dtf_tpu.models import build_model
    reference = families.load_reference(cell.config, ROOT)
    toy = cell.family.TOY["serve"]
    kw = dict(cell.config["build_model"]["kwargs"], **toy["model_kwargs"])
    kw["param_dtype"] = "float32"
    model, _ = build_model("routed_decoder", num_classes=toy["vocab_size"],
                           dtype=jnp.float32, **kw)
    params = model.init(jax.random.key(5),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, toy["vocab_size"], n, dtype=np.int32)
               for n in (45, 97)]
    served = reference.greedy_tokens(params, prompts, 4)
    return reference, model, params, prompts, served


def test_the_reference_is_the_programs_whole_sequence_forward(toy_sample):
    """Two writings of the equations — the reference's two sums a query
    block and the program's one masked softmax over tokens and summaries —
    give the same logits in float32."""
    import jax
    reference, model, params, prompts, served = toy_sample
    tokens = np.concatenate([prompts[1], served[1]])[None]
    with jax.default_matmul_precision("highest"):
        program = np.asarray(model.apply({"params": params}, tokens))
    np.testing.assert_allclose(np.asarray(reference.forward(params, tokens)),
                               program, atol=2e-5)


def test_the_references_own_comparison_is_lib_agreements(toy_sample):
    reference, _, params, prompts, served = toy_sample
    rows = reference.rows_that_chose(params, prompts, served)
    mine = reference.served_tokens_agree(params, prompts, served, 0.1, rows,
                                         0.01)
    theirs = agreement.tokens_agree(reference.forward, params, prompts,
                                    served, 0.1, rows, 0.01)
    assert mine["ok"] and mine["logit_rms"] == 0.0
    assert mine["greedy_identical"] == mine["tokens_compared"] == 8
    for key in ("worst_gap", "logit_scale", "allowed_gap"):
        assert mine[key] == pytest.approx(theirs[key], rel=1e-5, abs=1e-6)


@pytest.mark.parametrize("fault", ["no_summaries", "stale_summaries",
                                   "mean_pooling", "w8"])
def test_the_controls_read_worse_than_the_reference_itself(cell, toy_sample,
                                                           fault):
    """Each injected fault of the reference (summaries left out, the newest
    closed window's missing, mean pooling in place of ``softmax(k . phi)``)
    and its 8-bit weights change one thing and read a ``logit_rms`` above
    the toy's limit, on the tokens the sound reference serves."""
    reference, _, params, prompts, served = toy_sample
    kw = ({"weights": reference.rounded_to(8)} if fault == "w8"
          else {"faults": (fault,)})
    rows = reference.rows_that_chose(params, prompts, served, **kw)
    limit = cell.family.TOY["serve"]["agreement"]["logit_rms_limit"]
    said = reference.served_tokens_agree(params, prompts, served, 0.1, rows,
                                         limit)
    assert not said["ok"] and said["logit_rms"] > 1.5 * limit


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


def test_attention_cost_counts_rows_of_both_sorts_as_stored(cell):
    cost = cell.family.SPAN_COSTS["paged_attention_reads"]
    cfg = cell.config
    flops, nbytes = cost(cfg, {"kv_exact_rows_read": 8 * 1000,
                               "kv_summary_rows_read": 8 * 512})
    assert nbytes == 8 * 1512 * 16384
    assert flops == 2 * 2 * 8 * 1512 * 32 * 128
    # a continuation chunk of 1,024 queries sees half of its own keys
    flops, nbytes = cost(cfg, {"kv_exact_rows_read": 8 * 2048,
                               "kv_summary_rows_read": 8 * 128,
                               "tokens": 1024, "start": 3072})
    assert nbytes == 8 * 2176 * 16384
    assert flops == 2 * 2 * 8 * (2176 - 511.5) * 1024 * 32 * 128
    assert cost(cfg, {"kv_exact_rows_read": 8 * 1024,
                      "kv_summary_rows_read": 0, "tokens": 1024,
                      "start": 0}) is None        # the flash kernel's
    assert cost(cfg, {}) is None


def test_compaction_cost_counts_a_window_a_layer(cell):
    cost = cell.family.SPAN_COSTS["window_compactions"]
    flops, nbytes = cost(cell.config, {"windows_closed": 2})
    assert nbytes == 2 * 8 * (2048 + 128) * 16384
    assert flops == 2 * 8 * 2048 * 3 * 2 * 32 * 128
    assert cost(cell.config, {"windows_closed": 0}) is None
    assert cost(cell.config, {}) is None


def test_rooflines_read_the_spans_and_the_share_reads_the_steps(cell):
    records = [_span("serve_decode", kv_exact_rows_read=8 * 1000,
                     kv_summary_rows_read=8 * 500, windows_closed=1),
               _span("serve_prefill_chunk", kv_exact_rows_read=8 * 1024,
                     kv_summary_rows_read=8 * 128, windows_closed=1,
                     tokens=1024, start=2048),
               _span("serve_decode", ts=2.0, kv_exact_rows_read=8,
                     kv_summary_rows_read=0, windows_closed=5)]
    run = _run(cell, records, {"paged_flash_decode.3": 0.004,
                               "window_compact.1": 0.002})
    cfg, costs = cell.config, cell.family.SPAN_COSTS
    least = sum(peaks.least_seconds("TPU v5 lite", *costs[
        "paged_attention_reads"](cfg, r)) for r in records[:2])
    assert read_metric(_spec("paged_decode_roofline.by_span"), run) \
        == pytest.approx(100 * least / 0.004)
    least = 2 * 8 * 2176 * 16384 / 819e9
    got = read_metric(_spec("window_compact_roofline.bytedocs"), run)
    assert got == pytest.approx(100 * least / 0.002) and 0 < got < 100
    assert read_metric(_spec("summary_rows_share.bytedocs"), run) \
        == pytest.approx(1 / 3)
    assert read_metric(_spec("window_compact_ms.bytedocs"), run) \
        == pytest.approx(1.0)
    assert read_metric(_spec("paged_decode_kernel_ms"), run) \
        == pytest.approx(2.0)
    # a program that counts none of it: nothing, and no error
    bare = _run(cell, [_span("serve_decode")], {"paged_flash_decode": 0.004})
    for name in ("paged_decode_roofline.by_span",
                 "window_compact_roofline.bytedocs",
                 "window_compact_ms.bytedocs",
                 "summary_rows_share.bytedocs"):
        assert read_metric(_spec(name), bare) is None


def test_the_turns_laps_read_the_closes_host_time(cell, tmp_path):
    """The four host metrics of the cell on the accepted lap reader: the
    ``compact`` laps a decode launch, apart from the launch's own; nothing
    from a run that traced nothing."""
    turn = dict(kind="span", name="serve_iteration", ts=0.5, dur_s=0.01,
                step=1, laps=[["build", 0.001], ["launch_args", 0.002],
                              ["compact", 0.0005], ["compact", 0.0005],
                              ["launch_call", 0.001], ["ready", 0.005]])
    run = _run(cell, [turn, dict(turn, ts=0.6, step=2)], {})
    run.driver["profile_dir"] = str(tmp_path / "profile")
    assert read_metric(_spec("host_compact_ms.bytedocs"), run) \
        == pytest.approx(1.0)
    assert read_metric(_spec("host_launch_ms"), run) \
        == pytest.approx(4.0)
    # no device in the profile: no device's number
    assert read_metric(_spec("idle_host_pct"), run) is None
    assert read_metric(_spec("idle_wait_pct"), run) is None
    for name in ("host_compact_ms.bytedocs", "host_launch_ms",
                 "idle_host_pct", "idle_wait_pct"):
        spec = _spec(name)
        assert spec["reader"] == "host_laps"
        assert read_metric(spec, _run(cell, [], {})) is None
