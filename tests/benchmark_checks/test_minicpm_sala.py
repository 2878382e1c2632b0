"""Checks of what the family ``minicpm_sala`` and its cell add to the
benchmark: the configuration against the catalog's row, the bytes the file
states against what the program stores, the cell by name through the serve
driver at the toy size, the reference against the program's whole-sequence
forward and against its own injected faults, the cost functions and
readers of the new per-layer metrics.  CPU only; under BENCHMARK.json's
``paths``.

The toy's limit (``families/minicpm_sala.py`` ``TOY``, 0.006): the
program, bf16 matmuls and a bf16 cache on an f32 stream, reads a
``logit_rms`` of 0.0030 against the reference at the toy's three prompts of
24 new tokens (the rehearsal's seed); on two prompts past ``dense_len`` of
4 new tokens the reference with the chosen blocks left out reads 0.015,
dense at every length 0.015, pooled keys a page stale 0.008, 8-bit weights
0.009, the decay left out 0.078, the state zeroed a page 0.104 (a float32
tree, CPU, PR 45)."""

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

CELL = "minicpm-sala-serve-longdoc"
BENCH = load_benchmark()
_S, _L = "minicpm4", "lightning-attn"
# https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json as the
# catalog of architectures holds it
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": [_S] + [_L] * 8 + [_S] + [_L] * 6 + [_S, _S] + [_L] * 4
    + [_S] + [_L] * 6 + [_S] * 3,
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True}
# the per-layer metrics the cell needs, each under the entry's own name (a
# suffix says how an entry differs, never which cell reads it)
NEEDS = {CELL: [
    "serve_mfu", "decode_step_ms", "prefill_chunk_ms", "device_idle_pct",
    "decode_rows_per_step.longdoc", "host_launch_ms", "idle_host_pct",
    "blocks_read_share.longdoc", "block_select_kernel_ms.longdoc",
    "block_select_roofline.longdoc", "paged_decode_kernel_ms",
    "paged_decode_roofline.longdoc", "linear_state_kernel_ms",
    "linear_state_roofline"]}


@pytest.fixture(scope="module")
def cell():
    return load_cell(BENCH, CELL)


def test_the_published_keys_are_the_catalogs_row():
    """Where the catalog is at hand, ``PUBLISHED`` above is its row."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog of architectures on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiniCPM-SALA")
    assert row["config"] == PUBLISHED
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "minicpm-sala-9b")
    assert entry["source"] == row["source_url"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_key(cell, key):
    """Every published key unchanged but the depth and the kinds of the
    layers kept, which ``reduced`` names and ``published`` keeps."""
    cfg = cell.config
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    if key in cfg["reduced"]:
        assert cfg["published"][key] == PUBLISHED[key]
    else:
        assert cfg[key] == PUBLISHED[key]


def test_the_cut_is_stage_two_of_four(cell):
    """Layers 9-16 of the published order: S L L L L L L S, the published
    1 : 3, nothing else reduced."""
    cfg = cell.config
    assert cfg["num_hidden_layers"] == 8
    assert cfg["mixer_types"] == PUBLISHED["mixer_types"][9:17] \
        == [_S] + [_L] * 6 + [_S]
    entry = next(c for c in BENCH["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmark/configs/minicpm-sala-9b.json"
    published = PUBLISHED["mixer_types"]
    assert published.count(_L) == 3 * published.count(_S)


def test_the_build_call_is_the_configuration(cell):
    """What ``build_model`` is given is what the published keys and the
    ``assumed`` sizes say: no width comes from anywhere else."""
    cfg, kw = cell.config, cell.config["build_model"]["kwargs"]
    sizes = cfg["assumed"]["sparse_config"]["sizes"]
    assert cfg["build_model"]["name"] == "routed_decoder"
    assert kw["num_layers"] == kw["num_dense_layers"] \
        == cfg["num_hidden_layers"]
    assert kw["d_model"] == cfg["hidden_size"]
    assert kw["num_heads"] == cfg["num_attention_heads"]
    assert kw["num_kv_heads"] == cfg["num_key_value_heads"]
    assert kw["head_dim"] == cfg["head_dim"]
    assert kw["dense_width"] == cfg["intermediate_size"]
    assert kw["activation"] == cfg["hidden_act"]
    assert kw["layer_mixer"] == [{_S: "sparse_block", _L: "lightning"}[m]
                                 for m in cfg["mixer_types"]]
    assert kw["sparse"][:7] == [
        sizes[k] for k in ("block_size", "kernel_size", "kernel_stride",
                           "topk", "window_size", "init_blocks",
                           "dense_len")]
    assert kw["sparse"][7] == 2.0           # assumed.qk_norm_gain
    assert kw["lightning"] == [cfg["lightning_nh"], cfg["lightning_head_dim"],
                               9, cfg["published"]["num_hidden_layers"]]
    assert kw["mup"] == [cfg["scale_emb"], cfg["scale_depth"],
                         cfg["published"]["num_hidden_layers"],
                         cfg["hidden_size"] / cfg["dim_model_base"]]
    assert kw["rope_theta"] == cfg["rope_theta"]
    assert kw["rms_eps"] == cfg["rms_norm_eps"]
    assert kw["max_seq_len"] == cfg["max_position_embeddings"]
    assert cfg["num_classes"] == cfg["vocab_size"]
    for key in ("assumed", "not_built", "deployment", "stored", "published"):
        assert cfg[key]


def test_the_program_counts_the_bytes_the_file_states(cell):
    """``serving_memory_plan`` over the configuration's own build call:
    three kinds of leaf in one pool — K and V rows (2,048 B a token),
    pooled keys (64 B a token: one row of 512 B a layer a 16 tokens) and
    state entries (6 x 1,048,576 B a page) — and the pool's real bytes
    (the decoder's own cache shapes) are the plan's."""
    import jax
    import jax.numpy as jnp
    from dtf_tpu.models import build_model
    from dtf_tpu.serve.bridge import serving_memory_plan
    from dtf_tpu.serve.decode import trace_paged_init
    cfg, engine = cell.config, cell.workload["engine"]
    model, _ = build_model("routed_decoder", num_classes=cfg["vocab_size"],
                           dtype=jnp.bfloat16,
                           **cfg["build_model"]["kwargs"])
    plan = serving_memory_plan(
        model, num_slots=engine["max_batch"],
        max_seq_len=engine["max_seq_len"],
        kv_page_size=engine["kv_page_size"],
        kv_pool_pages=engine["kv_pool_pages"])
    lightning = 5 * 4096 ** 2 + 3 * 4096 * 16384 + 2 * 4096 + 3 * 128
    sparse = (4096 * (32 + 2 * 2) * 128 + 2 * 4096 ** 2
              + 3 * 4096 * 16384 + 2 * 4096 + 2 * 128)
    assert plan["param_bytes"] == 2 * (6 * lightning + 2 * sparse
                                       + 2 * 73448 * 4096 + 4096)
    assert round(plan["param_bytes"] / 1e7) == 564          # 5.64e9 B
    stored, fam = cfg["stored"], cell.family
    assert plan["per_token_kv_bytes"] == stored["kv_bytes_per_token"] \
        == fam.kv_bytes_per_token(cfg) == 2048 + 64
    assert plan["state_bytes_per_page"] == stored["state_bytes_per_page"] \
        == fam.state_bytes_per_page(cfg) == 6 * 1_048_576
    page, pages = engine["kv_page_size"], engine["kv_pool_pages"]
    assert (page, pages) == (stored["page_tokens"], stored["pool_pages"])
    per_page = plan["per_token_kv_bytes"] * page \
        + plan["state_bytes_per_page"]
    assert per_page == stored["bytes_per_page"] == 10_616_832
    assert plan["kv_bytes_paged"] + plan["state_bytes_paged"] \
        == (pages - 1) * per_page
    # the pool's real bytes: every leaf the decoder would allocate
    shapes = trace_paged_init(model, page, pages)[0]
    real = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(shapes))
    assert real == pages * per_page
    resident = plan["param_bytes"] + real
    assert 12.5e9 <= resident <= 13.2e9
    assert plan["pages_per_slot"] == 65


def test_the_traffic_is_the_mix_the_cell_was_asked_for(cell):
    mix, engine = cell.traffic, cell.workload["engine"]
    assert (mix["arrivals"], mix["clients"]) == ("closed", 24)
    assert mix["clients"] == engine["max_batch"]
    assert (mix["ramp_s"], mix["drain_s"]) == (40, 10)
    assert mix["prepare_per_s"] == 8
    # ISSUE 45's fallback, taken: six runs with prompts to 131,072 spread
    # ``serve_tok_s`` 7.1 %, so the two largest lengths are dropped and
    # nothing else moves (the engine still admits 133,120 positions)
    assert mix["prompt_len"] == {
        "median": 24576, "sigma": 0.8, "min": 4096, "max": 65536,
        "snap_to": [4096, 8192, 12288, 16384, 16385, 24576, 32768, 49152,
                    65536]}
    assert mix["output_len"] == {"median": 512, "sigma": 0.6, "min": 128,
                                 "max": 2048}
    assert engine == {"max_batch": 24, "max_seq_len": 133120,
                      "kv_page_size": 2048, "kv_pool_pages": 705,
                      "prefill_chunk": 2048, "queue_size": 256}
    assert 131072 + 2048 == engine["max_seq_len"]
    bases = [load_json(os.path.join(ROOT, "benchmark", "traffic", f))
             .get("base_seed") for f in os.listdir(
                 os.path.join(ROOT, "benchmark", "traffic"))]
    assert bases.count(mix["base_seed"]) == 1
    # the file's own draw: the mean prompt near 32k, about a seventh of
    # the requests at or under dense_len, about a tenth at 65,536 or more
    drawn = traffic.request_sizes(
        mix, 200_000, np.random.default_rng(mix["base_seed"]))[:, 0]
    assert 28_000 < drawn.mean() < 31_000               # 29,551
    assert 0.12 < np.mean(drawn <= 8192) < 0.15         # 0.136
    assert 0.12 < np.mean(drawn >= 65536) < 0.16        # 0.143
    # 24 rows hold their prompt + budget: the slots bind before the pool
    sizes = np.concatenate([traffic.phase_draw(mix, k, s)[0]
                            for k, s in enumerate((40, 51, 15))])
    pages = -(-(sizes[:, 0] + sizes[:, 1]) // engine["kv_page_size"])
    assert pages.max() <= 33
    assert 24 * pages.mean() < engine["kv_pool_pages"] - 1


def test_the_traced_window_outlasts_the_first_wave_of_prompts(cell):
    """A closed loop's client sends its next request when its last one
    finishes, and ``attempted`` counts the requests that fell due inside
    the window: a traced window in which nobody finishes attempts nothing,
    and the check refuses a line whose ``attempted`` is 0 (PR 45's first
    check, seed 190938441, at the 6 s the other cells trace).  The 24
    clients' first prompts are 336 chunks; on the v5e the last of them has
    its first token 60.3-61.9 s after the load starts and the first three
    answers end at 61.9-64.0 s, five by 76.1 s (my chip runs, PR 45), so
    the window (40 s of ramp, then ``trace_seconds``) has to reach well
    past that wave.  Reckoned here at a chunk's measured 210.6 ms, which
    overstates it (the dense path's chunks are cheaper)."""
    mix, engine = cell.traffic, cell.workload["engine"]
    first = traffic.phase_draw(mix, 0, mix["ramp_s"])[0][:mix["clients"]]
    chunks = int(sum(-(-int(p) // engine["prefill_chunk"])
                     for p, _ in first))
    assert chunks == 336 and first[:, 1].min() == 208
    assert mix["ramp_s"] + cell.workload["trace_seconds"] \
        > chunks * 0.2106 + 5 > 75
    assert cell.workload["trace_seconds"] <= 51         # run_seconds


def test_the_sample_reads_both_paths_and_a_page_of_one_token(cell):
    """4,096: the dense path throughout; 12,288: 192 blocks with 97 read;
    16,385: eight pages and ONE token of the ninth."""
    agree, mix = cell.workload["agreement"], cell.traffic
    sizes = cell.config["assumed"]["sparse_config"]["sizes"]
    assert agree["prompt_lens"] == [4096, 12288, 16385]
    assert agree["new_tokens"] == 64
    assert set(agree["prompt_lens"]) <= set(mix["prompt_len"]["snap_to"])
    assert 4096 + 64 <= sizes["dense_len"] < 12288
    assert 12288 // sizes["block_size"] == 192
    assert 1 + sizes["topk"] + sizes["window_size"] // sizes["block_size"] \
        == 97
    assert 16385 % cell.workload["engine"]["kv_page_size"] == 1
    toy = cell.family.TOY["serve"]
    block, _, _, top, window, init, dense_len, _ = toy["model_kwargs"][
        "sparse"]
    lens = toy["agreement"]["prompt_lens"]
    assert lens[0] + toy["agreement"]["new_tokens"] <= dense_len < lens[1]
    assert lens[2] % toy["engine"]["prefill_chunk"] == 1
    assert init + top + window // block < lens[1] // block


def test_serve_tok_s_is_judged_in_the_new_cell():
    """The cell's own claims on BENCHMARK.json (``structure.py``): nothing
    about its place in a list, or about what else lists an entry."""
    cell, mine = structure.check_cell(
        BENCH, ROOT, CELL, NEEDS[CELL], config="minicpm-sala-9b",
        traffic="longdoc-closed-24")
    assert cell.family.SPAN_COSTS["model_flops"] is cell.family.model_flops
    assert all(m["moves"] == "serve_tok_s" for m in mine.values())
    # a kernel's share of its roofline stands beside the kernel's time
    for name in mine:
        if "_roofline" in name:
            assert name.split(".")[0].replace("_roofline", "_kernel_ms") \
                in {n.split(".")[0] for n in mine}


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
        for name in ("blocks_read_share.longdoc",
                     "decode_rows_per_step.longdoc"):
            assert f"'{name}': None" not in read


# ------------------------------------------------------ the reference ----
@pytest.fixture(scope="module")
def toy_sample(cell):
    """The toy's weights (a float32 tree), two prompts past ``dense_len``
    (one a page entered by one real token) and what the reference would
    serve for them."""
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
                        jnp.zeros((1, 32), jnp.int32))["params"]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, toy["vocab_size"], n, dtype=np.int32)
               for n in (97, 161)]
    served = reference.greedy_tokens(params, prompts, 4)
    return reference, model, params, prompts, served


def test_the_reference_is_the_programs_whole_sequence_forward(toy_sample):
    """Two writings of the equations — the reference's literal recurrence
    and per-query choice, the program's table of blocks as a mask — give
    the same logits in float32, at every position of a prompt that crosses
    ``dense_len``."""
    import jax
    reference, model, params, prompts, served = toy_sample
    tokens = np.concatenate([prompts[1], served[1]])[None]
    with jax.default_matmul_precision("highest"):
        program = np.asarray(model.apply({"params": params}, tokens))
    ref = np.asarray(reference.forward(params, tokens))
    # float32 sums in another order: 1e-4 of the logits' spread
    np.testing.assert_allclose(ref, program, atol=1e-4 * ref.std() + 1e-6)


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


@pytest.mark.parametrize("fault", ["no_chosen", "dense", "stale_pooled",
                                   "no_decay", "zero_state", "w8"])
def test_the_controls_read_worse_than_the_reference_itself(cell, toy_sample,
                                                           fault,
                                                           monkeypatch):
    """Each injected fault of the reference (the chosen blocks left out,
    dense attention at every length, pooled keys a page stale, the decay
    left out, the state zeroed at a page boundary) and its 8-bit weights
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
                "decode_steps": decode_steps, "histograms": {}})


def test_the_costs_count_the_least_work_as_stored(cell):
    """Since PR 46 the paged kernel's bytes are what must move at least
    once, not the copies one implementation makes (65,536 B a block COPIED
    before): ``test_serve_mfu.py`` holds the cases."""
    cfg, costs = cell.config, cell.family.SPAN_COSTS
    assert cell.family.block_kv_bytes(cfg, 1) == 32_768
    assert cell.family.block_kv_bytes(cfg, cfg["num_key_value_heads"]) \
        == 65_536
    flops, nbytes = costs["paged_block_reads"](cfg, {"kv_blocks_read": 970})
    assert nbytes == 970 * 32_768
    assert flops == 970 * 2 * 2 * 64 * 16 * 128
    # a chunk past dense_len: its 160 visible blocks once, both KV heads,
    # two layers — under the decode form's count for the same blocks
    assert costs["paged_block_reads"](
        cfg, {"kv_blocks_read": 970, "tokens": 2048, "start": 8192,
              "rows_dense_path": 0}) == (flops, 160 * 2 * 65_536.0)
    # a chunk at or under it: FLOPs alone; the first: the flash kernel's
    assert costs["paged_block_reads"](
        cfg, {"kv_blocks_read": 970, "tokens": 2048, "start": 2048,
              "rows_dense_path": 2048}) == (flops, 0.0)
    assert costs["paged_block_reads"](
        cfg, {"kv_blocks_read": 970, "tokens": 2048, "start": 0,
              "rows_dense_path": 2048}) is None
    assert costs["paged_block_reads"](cfg, {}) is None
    flops, nbytes = costs["block_select_scores"](
        cfg, {"pooled_keys_scored": 1000})
    assert (flops, nbytes) == (1000 * 2 * 32 * 128, 1000 * 512)
    assert costs["block_select_scores"](cfg, {"pooled_keys_scored": 0}) \
        is None
    flops, nbytes = costs["linear_state_steps"](
        cfg, {"state_rows_advanced": 6 * 24})
    assert nbytes == 6 * 24 * 2 * 1_048_576
    assert flops == 6 * 24 * 2 * 2 * 32 * 128 * 128
    assert costs["linear_state_steps"](cfg, {}) is None


def test_the_readers_read_the_spans(cell):
    records = [_span("serve_decode", kv_blocks_read=2 * 2 * 97 * 10,
                     kv_blocks_visible=2 * 2 * 512 * 10,
                     pooled_keys_scored=2 * 2046 * 10,
                     state_rows_advanced=6 * 10, rows_dense_path=0),
               _span("serve_prefill_chunk", kv_blocks_read=4 * 97 * 2048,
                     kv_blocks_visible=4 * 300 * 2048, tokens=2048,
                     start=16384, rows_dense_path=0, pooled_keys_scored=7,
                     state_rows_advanced=6),
               _span("serve_decode", ts=2.0, kv_blocks_read=5,
                     kv_blocks_visible=5, state_rows_advanced=6)]
    run = _run(cell, records, {"paged_flash_decode.3": 0.2,
                               "block_select.1": 0.001,
                               "linear_state_decode_noerase_mxu1x3.2": 0.004})
    cfg, costs = cell.config, cell.family.SPAN_COSTS
    least = sum(peaks.least_seconds("TPU v5 lite", *costs[
        "paged_block_reads"](cfg, r)) for r in records[:2])
    got = read_metric(_spec("paged_decode_roofline.longdoc"), run)
    assert got == pytest.approx(100 * least / 0.2) and 0 < got < 100
    assert read_metric(_spec("blocks_read_share.longdoc"), run) \
        == pytest.approx(97 / 512)
    assert read_metric(_spec("decode_rows_per_step.longdoc"), run) \
        == pytest.approx(10.0)
    got = read_metric(_spec("block_select_roofline.longdoc"), run)
    assert got == pytest.approx(100 * 2 * 2046 * 10 * 512 / 819e9 / 0.001)
    got = read_metric(_spec("linear_state_roofline"), run)
    assert got == pytest.approx(100 * 60 * 2 * 1_048_576 / 819e9 / 0.004)
    assert 0 < got < 100
    assert read_metric(_spec("linear_state_kernel_ms"), run) \
        == pytest.approx(2.0)
    assert read_metric(_spec("block_select_kernel_ms.longdoc"), run) \
        == pytest.approx(0.5)
    assert read_metric(_spec("paged_decode_kernel_ms"), run) \
        == pytest.approx(100.0)
    # a program that counts none of it (the parent): nothing, and no error
    bare = _run(cell, [_span("serve_decode")], {"paged_flash_decode": 0.004})
    for name in ("paged_decode_roofline.longdoc",
                 "block_select_roofline.longdoc",
                 "block_select_kernel_ms.longdoc",
                 "linear_state_roofline",
                 "linear_state_kernel_ms",
                 "blocks_read_share.longdoc",
                 "decode_rows_per_step.longdoc"):
        assert read_metric(_spec(name), bare) is None
