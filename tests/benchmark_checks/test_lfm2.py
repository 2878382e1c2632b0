"""Checks of what the family ``lfm2`` and the closed long-prompt cell add to
the benchmark: the configuration against the published one, both cells by
name through the serve driver at the toy size, the reference's own
comparison against ``lib/agreement``, the cost functions and readers of the
new per-layer metrics, and that the serve bodies of the three serving
configurations the benchmark already had lower to the text they lowered to
at the parent commit.  CPU only; under BENCHMARK.json's ``paths``.

The toy's limit (``families/lfm2.py`` ``TOY``, 0.008): the program, bf16
matmuls on an f32 stream, reads a ``logit_rms`` of 0.0036-0.0048 over seeds
11-16 (``control.py --toy``), the reference with every matrix at 8 bits
0.0155-0.0169 over seeds 11-13 (``control_rows.py --toy --tokens random``),
CPU, PR 34, at heads of 64 and the sample of four prompts."""

import contextlib
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

CELL = "lfm2-serve-manyrows"
CLOSED = "gpt13b-serve-longprompt-closed"
BENCH = load_benchmark()
# the per-layer metrics the cell needs, each under the entry's own name (a
# suffix says how an entry differs, never which cell reads it)
NEEDS = {
    CELL: [
        "decode_step_ms", "device_idle_pct", "prefill_chunk_ms",
        "paged_decode_kernel_ms", "moe_experts_ms", "moe_experts_roofline",
        "paged_decode_roofline.by_span",
        "expert_load_max_over_mean.num_experts",
        "decode_rows_per_step.manyrows", "host_admit_ms", "host_chunk_ms",
        "host_launch_ms", "host_emit_ms", "idle_host_pct", "idle_wait_pct",
        "serve_mfu"],
    CLOSED: [
        "decode_step_ms", "device_idle_pct", "prefill_chunk_ms",
        "host_admit_ms", "host_chunk_ms", "host_launch_ms", "host_emit_ms",
        "idle_host_pct", "idle_wait_pct", "prefill_chunk_device_ms",
        "serve_mfu"],
}
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json as the
# catalog of architectures holds it
_PERIOD = ["conv", "conv", "full_attention", "conv"]
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": _PERIOD + ["conv", "conv", "full_attention", "conv"] * 4
    + ["conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
MIXER = {"conv": "short_conv", "full_attention": "attention"}


@pytest.fixture(scope="module")
def cell():
    return load_cell(BENCH, CELL)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_key(cell, key):
    """Every published key unchanged, but the depth, which ``reduced``
    names and the file states beside the published count.  ``layer_types``
    is kept whole (a nested group is copied whole; the build call takes
    its first 16)."""
    assert len(PUBLISHED["layer_types"]) == 24
    assert PUBLISHED["layer_types"].count("full_attention") == 6
    if key == "num_hidden_layers":
        assert cell.config["reduced"] == ["num_hidden_layers"]
        assert cell.config[key] == 16
        assert cell.config["published"][key] == PUBLISHED[key]
    else:
        assert cell.config[key] == PUBLISHED[key]


def test_the_build_call_is_the_configuration(cell):
    """What ``build_model`` is given is what the published keys say: the
    first 16 layers' kinds in published order, no width, expert count,
    scale or theta of its own; the stored bytes are stated."""
    c, kw = cell.config, cell.config["build_model"]["kwargs"]
    fam = cell.family
    assert kw["num_layers"] == c["num_hidden_layers"] == 16
    kinds = c["layer_types"][:16]
    assert kw["layer_mixer"] == [MIXER[k] for k in kinds]
    assert kinds == fam.layer_types(c)
    assert (kinds.count("conv"), kinds.count("full_attention")) == (12, 4)
    # 14 layers after the two dense ones, three and a half periods of four
    assert kw["num_layers"] - kw["num_dense_layers"] == 14
    for ours, theirs in [("d_model", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("conv_taps", "conv_L_cache"),
                         ("rope_theta", "rope_theta"),
                         ("num_dense_layers", "num_dense_layers"),
                         ("dense_width", "intermediate_size"),
                         ("num_experts", "num_experts"),
                         ("experts_per_token", "num_experts_per_tok"),
                         ("expert_width", "moe_intermediate_size"),
                         ("routed_scale", "routed_scaling_factor"),
                         ("rms_eps", "norm_eps"),
                         ("max_seq_len", "max_position_embeddings")]:
        assert kw[ours] == c[theirs], ours
    assert kw["head_dim"] == c["hidden_size"] // c["num_attention_heads"] == 64
    assert kw["shared_expert_width"] == 0 and kw["routed_scale"] == 1.0
    assert kw["routing"] == "sigmoid_bias" and c["use_expert_bias"] is True
    assert kw["routing_sum_eps"] == 1e-6 and c["norm_topk_prob"] is True
    assert kw["router_input"] == "post_attention"
    assert kw["router_bias_stddev"] == 0.05 and kw["activation"] == "silu"
    assert kw["qk_norm"] and kw["tie_head"]
    assert kw["layer_window"] == [False] and kw["layer_rope"] == [True]
    assert c["num_classes"] == c["vocab_size"]
    assert kw["param_dtype"] == "bfloat16" and c["dtype"] == "bf16"
    for key in ("depth", "head_dim", "conv_block", "qk_norm", "rope",
                "softmax_scale", "norm", "routing", "experts", "head",
                "state", "precision", "weights", "router_bias"):
        assert key in c["assumed"], key
    assert c["stored"]["kv_bytes_per_token"] == fam.kv_bytes_per_token(c) \
        == 8192
    assert c["stored"]["state_bytes_per_page"] == fam.state_bytes_per_page(c) \
        == 98304
    entry = next(x for x in BENCH["configs"] if x["name"] == cell.config_name)
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200
    assert entry["source"].endswith("config.json")
    assert entry["reduced"] == c["reduced"]


def test_the_traffic_is_the_mix_the_cell_was_asked_for(cell):
    assert cell.traffic == {
        "kind": "requests", "arrivals": "closed", "clients": 96,
        "prepare_per_s": 32.0, "prepare_block_per_s": 32.0,
        "base_seed": 20261201, "ramp_s": 20, "drain_s": 10,
        "prompt_len": {"median": 1536, "sigma": 0.8, "min": 256,
                       "max": 8192,
                       "snap_to": [256, 512, 1024, 1536, 2048, 3072, 4096,
                                   6144, 8192, 8193]},
        "output_len": {"median": 128, "sigma": 0.6, "min": 32, "max": 512}}
    eng = cell.workload["engine"]
    assert eng["max_batch"] == cell.traffic["clients"] == 96
    assert eng["max_seq_len"] == 8704 >= 8192 + 512
    assert eng["queue_size"] == 256 and eng["kv_page_size"] == 64
    assert (eng["kv_pool_pages"] - 1) * eng["kv_page_size"] == 327680
    assert eng["prefill_chunk"] in (1024, 2048)
    agree = cell.workload["agreement"]
    assert agree["prompt_lens"] == [1024, 6144, 8193]
    assert agree["new_tokens"] == 64
    assert cell.chips == 1


def test_the_sample_reads_a_carry_and_the_mix_never_draws_it(cell):
    """The driver takes the agreement's prompts from the mix's ``snap_to``
    and from nowhere else, and every length the mix draws is whole pages
    long and ends a thousand tokens after a chunk boundary: no compared
    position would see the convolution's carry.  So ``snap_to`` holds
    8,193, one past ``max``: no draw snaps to it (a length is clipped to
    8,192 first), the schedule is the one the cell was asked for, request
    for request, and the sample gains a prompt of four whole chunks and a
    final one of ONE real token — its first compared position is that
    token, whose filter reads both carried inputs, and its state entry is
    taken at ``last_pos`` 0 of a tail-padded chunk."""
    from benchmark.lib import traffic
    from dtf_tpu.serve.engine import chunk_plan
    mix, eng = cell.traffic, cell.workload["engine"]
    asked = dict(mix, prompt_len=dict(mix["prompt_len"],
                                      snap_to=mix["prompt_len"]["snap_to"][:-1]))
    assert max(asked["prompt_len"]["snap_to"]) == mix["prompt_len"]["max"]
    for phase, length_s in enumerate((mix["ramp_s"], 51.0, 15.0)):
        ours, _ = traffic.phase_draw(mix, phase, length_s)
        theirs, _ = traffic.phase_draw(asked, phase, length_s)
        assert (ours == theirs).all() and ours[:, 0].max() <= 8192
    plan = chunk_plan(8193, eng["prefill_chunk"], eng["kv_page_size"])
    assert plan[-1] == (8192, eng["kv_page_size"]) and len(plan) >= 5
    assert 8193 + cell.workload["agreement"]["new_tokens"] \
        <= eng["max_seq_len"]
    # every other prompt of the sample, and of the mix, is whole pages
    assert all(n % eng["kv_page_size"] == 0
               for n in asked["prompt_len"]["snap_to"])
    toy = cell.family.TOY["serve"]
    assert max(toy["agreement"]["prompt_lens"]) \
        == toy["traffic"]["prompt_len"]["max"] + 1 \
        == toy["engine"]["prefill_chunk"] * 5 + 1


def test_the_closed_long_prompt_cell_is_files_only():
    """The lengths of ``longprompt-poisson`` unchanged, closed loop, 16
    clients; the engine, agreement and traced window of
    ``gpt13b-serve-longprompt``'s workload file."""
    closed = load_cell(BENCH, CLOSED)
    open_loop = load_cell(BENCH, "gpt13b-serve-longprompt")
    assert closed.workload == open_loop.workload
    assert closed.config_name == open_loop.config_name == "cerebras-gpt-1.3b"
    for key in ("prompt_len", "output_len"):
        assert closed.traffic[key] == open_loop.traffic[key]
    rest = {k: v for k, v in closed.traffic.items()
            if k not in ("prompt_len", "output_len")}
    assert rest == {"kind": "requests", "arrivals": "closed", "clients": 16,
                    "prepare_per_s": 64.0, "prepare_block_per_s": 64.0,
                    "base_seed": 20261102, "ramp_s": 20, "drain_s": 10}
    assert closed.chips == 1
    # the entries that list it: test_serve_tok_s_is_judged_in_the_new_cells


@pytest.mark.parametrize("name,trace", [(CELL, "0"), (CELL, "1"),
                                        (CLOSED, "1")],
                         ids=["manyrows-trace0", "manyrows-trace1",
                              "longclosed-trace1"])
def test_the_cells_rehearse_through_the_serve_driver(name, trace):
    """Loaded by name, at the family's toy size, on the CPU; the traced
    rehearsal also walks the spans' counts into the readers."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.rehearse", "--workload", name,
         "--trace", trace, "--seconds", "2"],
        cwd=ROOT, capture_output=True, text=True, timeout=110,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    said = json.loads(last[last.index("{"):])
    assert said["line"]["correct"] is True and said["reasons"] == []
    assert said["line"]["device"]["platform"] == "cpu"
    assert said["contract_refuses_it_for"]      # never a result
    if name == CELL and trace == "1":
        read = done.stdout[done.stdout.index("readers without"):]
        assert "'decode_rows_per_step.manyrows': None" not in read
        assert "'expert_load_max_over_mean.num_experts': None" not in read


@contextlib.contextmanager
def zeroed_carry():
    """The fault ``correct`` has to refuse, put in from outside the model:
    every continued chunk finds zeros where the state entry of the page
    before its start should be — in the engine's own prefill and in the
    replay, which both go through ``Decoder.prefill_chunk``."""
    import jax
    from dtf_tpu.serve.decode import Decoder
    sound = Decoder.prefill_chunk

    def faulty(self, cache, chunk, block_row, start, *rest, **kw):
        if int(start) > 0:
            page = int(np.asarray(block_row)[(int(start) - 1)
                                             // self.page_size])
            cache = jax.tree_util.tree_map_with_path(
                lambda path, leaf: leaf.at[page].set(0)
                if path[-1].key == "conv_state" else leaf, cache)
        return sound(self, cache, chunk, block_row, start, *rest, **kw)
    Decoder.prefill_chunk = faulty
    try:
        yield
    finally:
        Decoder.prefill_chunk = sound


@pytest.mark.parametrize("fault", [None, zeroed_carry],
                         ids=["sound", "zeroed_carry"])
def test_correct_refuses_a_zeroed_carry_at_a_chunk_boundary(
        fault, capsys, monkeypatch):
    """``benchmark.control`` at the toy, the cell's comparison as a run
    makes it: the program passes its gap, and the same program with the
    carry zeroed at every chunk boundary does not — by the sample's chunk
    + 1 prompt, whose first served token the fault chose.  At a width of
    64 the initialiser's taps and gates leave the convolution a thousandth
    of the stream (at 2,048 it is as large as the stream), so the toy's
    are made louder here; seed 11 reads a gap of 0.005 sound and 1.10
    faulty against 0.4, ``logit_rms`` 0.015 and 0.186 (seeds 12, 13: 0.010
    and 0.68, 0.004 and 0.63; CPU, PR 34).  On the chip at the published
    widths: ``PERF.md`` section 2."""
    import jax
    from benchmark import control
    from benchmark.drivers import serve
    quiet = serve.model_and_sample

    def louder(ctx):
        m = quiet(ctx)
        m.params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf * {"in_proj": 4, "taps": 50}.get(
                path[-1].key, 1) if len(path) > 1
            and path[-2].key == "conv" else leaf,
            m.params)
        return m
    monkeypatch.setattr(serve, "model_and_sample", louder)
    with (fault or contextlib.nullcontext)():
        assert control.main(["--workload", CELL, "--seeds", "11",
                             "--toy"]) == 0
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["who"] == "program" and said["tokens_compared"] == 4 * 64
    if fault is None:
        assert said["gap"] < 0.1 * said["gap_limit"]
        assert said["logit_rms"] < 0.03
    else:
        assert not said["ok"] and said["gap"] > 1.5 * said["gap_limit"]
        assert said["logit_rms"] > 0.1


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


def test_the_reference_in_row_blocks_is_the_reference(toy_sample,
                                                       monkeypatch):
    """At the sample's longest prompt the reference's MLPs take their rows
    a block at a time (``ROW_BLOCK``): 45 rows in three blocks of 15 give
    what 45 rows at once give."""
    reference, params, prompts, _ = toy_sample
    tokens = np.asarray(prompts[1])[None]
    whole = np.asarray(reference.hidden(params, tokens))
    monkeypatch.setattr(reference, "ROW_BLOCK", 16)
    blocked = np.asarray(reference.hidden(params, tokens))
    assert blocked.shape == whole.shape == (1, 45, 64)
    np.testing.assert_allclose(blocked, whole, rtol=0, atol=1e-6)


@pytest.mark.parametrize("control", ["w8", "router_bf16", "state_bf16",
                                     "zero_bias"])
def test_the_controls_read_worse_than_the_reference_itself(toy_sample,
                                                           control):
    """Each control of the reference changes one thing and reads a
    ``logit_rms`` above 0: 8-bit weights; the router's input alone in
    bfloat16 (flipped top-k choices); the convolution's inputs ``u`` alone
    in bfloat16 (what the state holds); the router's bias left out (a part
    of the mathematics: it reads far above any rounding)."""
    import jax.numpy as jnp
    reference, params, prompts, served = toy_sample

    def bf16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    kw = {"w8": {"weights": reference.rounded_to(8)},
          "router_bf16": {"router_input": bf16},
          "state_bf16": {"state": bf16},
          "zero_bias": {"zero_bias": True}}[control]
    said = reference.served_tokens_agree(
        params, prompts, served, 0.01,
        reference.rows_that_chose(params, prompts, served, **kw), 1.0)
    low, high = {"w8": (1e-3, 0.1), "router_bf16": (0.0, 0.1),
                 "state_bf16": (1e-7, 0.05),
                 "zero_bias": (0.02, 2.0)}[control]
    assert low <= said["logit_rms"] < high, said["logit_rms"]


# ------------------------------------------------- costs and readers ----
def _span(name, ts=0.5, **attrs):
    return dict(kind="span", name=name, ts=ts, dur_s=0.01, **attrs)


def test_flops_and_bytes_count_the_layers_by_kind(cell):
    cfg, fam = cell.config, cell.family
    conv = 2048 * 6144 + 2048 * 2048
    attn = 2048 * (32 + 16) * 64 + 2048 * 2048
    routed = 2048 * 32 + 4 * 3 * 2048 * 1792
    assert fam.active_matmul_params(cfg) == (
        12 * conv + 4 * attn + 2 * 3 * 2048 * 7168 + 14 * routed
        + 2048 * 65536)
    assert fam.kv_row_lanes(cfg) == 128
    flops = fam.train_flops_per_sample(cfg, {"seq_len": 1024})
    assert flops == pytest.approx(3 * (2 * fam.active_matmul_params(cfg)
                                       + 4 * 2 * 2 * 32 * 64 * 1025 / 2)
                                  * 1024)


def test_expert_cost_counts_pairs_and_touched_experts(cell):
    cost = cell.family.SPAN_COSTS["expert_matmuls"]
    flops, nbytes = cost(cell.config, {"assignments": 384 * 14,
                                       "experts_touched": 32 * 14})
    assert flops == 2.0 * 384 * 14 * 3 * 2048 * 1792
    assert nbytes == 2.0 * 32 * 14 * 3 * 2048 * 1792
    assert cost(cell.config, {}) is None


def test_paged_cost_counts_the_row_as_stored_and_a_chunks_keys_at_half(cell):
    cost = cell.family.SPAN_COSTS["paged_attention_reads"]
    # a decode step: the 4 attention layers read 130,000 cached tokens
    # each, a row of 8 x 128 bf16 lanes a token: [k | v] of a head
    flops, nbytes = cost(cell.config, {"kv_tokens_read_global": 4 * 130000})
    assert nbytes == 4 * 130000 * 8 * 128 * 2
    assert flops == 2 * 2.0 * 32 * 64 * 4 * 130000
    # a 1,024-token chunk at 2,048: every attention layer attends 3,072
    # tokens, its own 1,024 at half
    span = {"kv_tokens_read_global": 4 * 3072, "tokens": 1024, "start": 2048}
    flops, nbytes = cost(cell.config, span)
    assert nbytes == 4 * 3072 * 2048
    assert flops == 2 * 2.0 * 32 * 64 * 1024 * 4 * (3072 - 1023 / 2)
    # the first chunk goes through the flash kernel and reads no page
    assert cost(cell.config, {"kv_tokens_read_global": 4 * 1024,
                              "tokens": 1024, "start": 0}) is None
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
     {"assignments": 5376, "experts_touched": 448}),
    ("paged_decode_roofline.by_span", "paged_flash_decode.7",
     {"kv_tokens_read_global": 520000}),
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


def test_decode_rows_is_the_state_rows_a_step_over_the_conv_layers(cell):
    """``span_mean``: 60 and 72 rows decoding in two steps, each counted
    in 12 convolution layers; a chunk's span is not read; a program that
    counts nothing reads nothing."""
    spec = _spec("decode_rows_per_step.manyrows")
    records = [_span("serve_decode", state_rows_advanced=60 * 12),
               _span("serve_decode", state_rows_advanced=72 * 12),
               _span("serve_prefill_chunk", state_rows_advanced=12),
               _span("serve_decode", ts=3.0, state_rows_advanced=12)]
    assert read_metric(spec, _run(cell, records, {})) == pytest.approx(66.0)
    assert read_metric(spec, _run(cell, [_span("serve_decode")], {})) is None


def test_expert_load_reads_max_over_mean_of_32(cell):
    spec = _spec("expert_load_max_over_mean.num_experts")
    # 14 routed layers of 384 pairs; the busiest expert of each holds 36
    # rows: 36 / (384 / 32) = 3
    records = [_span("serve_decode", assignments=384 * 14,
                     expert_load_max=36 * 14)] * 5
    assert read_metric(spec, _run(cell, records, {})) == pytest.approx(3.0)
    assert read_metric(spec, _run(cell, [_span("serve_decode")], {})) is None


@pytest.mark.parametrize("metric,kernel", [
    ("moe_experts_ms", "gmm.12"),
    ("paged_decode_kernel_ms", "paged_flash_decode.4")])
def test_kernel_time_is_per_decode_step(cell, metric, kernel):
    run = _run(cell, [], {kernel: 0.030, "gmm_like_fusion": 1.0})
    assert read_metric(_spec(metric), run) == pytest.approx(15.0)


@pytest.mark.parametrize("name", [CELL, CLOSED])
def test_serve_tok_s_is_judged_in_the_new_cells(name):
    """The cell's own claims on BENCHMARK.json (``structure.py``): nothing
    about its place in a list, or about what else lists an entry."""
    _, mine = structure.check_cell(BENCH, ROOT, name, NEEDS[name])
    assert all(m["moves"] == "serve_tok_s" for m in mine.values())


# ---------------------------- the bodies the benchmark already had ----
def body_hashes() -> dict:
    """sha256 of the lowered text (CPU: the gather path) of the first
    chunk, a continuation chunk and the decode step, at the toy sizes of
    the three serving families the benchmark had before this one."""
    import jax
    import jax.numpy as jnp
    from dtf_tpu.models import build_model
    from dtf_tpu.serve.decode import Decoder, _seed_row_keys, position_key
    out = {}
    for family, name, vocab in (("smallthinker", "routed_decoder", 512),
                                ("joyai", "routed_decoder", 512),
                                ("gpt2", "transformer", 256)):
        kw = dict(families.load(family, ROOT).TOY["serve"]["model_kwargs"])
        if family == "joyai":
            cfg = load_json(os.path.join(ROOT, "benchmark", "configs",
                                         "joyai-llm-flash.json"))
            kw = dict(cfg["build_model"]["kwargs"], **kw)
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
    """The routed decoder gained a mixer kind a layer, per-head norms, a
    tied head and a pool of ``[k | v]`` rows, the decoder a ``last_pos``
    for models that carry state; the SmallThinker, JoyAI and dense
    configurations' build calls are not edited and their compiled bodies
    lower, on the CPU, to the text the parent commit's lowered to
    (recorded from it in ``data/serve_bodies_lowered_pr34.json``; the two
    families ``data/serve_bodies_lowered.json`` records read the same
    there)."""
    got = body_hashes()
    assert got == load_json(
        os.path.join(DATA, "serve_bodies_lowered_pr34.json"))
    older = load_json(os.path.join(DATA, "serve_bodies_lowered.json"))
    assert {k: got[k] for k in older} == older


if __name__ == "__main__":      # record: run the copy in a parent checkout
    print(json.dumps(body_hashes(), indent=1, sort_keys=True))
