"""Checks of what PR 46 adds to the benchmark: ``serve_mfu`` (the reader
``span_mfu`` on synthetic spans, each family's ``model_flops`` against a
count made by hand from the published sizes), the sparse roofline's count
of the least work, the collectives' two spellings, ``attempted`` of at
least 1, and the phase a traced open-loop run's schedule gains.  CPU only;
under BENCHMARK.json's ``paths``."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.drivers import serve  # noqa: E402
from benchmark.lib import contract, costs, traffic  # noqa: E402
from benchmark.lib.runtime import (load_benchmark, load_cell,  # noqa: E402
                                   load_json)
from benchmark.lib.xplane import COLLECTIVE_RE, Reduction  # noqa: E402
from benchmark.readers import ReaderInput, read_metric, span_mfu  # noqa: E402

BENCH = load_benchmark()
SERVING = [w["name"] for w in BENCH["workloads"]
           if load_json(os.path.join(ROOT, "benchmark", "workloads",
                                     w["name"] + ".json"))["driver"]
           == "serve"]
PEAK = 197e12


def _spec(name):
    return load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  name + ".json"))


def _span(name, ts=0.5, **attrs):
    return dict(kind="span", name=name, ts=ts, dur_s=0.01, **attrs)


def _run(cell, records, kernels=None, **driver):
    reduction = None if kernels is None else Reduction(
        window_s=1.0, busy_s=0.5, self_s=dict(kernels),
        calls={k: 1 for k in kernels}, idle_gaps=[])
    driver = dict({"records": records, "window_wall": (0.0, 1.0),
                   "window_s": 2.0, "histograms": {},
                   "engine": {"max_batch": 48, "page_size": 16},
                   "prompt_lens": [128, 256, 384, 512, 768, 1024]}, **driver)
    return ReaderInput(cell=cell, device_kind="TPU v5 lite",
                       reduction=reduction, driver=driver)


# ------------------------------------------------ the metric's entry ----
def test_one_serve_mfu_lists_every_serving_cell():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "serve_mfu")
    # the count is the list's own: a serving cell appends its name to both
    tok = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tok_s")
    assert entry["workloads"] == SERVING == tok["workloads"]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("%", "higher", "program_span", "whole step",
                                "serve_tok_s")
    spec = _spec("serve_mfu")
    assert (spec["unit"], spec["layer"], spec["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])
    assert spec["reader"] == "span_mfu" and spec["scale"] == 100
    # one metric with a list, no suffixed copies; the training cells' lines
    # do not carry it
    assert [m["name"] for m in BENCH["per_layer"]
            if "mfu" in m["name"]] == ["serve_mfu"]
    assert not any("train" in name for name in entry["workloads"])


@pytest.mark.parametrize("name", SERVING)
def test_every_serving_family_counts_its_model(name):
    cell = load_cell(BENCH, name)
    assert "serve_mfu" in cell.per_layer
    assert callable(cell.family.SPAN_COSTS[_spec("serve_mfu")["args"]["cost"]])


# ------------------------------------------------------ the reader ----
GPT_BODY = 24 * (4 * 2048 * 2048 + 2 * 2048 * 8192)
GPT_HEAD = 2048 * 50257
GPT_KEY = 24 * 4 * 2048      # FLOPs a key a query sees, all layers


def _gpt_chunk(start, real):
    keys = sum(p + 1 for p in range(start, start + real))
    return 2 * GPT_BODY * real + 2 * GPT_HEAD + GPT_KEY * keys


def test_span_mfu_is_the_hand_reckoned_share():
    """A known chunk and a known decode step: their FLOPs over window
    seconds x the peak x one chip.  The step's rows are its turn's
    ``decoding``; its context the lower bound from the pages' histogram:
    500 pages a step with 48 slots of which 40 decode hold at least
    (500 - 48) x 16 + 40 positions."""
    cell = load_cell(BENCH, "gpt13b-serve-loaded")
    records = [
        _span("serve_iteration", ts=0.39, span_id="0.7", decoding=40,
              prefilling=2),
        _span("serve_prefill_chunk", ts=0.4, span_id="0.8",
              parent_span="0.7", start=256, tokens=256, last=False),
        _span("serve_decode", ts=0.45, span_id="0.9", parent_span="0.7",
              allheads=1)]
    run = _run(cell, records, {"fusion.1": 0.1}, histograms={
        "serve_decode_live_pages": {"count": 1, "mean": 500.0}})
    step = (2 * (GPT_BODY + GPT_HEAD) * 40
            + GPT_KEY * ((500 - 48) * 16 + 40))
    want = 100 * (_gpt_chunk(256, 256) + step) / (2.0 * PEAK)
    assert read_metric(_spec("serve_mfu"), run) == pytest.approx(want,
                                                                 rel=1e-12)
    assert 0 < want < 60


def test_a_span_outside_the_window_counts_nothing():
    cell = load_cell(BENCH, "gpt13b-serve-loaded")
    inside = _span("serve_prefill_chunk", start=0, tokens=128, last=True)
    late = _span("serve_prefill_chunk", ts=1.5, start=0, tokens=256,
                 last=True)
    early = _span("serve_prefill_chunk", ts=-0.1, start=0, tokens=256,
                  last=True)
    other = _span("serve_batch_form", admitted=3)
    spec = _spec("serve_mfu")
    one = read_metric(spec, _run(cell, [inside], {"fusion": 0.1}))
    assert one == pytest.approx(100 * _gpt_chunk(0, 128) / (2.0 * PEAK))
    assert read_metric(spec, _run(cell, [early, inside, late, other],
                                  {"fusion": 0.1})) == one


def test_nothing_to_count_reads_nothing():
    cell = load_cell(BENCH, "gpt13b-serve-loaded")
    spec = _spec("serve_mfu")
    assert read_metric(spec, _run(cell, [], {"fusion": 0.1})) is None
    # a step whose rows nobody counted (no turn record: an older program)
    assert read_metric(spec, _run(cell, [_span("serve_decode")],
                                  {"fusion": 0.1})) is None
    # no device trace (a rehearsal): no share of a device's peak
    chunk = _span("serve_prefill_chunk", start=0, tokens=128, last=True)
    assert read_metric(spec, _run(cell, [chunk])) is None
    # a training cell's family counts no serving call
    train = load_cell(BENCH, "resnet50-train")
    assert read_metric(spec, _run(train, [chunk], {"fusion": 0.1})) is None


@pytest.mark.parametrize("span,lens,page,want", [
    # not a prompt's last chunk: all of it real
    (dict(start=0, tokens=256, last=False), [128, 1024], 16, 256),
    # the last one: what the mix's grid leaves in a chunk of this length
    (dict(start=256, tokens=128, last=True), [128, 256, 384, 1024], 16, 128),
    # 16,385 = 16 x 1,024 + 1 in pages of 128: ONE real byte in 128
    (dict(start=16384, tokens=128, last=True), [16384, 16385, 24576], 128, 1),
    # two lengths fit the same padded chunk: the lesser
    (dict(start=0, tokens=1024, last=True), [256, 512, 1024], 1024, 256),
    # no grid: a page less one
    (dict(start=0, tokens=64, last=True), None, 16, 49),
    (dict(start=0, tokens=8, last=True), [], 16, 1)])
def test_the_least_real_tokens_of_a_chunk(span, lens, page, want):
    assert span_mfu.least_real_tokens(span, lens, page) == want


def test_a_count_the_span_carries_is_believed():
    """``rows`` on the span itself (a later program's) wins over the
    turn's, and a chunk carries no decode keys."""
    driver = {"engine": {"max_batch": 8, "page_size": 16}, "histograms": {
        "p": {"count": 3, "mean": 20.0}}, "prompt_lens": [64]}
    turn = {"decoding": 5}
    call = span_mfu.the_call({"name": "serve_decode", "rows": 7}, turn,
                             driver, {"pages": "p"})
    assert call["rows"] == 7 and call["slots"] == 8
    assert call["context_tokens"] == (20 - 8) * 16 + 5
    call = span_mfu.the_call({"name": "serve_decode"}, None, driver, {})
    assert "rows" not in call and "context_tokens" not in call
    call = span_mfu.the_call({"tokens": 64, "start": 0, "last": True},
                             turn, driver, {"pages": "p"})
    assert call["real_tokens"] == 64 and "rows" not in call


def test_causal_keys():
    assert costs.causal_keys(0, 4) == 1 + 2 + 3 + 4
    assert costs.causal_keys(10, 3) == 11 + 12 + 13
    assert costs.causal_keys(2, 4, window=4) == 3 + 4 + 4 + 4
    assert costs.causal_keys(0, 3, window=100) == costs.causal_keys(0, 3)


def test_step_keys_leave_the_idle_rows_out():
    call = {"rows": 3, "slots": 8, "a": 2 * (100 + 5), "b": 6 * (40 + 5)}
    assert costs.step_keys(call, ("a", "b"), 8) == 2 * 100 + 6 * 40
    assert costs.step_keys(call, ("a",), 2) == 2 * 100
    assert costs.step_keys(call, ("absent",), 2) == 0
    assert costs.step_keys(dict(call, a=3), ("a",), 2) == 0   # never < 0


# ----------------------------- each family's count, made by hand ----
def _smallthinker():
    layer = (2560 * (28 + 8) * 128 + 28 * 128 * 2560     # q k v, o
             + 2560 * 64 + 6 * 3 * 2560 * 768)           # router, top 6
    body, head, key = 12 * layer, 2560 * 151936, 4 * 28 * 128
    glob = 1024 * 4096 + 1024 * 1025 // 2    # 3 global layers: causal
    win = 1024 * 4096                        # 9 window layers: 4,096 each
    chunk = 2 * body * 1024 + 2 * head + key * (3 * glob + 9 * win)
    step = 2 * (body + head) * 14 + key * (3 * 50_000 + 9 * 30_000)
    return ("smallthinker-serve-mixedctx",
            dict(start=4096, tokens=1024, last=False, real_tokens=1024,
                 kv_tokens_read_global=1, kv_tokens_read_window=1), chunk,
            # the program counts one position a layer for each idle row
            dict(rows=14, slots=16, kv_tokens_read_global=3 * (50_000 + 2),
                 kv_tokens_read_window=9 * (30_000 + 2)), step)


def _gpt2():
    return ("gpt13b-serve-batch",
            dict(start=256, tokens=256, last=False, real_tokens=256),
            _gpt_chunk(256, 256),
            dict(rows=40, slots=48, context_tokens=10_000.0),
            2 * (GPT_BODY + GPT_HEAD) * 40 + GPT_KEY * 10_000)


def _joyai():
    attn = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
            + 32 * 128 * 2048)
    expert = 3 * 2048 * 768
    body = (5 * attn + 3 * 2048 * 7168
            + 4 * (2048 * 256 + (1 + 8) * expert))
    head = 2048 * 129280
    keys = 2048 * 2048 + 2048 * 2049 // 2
    # a chunk meets a key expanded (nope + rope + v), a step a latent row
    # over its width (512 + 64 to score, 512 to sum)
    chunk = 2 * body * 2048 + 2 * head + 5 * 2 * 32 * 320 * keys
    step = 2 * (body + head) * 24 + 2 * 32 * (2 * 512 + 64) * 5 * 200_000
    return ("joyai-serve-longctx",
            dict(start=2048, tokens=2048, last=False, real_tokens=2048,
                 latent_tokens_read=7), chunk,
            dict(rows=24, slots=24, latent_tokens_read=5 * 200_000), step)


def _lfm2():
    body = (12 * 4 * 2048 * 2048                            # 12 conv mixers
            + 4 * (2048 * (32 + 16) * 64 + 32 * 64 * 2048)  # 4 attention
            + 2 * 3 * 2048 * 7168                           # 2 dense MLPs
            + 14 * (2048 * 32 + 4 * 3 * 2048 * 1792))       # router, top 4
    head, key = 2048 * 65536, 4 * 32 * 64
    chunk = 2 * body * 2048 + 2 * head + 4 * key * (2048 * 2049 // 2)
    step = 2 * (body + head) * 90 + key * 4 * 180_000
    return ("lfm2-serve-manyrows",
            dict(start=0, tokens=2048, last=True, real_tokens=2048,
                 conv_tokens=12 * 2048), chunk,
            dict(rows=90, slots=96,
                 kv_tokens_read_global=4 * (180_000 + 6)), step)


def _ling():
    linear = 6 * 2560 * 4096 + 2560 * 32
    latent = (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
              + 32 * 128 * 2560)
    expert = 3 * 2560 * 768
    # outside the chosen experts: the router over the PUBLISHED 512, the
    # shared expert
    body = (7 * linear + latent + 2 * 3 * 2560 * 6144
            + 6 * (2560 * 512 + expert))
    head = 2560 * 39296
    state = 7 * 3 * 2 * 32 * 128 * 128
    # 256 real tokens in a chunk padded to a page of 1,024: the program's
    # linear_tokens says so, and they take a quarter of the pairs computed
    chunk = ((2 * body + state) * 256 + 2 * head + 2 * expert * 750
             + 2 * 32 * 320 * (256 * 257 // 2))
    step = ((2 * (body + head) + state) * 95 + 2 * expert * 1140
            + 2 * 32 * (2 * 512 + 64) * 230_000)
    return ("ling-serve-longgen",
            dict(start=0, tokens=1024, last=True, real_tokens=1024,
                 linear_tokens=7 * 256, assignments=3000,
                 latent_tokens_read=1024), chunk,
            dict(rows=95, slots=96, assignments=1152,
                 latent_tokens_read=230_000 + 1,
                 linear_tokens=7 * 96), step)


def _evabyte():
    body = 8 * (4 * 4096 * 4096 + 3 * 4096 * 11008)
    head, key = 4096 * 320, 4 * 32 * 128
    # positions 2,048..3,071: the own window's 1..1,024 exact rows and the
    # 128 summaries of window 0
    seen = 1024 * 1025 // 2 + 1024 * 128
    chunk = 2 * body * 1024 + 2 * head + 8 * key * seen
    step = 2 * (body + head) * 28 + key * 8 * 40_000
    return ("evabyte-serve-bytedocs",
            dict(start=2048, tokens=1024, last=False, real_tokens=1024,
                 kv_exact_rows_read=1, kv_summary_rows_read=1), chunk,
            dict(rows=28, slots=28, kv_exact_rows_read=8 * 30_000,
                 kv_summary_rows_read=8 * 10_000), step)


def _minicpm_sala():
    mlp = 3 * 4096 * 16384
    sparse = 4096 * (4096 + 2 * 256) + 2 * 4096 * 4096 + mlp
    lightning = 5 * 4096 * 4096 + mlp
    body, head = 2 * sparse + 6 * lightning, 4096 * 73448
    state, key = 2 * 2 * 32 * 128 * 128, 4 * 16 * 128
    # 2,048 queries from a block's edge: of its own block a query at
    # offset o cannot see the 63 - o keys after it, 2,016 a block of 64
    chunk = (2 * body * 2048 + 2 * head + state * 6 * 2048
             + 2 * 8_396_800 * 32 * 128
             + key * (4 * 97 * 2048 * 64 - 4 * 32 * 2016))
    step = (2 * (body + head) * 21 + state * 6 * 21 + 2 * 63_000 * 32 * 128
            + key * (4 * 97 * 21 * 64 - 4 * 63 * 21))
    return ("minicpm-sala-serve-longdoc",
            dict(start=32768, tokens=2048, last=False, real_tokens=2048,
                 kv_blocks_read=4 * 97 * 2048, linear_tokens=6 * 2048,
                 pooled_keys_scored=8_396_800, rows_dense_path=0), chunk,
            dict(rows=21, slots=24, kv_blocks_read=4 * 97 * 21,
                 linear_tokens=6 * 21, pooled_keys_scored=63_000), step)


FAMILIES = [_gpt2, _smallthinker, _joyai, _lfm2, _ling, _evabyte,
            _minicpm_sala]


@pytest.mark.parametrize("case", FAMILIES, ids=lambda f: f.__name__[1:])
@pytest.mark.parametrize("kind", ["chunk", "step"])
def test_model_flops_is_the_count_made_by_hand(case, kind):
    name, chunk, chunk_flops, step, step_flops = case()
    cell = load_cell(BENCH, name)
    call, want = (chunk, chunk_flops) if kind == "chunk" else (step,
                                                               step_flops)
    got = cell.family.model_flops(cell.config, call)
    assert got == pytest.approx(want, rel=1e-12)
    # a real call of the cell is nowhere near the chip's second
    assert 0 < got / PEAK < 0.2


@pytest.mark.parametrize("case", FAMILIES, ids=lambda f: f.__name__[1:])
def test_model_flops_counts_the_model_not_the_call(case):
    """Padding and idle rows add nothing: a last chunk with fewer real
    tokens counts less; a step's rows nobody counted reads None; a step
    with more idle slots and the positions the program counts for them
    reads the same."""
    name, chunk, chunk_flops, step, step_flops = case()
    cell = load_cell(BENCH, name)
    flops = cell.family.model_flops
    fewer = dict(chunk, real_tokens=chunk["real_tokens"] // 2)
    for key in ("linear_tokens", "kv_blocks_read", "pooled_keys_scored"):
        if key in fewer:
            fewer[key] //= 2
    assert 0 < flops(cell.config, fewer) < 0.75 * chunk_flops
    no_rows = {k: v for k, v in step.items() if k != "rows"}
    assert flops(cell.config, no_rows) is None
    if name.startswith(("gpt13b", "minicpm")):
        return      # no per-row counter that holds idle rows
    layers = {"smallthinker-serve-mixedctx": ("kv_tokens_read_global", 3,
                                              "kv_tokens_read_window", 9),
              "joyai-serve-longctx": ("latent_tokens_read", 5),
              "lfm2-serve-manyrows": ("kv_tokens_read_global", 4),
              "ling-serve-longgen": ("latent_tokens_read", 1),
              "evabyte-serve-bytedocs": ("kv_exact_rows_read", 8)}[name]
    more = dict(step, slots=step["slots"] + 10)
    for key, n in zip(layers[::2], layers[1::2]):
        more[key] += 10 * n
    if "assignments" in more:       # the pairs run over every row
        more["assignments"] = (more["assignments"] * more["slots"]
                               // step["slots"])
    assert flops(cell.config, more) == pytest.approx(step_flops, rel=1e-3)


# ------------------------ the sparse roofline counts the least work ----
@pytest.fixture(scope="module")
def longdoc():
    return load_cell(BENCH, "minicpm-sala-serve-longdoc")


BLOCK_FLOPS = 2 * 2 * 64 * 16 * 128


@pytest.mark.parametrize("span,want", [
    # a decode step: K and V of the query's OWN KV head, 32,768 B a block
    (dict(kv_blocks_read=970), (970 * BLOCK_FLOPS, 970 * 32_768)),
    # a chunk past dense_len: ceil((32,768 + 2,048) / 64) = 544 distinct
    # blocks, both KV heads' K and V (65,536 B) in each of 2 sparse layers
    (dict(kv_blocks_read=4 * 97 * 2048, tokens=2048, start=32768,
          rows_dense_path=0),
     (4 * 97 * 2048 * BLOCK_FLOPS, 544 * 2 * 65_536)),
    # ... and never more than the decode form's count: ONE real token of a
    # padded chunk reads 97 blocks a (KV head, layer)
    (dict(kv_blocks_read=4 * 97, tokens=2048, start=16384,
          rows_dense_path=0), (4 * 97 * BLOCK_FLOPS, 4 * 97 * 32_768)),
    # a chunk at or under dense_len: FLOPs alone, as before
    (dict(kv_blocks_read=970, tokens=2048, start=2048,
          rows_dense_path=2048), (970 * BLOCK_FLOPS, 0.0)),
    # a first chunk is the flash kernel's, a span without counts nobody's
    (dict(kv_blocks_read=970, tokens=2048, start=0, rows_dense_path=2048),
     None),
    ({}, None)],
    ids=["decode", "chunk-past-dense_len", "chunk-capped", "chunk-dense",
         "first-chunk", "no-counts"])
def test_paged_block_reads_counts_the_least_work(longdoc, span, want):
    got = longdoc.family.SPAN_COSTS["paged_block_reads"](longdoc.config,
                                                         span)
    assert got == want
    assert longdoc.family.block_kv_bytes(longdoc.config, 2) == 65_536
    assert not hasattr(longdoc.family, "block_copy_bytes")


def test_the_sparse_roofline_has_room_for_a_tenfold_faster_kernel(longdoc):
    """The traced window of PR 45 in small: 150 chunks past dense_len and
    295 steps of 21.6 rows took the kernel 15.04 s.  By the copies it read
    62.8 %, so a kernel 1.6 x faster read over 100 %; by the least work it
    reads a few per cent, and with ``kv_blocks_read`` held and the kernel's
    time divided by TEN still under 100 %."""
    chunk = _span("serve_prefill_chunk", kv_blocks_read=4 * 97 * 2048,
                  tokens=2048, start=32768, rows_dense_path=0)
    step = _span("serve_decode", kv_blocks_read=4 * 97 * 22)
    records = [chunk] * 150 + [step] * 295
    spec = _spec("paged_decode_roofline.longdoc")
    now = read_metric(spec, _run(longdoc, records,
                                 {"paged_flash_decode.3": 15.04}))
    assert 0 < now < 10
    least = 150 * 4 * 97 * 2048 * BLOCK_FLOPS / PEAK \
        + 295 * 4 * 97 * 22 * 32_768 / 819e9
    assert now == pytest.approx(100 * least / 15.04)
    tenfold = read_metric(spec, _run(longdoc, records,
                                     {"paged_flash_decode.3": 1.504}))
    assert tenfold == pytest.approx(10 * now) and tenfold < 100
    # the regex is the kernel's name whatever body calls it
    assert "paged_flash_decode" in spec["args"]["regex"]
    assert "paged_flash_decode" in spec["note"]


# ----------------------------------------------- the small repairs ----
@pytest.mark.parametrize("name,collective", [
    ("reduce_scatter.12", True), ("reduce-scatter.3", True),
    ("all-gather.7", True), ("all_gather_fusion", True),
    ("all-reduce", True), ("all-to-all.1", True),
    ("collective-permute.2", True), ("fusion.12", False),
    ("scatter.4", False), ("multiply_reduce_fusion", False)])
def test_the_collectives_answer_to_both_spellings(name, collective):
    assert bool(COLLECTIVE_RE.match(name)) == collective


def test_collective_exposed_ms_sees_the_gradient_scatter():
    """The x4 step of PR 35 in small: 18 steps, the scatter 1.195 s and the
    unfused gathers 0.144 s of the op line — 74.4 ms a step, not the 7.98
    the hyphen-only pattern read."""
    cell = load_cell(BENCH, "gpt13b-train-zero-x4")
    run = _run(cell, [], {"reduce_scatter.5": 1.0, "reduce_scatter.9": 0.195,
                          "all-gather.2": 0.144, "fusion.1": 2.2}, steps=18)
    got = read_metric(_spec("collective_exposed_ms"), run)
    assert got == pytest.approx(1000 * 1.339 / 18)


@pytest.mark.parametrize("attempted,ok", [(0, False), (1, True), (-1, False),
                                          (True, False)])
def test_a_line_that_attempted_nothing_is_refused(attempted, ok):
    line = {"correct": True, "attempted": attempted, "failed": 0,
            "metrics": {"serve_tok_s": {"value": 257.3, "unit": "tokens/s"},
                        "setup_s": {"value": 102.7, "unit": "s"}},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 15_240_000_000},
            "compared": {"logit_rms": [0.07, 0.106],
                         "worst_gap": [0.1, 0.4]}}
    faults = contract.check_line(line, BENCH, "minicpm-sala-serve-longdoc",
                                 False)
    assert (faults == []) == ok
    if not ok:
        assert "attempted" in faults[0]


def test_lings_stored_leaf_is_worded_as_it_is_since_pr_40():
    config = load_cell(BENCH, "ling-serve-longgen").config
    entry = config["stored"]["state_entry"]
    assert "conv_state [P, 16, 2304] bf16" in entry
    assert "[P, 36864]" not in entry and "73,728 B a page" in entry
    assert 16 * 2304 == 3 * 12_288 == 36_864


# ------------------- a traced open-loop run outlasts the profiler ----
@pytest.mark.parametrize("cell_name,traced,phases", [
    ("gpt13b-serve-loaded", False, [25, 51.0, 15]),
    ("gpt13b-serve-loaded", True, [25, 6.0, 15, serve.STOP_PHASE_S]),
    ("gpt13b-serve-longprompt", True, [25, 6.0, 15, serve.STOP_PHASE_S]),
    ("gpt13b-serve-batch", True, [20, 6.0, 15]),
    ("minicpm-sala-serve-longdoc", True, [40, 36.0, 15])])
def test_the_schedule_of_a_run(cell_name, traced, phases):
    mix = load_cell(BENCH, cell_name).traffic
    assert serve.load_phases(mix, phases[1], traced) == phases


def test_the_stop_phase_leaves_the_other_phases_as_they_were():
    """The requests a traced loaded run prepares are the untraced
    schedule's, to the token, and then 90 s more of arrivals: the profiler's
    stop (11.0-13.3 s, PR 37) no longer has to end inside the 15 s tail."""
    mix = load_cell(BENCH, "gpt13b-serve-loaded").traffic
    plain = traffic.make_requests(
        mix, 2147494601, serve.load_phases(mix, 6.0, False), 50257)
    traced = traffic.make_requests(
        mix, 2147494601, serve.load_phases(mix, 6.0, True), 50257)
    assert len(traced) > len(plain) + 600
    for a, b in zip(plain, traced):
        assert (a.due_s, a.max_new_tokens) == (b.due_s, b.max_new_tokens)
        assert (a.prompt == b.prompt).all()
    assert plain[-1].due_s < 25 + 6 + 15 <= traced[len(plain)].due_s
    assert traced[-1].due_s > 25 + 6 + 15 + serve.STOP_PHASE_S - 5
    # the untraced run of the cell: the three phases of before PR 46
    assert serve.load_phases(mix, 51.0, False) == [
        mix["ramp_s"], 51.0, mix["drain_s"] + 5]
