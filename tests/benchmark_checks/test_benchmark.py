"""Checks of the benchmark's own yardstick: CPU only, no TPU topology
call, each case well under a second.  They live under BENCHMARK.json's
``paths``, so no later PR can edit them."""

import copy
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import structure  # noqa: E402  (beside this file)
from benchmark import families, twins  # noqa: E402
from benchmark.lib import (agreement, contract, costs, peaks, stats,  # noqa: E402
                           traffic, xplane)
from benchmark.lib.runtime import load_benchmark, load_cell, load_json  # noqa: E402
from benchmark.readers import ReaderInput, read_metric  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = load_benchmark()
GPT2 = families.load("gpt2", ROOT)
RESNET50 = families.load("resnet50", ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
END_TO_END = {m["name"] for m in BENCH["end_to_end"]}
# the per-layer metrics the dense family's serving cells need (the other
# families' are in their own test files; structure.py)
_TURN = ["serve_mfu", "host_admit_ms", "host_chunk_ms", "host_launch_ms",
         "host_emit_ms", "idle_host_pct", "idle_wait_pct",
         "prefill_chunk_device_ms"]
NEEDS = {
    "gpt13b-serve-loaded": _TURN + [
        "decode_step_ms.loaded", "device_idle_pct.loaded",
        "prefill_chunk_ms.loaded", "paged_decode_kernel_ms.loaded",
        "paged_decode_roofline", "queue_wait_p90_ms"],
    "gpt13b-serve-longprompt": _TURN + [
        "decode_step_ms", "device_idle_pct", "prefill_chunk_ms",
        "paged_decode_kernel_ms", "flash_prefill_kernel_ms",
        "queue_wait_p90_ms.longprompt", "ttft_p90_ms.longprompt",
        "gap_p95_ms.longprompt"],
    "gpt13b-serve-batch": _TURN + [
        "decode_step_ms", "device_idle_pct", "paged_decode_roofline"],
}


# ------------------------------------------------------------ xplane.py --
def test_recorded_trace_reduces_to_the_checked_in_numbers():
    """A cut of a trace recorded on the v5e in this PR's first chip call."""
    want = load_json(os.path.join(DATA, "trace_cut.expected.json"))
    r = xplane.reduce_trace(xplane.load(os.path.join(DATA, "trace_cut.json")))
    assert 0 < r.busy_s <= r.window_s
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert sum(r.self_s.values()) == pytest.approx(r.busy_s, rel=1e-6)
    for pattern, seconds in want["kernel_s"].items():
        assert r.kernel_s(pattern) == pytest.approx(seconds, rel=1e-9)
    for pattern, n in want["kernel_calls"].items():
        assert r.kernel_calls(pattern) == n
    assert r.kernel_s("no_such_kernel_name") is None
    assert [n for n, _ in r.top_ops(3)] == want["top_ops"]


def _trace(op_events, extra_lines=(), extra_planes=()):
    lines = [{"name": "XLA Ops", "events": op_events}] + [
        {"name": n, "events": e} for n, e in extra_lines]
    planes = [{"name": "/device:TPU:0", "lines": lines}] + [
        {"name": n, "lines": [{"name": "XLA Ops", "events": e}]}
        for n, e in extra_planes]
    return {"planes": [{"name": "/host:CPU", "lines": []}] + planes}


SYNTHETIC = {
    # back to back, one gap of 100 ns
    "sequential": (_trace([["a", 0, 100], ["b", 100, 100], ["c", 300, 100]]),
                   400e-9, 300e-9),
    # a while op holds its body: the union, not the sum
    "nested": (_trace([["while", 0, 1000], ["a", 100, 300],
                       ["b", 500, 400]]), 1000e-9, 1000e-9),
    # "XLA Modules" and "Steps" cover the same time again: one line only
    "other_lines": (_trace([["a", 0, 100], ["b", 200, 100]],
                           extra_lines=[("XLA Modules", [["m", 0, 300]]),
                                        ("Steps", [["0", 0, 300]])]),
                    300e-9, 200e-9),
    # four devices: device 0 alone, or busy would pass the window
    "four_devices": (_trace([["a", 0, 100], ["b", 200, 100]],
                            extra_planes=[(f"/device:TPU:{i}",
                                           [["a", 0, 300]])
                                          for i in (1, 2, 3)]),
                     300e-9, 200e-9),
    # events out of order in the file
    "unsorted": (_trace([["b", 200, 100], ["a", 0, 100]]), 300e-9, 200e-9),
}


@pytest.mark.parametrize("case", sorted(SYNTHETIC))
def test_busy_is_a_union_on_one_line_of_one_device(case):
    trace, window_s, busy_s = SYNTHETIC[case]
    r = xplane.reduce_trace(trace)
    assert r.window_s == pytest.approx(window_s)
    assert r.busy_s == pytest.approx(busy_s)
    assert 0 < r.busy_s <= r.window_s
    assert sum(r.self_s.values()) == pytest.approx(r.busy_s)


def test_self_time_and_gaps_and_collectives():
    r = xplane.reduce_trace(_trace(
        [["while", 0, 1000], ["fusion.1", 100, 300],
         ["all-gather-done.2", 500, 400], ["paged_flash_decode.3", 1500, 250]]))
    assert r.self_s["while"] == pytest.approx(300e-9)
    assert r.kernel_s(xplane.COLLECTIVE_RE.pattern) == pytest.approx(400e-9)
    assert r.kernel_s("paged_flash_decode") == pytest.approx(250e-9)
    assert r.kernel_calls("paged_flash_decode") == 1
    (name, gap), = r.top_gaps()
    assert gap == pytest.approx(500e-9) and "paged_flash_decode" in name


@pytest.mark.parametrize("trace", [
    {"planes": [{"name": "/host:CPU", "lines": []}]},
    _trace([]) | {"planes": [{"name": "/device:TPU:0", "lines": []}]},
], ids=["no_device_plane", "no_op_line"])
def test_a_trace_without_the_device_line_is_an_error_not_a_zero(trace):
    with pytest.raises(LookupError):
        xplane.reduce_trace(trace)


# ---------------------------------------------------------- contract.py --
def good_line(cell, traced):
    return good_line_of(BENCH, cell, traced)


def good_line_of(bench, cell, traced):
    metrics = {n: {"value": 12.5, "unit": u} for n, u in
               contract.declared_metrics(bench, cell, traced).items()}
    chips = next(w["chips"] for w in bench["workloads"] if w["name"] == cell)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": chips,
              "memory_peak_bytes": 13958643712}
    line = {"correct": True, "attempted": 400, "failed": 0,
            "metrics": metrics, "device": device}
    if traced:
        device.update(busy_s=4.2, window_s=5.0)
        line["breakdown"] = {"device_ops": [["fusion.1", 1.5]],
                             "idle_gaps": [["after a before b", 0.01]]}
    return line


@pytest.mark.parametrize("traced", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("cell", CELLS)
def test_contract_takes_a_good_line(cell, traced):
    line = good_line(cell, traced)
    assert contract.check_line(line, BENCH, cell, traced) == []
    # and it survives the trip through the text the driver reads
    assert contract.check_line(json.loads(json.dumps(line)), BENCH, cell,
                               traced) == []


def _drop_metric(line):
    line["metrics"].pop(sorted(line["metrics"])[0])


def _set_metric(value):
    def edit(line):
        line["metrics"][sorted(line["metrics"])[0]]["value"] = value
    return edit


def _set_device(**kw):
    def edit(line):
        line["device"].update(kw)
    return edit


def _long_unit(line):
    line["metrics"][sorted(line["metrics"])[0]]["unit"] = "tokens_per_second"


def _extra_metric(line):
    line["metrics"]["made_up"] = {"value": 1.0, "unit": "ms"}


def _drop_key(key):
    def edit(line):
        line.pop(key)
    return edit


def _drop_device(key):
    def edit(line):
        line["device"].pop(key)
    return edit


REFUSED = {
    "missing_metric": (_drop_metric, True),
    "nan": (_set_metric(float("nan")), True),
    "infinite": (_set_metric(float("inf")), False),
    "null_value": (_set_metric(None), True),
    "value_as_text": (_set_metric("12.5"), False),
    "busy_zero": (_set_device(busy_s=0.0), True),
    "busy_over_window": (_set_device(busy_s=5.5), True),
    "no_busy": (_drop_device("busy_s"), True),
    "no_window": (_drop_device("window_s"), True),
    "wrong_count": (_set_device(count=3), False),
    "cpu": (_set_device(platform="cpu"), False),
    "no_memory_peak": (_set_device(memory_peak_bytes=0), False),
    "unit_over_16": (_long_unit, False),
    "undeclared_metric": (_extra_metric, True),
    "no_correct": (_drop_key("correct"), False),
    "no_device": (_drop_key("device"), True),
    "no_failed": (_drop_key("failed"), False),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_contract_refuses(case):
    edit, traced = REFUSED[case]
    line = good_line("gpt13b-serve-loaded", traced)
    edit(line)
    assert contract.check_line(line, BENCH, "gpt13b-serve-loaded", traced)


def test_contract_refuses_what_is_not_an_object():
    assert contract.check_line([1, 2], BENCH, CELLS[0], False)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_line(capsys):
    from benchmark import run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""


# ----------------------------------------------------------- traffic.py --
MIXES = sorted({w["traffic"] for w in BENCH["workloads"]})
REQUEST_MIXES = [m for m in MIXES if load_json(os.path.join(
    ROOT, "benchmark", "traffic", m + ".json"))["kind"] == "requests"]


def _mix(name):
    return load_json(os.path.join(ROOT, "benchmark", "traffic",
                                  name + ".json"))


def _key(reqs):
    return [(r.due_s, r.prompt.tobytes(), r.max_new_tokens) for r in reqs]


@pytest.mark.parametrize("name", REQUEST_MIXES)
def test_same_seed_same_requests_other_seed_same_schedule(name):
    mix = _mix(name)
    phases = [5.0, 20.0, 5.0]
    a = traffic.make_requests(mix, 2_400_000_011, phases, 50257)
    b = traffic.make_requests(mix, 2_400_000_011, phases, 50257)
    c = traffic.make_requests(mix, 5, phases, 50257)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    schedule = lambda rs: [(r.due_s, len(r.prompt), r.max_new_tokens)
                           for r in rs]
    assert schedule(a) == schedule(c)    # the schedule is the mix's own
    p, o = mix["prompt_len"], mix["output_len"]
    assert all(len(r.prompt) in p["snap_to"] for r in a)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in a)
    if mix["arrivals"] == "poisson":
        assert np.all(np.diff([r.due_s for r in a]) >= 0)
        window = [r for r in a if 5.0 <= r.due_s < 25.0]
        assert len(window) / 20.0 == pytest.approx(mix["rate_per_s"],
                                                   rel=0.4)


@pytest.mark.parametrize("name", REQUEST_MIXES)
def test_prefill_bodies_follow_the_engines_chunk_plan(name):
    from dtf_tpu.serve.engine import chunk_plan
    mix = _mix(name)
    want = set()
    for plen in mix["prompt_len"]["snap_to"]:
        for start, clen in chunk_plan(plen, 256, 16):
            want.add((clen, start == 0))
    assert traffic.prefill_bodies(mix, 256, 16) == sorted(want)


# chat-closed-48.json as it was before PR 26 raised prepare_per_s to 64:
# what the batch cell's ledger lines were measured on
CLOSED_48_AT_16 = dict(_mix("chat-closed-48"), prepare_per_s=16.0)
CLOSED_48_AT_16.pop("prepare_block_per_s")


@pytest.mark.parametrize("length_s", [20.0, 51.0, 15.0, 7.3])
def test_closed_mix_keeps_the_block_it_had_at_16_a_second(length_s):
    mix = _mix("chat-closed-48")
    assert mix["prepare_per_s"] == 64.0
    assert mix["prepare_block_per_s"] == CLOSED_48_AT_16["prepare_per_s"]
    n_old = math.ceil(16.0 * length_s)
    for phase in range(3):
        old, _ = traffic.phase_draw(CLOSED_48_AT_16, phase, length_s)
        new, gaps = traffic.phase_draw(mix, phase, length_s)
        assert len(old) == n_old and len(new) == math.ceil(64.0 * length_s)
        assert len(gaps) == len(new)
        assert np.array_equal(new[:n_old], old)     # size for size
        assert not np.array_equal(new[n_old:2 * n_old][:len(old)], old)


def test_closed_clients_meet_the_old_requests_first_then_the_further():
    """The clients take the list in order: a system at the old file's speed
    is sent the requests the old file sent it, token for token."""
    phases = [20.0, 51.0, 15.0]
    old = traffic.make_requests(CLOSED_48_AT_16, 2_400_000_011, phases, 50257)
    new = traffic.make_requests(_mix("chat-closed-48"), 2_400_000_011,
                                phases, 50257)
    assert len(old) == 320 + 816 + 240 and len(new) == 4 * len(old)
    assert _key(new[:len(old)]) == _key(old)
    sizes = lambda rs: sorted((len(r.prompt), r.max_new_tokens) for r in rs)
    drawn = np.concatenate([traffic.phase_draw(_mix("chat-closed-48"), k, s)[0]
                            for k, s in enumerate(phases)])
    assert sizes(new) == sorted(map(tuple, drawn.tolist()))


# ------------------------------------------------- the files, by name ----
@pytest.mark.parametrize("cell", CELLS)
def test_every_file_of_a_cell_is_found_by_name(cell):
    c = load_cell(BENCH, cell)
    assert c.workload["driver"] in ("train", "serve")
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "drivers", c.workload["driver"] + ".py"))
    assert c.config["reduced"] == next(
        x["reduced"] for x in BENCH["configs"] if x["name"] == c.config_name)
    assert c.per_layer, "every cell reports at least one per-layer metric"
    e2e = contract.declared_metrics(BENCH, cell, False)
    assert "setup_s" in e2e and len(e2e) >= 2


PAIRS = [(m["name"], cell) for m in BENCH["per_layer"]
         for cell in m["workloads"]]


@pytest.mark.parametrize("metric,cell", PAIRS,
                         ids=[f"{m}-{c}" for m, c in PAIRS])
def test_layer_metric_has_a_reader_and_moves_what_its_cell_reports(metric,
                                                                   cell):
    """One case a (metric, cell) pair: an entry is ONE measurement with a
    list of cells, and each cell it lists is held on its own."""
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    spec = load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  metric + ".json"))
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
        metric, entry["unit"], entry["layer"], entry["moves"])
    assert entry["moves"] in END_TO_END and entry["moves"] != "setup_s"
    assert entry["moves"] in contract.declared_metrics(BENCH, cell, False)
    loaded = load_cell(BENCH, cell)
    assert metric in loaded.per_layer
    assert structure.cost_is_the_familys(loaded, spec)
    # a reader that finds nothing returns nothing
    run = ReaderInput(cell=loaded, device_kind="TPU v5 lite", reduction=None,
                      driver={"window_wall": (0.0, 1.0)})
    assert read_metric(spec, run) is None


@pytest.mark.parametrize("cell", sorted(NEEDS))
def test_a_dense_serving_cell_is_listed_by_what_it_needs(cell):
    loaded, mine = structure.check_cell(BENCH, ROOT, cell, NEEDS[cell])
    assert loaded.config_name == "cerebras-gpt-1.3b"
    # an entry has ONE moves: the loaded cell's copies move its tails
    moved = {m["moves"] for m in mine.values()}
    assert moved == ({"serve_tok_s", "ttft_p90_ms", "gap_p95_ms"}
                     if cell == "gpt13b-serve-loaded" else {"serve_tok_s"})


def test_no_two_entries_are_one_measurement_under_two_names():
    """``python3 -m benchmark.twins``: every entry has its file and its
    ``workloads`` list, every file its entry, and two entries whose files
    are equal but for ``name`` and ``note`` are ONE entry with both cells,
    unless a note names the other and says what differs."""
    entries, files, faults = twins.declared(ROOT)
    assert faults == []
    assert twins.twins(entries, files) == []
    # and the tool sees twins where there are some: a copy under a suffix
    name = "decode_step_ms"
    entries[name + ".copy"] = dict(entries[name], name=name + ".copy")
    files[name + ".copy"] = dict(files[name], name=name + ".copy",
                                 note="the same, for one cell more")
    assert twins.twins(entries, files) == [[name, name + ".copy"]]
    files[name + ".copy"]["note"] = f"As {name}, but it moves nothing."
    assert twins.twins(entries, files) == []


def test_benchmark_json_keeps_to_the_contracts_shapes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert contract.NAME_RE.match(m["name"])
        assert contract.UNIT_RE.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    # the contract's caps, held HERE and nowhere else: a family's test
    # asserts what is its own (structure.py)
    assert len(BENCH["per_layer"]) <= 128 and len(BENCH["end_to_end"]) <= 16
    assert len(BENCH["workloads"]) <= 24 and len(BENCH["configs"]) <= 24
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    assert all(m.get("workloads") for m in BENCH["per_layer"])
    # 2 + 14 runs a cell, each run_seconds + 60 s, 180 s a cell to compile,
    # 1200 s spare, at the full 24 cells
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


# ------------------------------------------------------------- costs.py --
def test_gpt_flops_per_token_match_the_hand_count():
    cfg = load_cell(BENCH, "gpt13b-train-zero-x4")
    assert cfg.family is GPT2
    per_token = GPT2.train_flops_per_sample(
        cfg.config, cfg.traffic) / cfg.traffic["seq_len"]
    # 6 x 1.311e9 matmul parameters + 3 x 24 layers x 2 x 2048 x 2048
    assert per_token == pytest.approx(8.5e9, rel=0.05)
    assert GPT2.gpt_matmul_params(cfg.config) == 24 * (
        4 * 2048 ** 2 + 2 * 2048 * 8192) + 2048 * 50257


def test_resnet50_flops_per_image_match_the_hand_count():
    cfg = load_cell(BENCH, "resnet50-train")
    assert cfg.family is RESNET50
    fwd = RESNET50.resnet50_forward_flops_per_image(cfg.config)
    assert fwd == pytest.approx(2 * 4.09e9, rel=0.03)   # 4.1 GMACs, v1.5
    assert RESNET50.train_flops_per_sample(
        cfg.config, cfg.traffic) == pytest.approx(2.4e10, rel=0.05)


def test_flash_costs_and_the_roofline_reader():
    f, b = costs.flash_fwd(2, 16, 2048, 128)        # 2 sequences a chip
    assert f == 2 * 2 * 16 * 2048 * 2048 * 128      # causal half of 4·B·H·S²·D
    assert b == 4 * 2 * 16 * 2048 * 128 * 2
    assert costs.flash_bwd(2, 16, 2048, 128)[0] == 2.5 * f
    # decode attention reads every cached K and V row once: 2 FLOPs a byte
    pf, pb = costs.paged_decode(10_000, 16, 128)
    assert pb == 2 * 10_000 * 16 * 128 * 2 and pf == 2 * pb / 2
    cell = load_cell(BENCH, "gpt13b-train-zero-x4")
    flops, _ = GPT2.STEP_COSTS["flash_train_step"](cell.config, cell.traffic, 4)
    assert flops == 24 * 3.5 * f
    # a kernel that took exactly its compute-bound least time reads 100 %
    least = flops / peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    trace = _trace([["flash_fwd.1", 0, least * 1e9 * 2 / 3.5],
                    ["flash_bwd_fused.2", least * 1e9, least * 1e9 * 5 / 3.5]])
    spec = load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  "flash_attention_roofline.json"))
    run = ReaderInput(cell=cell, device_kind="TPU v5 lite",
                      reduction=xplane.reduce_trace(trace),
                      driver={"steps": 2})
    assert read_metric(spec, run) == pytest.approx(100.0 * 2 * 3.5 / 7)


def test_an_unknown_device_has_no_peak():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("q", [0, 50, 90, 95, 100])
def test_percentile_is_numpys_linear_one(q):
    data = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    assert stats.percentile(data, q) == pytest.approx(np.percentile(data, q))


# ------------------------------------- paged_decode_roofline's reader ----
def _paged_roofline_run(cell_name, kernel_ns, histogram):
    cell = load_cell(BENCH, cell_name)
    trace = _trace([["fusion.9", 0, 4e6],
                    ["paged_flash_decode.7", 5e6, kernel_ns]])
    return ReaderInput(cell=cell, device_kind="TPU v5 lite",
                       reduction=xplane.reduce_trace(trace),
                       driver={"decode_steps": 10,
                               "histograms": {"serve_decode_live_pages":
                                              histogram}})


@pytest.mark.parametrize("cell_name", next(
    m["workloads"] for m in BENCH["per_layer"]
    if m["name"] == "paged_decode_roofline"))
def test_paged_decode_roofline_reads_100_at_the_byte_floor(cell_name):
    spec = load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  "paged_decode_roofline.json"))
    # 10 decode steps of 1,283 live pages each; a page of 16 tokens is
    # 16 x 196,608 bytes of K and V over the 24 layers
    nbytes = 10 * 1283 * 16 * 196_608
    floor_ns = 1e9 * nbytes / 819e9
    hist = {"count": 10, "mean": 1283.0, "q": {}}
    at_floor = _paged_roofline_run(cell_name, floor_ns, hist)
    assert read_metric(spec, at_floor) == pytest.approx(100.0)
    twice = _paged_roofline_run(cell_name, 2 * floor_ns, hist)
    assert read_metric(spec, twice) == pytest.approx(50.0)
    # the count is the family's: bytes bound, 2 FLOPs a byte
    flops, counted = GPT2.COUNTED_COSTS["paged_decode_context"](
        at_floor.cell.config, 10 * 1283 * 16)
    assert counted == nbytes and flops == nbytes
    assert flops / 197e12 < counted / 819e9


@pytest.mark.parametrize("histogram", [
    None, {"count": 0, "mean": 0.0, "q": {}}], ids=["absent", "no_samples"])
def test_paged_decode_roofline_reads_nothing_without_samples(histogram):
    spec = load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  "paged_decode_roofline.json"))
    run = _paged_roofline_run("gpt13b-serve-batch", 1e6, histogram)
    if histogram is None:
        run.driver["histograms"].clear()
    assert read_metric(spec, run) is None


# ---------------------- tails recorded per layer where they are not judged --
@pytest.mark.parametrize("metric,series,q", [
    ("ttft_p90_ms.longprompt", "ttft_ms", 90),
    ("gap_p95_ms.longprompt", "gap_ms", 95)])
def test_client_reader_reads_what_the_clients_saw(metric, series, q):
    spec = load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  metric + ".json"))
    cell = load_cell(BENCH, "gpt13b-serve-longprompt")
    client = {"ttft_ms": {50: 230.0, 90: 356.0, 99: 700.0},
              "gap_ms": {50: 18.0, 95: 36.7, 99: 41.0}}

    def run_with(c):
        return ReaderInput(cell=cell, device_kind="TPU v5 lite",
                           reduction=None, driver={"client": c})
    assert read_metric(spec, run_with(client)) == client[series][q]
    # a window in which no request got a token: nothing to read
    assert read_metric(spec, run_with(dict(client, **{series: None}))) is None
    # the tail is judged end to end in the loaded cell alone, and every
    # per-layer metric of this cell moves a metric the cell's line carries
    e2e = contract.declared_metrics(BENCH, cell.name, False)
    assert metric.split(".")[0] not in e2e
    assert metric.split(".")[0] in contract.declared_metrics(
        BENCH, "gpt13b-serve-loaded", False)


@pytest.mark.parametrize("cell,judged", [
    ("gpt13b-serve-loaded",
     {"serve_tok_s", "ttft_p90_ms", "gap_p95_ms", "setup_s"}),
    ("gpt13b-serve-longprompt", {"serve_tok_s", "setup_s"}),
    ("gpt13b-serve-batch", {"serve_tok_s", "setup_s"})])
def test_the_line_carries_the_numbers_judged_in_its_cell(cell, judged):
    from benchmark.run import end_to_end_metrics
    taken = {"serve_tok_s": 48.5, "ttft_p90_ms": 356.0, "gap_p95_ms": 36.7,
             "setup_s": 70.0}
    metrics = end_to_end_metrics(BENCH, cell, taken)
    assert set(metrics) == judged
    assert metrics["serve_tok_s"] == {"value": 48.5, "unit": "tokens/s"}
    line = dict(good_line(cell, False), metrics=metrics)
    assert contract.check_line(line, BENCH, cell, False) == []
    # a number the driver did not take is missing, which the contract refuses
    taken.pop("serve_tok_s")
    line["metrics"] = end_to_end_metrics(BENCH, cell, taken)
    assert any("serve_tok_s" in f for f in
               contract.check_line(line, BENCH, cell, False))


# ------------------------------------------------ a family is its files --
HARNESS_DIRS = ("", "drivers", "readers", "lib")


def _harness_files():
    bench = os.path.join(ROOT, "benchmark")
    for sub in HARNESS_DIRS:
        for name in sorted(os.listdir(os.path.join(bench, sub))):
            if name.endswith(".py"):
                yield os.path.join(bench, sub, name)


def test_no_harness_file_names_a_family_or_imports_a_reference():
    """run.py, rehearse.py, sweep.py, sets.py, drivers/, readers/ and lib/
    find a family by the name in the configuration and nothing else; only a
    family's own files import a ``reference_*`` module."""
    family_names = [f[:-3] for f in os.listdir(os.path.join(
        ROOT, "benchmark", "families"))
        if f.endswith(".py") and not f.startswith(("_", "reference_"))]
    assert {"gpt2", "resnet50"} <= set(family_names)
    banned = re.compile(
        r"reference_\w+|SAMPLE_FLOPS|\b(TOY|STEP_COSTS)\s*=|(?<!\.)\b(TOY|STEP_COSTS)\[|"
        + "|".join(rf"[\"']{re.escape(n)}[\"']" for n in family_names))
    hits = []
    for path in _harness_files():
        for n, line in enumerate(open(path), 1):
            if banned.search(line.split("#")[0]):
                hits.append(f"{os.path.relpath(path, ROOT)}:{n}: "
                            f"{line.strip()}")
    assert not hits, "\n".join(hits)
    for root, _, names in os.walk(os.path.join(ROOT, "benchmark")):
        for name in names:
            if not name.endswith(".py") or root.endswith("families"):
                continue
            text = open(os.path.join(root, name)).read()
            assert not re.search(r"^\s*(from|import)\s.*reference_", text,
                                 re.M), name


def _copy_of_the_data(tmp_path, bench, files=()):
    """A copy of the benchmark's data files (no harness code) with
    ``files`` written in as new ones and ``bench`` as its BENCHMARK.json."""
    root = str(tmp_path)
    for sub in ("configs", "traffic", "workloads", "layer_metrics",
                "families"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub),
                        os.path.join(root, "benchmark", sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    for path, body in files:
        assert not os.path.exists(os.path.join(root, path)), path
        with open(os.path.join(root, path), "w") as f:
            f.write(body)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_a_family_without_a_reference_cannot_be_served(tmp_path):
    bench = copy.deepcopy(BENCH)
    bench["workloads"].append({"name": "resnet50-serve", "chips": 1,
                               "config": "resnet50-v1.5",
                               "traffic": "chat-poisson-loaded", "why": "x"})
    served = open(os.path.join(ROOT, "benchmark", "workloads",
                               "gpt13b-serve-loaded.json")).read()
    root = _copy_of_the_data(
        tmp_path, bench, [("benchmark/workloads/resnet50-serve.json", served)])
    with pytest.raises(ValueError, match="names no plain reference"):
        load_cell(bench, "resnet50-serve", root=root)
    assert load_cell(bench, "resnet50-train",
                     root=root).config["reference"] is None


REFERENCE_FILES = {
    "whole": "def forward(params, tokens):\n    return tokens\n\n\n"
             "def served_tokens_agree(*a):\n    return {'ok': True}\n",
    "no_comparison": "def forward(params, tokens):\n    return tokens\n",
    "missing": None,
}


@pytest.mark.parametrize("case", sorted(REFERENCE_FILES))
def test_the_reference_is_the_file_the_configuration_names(tmp_path, case):
    """One source of the path: the configuration's ``reference``.  A file
    that is not there, or lacks ``forward`` or ``served_tokens_agree``, is
    an error when the driver loads it."""
    body = REFERENCE_FILES[case]
    named = "benchmark/families/reference_other.py"
    root = _copy_of_the_data(tmp_path, BENCH,
                             [(named, body)] if body else [])
    path = os.path.join(root, "benchmark", "configs", "cerebras-gpt-1.3b.json")
    config = dict(load_json(path), reference=named)
    with open(path, "w") as f:
        json.dump(config, f)
    cell = load_cell(BENCH, "gpt13b-serve-batch", root=root)
    assert cell.config["reference"] == named
    if case == "whole":
        module = families.load_reference(cell.config, root)
        assert module.__file__ == os.path.join(root, named)
        assert module.served_tokens_agree() == {"ok": True}
    else:
        with pytest.raises(FileNotFoundError if body is None
                           else AttributeError):
            families.load_reference(cell.config, root)


def test_a_family_file_without_the_interface_is_refused(tmp_path):
    root = _copy_of_the_data(tmp_path, BENCH, [
        ("benchmark/families/halfdone.py", "TOY = {}\n"),
        ("benchmark/families/costless.py",
         "TOY = {}\n\n\ndef train_flops_per_sample(config, traffic):\n"
         "    return 1.0\n")])
    with pytest.raises(AttributeError, match="train_flops_per_sample"):
        families.load("halfdone", root)
    with pytest.raises(FileNotFoundError):
        families.load("never_written", root)
    # cost functions are named by layer_metrics files: none named, none owed
    costless = families.load("costless", root)
    assert costless.STEP_COSTS == {} and costless.COUNTED_COSTS == {}


# ----------------------------- a later PR adds files and entries only ----
def _readme_example():
    """(files, BENCHMARK.json entries) of benchmark/README.md's worked
    example: every block that follows a path in backquotes."""
    readme = open(os.path.join(ROOT, "benchmark", "README.md")).read()
    blocks = re.findall(
        r"`([\w./\-]+\.(?:json|py))`[^\n]*\n+```(?:json|python)\n(.*?)```",
        readme, re.S)
    files = dict(blocks)
    assert len(files) == len(blocks)
    entries = json.loads(files.pop("BENCHMARK.json"))
    return sorted(files.items()), entries


def _bench_with(entries):
    """BENCHMARK.json with the README's entries appended to its lists and
    each new cell's name appended to the ``workloads`` of the entries the
    README says it joins (``joins``: an edit of lists, not a key)."""
    bench = copy.deepcopy(BENCH)
    entries = dict(entries)
    joins = entries.pop("joins")
    for key, more in entries.items():
        bench[key].extend(more)
    assert sorted(joins) == sorted(w["name"] for w in entries["workloads"])
    for cell, names in joins.items():
        for name in names:
            entry = next(m for m in bench["end_to_end"] + bench["per_layer"]
                         if m["name"] == name)
            entry["workloads"].append(cell)
    return bench


def test_readme_examples_load_through_the_harness(tmp_path):
    """The README's worked examples — a family with its reference, a
    configuration, two traffic mixes, two cells and a per-layer metric —
    written as new files into a copy of the benchmark's data and found by
    name, with no file of the harness edited."""
    files, entries = _readme_example()
    assert len(files) >= 8
    assert {os.path.dirname(p) for p, _ in files} == {
        "benchmark/" + d for d in ("families", "configs", "traffic",
                                   "workloads", "layer_metrics")}
    bench = _bench_with(entries)
    root = _copy_of_the_data(tmp_path, bench, files)
    new_family = load_json(os.path.join(
        root, entries["configs"][0]["file"]))["family"]
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "families", new_family + ".py"))
    for entry in entries["workloads"]:
        cell = load_cell(bench, entry["name"], root=root)
        assert cell.config_name == entries["configs"][0]["name"]
        assert cell.family.__file__.startswith(root)
        assert cell.family.TOY[cell.workload["driver"]]
        flops = cell.family.train_flops_per_sample(
            cell.config, {"seq_len": 1024})
        assert flops == pytest.approx(6 * 5.87e8 * 1024, rel=0.01)
    assert callable(families.load_reference(cell.config, root).forward)
    new_metric = entries["per_layer"][-1]
    assert new_metric["name"] in cell.per_layer
    spec = load_json(os.path.join(root, "benchmark", "layer_metrics",
                                  new_metric["name"] + ".json"))
    trace = _trace([["fusion.9", 0, 4e6], ["convert_element_type.1", 5e6, 2e6]])
    run = ReaderInput(cell=cell, device_kind="TPU v5 lite",
                      reduction=xplane.reduce_trace(trace),
                      driver={"steps": 2, "decode_steps": 2})
    assert read_metric(spec, run) == pytest.approx(1.0)     # ms per step
    assert new_metric["name"] in contract.declared_metrics(
        bench, cell.name, True)
    assert contract.check_line(good_line_of(bench, cell.name, False), bench,
                               cell.name, False) == []


@pytest.mark.parametrize("driver", ["serve", "train"])
def test_a_new_family_rehearses_through_the_driver(tmp_path, driver):
    """The README's made-up family, rehearsed at its own toy size on the
    CPU through the harness as it stands: new files in the copy only."""
    files, entries = _readme_example()
    bench = _bench_with(entries)
    root = _copy_of_the_data(tmp_path, bench, files)
    name = next(w["name"] for w in entries["workloads"]
                if load_json(os.path.join(root, "benchmark", "workloads",
                                          w["name"] + ".json"))["driver"]
                == driver)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.rehearse", "--workload", name,
         "--root", root, "--seconds", "2"],
        cwd=ROOT, capture_output=True, text=True, timeout=110,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    said = json.loads(last[last.index("{"):])
    assert said["line"]["correct"] is True and said["reasons"] == []
    assert said["line"]["device"]["platform"] == "cpu"
    assert said["contract_refuses_it_for"]      # never a result


# ------------------------------------- the served-token check can fail ----
def test_a_cell_that_joins_breaks_no_familys_claims(tmp_path):
    """The next ``model_config`` PR, rehearsed: a made-up cell appends its
    name to ``serve_tok_s``, ``serve_mfu``, ``decode_step_ms`` and
    ``device_idle_pct``, one new per-layer entry is added, and every
    family's own claims (the ``NEEDS`` of the test files beside this one,
    through ``structure.check_cell``) hold on that copy as they do here."""
    files, entries = _readme_example()
    joined = entries["joins"]["gpt590m-serve-longoutput"]
    assert {"serve_tok_s", "serve_mfu", "decode_step_ms",
            "device_idle_pct"} <= set(joined)
    bench = _bench_with(entries)
    root = _copy_of_the_data(tmp_path, bench, files)
    needs = structure.family_needs()
    serving = next(m["workloads"] for m in BENCH["end_to_end"]
                   if m["name"] == "serve_tok_s")
    assert set(needs) == set(serving)   # a serving cell's file claims it
    for cell, names in needs.items():
        _, here = structure.check_cell(BENCH, ROOT, cell, names)
        _, there = structure.check_cell(bench, root, cell, names)
        assert sorted(here) == sorted(there)
    # the made-up cells hold the same claims, and the entries grew by one
    new, mine = structure.check_cell(
        bench, root, "gpt590m-serve-longoutput",
        [n for n in joined if n not in END_TO_END] + ["weight_cast_ms"])
    assert sorted(mine) == sorted(new.per_layer)
    structure.check_cell(bench, root, "gpt590m-train",
                         ["step_ms.train", "device_idle_pct.train"],
                         reports="train_mfu")
    assert len(bench["per_layer"]) == len(BENCH["per_layer"]) + 1
    for traced in (False, True):
        for cell in ("gpt590m-serve-longoutput", "gpt590m-train"):
            assert contract.check_line(good_line_of(bench, cell, traced),
                                       bench, cell, traced) == []
    # what arrived changed no line another cell prints
    for cell in CELLS:
        for traced in (False, True):
            assert contract.declared_metrics(bench, cell, traced) \
                == contract.declared_metrics(BENCH, cell, traced)


def _toy_forward(params, tokens):
    """logits[b, s] = params[tokens[b, s]]: a 'model' the check can be shown
    on without a model."""
    return params[tokens]


def _toy_case(noise, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    table = rng.normal(size=(16, 64)).astype(np.float32)
    prompts = [rng.integers(0, 16, size=n) for n in (5, 9)]
    served = agreement.greedy_tokens(_toy_forward, jnp.asarray(table),
                                     prompts, 4)
    rows = agreement.rows_that_chose(_toy_forward, jnp.asarray(table),
                                     prompts, served)
    program = [r + noise * table.std() * rng.normal(size=r.shape)
               for r in rows]
    return jnp.asarray(table), prompts, served, program


@pytest.mark.parametrize("noise,ok", [(0.0, True), (0.01, True),
                                      (0.015, True), (0.026, False),
                                      (0.05, False), (float("nan"), False)])
def test_logit_rms_reads_the_noise_put_in(noise, ok):
    """The number that tells precisions apart: the program's logits against
    the reference's, root mean square over the reference's spread."""
    table, prompts, served, program = _toy_case(noise)
    said = agreement.tokens_agree(_toy_forward, table, prompts, served, 0.01,
                                  program, 0.02)
    assert said["ok"] is ok and said["tokens_compared"] == 8
    assert said["worst_gap"] == 0.0 and said["greedy_identical"] == 8
    if math.isfinite(noise):
        assert said["logit_rms"] == pytest.approx(noise, rel=0.2, abs=1e-9)


def test_a_served_token_outside_a_tie_is_refused_whatever_the_logits():
    table, prompts, served, program = _toy_case(0.0)
    rows = agreement.rows_that_chose(_toy_forward, table, prompts, served)
    served[1][2] = int(np.argsort(rows[1][2])[-2])      # the runner-up
    said = agreement.tokens_agree(_toy_forward, table, prompts, served, 0.01)
    assert not said["ok"] and said["worst_gap"] > said["allowed_gap"]
    assert "logit_rms" not in said


def test_the_ticker_keeps_the_longest_it_overslept():
    from benchmark.lib.runtime import Ticker
    ticker = Ticker(every_s=0.01)
    time.sleep(0.1)
    late = ticker.close()
    assert 0.0 <= late < 0.1 and not ticker.is_alive()


def test_the_control_at_eight_bit_weights_is_refused_on_three_seeds(capsys):
    """The control at the family's toy size, through the engine on the CPU:
    the program (bf16 compute) passes on six seeds; the reference with its
    weights rounded to 8 bits, put in the program's place, is refused on
    every control seed by ``logit_rms`` — and by nothing else: its tokens
    are the reference's or tied —, and at 4 bits by both numbers.  (The
    cell's own size: PERF.md section 2.)"""
    from benchmark import control
    assert control.main(["--workload", "gpt13b-serve-batch", "--seeds",
                         "11,12,13,14,15,16", "--control-seeds", "11,12,13",
                         "--toy"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith('{"control"')]
    by = {w: [l for l in lines if l["who"] == w]
          for w in ("program", "w8", "w4")}
    assert [len(by[w]) for w in ("program", "w8", "w4")] == [6, 3, 3]
    assert all(l["ok"] for l in by["program"])
    limit = GPT2.TOY["serve"]["agreement"]["logit_rms_limit"]
    assert all(l["logit_rms_limit"] == limit for l in lines)
    assert all(not l["ok"] and l["logit_rms"] > 1.25 * limit
               and l["gap"] < l["gap_limit"] for l in by["w8"])
    assert all(not l["ok"] and l["logit_rms"] > 10 * limit
               and l["gap"] > 2 * l["gap_limit"] for l in by["w4"])
    assert max(l["logit_rms"] for l in by["program"]) < 0.8 * limit
