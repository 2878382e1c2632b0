"""Checks of the benchmark's own yardstick: CPU only, no TPU topology
call, each case well under a second.  They live under BENCHMARK.json's
``paths``, so no later PR can edit them."""

import copy
import json
import math
import os
import re
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import contract, costs, peaks, stats, traffic, xplane  # noqa: E402
from benchmark.lib.runtime import load_benchmark, load_cell, load_json  # noqa: E402
from benchmark.readers import ReaderInput, read_metric  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
END_TO_END = {m["name"] for m in BENCH["end_to_end"]}


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
    metrics = {n: {"value": 12.5, "unit": u} for n, u in
               contract.declared_metrics(BENCH, cell, traced).items()}
    chips = next(w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
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
    line = good_line("gpt13b-serve-steady", traced)
    edit(line)
    assert contract.check_line(line, BENCH, "gpt13b-serve-steady", traced)


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


def test_prefill_bodies_follow_the_engines_chunk_plan():
    from dtf_tpu.serve.engine import chunk_plan
    mix = _mix(REQUEST_MIXES[0])
    want = set()
    for plen in mix["prompt_len"]["snap_to"]:
        for start, clen in chunk_plan(plen, 256, 16):
            want.add((clen, start == 0))
    assert traffic.prefill_bodies(mix, 256, 16) == sorted(want)


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


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_layer_metric_has_a_reader_and_moves_what_its_cells_report(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    spec = load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  metric + ".json"))
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    assert spec["unit"] == entry["unit"] and spec["layer"] == entry["layer"]
    assert entry["moves"] in END_TO_END and entry["moves"] != "setup_s"
    for cell in entry.get("workloads", CELLS):
        assert entry["moves"] in contract.declared_metrics(BENCH, cell, False)
    # a reader that finds nothing returns nothing
    run = ReaderInput(cell=load_cell(BENCH, entry.get("workloads", CELLS)[0]),
                      device_kind="TPU v5 lite", reduction=None,
                      driver={"window_wall": (0.0, 1.0)})
    assert read_metric(spec, run) is None


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
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    # 2 + 14 runs a cell, each run_seconds + 60 s, 180 s a cell to compile,
    # 1200 s spare, at the full 24 cells
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


# ------------------------------------------------------------- costs.py --
def test_gpt_flops_per_token_match_the_hand_count():
    cfg = load_cell(BENCH, "gpt13b-train-zero-x4")
    per_token = costs.gpt_train_flops_per_sample(
        cfg.config, cfg.traffic) / cfg.traffic["seq_len"]
    # 6 x 1.311e9 matmul parameters + 3 x 24 layers x 2 x 2048 x 2048
    assert per_token == pytest.approx(8.5e9, rel=0.05)
    assert costs.gpt_matmul_params(cfg.config) == 24 * (
        4 * 2048 ** 2 + 2 * 2048 * 8192) + 2048 * 50257


def test_resnet50_flops_per_image_match_the_hand_count():
    cfg = load_cell(BENCH, "resnet50-train")
    fwd = costs.resnet50_forward_flops_per_image(cfg.config)
    assert fwd == pytest.approx(2 * 4.09e9, rel=0.03)   # 4.1 GMACs, v1.5
    assert costs.resnet50_train_flops_per_sample(
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
    flops, _ = costs.flash_train_step(cell.config, cell.traffic, 4)
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


# ----------------------------- a later PR adds files and entries only ----
def test_readme_examples_load_through_the_harness(tmp_path):
    """The README's worked examples — a configuration, a traffic mix, a
    cell and a per-layer metric — written as new files into a copy of the
    benchmark and found by name, with no file of the harness edited."""
    readme = open(os.path.join(ROOT, "benchmark", "README.md")).read()
    blocks = re.findall(r"`([\w./\-]+\.json)`[^\n]*\n+```json\n(.*?)```",
                        readme, re.S)
    files = {path: json.loads(body) for path, body in blocks}
    assert {"BENCHMARK.json"} < set(files) and len(files) >= 5
    root = str(tmp_path)
    for sub in ("configs", "traffic", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub),
                        os.path.join(root, "benchmark", sub))
    bench = copy.deepcopy(BENCH)
    for key, entries in files.pop("BENCHMARK.json").items():
        bench[key].extend(entries)
    for path, body in files.items():
        assert not os.path.exists(os.path.join(root, path)), path
        with open(os.path.join(root, path), "w") as f:
            json.dump(body, f)
    new_cell = bench["workloads"][-1]["name"]
    cell = load_cell(bench, new_cell, root=root)
    assert cell.config_name == bench["configs"][-1]["name"]
    new_metric = bench["per_layer"][-1]
    assert new_metric["name"] in cell.per_layer
    spec = load_json(os.path.join(root, "benchmark", "layer_metrics",
                                  new_metric["name"] + ".json"))
    trace = _trace([["fusion.9", 0, 4e6], ["flash_fwd.1", 5e6, 2e6]])
    run = ReaderInput(cell=cell, device_kind="TPU v5 lite",
                      reduction=xplane.reduce_trace(trace),
                      driver={"steps": 2, "decode_steps": 2})
    assert read_metric(spec, run) == pytest.approx(1.0)     # ms per step
    assert new_metric["name"] in contract.declared_metrics(
        bench, new_cell, True)
    assert math.isfinite(costs.gpt_forward_flops_per_token(cell.config, 2048))
