"""``benchmark/readers/host_laps.py`` on a recorded fixture: 22 decode
steps, two prefill chunks among them, cut out of a traced run of
``gpt13b-serve-loaded`` on the v5e (PR 36) — device 0's op line AND its
module line (``data/host_laps_trace.json.gz``), the program's span records
of the same stretch (``data/host_laps_spans.jsonl``) and the numbers they
reduce to (``data/host_laps.expected.json``).  CPU only."""

import copy
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import xplane  # noqa: E402
from benchmark.lib.runtime import load_benchmark, load_cell, load_json  # noqa: E402
from benchmark.readers import ReaderInput, host_laps, read_metric  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = load_benchmark()
WANT = load_json(os.path.join(DATA, "host_laps.expected.json"))
WINDOW = tuple(WANT["window_wall"])
NEW = ("host_admit_ms", "host_chunk_ms", "host_launch_ms", "host_emit_ms",
       "idle_host_pct", "idle_wait_pct", "prefill_chunk_device_ms")


@pytest.fixture(scope="module")
def trace():
    with gzip.open(os.path.join(DATA, "host_laps_trace.json.gz"), "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def records():
    with open(os.path.join(DATA, "host_laps_spans.jsonl")) as f:
        return [json.loads(line) for line in f]


def _shifted(records, seconds=0.0, steps=0, chunks=0):
    out = copy.deepcopy(records)
    for r in out:
        r["ts"] += seconds
        if "step" in r:
            r["step"] += steps
        if "chunk" in r:
            r["chunk"] += chunks
    return out


def _idle_adds_up(a, trace):
    r = xplane.reduce_trace(trace)
    assert a["window_s"] == pytest.approx(r.window_s, rel=1e-9)
    assert a["idle_s"] == pytest.approx(r.window_s - r.busy_s, rel=1e-9)
    assert sum(a["idle_by_lap_s"].values()) + a["unattributed_s"] \
        == pytest.approx(a["idle_s"], rel=1e-9)
    assert sum(a["busy_by_lap_s"].values()) <= r.busy_s * (1 + 1e-9)


def test_the_recorded_turns_reduce_to_the_checked_in_numbers(trace, records):
    a = host_laps.analysis(trace, records, WINDOW)
    assert a["why"] is None and a["k"] == WANT["k"]
    assert a["pairs"] == WANT["pairs"] == 22 - 2 * host_laps.EDGE
    assert a["delta_s"] == pytest.approx(WANT["delta_s"], abs=1e-6)
    assert 0 < a["delta_width_s"] < 2e-3
    for key in ("idle_by_lap_s", "busy_by_lap_s", "lap_s"):
        assert a[key] == pytest.approx(WANT[key], rel=1e-6, abs=1e-9), key
    for key in ("unattributed_s", "idle_s", "window_s",
                "prefill_chunk_device_ms"):
        assert a[key] == pytest.approx(WANT[key], rel=1e-6), key
    assert (a["decode_launches"], a["chunk_runs"]) == (
        WANT["decode_launches"], WANT["chunk_runs"]) == (21, 3)
    assert a["chunk_why"] is None and a["chunk_k"] == WANT["chunk_k"]
    for key in ("chunk_device_ms_by_tokens", "counts"):
        assert set(a[key]) == set(WANT[key]), key
        for row in a[key]:
            assert a[key][row] == pytest.approx(WANT[key][row]), (key, row)
    _idle_adds_up(a, trace)
    # the ten longest gaps: each filled by the laps under it, to delta's width
    assert len(a["longest_gaps"]) == 10
    for gap in a["longest_gaps"]:
        assert sum(gap["laps"].values()) <= gap["seconds"] * (1 + 1e-9)
        assert sum(gap["laps"].values()) >= gap["seconds"] - 1e-4
        assert gap["turn"]["pages_used"] > 0 and "decoding" in gap["turn"]
    # the longest: the turn of the first chunk, which admitted its request
    assert a["longest_gaps"][0]["turn"]["chunk"] == WANT["chunk_k"]
    assert a["longest_gaps"][0]["turn"]["admitted"] == 1
    # at either end of delta's interval the same idle, cut elsewhere
    for end in ("lo", "hi"):
        by = a["idle_by_lap_s_at_delta"][end]
        assert sum(by.values()) <= a["idle_s"] * (1 + 1e-9)
    assert a["idle_by_lap_s_at_delta"]["lo"]["ready"] \
        < a["idle_by_lap_s"]["ready"] \
        < a["idle_by_lap_s_at_delta"]["hi"]["ready"]


def test_laps_add_up_to_their_turn_and_the_turns_to_the_window(records):
    turns = host_laps.turns_of(records)
    assert len(turns) >= 22
    for r in turns:
        assert sum(s for _, s in r["laps"]) == pytest.approx(r["dur_s"],
                                                             rel=1e-9)
    laps, launches, counts = host_laps.lap_seconds(turns, WINDOW)
    # turns follow each other but for the tracer's own record between them
    mine = [r for r in turns if WINDOW[0] <= r["ts"] <= WINDOW[1]]
    assert sum(laps.values()) == pytest.approx(
        mine[-1]["ts"] + mine[-1]["dur_s"] - mine[0]["ts"], rel=0.01)
    assert launches == 21        # the first began before the wall stamp
    # the turns' counts over the window; the ordinals are not counts
    assert set(counts) == {"decoding", "prefilling", "admitted", "retired",
                           "cancelled", "pending", "pages_used"}
    assert counts["decoding"]["total"] == sum(r["decoding"] for r in mine
                                              if "decoding" in r)
    assert counts["retired"] == {"total": 6, "mean": pytest.approx(6 / 21),
                                 "max": 1}


@pytest.mark.parametrize("seconds", [3.0, -7200.0, 86400.0])
def test_a_host_clock_whole_seconds_off_is_recovered(trace, records, seconds):
    """The device's clock owes the host's nothing: delta moves with it."""
    base = host_laps.analysis(trace, records, WINDOW)
    a = host_laps.analysis(trace, _shifted(records, seconds),
                           (WINDOW[0] + seconds, WINDOW[1] + seconds))
    assert a["why"] is None and a["k"] == base["k"]
    true = sum(base["delta_s"]) / 2 - seconds
    assert a["delta_s"][0] <= true <= a["delta_s"][1]
    assert a["delta_width_s"] == pytest.approx(base["delta_width_s"],
                                               abs=1e-6)
    assert a["idle_by_lap_s"] == pytest.approx(base["idle_by_lap_s"],
                                               abs=2e-5)


@pytest.mark.parametrize("steps", [1, -2])
def test_ordinals_counted_from_elsewhere_are_found(trace, records, steps):
    base = host_laps.analysis(trace, records, WINDOW)
    a = host_laps.analysis(trace, _shifted(records, steps=steps), WINDOW)
    assert a["why"] is None and a["k"] == base["k"] + steps
    assert a["delta_s"] == base["delta_s"]
    assert a["idle_by_lap_s"] == base["idle_by_lap_s"]


def test_each_chunk_run_is_paired_with_the_turn_that_launched_it(trace,
                                                                records):
    """By the ``chunk`` ordinal, wherever it was counted from: the program's
    time on the device beside the ``serve_prefill_chunk`` span of the same
    launch, by the chunk's tokens (two programs of 256, one of 128)."""
    base = host_laps.analysis(trace, records, WINDOW)
    by = base["chunk_device_ms_by_tokens"]
    assert {t: row["runs"] for t, row in by.items()} == {"128": 1, "256": 2}
    assert by["128"]["device_ms"] < by["256"]["device_ms"]
    assert all(3 < row["span_ms"] < 7 for row in by.values())
    runs = host_laps.program_runs(trace, host_laps.CHUNK_BODY)
    kc, pairs = host_laps.pair_chunks(
        host_laps.turns_of(records),
        host_laps.lap_table(host_laps.turns_of(records)), runs,
        *base["delta_s"])
    assert [(t["chunk"], run) for t, run in pairs] == [
        (kc + n, run) for n, run in enumerate(runs)]
    for chunks in (5, -300):
        a = host_laps.analysis(trace, _shifted(records, chunks=chunks),
                               WINDOW)
        assert a["chunk_why"] is None and a["chunk_k"] == kc + chunks
        assert a["chunk_device_ms_by_tokens"] == by


@pytest.mark.parametrize("fault", ["renumbered", "on_the_next_turn"])
def test_chunk_runs_no_offset_can_pair_are_refused(trace, records, fault):
    """A chunk's ordinal miscounted, or stamped on the turn after the one
    that launched it: the chunks' device time is not read and the analysis
    says why; the idle attribution, which the steps pair, stands."""
    kc = WANT["chunk_k"]
    bad = copy.deepcopy(records)
    turns = host_laps.turns_of(bad)
    at = next(i for i, r in enumerate(turns) if r.get("chunk") == kc + 1)
    if fault == "renumbered":
        turns[at]["chunk"] = kc + 7
    else:
        turns[at + 1]["chunk"] = turns[at].pop("chunk")
    a = host_laps.analysis(trace, bad, WINDOW)
    assert "no offset of chunk ordinals" in a["chunk_why"]
    assert "prefill_chunk_device_ms" not in a
    assert a["why"] is None and a["idle_by_lap_s"] == WANT["idle_by_lap_s"]


def test_a_job_that_called_the_decoder_itself_pairs_with_no_run(trace,
                                                               records):
    """The benchmark's logit replay runs ``decode_step`` as a job of a
    turn that launches no step: its ``launch_*`` laps carry no ordinal."""
    first = min(r["ts"] for r in records)
    job = {"kind": "span", "name": "serve_iteration", "ts": first - 1.0,
           "dur_s": 0.004, "laps": [["launch_args", 0.001],
                                    ["launch_call", 0.001],
                                    ["sweep", 0.002]]}
    base = host_laps.analysis(trace, records, WINDOW)
    a = host_laps.analysis(trace, [job] + records, WINDOW)
    assert a["why"] is None and a["k"] == base["k"]
    assert a["idle_by_lap_s"] == base["idle_by_lap_s"]


@pytest.mark.parametrize("fault", ["lost", "swapped"])
def test_a_pairing_no_offset_can_mend_is_refused(trace, records, fault):
    """A turn's record lost from the middle, or two turns' laps swapped: no
    offset of ordinals and clocks holds every run, and the reader says so
    rather than attribute by a pairing one step out."""
    k = WANT["k"]
    bad = copy.deepcopy(records)
    if fault == "lost":
        bad = [r for r in bad if r.get("step") != k + 10]
    else:
        one, other = (next(r for r in bad if r.get("step") == k + n
                           and r["name"] == "serve_iteration")
                      for n in (8, 12))
        one["ts"], other["ts"] = other["ts"], one["ts"]
    a = host_laps.analysis(trace, bad, WINDOW)
    assert a["why"] and "no offset" in a["why"]
    assert "idle_by_lap_s" not in a and a["decode_launches"] >= 20


def _run(tmp_path, trace, records, cell="gpt13b-serve-loaded"):
    out = tmp_path / "out"
    (out / "profile").mkdir(parents=True)
    path = out / "profile" / "trace.json"
    path.write_text(json.dumps(trace))
    return ReaderInput(
        cell=load_cell(BENCH, cell), device_kind="TPU v5 lite",
        reduction=None,
        driver={"window_wall": WINDOW, "records": records,
                "profile_dir": str(out / "profile"), "trace_path": str(path)})


@pytest.fixture
def found(monkeypatch):
    """The fixture is JSON, which ``xplane.load`` reads as it reads a
    profile; only the search for an ``.xplane.pb`` is stood in for."""
    loads = []

    def find(profile_dir):
        loads.append(profile_dir)
        return os.path.join(profile_dir, "trace.json")
    monkeypatch.setattr(host_laps.xplane, "find_xplane", find)
    return loads


def _spec(name):
    return load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  name + ".json"))


def test_the_seven_metrics_read_the_fixture_and_load_it_once(
        tmp_path, trace, records, found):
    run = _run(tmp_path, trace, records)
    got = {n: read_metric(_spec(n), run) for n in NEW}
    assert got == pytest.approx(WANT["metrics"], rel=1e-6)
    assert len(found) == 1
    # the lines of one budget: with the laps the host_*_ms leave out, the
    # window over its launches
    a = run.driver["host_laps"]
    others = sum(a["lap_s"].get(n, 0.0)
                 for n in ("ready", "chunk_sync", "wait"))
    assert (sum(got[n] for n in NEW[:4]) + 1e3 * others / 21
            == pytest.approx(1e3 * sum(a["lap_s"].values()) / 21, rel=1e-9))
    device_idle_pct = 100 * a["idle_s"] / a["window_s"]
    assert 0 < got["idle_host_pct"] + got["idle_wait_pct"] < device_idle_pct
    written = load_json(str(tmp_path / "out" / "idle_by_lap.json"))
    assert written["k"] == WANT["k"] and len(written["longest_gaps"]) == 10


def test_a_trace_that_cannot_be_paired_reads_0_attributed_not_none(
        tmp_path, trace, records, found, capsys):
    """No module line (an older profiler, another device): the laps' own
    metrics stand, the idle ones read 0, and the JSON says why."""
    bare = {"planes": [{"name": p["name"], "lines": [
        ln for ln in p["lines"] if ln["name"] != host_laps.MODULE_LINE]}
        for p in trace["planes"]]}
    run = _run(tmp_path, bare, records)
    got = {n: read_metric(_spec(n), run) for n in NEW}
    assert all(v is not None for v in got.values())
    assert got["idle_host_pct"] == got["idle_wait_pct"] == 0.0
    assert got["prefill_chunk_device_ms"] == 0.0
    assert got["host_launch_ms"] == pytest.approx(
        WANT["metrics"]["host_launch_ms"])
    why = load_json(str(tmp_path / "out" / "idle_by_lap.json"))
    assert "XLA Modules" in why["why"] and "XLA Modules" in why["chunk_why"]
    # and outside the JSON: a 0 here is not a measurement
    err = capsys.readouterr().err
    assert "idle_host_pct and idle_wait_pct read 0, not measured" in err
    assert "prefill_chunk_device_ms read 0, not measured" in err
    assert "XLA Modules" in err and "idle_by_lap.json" in err


def test_a_profile_without_a_device_gives_no_devices_number(
        tmp_path, trace, records, found):
    """A rehearsal on the CPU: the laps' own milliseconds, nothing under
    the name of what is read off the chip."""
    cpu = {"planes": [{"name": "/host:CPU", "lines": []}]}
    run = _run(tmp_path, cpu, records)
    got = {n: read_metric(_spec(n), run) for n in NEW}
    assert [got[n] for n in NEW[4:]] == [None] * 3
    assert got["host_launch_ms"] == pytest.approx(
        WANT["metrics"]["host_launch_ms"])


def test_a_program_without_the_span_reads_0(tmp_path, trace, records,
                                            found, capsys):
    """The parent's program: spans, none of them a ``serve_iteration``.
    (``run.py`` prints no traced line that lacks a declared metric, so
    the readers answer 0 there, and the JSON and stderr say why.)"""
    older = [r for r in records if r["name"] != "serve_iteration"]
    assert older
    run = _run(tmp_path, trace, older)
    got = {n: read_metric(_spec(n), run) for n in NEW}
    assert [got[n] for n in NEW] == [0.0] * 7
    assert "no serve_iteration" in run.driver["host_laps"]["why"]
    assert "no serve_iteration" in run.driver["host_laps"]["chunk_why"]
    assert capsys.readouterr().err.count("no serve_iteration") == 2


def test_the_bodies_the_reader_looks_for_are_the_decoders_own():
    """``DECODE_BODY`` and ``CHUNK_BODY`` are found on the module line by
    name: the names ``serve/decode.py`` compiles its two bodies under."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dtf_tpu.models.transformer import TransformerLM
    from dtf_tpu.serve import Decoder

    model = TransformerLM(vocab_size=64, num_layers=1, d_model=32,
                          num_heads=2, d_ff=64, max_seq_len=16)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    dec = Decoder(model, params, num_slots=2, max_seq_len=16, kv_page_size=4)
    row = np.arange(1, dec.pages_per_slot + 1, dtype=np.int32)
    _, cache, _ = dec.prefill_chunk(dec.fresh_cache(), np.arange(4), row,
                                    0, 3, 0.0, seed=0)
    tables = np.zeros((2, dec.pages_per_slot), np.int32)
    tables[0] = row
    dec.decode_step(cache, np.zeros(2, np.int32), np.array([4, 0], np.int32),
                    np.zeros(2, np.float32), np.zeros(2, np.uint32), tables)
    names = {key[0] if isinstance(key, tuple) else key:
             fn.as_text().split("\n", 1)[0] for key, fn in dec._execs.items()}
    assert f"jit_{host_laps.DECODE_BODY}" in names["decode"]
    assert f"jit_{host_laps.CHUNK_BODY}" in names["chunk"]


@pytest.mark.parametrize("metric", NEW)
def test_nothing_traced_reads_nothing(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["layer"] == "serving engine (serve/engine.py)"
    assert entry["moves"] == "serve_tok_s" and entry["better"] == "lower"
    # PR 36's four serving cells, and since PR 46 longctx, manyrows,
    # longprompt-closed and longgen; the chunk's device time in the four
    # dense cells.  Later cells join by appending their names: the list
    # holds these, and whatever it holds reports what the entry moves
    cells = ["gpt13b-serve-loaded", "gpt13b-serve-longprompt",
             "gpt13b-serve-batch", "smallthinker-serve-mixedctx",
             "joyai-serve-longctx", "lfm2-serve-manyrows",
             "gpt13b-serve-longprompt-closed", "ling-serve-longgen"]
    if "chunk_device" in metric:
        cells = [c for c in cells if c.startswith("gpt13b")]
    assert set(cells) <= set(entry["workloads"])
    tok = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tok_s")
    assert set(entry["workloads"]) <= set(tok["workloads"])
    for driver in ({"window_wall": (0.0, 1.0)},
                   {"window_wall": (0.0, 1.0), "records": [],
                    "profile_dir": None}):
        run = ReaderInput(cell=load_cell(BENCH, entry["workloads"][0]),
                          device_kind="TPU v5 lite", reduction=None,
                          driver=driver)
        assert read_metric(_spec(metric), run) is None
