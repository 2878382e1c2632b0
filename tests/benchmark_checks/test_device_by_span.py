"""``benchmark/readers/device_by_span.py`` on a SYNTHETIC run: a device
timeline (module line + op line) and the program's records made here from
one schedule with a planted offset between the two clocks, so every
number the reader gives has a true value to be held to.  CPU only."""

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
from benchmark.readers import (ReaderInput, device_by_span, host_laps,  # noqa: E402
                               read_metric)

BENCH = load_benchmark()
FOUR = ["gpt13b-serve-loaded", "gpt13b-serve-longprompt",
        "gpt13b-serve-batch", "smallthinker-serve-mixedctx"]
NEW = {"span_runs_paired_pct": FOUR, "decode_dispatch_ms": FOUR,
       "decode_readback_ms": FOUR, "decode_body_device_ms": FOUR,
       "paged_decode_step_ms": FOUR, "paged_decode_chunk_ms": FOUR,
       "moe_experts_step_ms": FOUR[3:], "moe_experts_chunk_ms": FOUR[3:],
       "idle_between_runs_pct": FOUR}

HOST0 = 1.79e9              # the host's clock at the schedule's start
DELTA0 = 2e-3 - HOST0       # device − host there: a profile's clock starts
#                             near 0 (the v5e's reads ≈ −1.79e9 s of the host's)
DISPATCH, READBACK = 4e-4, 2.5e-4       # a step's, planted
ANCHOR_LEAD, ANCHOR_RUN, ANCHOR_WAKE = 6e-5, 5e-6, 1.8e-4
STEP_S, CHUNK_S, OPERANDS_S = 8e-3, 2e-2, 1e-5
STEP_KERNEL, CHUNK_KERNEL = 3 * 4e-4, 2 * 3e-3   # paged_flash_decode, self
STEP_GMM, CHUNK_GMM = 1e-3, 4e-3


class Synth:
    """One schedule, written twice: as the program's records on the
    host's clock and as device 0's two lines on the device's.

    ``anchors``: a ``clock_anchor`` every ``every`` steps, and spans that
    name their launch (the change's program); without them the parent's.
    ``drift_ppm``: the device's clock gains that on the host's.  ``stray``:
    program runs no span launched.  ``closes``: a ``serve_close_window``
    launch before every chunk."""

    def __init__(self, steps=96, every=8, anchors=True, drift_ppm=0.0,
                 stray=0, closes=False, chunk_every=5):
        self.drift = drift_ppm * 1e-6
        self.records, self.modules, self.ops = [], [], []
        self.truth = {"dispatch": [], "readback": []}
        self._ids = iter(range(1, 10 ** 6))
        t = HOST0
        free = 0.0              # host time at which the device falls idle
        self._run("jit__lambda_", t, 5e-6, [("add.0", 0, 5e-6)])
        t += 1e-3
        chunk = close = anchor = 0
        for step in range(1, steps + 1):
            laps, ts, turn_id = [], t, f"0.{next(self._ids)}"
            children = []

            def lap(name, seconds):
                nonlocal t
                laps.append([name, seconds])
                t += seconds
            lap("sweep", 1e-4), lap("admit", 1e-4), lap("sweep", 5e-5)
            turn = {"step": 2000 + step, "decoding": 8, "prefilling": 0}
            if step % chunk_every == 0:
                chunk += 1
                turn["chunk"] = 300 + chunk
                last = chunk % 2 == 1
                began = t
                if closes:
                    # the decoder's close is a jitted lambda, as the
                    # driver's marker is: one name for both on the line
                    close += 1
                    free = self._run("jit_convert_element_type",
                                     max(t + 6e-5, free), 2e-6,
                                     [("convert.3", 0, 2e-6)])
                    free = self._run("jit__lambda_", max(t + 1e-4, free),
                                     2e-4, [("summary.1", 0, 2e-4)])
                    children.append(dict(
                        name="serve_close_window", ts=t + 2e-5, dur_s=1e-4,
                        close=40 + close, program="jit__lambda_", row=0))
                    lap("compact", 2e-4)
                free = self._run("jit__chunk_operands",
                                 max(t + 3e-4, free), OPERANDS_S,
                                 [("fusion.8", 0, OPERANDS_S)])
                free = self._run("jit__chunk_impl", max(t + 7e-4, free),
                                 CHUNK_S, self._body_ops(
                                     CHUNK_S, CHUNK_KERNEL / 2, 2, CHUNK_GMM))
                span = dict(name="serve_prefill_chunk", ts=began + 1e-5,
                            dur_s=8e-4, slot=0, start=0, tokens=256,
                            last=last)
                if anchors:
                    span.update(chunk=300 + chunk, program="jit__chunk_impl",
                                real_tokens=200 if last else 256)
                children.append(span)
                lap("chunk_host", 8e-4 - (2e-4 if closes else 0))
                if last:
                    lap("chunk_sync", max(free + 2e-4 - t, 1e-5))
            lap("gauges", 1e-4), lap("build", 1e-4)
            args = t
            if anchors and step % every == 0:
                anchor += 1
                start = max(t + ANCHOR_LEAD, free)
                free = self._run("jit__clock_anchor", start, ANCHOR_RUN,
                                 [("add.1", 0, ANCHOR_RUN)])
                dur = free - t + ANCHOR_WAKE
                children.append(dict(name="clock_anchor", ts=t + 5e-6,
                                     dur_s=dur - 5e-6, n=70 + anchor,
                                     program="jit__clock_anchor"))
                t += dur
            decode = dict(name="serve_decode", ts=t + 1e-5)
            if anchors:
                decode.update(step=2000 + step, rows=8, context_tokens=999,
                              program="jit__decode_paged_impl")
            free = self._run("jit__step_operands", max(t + 4e-4, free),
                             OPERANDS_S, [("fusion.9", 0, OPERANDS_S)])
            t += 6e-4
            laps.append(["launch_args", t - args])
            start = max(t + DISPATCH, free)
            free = self._run("jit__decode_paged_impl", start, STEP_S,
                             self._body_ops(STEP_S, STEP_KERNEL / 3, 3,
                                            STEP_GMM))
            self.truth["dispatch"].append(start - t)
            self.truth["readback"].append(READBACK)
            lap("launch_call", 1e-3)
            lap("ready", free + READBACK - t)
            decode["dur_s"] = t - decode["ts"]
            children.append(decode)
            lap("emit", 2e-4), lap("rest", 5e-5)
            for _ in range(stray if step == steps // 2 else 0):
                free = self._run("jit_convert_element_type", max(t, free),
                                 2e-6, [("convert.1", 0, 2e-6)])
            self.records.append(dict(
                kind="span", name="serve_iteration", ts=ts, dur_s=t - ts,
                span_id=turn_id, laps=laps, rank=0, pending=0,
                pages_used=9, retired=0, cancelled=0, admitted=0, **turn))
            for child in children:
                self.records.append(dict(
                    child, kind="span", span_id=f"0.{next(self._ids)}",
                    parent="serve_iteration", parent_span=turn_id, rank=0))
            t += 2e-5           # the tracer's record between two turns
        self._run("jit__lambda_", max(t, free) + 1e-4, 5e-6,
                  [("add.0", 0, 5e-6)])
        self.window = (HOST0 + 1e-4, t)
        self.trace = {"planes": [{"name": "/device:TPU:0", "lines": [
            {"name": host_laps.MODULE_LINE, "events": self.modules},
            {"name": xplane.OP_LINE, "events": self.ops}]}]}

    def delta(self, t):
        return DELTA0 + self.drift * (t - HOST0)

    def _run(self, name, start, dur, ops):
        """A program run from host time ``start`` for ``dur`` with its ops
        ([name, offset, dur]); returns when it ends (host time)."""
        at = 1e9 * ((start - HOST0) * (1 + self.drift) + DELTA0 + HOST0)
        self.modules.append([f"{name}(12345)", at, 1e9 * dur])
        self.ops += [[n, at + 1e9 * off, 1e9 * d] for n, off, d in ops]
        return start + dur

    @staticmethod
    def _body_ops(dur, kernel, calls, gmm):
        """A ``while`` that holds ``calls`` paged kernels, then one grouped
        product and a fusion to the body's end, with idle between them."""
        ops = [("while.1", 0.0, calls * (kernel + 1e-5))]
        ops += [(f"paged_flash_decode.{3 + i}", i * (kernel + 1e-5), kernel)
                for i in range(calls)]
        at = calls * (kernel + 1e-5) + 2e-5
        ops.append(("gmm.1", at, gmm))
        at += gmm + 2e-5
        ops.append(("fusion.2", at, dur - at))
        return ops


@pytest.fixture(scope="module")
def change():
    s = Synth()
    return s, device_by_span.analysis(s.trace, s.records, s.window)


def test_anchors_bound_delta_to_their_round_trip(change):
    s, a = change
    assert a["why"] is None and a["clock"] == "anchors"
    assert a["delta"] == "once a window"
    assert a["anchors"] == a["anchors_paired"] == 12 and a["anchor_k"] == 71
    lo, hi = a["delta_s"]
    assert lo <= DELTA0 <= hi
    # a tight anchor is as wide as the program's launch and the wake-up
    assert a["delta_width_s"] == pytest.approx(ANCHOR_LEAD - 5e-6
                                               + ANCHOR_WAKE, abs=2e-6)
    assert abs(a["drift_ppm"]) < 1.0
    assert a["anchor_dur_s_median"] == pytest.approx(
        ANCHOR_LEAD + ANCHOR_RUN + ANCHOR_WAKE - 5e-6, abs=2e-6)
    # the causal join of host_laps holds too, wider, round the anchors'
    causal = a["causal"]
    assert causal["delta_s"][0] <= lo and hi <= causal["delta_s"][1]
    assert a["inside_causal"] and a["overlaps_causal"]
    assert causal["delta_width_s"] > a["delta_width_s"]


def test_an_anchor_that_waited_behind_a_chunk_bounds_nothing(change):
    """Step 40 anchors AND chunks: its anchor ran when the chunk was
    done, so its interval is as wide as the chunk and no tighter bound."""
    _, a = change
    widths = a["anchor_width_s"]
    assert max(widths) > CHUNK_S / 2 and min(widths) < 3e-4
    assert a["anchors_tight"] == sum(w <= 2 * min(widths) for w in widths)
    assert a["anchors_tight"] < a["anchors_paired"]


@pytest.mark.parametrize("ppm", [400.0, -250.0])
def test_a_planted_drift_is_recovered(ppm):
    s = Synth(drift_ppm=ppm)
    a = device_by_span.analysis(s.trace, s.records, s.window)
    assert a["clock"] == "anchors" and a["delta"] == "between anchors"
    assert a["drift_ppm"] == pytest.approx(ppm, rel=0.02)
    # over the schedule the clocks part by more than an anchor is wide
    assert abs(a["drift_s"]) > a["delta_width_s"]
    # and every step's dispatch is read on the clock of its neighbours
    d = a["boundary"]["decode_dispatch_ms"]
    assert d["count"] == 96
    assert d["median"] == pytest.approx(1e3 * DISPATCH, abs=0.2)
    quiet = sorted(1e3 * v for v in s.truth["dispatch"])
    assert d["p95"] == pytest.approx(quiet[int(0.95 * 95)], abs=0.25)


def test_without_anchors_the_causal_interval_is_used_and_said(change):
    """The parent's program: no anchor, no ``program``, no ordinal on a
    launch's span — the turn's ordinals and host_laps' body names."""
    _, with_anchors = change
    s = Synth(anchors=False)
    assert not any("program" in r or r["name"] == "clock_anchor"
                   for r in s.records)
    a = device_by_span.analysis(s.trace, s.records, s.window)
    assert a["why"] is None and a["clock"] == "causal"
    assert a["anchors"] == 0 and "inside_causal" not in a
    assert a["delta_s"] == a["causal"]["delta_s"]
    assert a["delta_s"][0] <= DELTA0 <= a["delta_s"][1]
    assert a["delta_width_s"] > with_anchors["delta_width_s"]
    assert a["paired"] == a["runs"] - 2 + 2       # the markers are their own
    # what needs no finer clock reads the same on both programs
    for key in ("decode_body_device_ms",):
        assert a["boundary"][key]["median"] == pytest.approx(
            with_anchors["boundary"][key]["median"], rel=1e-6)
    for g in ("serve_decode", "serve_prefill_chunk"):
        assert a["reductions"][g].kernel_s("paged_flash_decode") \
            == pytest.approx(with_anchors["reductions"][g].kernel_s(
                "paged_flash_decode"), rel=1e-9)


def test_every_run_finds_its_span_and_a_stray_one_lowers_the_share(change):
    s, a = change
    assert a["paired"] == a["runs"] == len(s.modules) and not a["unpaired"]
    by = a["by_span"]
    assert by["serve_decode"]["runs"] == 2 * 96
    assert by["serve_decode"]["bodies"] == 96
    assert by["serve_prefill_chunk"]["runs"] == 2 * 19
    assert by["clock_anchor"]["runs"] == 12
    assert by[device_by_span.MARKER]["runs"] == 2
    stray = Synth(stray=5)
    b = device_by_span.analysis(stray.trace, stray.records, stray.window)
    assert b["unpaired"] == {"jit_convert_element_type": 5}
    assert b["paired"] == b["runs"] - 5


def test_closes_are_runs_of_their_own_span():
    s = Synth(closes=True)
    a = device_by_span.analysis(s.trace, s.records, s.window)
    assert a["paired"] == a["runs"] and not a["unpaired"]
    assert a["by_span"]["serve_close_window"]["runs"] == 2 * 19
    assert a["by_span"]["serve_close_window"]["bodies"] == 19
    assert a["by_span"][device_by_span.MARKER]["runs"] == 2
    assert a["reductions"]["serve_close_window"].kernel_s("summary") \
        == pytest.approx(19 * 2e-4)


def test_ops_are_split_by_the_run_that_holds_them(change):
    s, a = change
    whole = xplane.reduce_trace(s.trace)
    for regex, step, chunk in (("paged_flash_decode", STEP_KERNEL,
                                CHUNK_KERNEL),
                               (r"^gmm(\.|$)", STEP_GMM, CHUNK_GMM)):
        by = {g: a["reductions"][g].kernel_s(regex)
              for g in ("serve_decode", "serve_prefill_chunk")}
        assert by["serve_decode"] == pytest.approx(96 * step, rel=1e-6)
        assert by["serve_prefill_chunk"] == pytest.approx(19 * chunk,
                                                          rel=1e-6)
        assert sum(by.values()) == pytest.approx(whole.kernel_s(regex),
                                                 rel=1e-9)
    # the while's self time is what its kernels leave of it
    assert a["reductions"]["serve_decode"].kernel_s("^while") \
        == pytest.approx(96 * 3 * 1e-5, rel=1e-3)
    # busy and idle add up: inside the runs and between them
    # (to the host clock's grain at 1.79e9 s, 0.24 us, which the schedule's
    # run starts are rounded to)
    assert sum(v["busy_s"] for v in a["by_span"].values()) \
        == pytest.approx(whole.busy_s, abs=1e-6)
    assert a["idle_s"] == pytest.approx(whole.window_s - whole.busy_s,
                                        rel=1e-9)
    assert a["idle_inside_runs_s"] == pytest.approx(
        (96 + 19) * 4e-5, abs=1e-6)
    assert a["idle_inside_runs_s"] + a["idle_between_runs_s"] \
        == pytest.approx(a["idle_s"], rel=1e-12)
    assert a["by_span"]["serve_decode"]["idle_inside_s"] \
        == pytest.approx(96 * 4e-5, rel=1e-3)
    assert sum(a["idle_by_lap_s"].values()) + a["unattributed_s"] \
        == pytest.approx(a["idle_s"], rel=1e-9)


def test_the_boundary_is_read_to_the_clocks_width(change):
    s, a = change
    b, w = a["boundary"], 1e3 * a["delta_width_s"]
    assert b["decode_dispatch_ms"]["median"] == pytest.approx(
        1e3 * DISPATCH, abs=w / 2 + 1e-3)
    assert b["decode_readback_ms"]["median"] == pytest.approx(
        1e3 * READBACK, abs=w / 2 + 1e-3)
    assert b["decode_body_device_ms"]["median"] == pytest.approx(
        1e3 * (STEP_S + OPERANDS_S), rel=1e-6)
    # the identity: the two and the body are the turn's launch_call +
    # ready laps and the operands program, step by step
    laps = {r["step"]: dict(r["laps"]) for r in s.records
            if r["name"] == "serve_iteration"}
    want = sorted(1e3 * (v["launch_call"] + v["ready"] + OPERANDS_S)
                  for v in laps.values())[48]
    assert (b["decode_dispatch_ms"]["median"]
            + b["decode_readback_ms"]["median"]
            + b["decode_body_device_ms"]["median"]
            == pytest.approx(want, abs=w + 0.05))
    assert b["last_chunk_host_ms"]["count"] == 10
    assert b["last_chunk_sync_ms"]["median"] == pytest.approx(
        0.2 + 1e3 * (STEP_S + OPERANDS_S) * 0, abs=w / 2 + 0.01)


def test_a_kept_run_is_read_by_hand_without_the_wall_stamp(change,
                                                           tmp_path, capsys,
                                                           monkeypatch):
    s, a = change
    by_hand = device_by_span.analysis(s.trace, s.records)
    assert by_hand["delta_s"] == a["delta_s"] and by_hand["paired"] \
        == a["paired"]
    out = tmp_path / "cell"
    (out / "spans").mkdir(parents=True)
    (out / "profile").mkdir()
    (out / "spans" / "trace_rank0.jsonl").write_text(
        "\n".join(json.dumps(r) for r in s.records) + "\n")
    (out / "profile" / "trace.json").write_text(json.dumps(s.trace))
    monkeypatch.setattr(device_by_span.xplane, "find_xplane",
                        lambda d: os.path.join(d, "trace.json"))
    assert device_by_span.main([str(out), "paged_flash_decode"]) == 0
    said = capsys.readouterr().out
    assert '"clock": "anchors"' in said
    assert "paged_flash_decode  serve_decode:" in said
    assert "paged_flash_decode  serve_prefill_chunk:" in said
    written = load_json(str(out / "device_by_span.json"))
    assert written["clock"] == "anchors" and "reductions" not in written


def _run(tmp_path, trace, records, window, cell="smallthinker-serve-mixedctx"):
    out = tmp_path / "out"
    (out / "profile").mkdir(parents=True)
    (out / "profile" / "trace.json").write_text(json.dumps(trace))
    return ReaderInput(
        cell=load_cell(BENCH, cell), device_kind="TPU v5 lite",
        reduction=None,
        driver={"window_wall": window, "records": records,
                "profile_dir": str(out / "profile")})


@pytest.fixture
def found(monkeypatch):
    loads = []

    def find(profile_dir):
        loads.append(profile_dir)
        return os.path.join(profile_dir, "trace.json")
    monkeypatch.setattr(device_by_span.xplane, "find_xplane", find)
    return loads


def _spec(name):
    return load_json(os.path.join(ROOT, "benchmark", "layer_metrics",
                                  name + ".json"))


def test_the_nine_metrics_read_the_run_and_load_it_once(change, tmp_path,
                                                        found):
    s, _ = change
    run = _run(tmp_path, s.trace, s.records, s.window)
    got = {n: read_metric(_spec(n), run) for n in NEW}
    assert len(found) == 1
    assert got["span_runs_paired_pct"] == 100.0
    assert got["decode_dispatch_ms"] == pytest.approx(1e3 * DISPATCH, abs=0.13)
    assert got["decode_readback_ms"] == pytest.approx(1e3 * READBACK, abs=0.13)
    assert got["decode_body_device_ms"] == pytest.approx(
        1e3 * (STEP_S + OPERANDS_S))
    assert got["paged_decode_step_ms"] == pytest.approx(1e3 * STEP_KERNEL)
    assert got["paged_decode_chunk_ms"] == pytest.approx(1e3 * CHUNK_KERNEL)
    assert got["moe_experts_step_ms"] == pytest.approx(1e3 * STEP_GMM)
    assert got["moe_experts_chunk_ms"] == pytest.approx(1e3 * CHUNK_GMM)
    a = run.driver["device_by_span"]
    assert got["idle_between_runs_pct"] == pytest.approx(
        100 * a["idle_between_runs_s"] / a["window_s"])
    assert 0 < got["idle_between_runs_pct"] < 100 * a["idle_s"] / a["window_s"]
    written = load_json(str(tmp_path / "out" / "device_by_span.json"))
    assert written["clock"] == "anchors" and written["inside_causal"]


@pytest.mark.parametrize("fault", ["no_turn", "no_module_line"])
def test_records_with_nothing_to_pair_read_0_and_say_why(change, tmp_path,
                                                         found, capsys,
                                                         fault):
    """The contract refuses a traced line that lacks a declared metric:
    0, never nothing, and ``span_runs_paired_pct`` 0 beside it."""
    s, _ = change
    trace, records = s.trace, s.records
    if fault == "no_turn":
        records = [r for r in records if r["name"] != "serve_iteration"]
        why = "no serve_iteration"
    else:
        trace = {"planes": [{"name": p["name"], "lines": [
            ln for ln in p["lines"] if ln["name"] != host_laps.MODULE_LINE]}
            for p in s.trace["planes"]]}
        why = "XLA Modules"
    run = _run(tmp_path, trace, records, s.window)
    got = {n: read_metric(_spec(n), run) for n in NEW}
    assert got == {n: 0.0 for n in NEW}
    assert why in run.driver["device_by_span"]["why"]
    err = capsys.readouterr().err
    assert err.count("reads 0, not measured") == 1 and why in err
    assert "device_by_span.json" in err


def test_a_profile_without_a_device_gives_no_devices_number(change, tmp_path,
                                                            found):
    s, _ = change
    cpu = {"planes": [{"name": "/host:CPU", "lines": []}]}
    run = _run(tmp_path, cpu, s.records, s.window)
    assert [read_metric(_spec(n), run) for n in NEW] == [None] * 9


@pytest.mark.parametrize("metric", sorted(NEW))
def test_nothing_traced_reads_nothing(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert set(NEW[metric]) <= set(entry["workloads"])
    assert entry["moves"] == "serve_tok_s"
    assert entry["source"] == ("program_span" if metric in (
        "span_runs_paired_pct", "decode_dispatch_ms", "decode_readback_ms")
        else "device_trace")
    assert _spec(metric)["reader"] == "device_by_span"
    for driver in ({"window_wall": (0.0, 1.0)},
                   {"window_wall": (0.0, 1.0), "records": [],
                    "profile_dir": None}):
        run = ReaderInput(cell=load_cell(BENCH, entry["workloads"][0]),
                          device_kind="TPU v5 lite", reduction=None,
                          driver=driver)
        assert read_metric(_spec(metric), run) is None


def test_the_nine_are_declared_in_serving_cells_under_layers_it_had():
    """Their content, not their place: the order of ``per_layer`` carries
    no meaning, and a later serving cell may append its name."""
    nine = [m for m in BENCH["per_layer"] if m["name"] in NEW]
    assert sorted(m["name"] for m in nine) == sorted(NEW)
    tok = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tok_s")
    assert all(set(m["workloads"]) <= set(tok["workloads"]) for m in nine)
    assert {m["layer"] for m in nine} <= {
        m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}


def test_the_programs_the_reader_names_are_the_decoders_own():
    """The operands programs, the anchor and the two bodies are found on
    the module line by name: the names ``serve/decode.py`` compiles them
    under, which the spans carry as ``program``."""
    from dtf_tpu.serve import decode
    assert {f"jit_{f.__name__}" for f in (decode._step_operands,
                                          decode._chunk_operands)} \
        <= set(device_by_span.OPERANDS)
    assert decode.program_name(decode._clock_anchor) == "jit__clock_anchor"
    assert decode.program_name(decode._clock_anchor.lower(
        __import__("numpy").zeros((8, 128), "float32")).compile()) \
        == "jit__clock_anchor"
    for span, (_, body) in device_by_span.LAUNCHES.items():
        if body is not None:
            assert body in (host_laps.DECODE_BODY, host_laps.CHUNK_BODY)
