"""Observability subsystem (dtf_tpu/obs): span emission/nesting,
registry percentile math, watchdog trigger/abort paths, launcher
heartbeat consumption, trace_main summarizer/--check, the <5%
tracing-overhead bound on a smoke-train step, the distributed span
context (trace ids, request timelines), the MFU/cost ledger, and the
Prometheus /metrics + /healthz endpoint under concurrent scrapes."""

import dataclasses
import json
import os
import sys
import time

import numpy as np
import pytest

import dtf_tpu.data.base as data_base
from dtf_tpu.cli import run
from dtf_tpu.cli.trace_main import main as trace_main
from dtf_tpu.config import Config
from dtf_tpu.obs import trace
from dtf_tpu.obs.registry import (Counter, Gauge, Histogram,
                                  MetricsRegistry, percentile)
from dtf_tpu.obs.watchdog import (Heartbeat, NanLossWatchdog,
                                  StepTimeWatchdog, TrainingAnomaly,
                                  heartbeat_path, read_heartbeat)

TINY = dataclasses.replace(data_base.CIFAR10, image_size=8, num_train=64,
                           num_eval=16)


@pytest.fixture(autouse=True)
def tiny_specs(monkeypatch):
    monkeypatch.setitem(data_base._SPECS, "cifar10", TINY)


@pytest.fixture(autouse=True)
def clean_tracer():
    """The tracer is process-global — never leak one between tests."""
    trace.disable()
    yield
    trace.disable()


def base_cfg(**kw):
    kw.setdefault("model", "resnet20")
    kw.setdefault("dataset", "cifar10")
    kw.setdefault("use_synthetic_data", True)
    kw.setdefault("train_steps", 3)
    kw.setdefault("batch_size", 8)
    kw.setdefault("skip_eval", True)
    kw.setdefault("skip_checkpoint", True)
    kw.setdefault("log_steps", 1)
    kw.setdefault("model_dir", "")
    kw.setdefault("distribution_strategy", "off")
    return Config(**kw)


# --- trace: span emission + nesting ---------------------------------------

def test_span_emission_and_nesting(tmp_path):
    t = trace.configure(str(tmp_path), rank=3)
    with trace.span("outer", step=7):
        with trace.span("inner"):
            time.sleep(0.01)
        trace.event("marker", note="hello")
    t.flush()
    recs = trace.read_records(t.path)
    by_name = {r["name"]: r for r in recs}
    inner, outer = by_name["inner"], by_name["outer"]
    assert inner["kind"] == outer["kind"] == "span"
    assert inner["parent"] == "outer"
    assert "parent" not in outer
    assert inner["dur_s"] >= 0.01
    assert outer["dur_s"] >= inner["dur_s"]
    assert outer["step"] == 7
    assert all(r["rank"] == 3 for r in recs)
    # spans close inner-first, so file order is inner before outer
    names = [r["name"] for r in recs if r["kind"] == "span"]
    assert names.index("inner") < names.index("outer")
    assert by_name["marker"]["kind"] == "event"


def test_span_records_error_and_disabled_is_noop(tmp_path, monkeypatch):
    # disabled: the module API must be callable and free of effects —
    # one shared object whatever is asked for, and no clock read
    assert trace.get() is None

    def no_clock():
        raise AssertionError("a disabled tracer read a clock")
    monkeypatch.setattr(trace.time, "time", no_clock)
    monkeypatch.setattr(trace.time, "perf_counter", no_clock)
    with trace.span("nothing") as plain:
        pass
    with trace.lap_span("nothing") as lapped:
        assert lapped.lap("piece") is None
        assert trace.lap("piece") is None
    assert plain is lapped is trace._NULL_SPAN
    trace.event("nothing")
    monkeypatch.undo()
    t = trace.configure(str(tmp_path), rank=0)
    with pytest.raises(RuntimeError):
        with trace.span("boom"):
            raise RuntimeError("x")
    t.flush()
    recs = [r for r in trace.read_records(t.path) if r.get("name") == "boom"]
    assert recs and recs[0]["error"] == "RuntimeError"


def _spans(tracer, name):
    tracer.flush()
    return [r for r in trace.read_records(tracer.path)
            if r.get("kind") == "span" and r.get("name") == name]


def test_laps_are_contiguous_ordered_and_add_up_to_the_span(tmp_path):
    t = trace.configure(str(tmp_path), rank=0)
    with trace.lap_span("turn", n=3) as sp:
        time.sleep(0.002)
        sp.lap("a")
        time.sleep(0.001)
        trace.lap("b")              # the module's: this thread's open span
        sp.lap("a")                 # a name may repeat; order is kept
        time.sleep(0.001)
    with trace.span("plain"):
        trace.lap("nobody")         # no lap-keeping span open: nothing
    (rec,), (plain,) = _spans(t, "turn"), _spans(t, "plain")
    assert [n for n, _ in rec["laps"]] == ["a", "b", "a", "rest"]
    assert all(s >= 0 for _, s in rec["laps"])
    assert rec["laps"][0][1] >= 0.002 and rec["laps"][1][1] >= 0.001
    assert sum(s for _, s in rec["laps"]) == pytest.approx(rec["dur_s"],
                                                           abs=1e-9)
    assert rec["n"] == 3 and "laps" not in plain
    # ts is the wall clock as on every span; laps lie inside it
    assert abs(rec["ts"] - time.time()) < 60


def test_a_span_under_a_lap_keeping_span_reads_its_clock(tmp_path,
                                                        monkeypatch):
    """A turn's laps and its children's edges are ONE clock's readings:
    the child's ``ts`` is the outer ``ts`` plus a ``perf_counter`` offset,
    whatever ``time.time()`` does meanwhile; a span under no lap-keeping
    span reads ``time.time()`` as before."""
    t = trace.configure(str(tmp_path), rank=0)
    with trace.lap_span("turn") as outer:
        time.sleep(0.001)
        outer.lap("before")
        # the wall clock steps (NTP, a suspended VM): the child must not see it
        real = time.time
        monkeypatch.setattr(time, "time", lambda: real() + 3600.0)
        with trace.span("child", n=1):
            time.sleep(0.002)
        monkeypatch.setattr(time, "time", real)
        outer.lap("child")
    with trace.span("plain"):
        time.sleep(0.001)
    (turn,), (child,), (plain,) = (_spans(t, n) for n in ("turn", "child",
                                                          "plain"))
    before, during = turn["laps"][0][1], turn["laps"][1][1]
    assert turn["ts"] + before <= child["ts"]
    assert child["ts"] + child["dur_s"] <= turn["ts"] + before + during
    assert 0.002 <= child["dur_s"] <= during
    assert child["parent_span"] == turn["span_id"] and child["n"] == 1
    assert plain["dur_s"] >= 0.001 and abs(plain["ts"] - time.time()) < 60


def test_trace_main_counts_the_clock_anchors_under_a_turn(tmp_path, capsys):
    t = trace.configure(str(tmp_path), rank=0)
    for n in range(5):
        with trace.lap_span("serve_iteration", step=n + 1) as sp:
            sp.lap("build")
            if n % 2 == 0:
                with trace.span("clock_anchor", n=n // 2 + 1,
                                program="jit__clock_anchor"):
                    time.sleep(0.001)
            sp.lap("launch_args")
    t.flush()
    trace.disable()
    assert trace_main([str(tmp_path), "--json"]) == 0
    spans = json.loads(capsys.readouterr().out)["spans"]
    row = spans["serve_iteration"]["anchors"]
    assert row["count"] == 3 == spans["clock_anchor"]["count"]
    assert 0.001 <= row["median_s"] <= row["total_s"] \
        <= spans["serve_iteration"]["laps"]["launch_args"]["total_s"]
    assert trace_main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "clock anchors" in out and "inside launch_args" in out


def test_a_callees_lap_lands_on_its_own_threads_open_span(tmp_path):
    import threading
    t = trace.configure(str(tmp_path), rank=0)
    inner_open, outer_marked = threading.Event(), threading.Event()

    def callee(name):
        trace.lap(name)             # knows no span: the tracer finds it

    def other_thread():
        with trace.lap_span("theirs"):
            callee("theirs_1")
            inner_open.set()
            outer_marked.wait(5)
            callee("theirs_2")

    th = threading.Thread(target=other_thread)
    with trace.lap_span("mine"):
        th.start()
        inner_open.wait(5)
        callee("mine_1")
        with trace.lap_span("nested"):
            callee("nested_1")      # the innermost lap-keeping span
        callee("mine_2")            # ... and the outer one again after it
        outer_marked.set()
        th.join(5)
    names = {n: [lap for lap, _ in _spans(t, n)[0]["laps"]]
             for n in ("mine", "nested", "theirs")}
    assert names == {"mine": ["mine_1", "mine_2", "rest"],
                     "nested": ["nested_1", "rest"],
                     "theirs": ["theirs_1", "theirs_2", "rest"]}
    assert _spans(t, "nested")[0]["parent"] == "mine"


def test_read_records_tolerates_torn_tail(tmp_path):
    p = tmp_path / "t.jsonl"
    p.write_text(json.dumps({"kind": "event", "name": "a", "ts": 1}) +
                 "\n{\"kind\": \"ev")
    recs = trace.read_records(str(p))
    assert len(recs) == 1 and recs[0]["name"] == "a"


# --- registry --------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs", unit="requests")
    c.inc()
    c.inc(4)
    g = reg.gauge("depth", unit="requests")
    g.set(7)
    assert c.value == 5 and g.value == 7.0
    # get-or-create returns the same instrument; type morphs refuse
    assert reg.counter("reqs") is c
    with pytest.raises(TypeError):
        reg.gauge("reqs")


def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    data = rng.lognormal(size=997).tolist()
    h = Histogram("lat", unit="s")
    for v in data:
        h.observe(v)
    for q in (0.0, 25.0, 50.0, 90.0, 99.0, 100.0):
        np.testing.assert_allclose(h.percentile(q),
                                   np.percentile(data, q), rtol=1e-12)
    snap = h.snapshot()
    assert snap["count"] == len(data)
    np.testing.assert_allclose(snap["mean"], np.mean(data), rtol=1e-9)
    np.testing.assert_allclose(snap["p50"], np.percentile(data, 50))
    assert snap["min"] == min(data) and snap["max"] == max(data)


def test_percentile_edge_cases():
    assert percentile([], 50) == 0.0
    assert percentile([4.0], 99) == 4.0
    assert percentile([1.0, 3.0], 50) == 2.0


def test_histogram_reservoir_keeps_exact_extremes():
    h = Histogram("x", max_samples=64)
    for i in range(1000):
        h.observe(float(i))
    snap = h.snapshot()
    assert snap["count"] == 1000
    assert snap["min"] == 0.0 and snap["max"] == 999.0
    assert len(h._samples) == 64
    # the reservoir stays representative enough for a coarse median
    assert 200.0 < snap["p50"] < 800.0


def test_registry_benchmark_metric_export():
    reg = MetricsRegistry()
    reg.counter("sheds", unit="requests").inc(2)
    reg.gauge("depth", unit="requests").set(3)
    h = reg.histogram("lat", unit="s")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    reg.histogram("never_observed", unit="s")
    recs = reg.to_benchmark_metrics()
    names = {r["name"] for r in recs}
    assert {"sheds", "depth", "lat_p50", "lat_p90", "lat_p99", "lat_mean",
            "lat_count"} <= names
    assert not any(n.startswith("never_observed") for n in names)
    for r in recs:  # the one BenchmarkMetric shape, every record
        assert set(r) == {"name", "value", "unit"}
        assert isinstance(r["value"], float)
    by = {r["name"]: r for r in recs}
    assert by["sheds"]["value"] == 2.0
    np.testing.assert_allclose(by["lat_p50"]["value"], 0.2)


# --- watchdogs -------------------------------------------------------------

def test_nan_watchdog_abort_path(tmp_path):
    t = trace.configure(str(tmp_path), rank=0)
    wd = NanLossWatchdog()
    wd.check(5, 1.25)  # finite: no-op
    with pytest.raises(TrainingAnomaly) as ei:
        wd.check(6, float("nan"))
    assert ei.value.record["name"] == "nan_loss"
    assert ei.value.record["step"] == 6
    with pytest.raises(TrainingAnomaly):
        NanLossWatchdog().check(7, float("inf"))
    # the anomaly was flushed to the trace before the raise
    recs = trace.read_records(t.path)
    assert any(r["kind"] == "anomaly" and r["name"] == "nan_loss"
               for r in recs)
    assert NanLossWatchdog(enabled=False).check(8, float("nan")) is None


def test_step_time_watchdog_trigger(tmp_path):
    t = trace.configure(str(tmp_path), rank=0)
    wd = StepTimeWatchdog(factor=3.0, warmup=5)
    for step in range(5):
        assert not wd.observe(step, 0.1)
    assert not wd.observe(5, 0.25)       # 2.5x median: below factor
    assert wd.observe(6, 0.5)            # 5x median: regression
    # the spike is NOT absorbed into the baseline — it keeps triggering
    assert wd.observe(7, 0.5)
    assert wd.trigger_count == 2
    t.flush()
    recs = [r for r in trace.read_records(t.path)
            if r.get("name") == "step_time_regression"]
    assert len(recs) == 2
    assert recs[0]["window_s"] == 0.5 and recs[0]["kind"] == "anomaly"


def test_heartbeat_write_read_interval(tmp_path, monkeypatch):
    path = heartbeat_path(str(tmp_path), 2)
    hb = Heartbeat(path, interval_s=60.0)  # constructor beats once
    first = read_heartbeat(path)
    assert first is not None and first["pid"] == os.getpid()
    assert not hb.beat(step=1)             # interval not elapsed
    assert hb.beat(step=2, force=True)
    assert read_heartbeat(path)["step"] == 2
    # from_env: None without the env var, armed with it
    monkeypatch.delenv("DTF_HEARTBEAT_DIR", raising=False)
    assert Heartbeat.from_env() is None
    monkeypatch.setenv("DTF_HEARTBEAT_DIR", str(tmp_path))
    monkeypatch.setenv("DTF_PROCESS_ID", "4")
    hb2 = Heartbeat.from_env()
    assert read_heartbeat(heartbeat_path(str(tmp_path), 4)) is not None
    assert hb2.path.endswith("heartbeat_rank4.json")


def test_launcher_watchdog_heartbeat_contract_parity(tmp_path):
    """cli/launch.py duplicates the heartbeat helpers to stay
    stdlib-only; the two sides must agree on the contract."""
    from dtf_tpu.cli import launch
    from dtf_tpu.obs import watchdog
    assert launch.HEARTBEAT_DIR_ENV == watchdog.HEARTBEAT_DIR_ENV
    assert (launch.heartbeat_path(str(tmp_path), 3)
            == watchdog.heartbeat_path(str(tmp_path), 3))
    Heartbeat(watchdog.heartbeat_path(str(tmp_path), 3))  # writes once
    got = launch.read_heartbeat(launch.heartbeat_path(str(tmp_path), 3))
    assert got is not None and got["pid"] == os.getpid()
    assert launch.read_heartbeat(str(tmp_path / "missing.json")) is None


def test_launcher_consumes_heartbeat_file(tmp_path):
    """A rank that is silent on stdout but beats its heartbeat file
    survives the supervisor's hang watchdog (the structured liveness
    signal the launcher now prefers over log-size scraping)."""
    from dtf_tpu.cli.launch import launch_local
    script = (
        "import json, os, time\n"
        "d = os.environ['DTF_HEARTBEAT_DIR']\n"
        "p = os.path.join(d, 'heartbeat_rank%s.json' % "
        "os.environ['DTF_PROCESS_ID'])\n"
        "for _ in range(16):\n"
        "    tmp = p + '.tmp'\n"
        "    open(tmp, 'w').write(json.dumps({'ts': time.time()}))\n"
        "    os.replace(tmp, p)\n"
        "    time.sleep(0.25)\n")
    t0 = time.monotonic()
    rc = launch_local([sys.executable, "-c", script], num_processes=1,
                      coordinator="localhost:0",
                      log_dir=str(tmp_path / "logs"),
                      devices_per_process=None, heartbeat_timeout=1.0,
                      startup_grace=1.0)
    # without heartbeat consumption the silent rank dies at ~1s and rc
    # is nonzero; with it the rank runs its full ~4s and exits clean
    assert rc == 0
    assert time.monotonic() - t0 >= 3.0


# --- trace_main summarizer -------------------------------------------------

def _write_trace(tmp_path, with_anomaly: bool):
    t = trace.configure(str(tmp_path), rank=0)
    for step in range(4):
        with trace.span("step", step=step):
            pass
    trace.event("heartbeat", step=3)
    if with_anomaly:
        trace.anomaly("nan_loss", step=3, loss="nan")
    t.flush()
    trace.disable()


def test_trace_main_summarizes_and_check_clean(tmp_path, capsys):
    _write_trace(tmp_path, with_anomaly=False)
    assert trace_main([str(tmp_path), "--check"]) == 0
    out = capsys.readouterr().out
    assert "step spans: 4" in out
    assert "anomalies: none" in out


def test_trace_main_check_fails_on_anomaly(tmp_path, capsys):
    _write_trace(tmp_path, with_anomaly=True)
    assert trace_main([str(tmp_path)]) == 0       # report-only: exit 0
    assert "ANOMALY: nan_loss" in capsys.readouterr().out
    assert trace_main([str(tmp_path), "--check"]) == 1


def test_trace_main_json_mode(tmp_path, capsys):
    _write_trace(tmp_path, with_anomaly=False)
    assert trace_main([str(tmp_path), "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["spans"]["step"]["count"] == 4
    assert summary["events"] == {"heartbeat": 1, "trace_start": 1}


def test_trace_main_shows_a_spans_laps_by_name(tmp_path, capsys):
    t = trace.configure(str(tmp_path), rank=0)
    for n in range(3):
        with trace.lap_span("turn", decoding=n + 2, step=n + 7,
                            note="words") as sp:
            sp.lap("build")
            time.sleep(0.002)
            sp.lap("ready")
            sp.lap("build")         # a second piece of the same name
    with trace.span("plain"):
        pass
    t.flush()
    trace.disable()
    assert trace_main([str(tmp_path), "--json"]) == 0
    spans = json.loads(capsys.readouterr().out)["spans"]
    laps = spans["turn"]["laps"]
    assert list(laps)[0] == "ready" and set(laps) == {"ready", "build",
                                                      "rest"}
    assert laps["build"]["spans"] == 3 and "laps" not in spans["plain"]
    assert sum(r["total_s"] for r in laps.values()) == pytest.approx(
        spans["turn"]["total_s"], rel=1e-6)
    assert sum(r["share"] for r in laps.values()) == pytest.approx(1.0,
                                                                   rel=1e-6)
    # the whole-number attributes of a lap-keeping span: its counts
    assert spans["turn"]["counts"] == {
        "decoding": {"spans": 3, "total": 9, "mean": 3.0, "min": 2, "max": 4},
        "step": {"spans": 3, "total": 24, "mean": 8.0, "min": 7, "max": 9}}
    assert "counts" not in spans["plain"]
    assert trace_main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "lap ready" in out and "lap build" in out
    assert "count decoding" in out and "min 7  max 9" in out


def test_trace_main_ledger_json_machine_readable(tmp_path, capsys):
    """--ledger --json emits the ledger rows as one JSON object — the
    join surface plan_serve_main's calibration consumes (scraping the
    human table was the alternative)."""
    t = trace.configure(str(tmp_path), rank=0)
    trace.event("ledger_exec", exec="serve_decode_step", flops=1.5e9,
                bytes=2.0e8, peak_tflops=None, peak_hbm_gbps=None)
    trace.event("ledger_summary", exec="serve_decode_step", count=32,
                mean_s=0.011, achieved_tflops=0.136, mfu=None,
                hbm_frac=None)
    t.flush()
    trace.disable()
    assert trace_main([str(tmp_path), "--ledger", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    rows = payload["ledger"]
    assert len(rows) == 1
    row = rows[0]
    assert row["exec"] == "serve_decode_step" and row["rank"] == "0"
    assert row["flops"] == 1.5e9 and row["count"] == 32
    assert row["mean_s"] == 0.011
    # a stream with no ledger records exits 2 in json mode too
    t2 = trace.configure(str(tmp_path / "empty"), rank=0)
    t2.flush()
    trace.disable()
    assert trace_main([str(tmp_path / "empty"), "--ledger",
                       "--json"]) == 2


def test_trace_main_missing_dir(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_main([str(tmp_path / "empty")])


def test_trace_main_merge_time_ordered_cross_rank(tmp_path, capsys):
    """--merge interleaves every rank's records into ONE stream sorted
    by timestamp, each record rank-tagged — the cross-rank post-mortem
    view."""
    for rank in (0, 1):
        t = trace.configure(str(tmp_path), rank=rank)
        for step in range(3):
            with trace.span("step", step=step):
                time.sleep(0.002)
        trace.event("heartbeat", step=2)
        t.flush()
        trace.disable()
    assert trace_main([str(tmp_path), "--merge"]) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    # every record from both ranks, rank-tagged
    assert {r["rank"] for r in lines} == {0, 1}
    assert sum(r.get("kind") == "span" and r.get("name") == "step"
               for r in lines) == 6
    # the stream is time-ordered
    ts = [float(r["ts"]) for r in lines]
    assert ts == sorted(ts)
    # rank 0's steps finished before rank 1 started writing here, so a
    # correct merge cannot simply concatenate files — order mixes the
    # trace_start/step records by wall clock
    assert all("ts" in r for r in lines)


def test_trace_main_merge_orders_router_and_replica_streams(tmp_path,
                                                            capsys):
    """The serving router writes a NAMED stream (trace_router.jsonl,
    records tagged rank="router") next to its replicas' per-rank
    files; --merge interleaves the tiers into one timeline — the view
    that answers "what did the router see when replica 1 died?"."""
    for rank in (0, 1):
        t = trace.configure(str(tmp_path), rank=rank)
        trace.event("serve_submit", step=rank)
        t.flush()
        trace.disable()
        time.sleep(0.002)
    t = trace.configure(str(tmp_path), stream="router")
    trace.event("replica_registered", replica=0)
    trace.anomaly("replica_lost", replica=1, reason="heartbeat_timeout")
    t.flush()
    trace.disable()
    assert os.path.exists(str(tmp_path / "trace_router.jsonl"))
    # the router anomaly fails --check like any rank's would
    assert trace_main([str(tmp_path), "--merge", "--check"]) == 1
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert {r["rank"] for r in lines} == {0, 1, "router"}
    ts = [float(r["ts"]) for r in lines]
    assert ts == sorted(ts)
    # allowed named-stream anomalies pass, exactly like rank anomalies
    assert trace_main([str(tmp_path), "--merge", "--check",
                       "--allow", "replica_lost"]) == 0


def test_trace_main_allow_warns_on_unknown_kind(tmp_path, capsys):
    """A typo'd --allow silently tolerating nothing is the bug an
    expected-anomaly list invites — unknown kinds warn loudly (but do
    not fail: new subsystems may emit kinds the registry hasn't
    learned)."""
    _write_trace(tmp_path, with_anomaly=False)
    assert trace_main([str(tmp_path), "--check",
                       "--allow", "replica_lsot"]) == 0
    assert "replica_lsot" in capsys.readouterr().err
    _write_trace(tmp_path, with_anomaly=False)
    assert trace_main([str(tmp_path), "--check",
                       "--allow", "replica_lost"]) == 0
    assert "not a known anomaly kind" not in capsys.readouterr().err


def test_trace_main_merge_composes_with_check(tmp_path, capsys):
    _write_trace(tmp_path, with_anomaly=True)
    assert trace_main([str(tmp_path), "--merge", "--check"]) == 1
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()
             if ln.startswith("{")]
    assert any(r.get("kind") == "anomaly" for r in lines)


# --- end-to-end: traced smoke train ---------------------------------------

def test_traced_smoke_train_reconciles_step_spans(tmp_path):
    """Acceptance bar: a traced smoke run's step spans match the loop's
    reported step count, a compile span exists, and the trace is clean
    under --check."""
    steps = 3
    stats = run(base_cfg(train_steps=steps, trace_dir=str(tmp_path)))
    assert np.isfinite(stats["loss"])
    trace.flush()
    path = os.path.join(str(tmp_path), "trace_rank0.jsonl")
    recs = trace.read_records(path)
    step_spans = [r for r in recs
                  if r["kind"] == "span" and r["name"] == "step"]
    assert len(step_spans) == steps
    assert [r["step"] for r in step_spans] == list(range(steps))
    compile_spans = [r for r in recs
                     if r["kind"] == "span" and r["name"] == "compile"]
    assert len(compile_spans) == 1
    # the first step nests under the compile span
    assert step_spans[0]["parent"] == "compile"
    assert compile_spans[0]["dur_s"] >= step_spans[0]["dur_s"]
    # synced per-step timing: one log_window span per post-compile
    # log_steps window (log_steps=1 → steps-1 windows), with real
    # (sync-inclusive) durations — orders of magnitude above the
    # async-dispatch step spans
    windows = [r for r in recs
               if r["kind"] == "span" and r["name"] == "log_window"]
    assert len(windows) == steps - 1
    for w in windows:
        assert w["steps"] == 1
        assert w["dur_s"] > 0 and abs(w["step_s"] - w["dur_s"]) < 1e-9
    trace.disable()
    assert trace_main([str(tmp_path), "--check"]) == 0


def test_nan_guard_aborts_training_e2e(tmp_path, monkeypatch):
    """NaN input → NaN loss at the first log boundary → structured
    abort, anomaly record in the trace, --check exits nonzero."""
    from dtf_tpu.cli import runner as runner_mod
    from dtf_tpu.data import synthetic_input_fn as real_synth

    def poisoned(spec, train, batch, seed, start_step=0):
        for images, labels in real_synth(spec, train, batch, seed,
                                         start_step=start_step):
            yield np.full_like(images, np.nan), labels

    monkeypatch.setattr(runner_mod, "synthetic_input_fn", poisoned)
    with pytest.raises(TrainingAnomaly) as ei:
        run(base_cfg(train_steps=2, trace_dir=str(tmp_path)))
    assert ei.value.record["name"] == "nan_loss"
    assert ei.value.record["step"] == 1
    trace.disable()
    assert trace_main([str(tmp_path), "--check"]) == 1


@pytest.mark.slow  # negative twin of test_nan_guard_aborts_training_e2e (tier-1)
def test_nan_guard_can_be_disabled(monkeypatch):
    from dtf_tpu.cli import runner as runner_mod
    from dtf_tpu.data import synthetic_input_fn as real_synth

    def poisoned(spec, train, batch, seed, start_step=0):
        for images, labels in real_synth(spec, train, batch, seed,
                                         start_step=start_step):
            yield np.full_like(images, np.nan), labels

    monkeypatch.setattr(runner_mod, "synthetic_input_fn", poisoned)
    stats = run(base_cfg(train_steps=2, nan_guard=False))
    assert not np.isfinite(stats["loss"])  # trained on NaNs, loudly


# --- distributed span context ---------------------------------------------

def test_span_context_default_context_and_explicit_precedence(tmp_path):
    """Three propagation layers, explicit > context() > default; spans
    get rank-qualified ids and parent_span links."""
    t = trace.configure(str(tmp_path), rank=2)
    trace.set_default_trace("runid")
    with trace.span("step", step=1):
        with trace.span("inner"):
            pass
    tid = trace.new_trace_id()
    assert len(tid) == 16 and tid != trace.new_trace_id()
    with trace.context(tid, parent="psid"):
        trace.event("serve_submit", request=1)
        trace.event("tagged", trace="explicit-wins")
    trace.event("after_ctx")
    t.flush()
    recs = {r["name"]: r for r in trace.read_records(t.path)}
    # default trace covers the run-scoped records
    assert recs["step"]["trace"] == "runid"
    assert recs["inner"]["trace"] == "runid"
    # span ids + parent link
    assert recs["inner"]["parent_span"] == recs["step"]["span_id"]
    assert recs["step"]["span_id"].startswith("2.")
    assert "parent_span" not in recs["step"]
    # context() shadows the default, carries the cross-process parent
    assert recs["serve_submit"]["trace"] == tid
    assert recs["serve_submit"]["parent_span"] == "psid"
    # explicit attr beats the ambient context
    assert recs["tagged"]["trace"] == "explicit-wins"
    assert recs["after_ctx"]["trace"] == "runid"
    # disable() clears the default — no leak into the next test's run
    trace.disable()
    assert trace.default_trace() is None


def test_trace_main_request_timeline_cross_rank(tmp_path, capsys):
    """--request joins one trace id's records across rank files and a
    named stream; batch spans match via their `traces` list; an
    unknown id exits 2."""
    tid = "feedfacefeedface"
    t = trace.configure(str(tmp_path), stream="router")
    trace.event("router_submit", request=1, trace=tid, span_id="r1")
    trace.event("router_dispatch", request=1, trace=tid, replica=0,
                attempt=1)
    t.flush()
    trace.disable()
    t = trace.configure(str(tmp_path), rank=0)
    trace.event("serve_submit", request=7, trace=tid, parent_span="r1")
    with trace.span("serve_decode", traces=[tid, "othertrace"]):
        time.sleep(0.002)
    trace.event("serve_retire", request=7, trace=tid)
    trace.event("unrelated", trace="othertrace")
    t.flush()
    trace.disable()
    assert trace_main([str(tmp_path), "--request", tid]) == 0
    out = capsys.readouterr().out
    assert "router_submit" in out and "serve_retire" in out
    assert "serve_decode" in out         # via the traces list
    assert "unrelated" not in out
    assert "router" in out and tid in out
    # --merge --request: the raw filtered records
    assert trace_main([str(tmp_path), "--merge", "--request", tid]) == 0
    recs = [json.loads(ln) for ln in
            capsys.readouterr().out.strip().splitlines()]
    assert len(recs) == 5
    ts = [float(r["ts"]) for r in recs]
    assert ts == sorted(ts)
    assert {str(r["rank"]) for r in recs} == {"router", "0"}
    # unknown trace id: loud exit 2, not an empty timeline
    assert trace_main([str(tmp_path), "--request", "nope"]) == 2


def test_profiler_trace_event_surfaced_in_summary(tmp_path, capsys):
    t = trace.configure(str(tmp_path), rank=0)
    trace.event("profiler_trace", path="/tmp/xyz/traces", start_step=2,
                stop_step=4)
    t.flush()
    trace.disable()
    assert trace_main([str(tmp_path)]) == 0
    assert "profiler trace: /tmp/xyz/traces" in capsys.readouterr().out


@pytest.mark.slow  # routing variant of the tier-1 traced-run tests
def test_profile_steps_routes_to_trace_dir(tmp_path):
    """--profile_steps with a trace dir writes the jax.profiler dump
    under the TRACE dir (not model_dir, where it buried checkpoints)
    and emits a profiler_trace event carrying the path."""
    model_dir = tmp_path / "model"
    trace_dir = tmp_path / "trace"
    run(base_cfg(train_steps=3, profile_steps="1,2",
                 model_dir=str(model_dir), trace_dir=str(trace_dir)))
    trace.disable()
    recs = trace.read_records(str(trace_dir / "trace_rank0.jsonl"))
    ev = [r for r in recs if r.get("name") == "profiler_trace"]
    assert len(ev) == 1
    assert ev[0]["path"] == str(trace_dir)
    # the XLA plugin dump landed under the trace dir, not model_dir
    assert (trace_dir / "plugins").exists()
    assert not (model_dir / "plugins").exists()


# --- MFU/cost ledger -------------------------------------------------------

def _benchmark_peaks():
    from benchmark.lib.peaks import PEAKS
    return [pytest.param(kind, q, row[q], id=f"{kind}-{q}")
            for kind, row in PEAKS.items()
            for q in ("bf16_flops_per_s", "hbm_bytes_per_s")]


@pytest.mark.parametrize("kind,quantity,want", _benchmark_peaks())
def test_ledger_peaks_agree_with_the_benchmarks_table(kind, quantity, want,
                                                      monkeypatch):
    """The program keeps one table of peaks (obs/ledger.py) and the
    benchmark keeps its own (a program module does not import
    ``benchmark/``): for every device kind the benchmark knows, the
    ledger's lookup gives the same figure.  This is where the two meet."""
    import types

    import jax

    from dtf_tpu.obs import ledger as ledger_mod
    monkeypatch.delenv("DTF_PEAK_TFLOPS", raising=False)
    monkeypatch.delenv("DTF_PEAK_HBM_GBPS", raising=False)
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(device_kind=kind)])
    peak_f, peak_b = ledger_mod.device_peaks()
    got = {"bf16_flops_per_s": peak_f, "hbm_bytes_per_s": peak_b}[quantity]
    assert got == want


@pytest.mark.parametrize("op_name,want", [
    ("jit(_decode_impl)/layers_3/paged_flash_decode/pallas_call",
     "paged_flash_decode"),
    # megablox wraps its call in a jit of its own: the caller's scope, which
    # carries the tile the expert layer chose, is the kernel's name
    ("jit(_chunk_impl)/layers_2/gmm_288x1024x1792/jit(gmm)/pallas_call",
     "gmm_288x1024x1792"),
    ("jit(f)/jit(gmm)/pallas_call", "pallas_call"),
])
def test_ledger_names_a_kernel_by_the_scope_in_front_of_its_call(op_name,
                                                                 want):
    from dtf_tpu.obs.ledger import _pallas_kernels
    hlo = (f'  %k.1 = f32[8,128] custom-call(%a), custom_call_target='
           f'"tpu_custom_call", metadata={{op_name="{op_name}"}}\n'
           '  %other = f32[8] custom-call(%a), custom_call_target="Sharding"')
    assert _pallas_kernels(hlo) == {want: 1}


def test_ledger_mfu_crosschecked_against_cost_analysis(tmp_path,
                                                       monkeypatch):
    """The acceptance bar: the ledger's MFU for the compiled train
    step equals the formula — flops from the SAME
    compiled executable's cost_analysis, divided by wall time and the
    (env-pinned) peak — to float precision when both use the same
    wall time, and the e2e fit() number lands within the documented
    20% host-overhead tolerance of the formula applied to the loop's
    own measured step time."""
    monkeypatch.setenv("DTF_PEAK_TFLOPS", "0.5")
    monkeypatch.setenv("DTF_PEAK_HBM_GBPS", "10")
    from dtf_tpu.models import build_model
    from dtf_tpu.obs.ledger import Ledger, cost_of
    from dtf_tpu.obs.registry import MetricsRegistry
    from dtf_tpu.runtime import initialize
    from dtf_tpu.train import Trainer

    cfg = base_cfg(train_steps=2, batch_size=8)
    rt = initialize(cfg)
    model, l2 = build_model("resnet20", num_classes=10)
    trainer = Trainer(cfg, rt, model, l2, TINY)
    rng = np.random.default_rng(0)
    images = rng.normal(0, 1, (8, 8, 8, 3)).astype(np.float32)
    labels = rng.integers(0, 10, (8,), dtype=np.int32)
    state = trainer.init_state(__import__("jax").random.key(0),
                               (images, labels))
    sharded = rt.shard_batch((images, labels))
    compiled = trainer.train_step.lower(state, *sharded).compile()
    flops, nbytes = cost_of(compiled)
    assert flops > 0 and nbytes > 0

    reg = MetricsRegistry()
    ledger = Ledger(reg)
    ledger.register("train_step", compiled=compiled)
    wall = 0.0125
    ledger.observe("train_step", wall)
    mfu_ledger = reg.get("ledger_train_step_mfu").value
    mfu_ref = (flops / wall) / (0.5e12)     # the formula, by hand
    np.testing.assert_allclose(mfu_ledger, mfu_ref, rtol=1e-9)
    hbm_ref = (nbytes / wall) / (10e9)
    np.testing.assert_allclose(
        reg.get("ledger_train_step_hbm_frac").value, hbm_ref, rtol=1e-9)
    s = ledger.summary()["train_step"]
    assert s["count"] == 1 and s["mfu"] == mfu_ledger


# the optimized-HLO lines ``collectives`` reads, as the TPU's compiler and
# the CPU's print them: a plain op, a tuple-shaped combined one, an async
# pair (counted once, at its start), and lines that only look alike
_HLO = """
HloModule jit_step, entry_computation_layout={(f32[8,128]{1,0})->f32[8,128]{1,0}}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  ROOT %add.1 = f32[] add(%a, %b)
}

ENTRY %main.1 (p0: f32[8,128], p1: bf16[4,256]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0:T(8,128)} parameter(0)
  %p1 = bf16[4,256]{1,0:T(4,128)(2,1)} parameter(1)
  %div.709 = f32[]{:T(128)} constant(1)
  %div.711 = f32[]{:T(128)} constant(2)
  %rs.1 = f32[8,32]{1,0:T(8,128)S(1)} reduce-scatter(%p0), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={1}, to_apply=%region_0.1
  %all-reduce.21 = (f32[]{:T(128)}, f32[]{:T(128)}) all-reduce(%div.709, %div.711), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%region_0.1
  %ags.1 = (bf16[4,256]{1,0}, bf16[4,1024]{1,0}) all-gather-start(%p1), channel_id=3, dimensions={1}
  %agd.1 = bf16[4,1024]{1,0} all-gather-done(%ags.1)
  %ag.2 = bf16[4,1024]{1,0:T(4,128)(2,1)} all-gather(bf16[4,256]{1,0} %p1), channel_id=4, dimensions={1}
  %ds.1 = f32[8,32]{1,0} dynamic-slice(%p0, %div.709, %div.709), dynamic_slice_sizes={8,32}
  %collective-permute-start.24 = (f32[8,32]{0,1:T(8,128)}, f32[8,32]{0,1:T(8,128)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%ds.1), channel_id=5, source_target_pairs={{0,1},{1,3},{3,2},{2,0}}, metadata={op_name="jit(step)/shard_map/ppermute"}
  %collective-permute-done.24 = f32[8,32]{0,1:T(8,128)} collective-permute-done(%collective-permute-start.24)
  ROOT %fusion.1 = f32[8,128]{1,0} fusion(%rs.1, %agd.1), kind=kLoop, calls=%region_0.1, metadata={op_name="jit(step)/all-reduce(not one)"}
}
"""


def test_ledger_counts_the_collectives_the_compiler_left(caplog):
    """``collectives`` reads call sites and operand bytes off the optimized
    HLO — one ``reduce-scatter`` of a 4 KiB operand, one ``all-reduce`` of
    two scalars, two ``all-gather`` (an async pair once) of 2 KiB each,
    one ``collective-permute`` pair (a hop of the ZeRO scatter's ring) of
    1 KiB — and ``register`` puts them on the entry, the trace event and ONE log
    line at compile."""
    import logging
    from dtf_tpu.obs.ledger import Ledger, collectives
    from dtf_tpu.obs.registry import MetricsRegistry

    class Compiled:
        def as_text(self):
            return _HLO

        def cost_analysis(self):
            return {"flops": 7.0, "bytes accessed": 9.0}

    want = {"reduce-scatter": {"ops": 1, "bytes": 8 * 128 * 4},
            "all-reduce": {"ops": 1, "bytes": 8},
            "all-gather": {"ops": 2, "bytes": 2 * 4 * 256 * 2},
            "collective-permute": {"ops": 1, "bytes": 8 * 32 * 4}}
    assert collectives(Compiled()) == want
    ledger = Ledger(MetricsRegistry())
    with caplog.at_level(logging.INFO, logger="dtf_tpu"):
        ledger.register("train_step", compiled=Compiled())
    lines = [r.getMessage() for r in caplog.records
             if "train_step compiled" in r.getMessage()]
    assert len(lines) == 1
    assert "1 reduce-scatter (4096 B), 1 all-reduce (8 B), " \
           "2 all-gather (4096 B), 1 collective-permute (1024 B)" in lines[0]
    assert ledger.summary()["train_step"]["collectives"] == want


@pytest.mark.parametrize("stage", [0, 3])
def test_ledger_collectives_of_a_compiled_toy_step(tmp_path, capsys, stage,
                                                   eight_devices):
    """The real thing on four virtual devices: the plain data-parallel
    step all-reduces its gradients and holds no other collective; the
    ZeRO-3 step gathers every leaf and all-reduces no gradient — and the
    counts ride the ``ledger_exec`` trace event to ``trace_main --ledger
    --json``."""
    import jax
    from dtf_tpu.models import build_model
    from dtf_tpu.obs.ledger import Ledger
    from dtf_tpu.obs.registry import MetricsRegistry
    from dtf_tpu.runtime import initialize
    from dtf_tpu.train import Trainer

    cfg = base_cfg(train_steps=2, batch_size=8, num_devices=4,
                   distribution_strategy="mirrored", zero_stage=stage)
    rt = initialize(cfg)
    model, l2 = build_model("resnet20", num_classes=10)
    trainer = Trainer(cfg, rt, model, l2, TINY)
    images = np.zeros((8, 8, 8, 3), np.float32)
    labels = np.zeros((8,), np.int32)
    state = trainer.init_state(jax.random.key(0), (images, labels))
    n_leaves = len(jax.tree_util.tree_leaves(
        trainer.canonical_state(state).params))
    compiled = trainer.train_step.lower(
        state, *rt.shard_batch((images, labels))).compile()
    trace.configure(str(tmp_path))
    Ledger(MetricsRegistry()).register("train_step", compiled=compiled)
    trace.disable()
    recs = trace.read_records(str(tmp_path / "trace_rank0.jsonl"))
    [ev] = [r for r in recs if r.get("name") == "ledger_exec"]
    got = ev["collectives"]
    param_bytes = sum(
        int(np.prod(p.shape)) * 4 for p in jax.tree_util.tree_leaves(
            trainer.canonical_state(state).params))
    if stage == 0:
        assert got["all-gather"]["ops"] == got["reduce-scatter"]["ops"] == 0
        assert got["all-reduce"]["bytes"] >= param_bytes
    else:
        assert got["all-gather"]["ops"] >= n_leaves
        # the CPU's compiler may keep the scatter or decompose it: every
        # gradient crosses in one or the other
        assert (got["reduce-scatter"]["bytes"]
                + got["all-reduce"]["bytes"]) >= param_bytes
    capsys.readouterr()
    assert trace_main([str(tmp_path), "--ledger", "--json"]) == 0
    [row] = json.loads(capsys.readouterr().out)["ledger"]
    assert row["exec"] == "train_step" and row["collectives"] == got


@pytest.mark.slow  # near-twin of test_traced_smoke_train_reconciles_step_spans (tier-1)
def test_traced_run_carries_run_trace_and_ledger(tmp_path, monkeypatch):
    """E2E: a traced smoke run's records all share ONE run-scoped
    trace id (steps, windows, train_end — so --request joins them),
    the ledger registered the train step from the executed AOT
    executable, observed clean windows, and emitted a summary that
    trace_main --ledger renders; the e2e MFU agrees with the formula
    on the run's own mean step time within float tolerance."""
    monkeypatch.setenv("DTF_PEAK_TFLOPS", "0.5")
    run(base_cfg(train_steps=4, trace_dir=str(tmp_path)))
    trace.disable()
    recs = trace.read_records(str(tmp_path / "trace_rank0.jsonl"))
    steps = [r for r in recs if r.get("name") == "step"]
    tids = {r.get("trace") for r in steps}
    assert len(tids) == 1 and None not in tids
    run_tid = tids.pop()
    assert [r.get("trace") for r in recs
            if r.get("name") == "train_end"] == [run_tid]
    # --request on the run id reconstructs the run timeline
    assert trace_main([str(tmp_path), "--request", run_tid]) == 0
    # ledger records: registration + summary, consistent numbers
    reg_ev = [r for r in recs if r.get("name") == "ledger_exec"
              and r.get("exec") == "train_step"]
    assert len(reg_ev) == 1 and reg_ev[0]["flops"] > 0
    summ = [r for r in recs if r.get("name") == "ledger_summary"
            and r.get("exec") == "train_step"]
    assert len(summ) == 1
    s = summ[0]
    assert s["count"] >= 1 and s["mean_s"] > 0
    np.testing.assert_allclose(
        s["mfu"], (reg_ev[0]["flops"] / s["mean_s"]) / 0.5e12,
        rtol=1e-6)
    assert trace_main([str(tmp_path), "--ledger"]) == 0


@pytest.mark.slow  # ledger contract itself stays tier-1 (mfu crosscheck test)
def test_ledger_env_kill_switch(tmp_path, monkeypatch):
    monkeypatch.setenv("DTF_LEDGER", "0")
    run(base_cfg(train_steps=3, trace_dir=str(tmp_path)))
    trace.disable()
    recs = trace.read_records(str(tmp_path / "trace_rank0.jsonl"))
    assert not any(r.get("name") == "ledger_exec" for r in recs)
    assert trace_main([str(tmp_path), "--ledger"]) == 2


# --- Prometheus endpoint: /healthz + concurrent scrapes --------------------

def test_prom_healthz_and_concurrent_scrape():
    """/healthz answers 200 with the health_fn payload (503 on
    ok=False), and 8 threads hammering /metrics + /healthz while
    another mutates the registry all get parseable, complete
    responses — the endpoint is re-snapshotted per request, never
    torn."""
    import threading
    import urllib.error
    import urllib.request
    from dtf_tpu.obs.prom import MetricsServer
    from dtf_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    c = reg.counter("scrapes_total", unit="scrapes")
    h = reg.histogram("lat", unit="s")
    state = {"ok": True}
    srv = MetricsServer(0, registry_fn=lambda: reg,
                        health_fn=lambda: {"ok": state["ok"],
                                           "outstanding": c.value})
    base = f"http://127.0.0.1:{srv.port}"
    try:
        stop = threading.Event()

        def mutate():
            i = 0
            while not stop.is_set():
                c.inc()
                h.observe(0.001 * (i % 7))
                i += 1

        mt = threading.Thread(target=mutate, daemon=True)
        mt.start()
        errors = []

        def scrape(n):
            try:
                for i in range(20):
                    body = urllib.request.urlopen(
                        f"{base}/metrics", timeout=10).read().decode()
                    assert "# TYPE scrapes_total counter" in body
                    assert body.endswith("\n")
                    hz = json.loads(urllib.request.urlopen(
                        f"{base}/healthz", timeout=10).read())
                    assert hz["ok"] is True and "outstanding" in hz
            except Exception as e:  # noqa: BLE001
                errors.append(f"scraper {n}: {e!r}")

        threads = [threading.Thread(target=scrape, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        stop.set()
        mt.join(timeout=5)
        assert not errors, errors
        # degraded health reads 503 with the payload intact
        state["ok"] = False
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/healthz", timeout=10)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["ok"] is False
        # unknown path stays 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.shutdown()


# --- overhead bound --------------------------------------------------------

def test_tracing_overhead_under_5pct_of_smoke_step(tmp_path):
    """Per-step tracing cost (one 'step' span: two clock reads + one
    buffered JSONL record) must stay under 5% of a smoke-train step.

    Measured as span-cost vs. the smoke run's own post-compile step
    times (TimeHistory timestamps), which is exactly what tracing adds
    per step — a full A/B of two training runs on a shared CI box would
    measure scheduler noise, not tracing."""
    steps = 6
    stats = run(base_cfg(train_steps=steps, trace_dir=str(tmp_path)))
    # per-step wall times from the run's own timestamp log (log_steps=1
    # → one entry per step); drop the first interval (compile-skewed)
    ts = [b.timestamp for b in stats["step_timestamp_log"]]
    assert len(ts) >= 3
    step_times = np.diff(ts)[1:]
    step_s = float(np.median(step_times))
    assert step_s > 0

    t = trace.get()
    assert t is not None
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        with trace.span("step", step=i):
            pass
    span_cost = (time.perf_counter() - t0) / n
    assert span_cost < 0.05 * step_s, (
        f"tracing costs {span_cost * 1e6:.1f}µs/step vs step time "
        f"{step_s * 1e3:.2f}ms — over the 5% bound")
