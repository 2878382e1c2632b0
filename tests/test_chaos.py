"""Chaos layer + crash-exact recovery tests.

The recovery machinery a pod run lives on — preemption checkpointing,
checkpoint integrity fallback, supervisor exit-code classification —
verified by actually killing processes (deterministic fault injection,
dtf_tpu/chaos) and asserting the resumed run is BIT-IDENTICAL to the
uninterrupted one, data-batch order included.
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from dtf_tpu import chaos
from dtf_tpu.cli import launch
from dtf_tpu.obs import trace
from dtf_tpu.train import preemption
from dtf_tpu.train.checkpoint import (Checkpointer, load_train_checkpoint,
                                      manifest_path, verify_step)


@pytest.fixture(autouse=True)
def clean_chaos():
    yield
    chaos.disable()
    trace.disable()
    preemption.restore()


# ---------------------------------------------------------------------------
# spec grammar
# ---------------------------------------------------------------------------

def test_parse_spec_grammar():
    specs = chaos.parse_spec(
        "crash@step:120, sigterm@rank1:step:80,ps_drop@version:50,"
        "heartbeat_stall@step:60,ckpt_truncate@latest")
    kinds = [(s.kind, s.rank, s.value) for s in specs]
    assert kinds == [("crash", None, 120), ("sigterm", 1, 80),
                     ("ps_drop", None, 50), ("heartbeat_stall", None, 60),
                     ("ckpt_truncate", None, None)]
    assert str(specs[1]) == "sigterm@rank1:step:80"
    # the elastic topology-loss kinds ride the same step point
    specs = chaos.parse_spec("device_loss@step:4,host_loss@rank2:step:6")
    assert [(s.kind, s.rank, s.value) for s in specs] == [
        ("device_loss", None, 4), ("host_loss", 2, 6)]
    assert str(specs[1]) == "host_loss@rank2:step:6"


def test_parse_spec_distributed_kinds():
    """The serving-tier kinds: replica selectors, the bare-value
    shorthand the grammar docs promise (net_partition@replica1:6),
    and round-tripping through str()."""
    specs = chaos.parse_spec(
        "replica_kill@req:5, replica_kill@replica0:req:3,"
        "net_partition@replica1:6, slow_replica@replica0:2.5,"
        "net_partition@replica2:ticks:4")
    got = [(s.kind, s.replica, s.value) for s in specs]
    assert got == [("replica_kill", None, 5), ("replica_kill", 0, 3),
                   ("net_partition", 1, 6), ("slow_replica", 0, 2.5),
                   ("net_partition", 2, 4)]
    # canonical str() re-parses to the same spec
    for s in specs:
        (again,) = chaos.parse_spec(str(s))
        assert (again.kind, again.replica, again.value) == (
            s.kind, s.replica, s.value)


@pytest.mark.parametrize("bad", [
    "explode@step:3",           # unknown kind
    "crash@version:3",          # wrong point for the kind
    "crash@step:x",             # non-int value
    "crash",                    # no point
    "ckpt_truncate@step:3",     # kind takes 'latest'
    "crash@rankX:step:3",       # bad rank selector
    "crash@step:-1",            # negative value
    "net_partition@4",          # partition needs a replica target
    "slow_replica@replica0:1.0",  # factor must be > 1
    "net_partition@replica1:0",   # >= 1 probe tick
    "replica_kill@step:4",      # wrong point for the kind
    "net_partition@replicaX:4",  # bad replica selector
])
def test_parse_spec_rejects(bad):
    with pytest.raises(ValueError):
        chaos.parse_spec(bad)


def test_config_flag_validates_spec():
    from dtf_tpu.config import Config
    with pytest.raises(ValueError):
        Config(fault="explode@step:3")
    Config(fault="crash@step:3")  # valid spec constructs


def test_rank_filtering():
    inj = chaos.configure("crash@rank1:step:5,heartbeat_stall@step:2",
                          rank=0)
    # the rank-1 crash is not armed on rank 0
    assert [s.kind for s in inj.specs] == ["heartbeat_stall"]
    inj.step(5)  # must NOT crash this process
    assert inj.heartbeat_stalled(3)


# ---------------------------------------------------------------------------
# no-op when off (the zero-cost contract)
# ---------------------------------------------------------------------------

def test_off_by_default_and_probes_are_noops():
    from dtf_tpu.config import Config
    assert Config(model="resnet20", dataset="cifar10").fault == ""
    chaos.disable()
    assert not chaos.enabled()
    assert chaos.maybe_configure(None) is None
    assert not chaos.enabled()  # maybe_configure without a spec disarms
    # every probe is a None check returning the identity answer
    chaos.step(10**9)
    assert chaos.heartbeat_stalled(10**9) is False
    assert chaos.ps_drop(10**9) is False
    assert chaos.ckpt_truncate() is False


def test_maybe_configure_disarms_stale_injector():
    chaos.configure("crash@step:1")
    assert chaos.enabled()
    chaos.maybe_configure(None)  # a run with no --fault must disarm it
    assert not chaos.enabled()


def test_armed_but_unfired_is_behavior_identical(tmp_path):
    """A fault armed far beyond the run's horizon changes NOTHING: the
    loss trajectory is bit-identical to the chaos-off run — the probe
    sites alter no RNG stream, no batch order, no update math."""
    from dtf_tpu.cli.runner import run
    from dtf_tpu.config import Config

    def traced_run(sub, fault):
        tdir = tmp_path / sub
        run(Config(model="resnet20", dataset="cifar10",
                   use_trivial_model=True, use_synthetic_data=True,
                   batch_size=4, train_steps=3, log_steps=1,
                   skip_eval=True, skip_checkpoint=True, verbose=0,
                   distribution_strategy="off",
                   model_dir=str(tmp_path / (sub + "_m")),
                   trace_dir=str(tdir), fault=fault))
        trace.disable()
        return _loss_by_step(str(tdir))

    off = traced_run("off", "")
    armed = traced_run("armed", "crash@step:999999,sigterm@step:888888")
    assert off and armed == off


def test_exit_code_contract_parity():
    """launch.py is stdlib-only by design and carries its own copy of
    the exit-code contract — the three sides must agree."""
    assert (launch.EXIT_PREEMPTED == preemption.EXIT_PREEMPTED
            == chaos.EXIT_PREEMPTED == 75)
    assert chaos.EXIT_INJECTED_CRASH == 77
    assert launch.classify_exit(0) == "ok"
    assert launch.classify_exit(75) == "preempted"
    assert launch.classify_exit(77) == "crash"
    assert launch.classify_exit(-9) == "crash"


# ---------------------------------------------------------------------------
# helpers: tiny real-data runs whose batch ORDER matters
# ---------------------------------------------------------------------------

def _make_cifar(root) -> str:
    from dtf_tpu.data import cifar
    d = os.path.join(root, "cifar-10-batches-bin")
    os.makedirs(d)
    rng = np.random.default_rng(0)
    for i in range(1, 6):
        n = 64
        cifar.write_binary_file(
            os.path.join(d, f"data_batch_{i}.bin"),
            rng.integers(0, 256, (n, 32, 32, 3)).astype(np.uint8),
            rng.integers(0, 10, n))
    cifar.write_binary_file(
        os.path.join(d, "test_batch.bin"),
        rng.integers(0, 256, (16, 32, 32, 3)).astype(np.uint8),
        rng.integers(0, 10, 16))
    return root


def _loss_by_step(trace_dir):
    """{step: {loss values seen}} across every rank/attempt trace."""
    out = {}
    import glob
    for path in glob.glob(os.path.join(trace_dir, "trace_rank*.jsonl")):
        for rec in trace.read_records(path):
            if rec.get("kind") == "event" and rec.get("name") == "train_loss":
                out.setdefault(int(rec["step"]), set()).add(rec["loss"])
    return out


def _train_cmd(data_dir, model_dir, trace_dir, steps=8, extra=()):
    return [sys.executable, "-m", "dtf_tpu.cli.cifar_main",
            "--use_trivial_model", "--data_dir", data_dir,
            "--batch_size", "4", "--train_steps", str(steps),
            "--log_steps", "1", "--skip_eval", "--verbose", "0",
            "--distribution_strategy", "off",
            # 1-step log windows on a trivial model are jittery enough
            # to trip the report-only step-time guard; these traces
            # must contain ONLY the injected fault
            "--step_time_guard_factor", "0",
            "--model_dir", model_dir, "--trace_dir", trace_dir,
            *extra]


STEPS = 8


@pytest.fixture(scope="module")
def e2e_runs(tmp_path_factory):
    """The crash-exactness experiment, run ONCE for the module:

      baseline — uninterrupted STEPS-step run
      crash    — same run with an injected hard crash at step 4
                 (checkpoint_steps=2 → durable sealed ckpt at 4),
                 supervised by launch_local --max_restarts, resumed
      sigterm  — same run with injected SIGTERM at step 3 (NO interval
                 checkpoints: the emergency preemption save is the only
                 thing that makes resume possible), max_restarts=0 —
                 the preempted restart must not consume the budget
    """
    base = str(tmp_path_factory.mktemp("chaos_e2e"))
    data = _make_cifar(os.path.join(base, "data"))
    runs = {"base_dir": base, "data": data}

    # baseline (plain subprocess — no supervision needed)
    m, t = os.path.join(base, "m0"), os.path.join(base, "t0")
    r = subprocess.run(_train_cmd(data, m, t), capture_output=True)
    assert r.returncode == 0, r.stdout.decode()[-2000:] + r.stderr.decode()[-2000:]
    runs["baseline"] = _loss_by_step(t)

    # injected crash at step 4 under the supervisor
    m, t = os.path.join(base, "m1"), os.path.join(base, "t1")
    logs = os.path.join(base, "logs_crash")
    rc = launch.launch_local(
        _train_cmd(data, m, t, extra=(
            "--resume", "--checkpoint_steps", "2",
            "--fault", "crash@step:4")),
        num_processes=1, coordinator="localhost:0", log_dir=logs,
        devices_per_process=None, max_restarts=2,
        restart_backoff_s=0.05)
    runs["crash_rc"] = rc
    runs["crash"] = _loss_by_step(t)
    runs["crash_logs"] = logs
    runs["crash_trace"] = t

    # injected SIGTERM at step 3: emergency checkpoint only.
    # max_restarts=1 turns supervision on; the events assert below
    # proves the preempted restart left that crash budget UNTOUCHED
    m, t = os.path.join(base, "m2"), os.path.join(base, "t2")
    logs = os.path.join(base, "logs_sigterm")
    rc = launch.launch_local(
        _train_cmd(data, m, t, extra=(
            "--resume", "--fault", "sigterm@step:3")),
        num_processes=1, coordinator="localhost:0", log_dir=logs,
        devices_per_process=None, max_restarts=1,
        restart_backoff_s=0.05)
    runs["sigterm_rc"] = rc
    runs["sigterm"] = _loss_by_step(t)
    runs["sigterm_logs"] = logs
    runs["sigterm_model"] = m
    return runs


def _events(log_dir):
    path = os.path.join(log_dir, "supervisor_events.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_e2e_crash_trajectory_bit_identical(e2e_runs):
    """Killed at step 4 (hard os._exit), restarted by the supervisor,
    resumed from the sealed step-4 checkpoint: every step's loss —
    including the overlap steps both attempts logged — is bit-identical
    to the uninterrupted run.  Batch order included: the data is real
    (shuffled + augmented CIFAR), so a repeated/skipped batch would
    diverge the trajectory immediately."""
    assert e2e_runs["crash_rc"] == 0
    base, crash = e2e_runs["baseline"], e2e_runs["crash"]
    assert set(base) == set(range(1, STEPS + 1))
    assert set(crash) == set(base)
    for step in base:
        assert len(base[step]) == 1
        assert crash[step] == base[step], (
            f"step {step}: crash-run losses {crash[step]} != "
            f"baseline {base[step]}")


def test_e2e_sigterm_trajectory_bit_identical(e2e_runs):
    """SIGTERM at step 3 with NO interval checkpoints: only the
    emergency preemption save makes resume possible — and the resumed
    trajectory is still bit-identical."""
    assert e2e_runs["sigterm_rc"] == 0
    base, st = e2e_runs["baseline"], e2e_runs["sigterm"]
    assert set(st) == set(base)
    for step in base:
        assert st[step] == base[step], (
            f"step {step}: sigterm-run losses {st[step]} != "
            f"baseline {base[step]}")
    # the emergency checkpoint exists at the preemption boundary and is
    # sealed (manifest verifies)
    ckpt = Checkpointer(e2e_runs["sigterm_model"])
    try:
        steps = ckpt.all_steps()
        assert 3 in steps
        assert ckpt.verify(3) == "ok"
        host = ckpt.host_state(3)
        assert host["global_step"] == 3
    finally:
        ckpt.close()


def test_e2e_supervisor_events_and_classification(e2e_runs):
    """supervisor_events.jsonl (the post-mortem record): the crash run
    logs a budgeted crash restart with backoff; the sigterm run logs a
    preempted rank exit and a restart with the crash budget
    untouched."""
    crash_ev = _events(e2e_runs["crash_logs"])
    exits = [e for e in crash_ev if e["event"] == "rank_exit"]
    assert any(e["code"] == chaos.EXIT_INJECTED_CRASH
               and e["classification"] == "crash" for e in exits)
    restarts = [e for e in crash_ev if e["event"] == "restart"]
    assert restarts and restarts[0]["classification"] == "crash"
    assert restarts[0]["backoff_s"] > 0
    assert any(e["event"] == "job_done" for e in crash_ev)

    st_ev = _events(e2e_runs["sigterm_logs"])
    exits = [e for e in st_ev if e["event"] == "rank_exit"]
    assert any(e["code"] == launch.EXIT_PREEMPTED
               and e["classification"] == "preempted" for e in exits)
    restarts = [e for e in st_ev if e["event"] == "restart"]
    assert restarts and restarts[0]["classification"] == "preempted"
    assert restarts[0]["backoff_s"] == 0.0
    assert restarts[0]["crashes_in_window"] == 0  # budget untouched


def test_e2e_trace_check_allows_injected_fault(e2e_runs):
    """`trace_main --check --allow injected_fault` is the chaos-run CI
    contract: the injected fault is tolerated, anything else fails —
    and without --allow the same trace fails the check."""
    from dtf_tpu.cli.trace_main import main as trace_main
    t = e2e_runs["crash_trace"]
    assert trace_main([t, "--check"]) == 1
    assert trace_main([t, "--check", "--allow", "injected_fault"]) == 0


@pytest.mark.slow
@pytest.mark.parametrize("fault_kind,kill_step,ckpt_steps", [
    # crashes must land on a sealed-checkpoint boundary (a hard crash
    # at an unsaved step deterministically re-fires on every resume —
    # by design: that is what the restart budget is for)
    ("crash", 2, 2),
    ("crash", 6, 3),
    ("crash", 8, 2),      # killed at the very last step
    # sigterm carries its own durability (the emergency save happens
    # AT the kill boundary), so any step works, incl. no-interval runs
    ("sigterm", 1, 0),
    ("sigterm", 5, 2),
    ("sigterm", 7, 0),
])
def test_kill_matrix_trajectory_exact(e2e_runs, tmp_path, fault_kind,
                                      kill_step, ckpt_steps):
    """The long kill matrix: kill at assorted steps, with assorted
    checkpoint intervals, by crash and by preemption — every variant
    resumes to a bit-identical trajectory."""
    m, t = str(tmp_path / "m"), str(tmp_path / "t")
    extra = ["--resume", "--fault", f"{fault_kind}@step:{kill_step}"]
    if ckpt_steps:
        extra += ["--checkpoint_steps", str(ckpt_steps)]
    rc = launch.launch_local(
        _train_cmd(e2e_runs["data"], m, t, extra=extra),
        num_processes=1, coordinator="localhost:0",
        log_dir=str(tmp_path / "logs"), devices_per_process=None,
        max_restarts=2, restart_backoff_s=0.05)
    assert rc == 0
    base, got = e2e_runs["baseline"], _loss_by_step(t)
    assert set(got) == set(base)
    for step in base:
        assert got[step] == base[step], (
            f"{fault_kind}@{kill_step} ckpt_steps={ckpt_steps} step "
            f"{step}: {got[step]} != {base[step]}")


# ---------------------------------------------------------------------------
# in-process preemption (the emergency-checkpoint path, no subprocess)
# ---------------------------------------------------------------------------

def test_inprocess_sigterm_writes_emergency_checkpoint(tmp_path):
    from dtf_tpu.cli.runner import run
    from dtf_tpu.config import Config
    base = dict(model="resnet20", dataset="cifar10",
                use_trivial_model=True, use_synthetic_data=True,
                batch_size=4, log_steps=1, skip_eval=True, verbose=0,
                distribution_strategy="off", model_dir=str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        run(Config(train_steps=4, fault="sigterm@step:2", **base))
    assert exc.value.code == preemption.EXIT_PREEMPTED
    ckpt = Checkpointer(str(tmp_path))
    try:
        assert ckpt.latest_step() == 2
        assert ckpt.verify(2) == "ok"
    finally:
        ckpt.close()
    # and the resumed run finishes the remaining steps normally
    chaos.disable()
    stats = run(Config(train_steps=4, resume=True, **base))
    assert np.isfinite(stats["loss"])


# ---------------------------------------------------------------------------
# checkpoint integrity: corruption/truncation fallback
# ---------------------------------------------------------------------------

def _toy_state(step, scale):
    return {"step": np.asarray(step, np.int32),
            "w": np.full((64,), float(scale), np.float32)}


def _save_two_steps(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    ckpt.save(_toy_state(1, 1.0), step=1, host_state={"seed": 7}, sync=True)
    ckpt.save(_toy_state(2, 2.0), step=2, host_state={"seed": 7}, sync=True)
    return ckpt


def _payload_files(tmp_path, step):
    out = []
    step_dir = os.path.join(str(tmp_path), "checkpoints", str(step))
    for root, _, names in os.walk(step_dir):
        out += [os.path.join(root, n) for n in names]
    return out


def test_manifest_sealed_and_verified(tmp_path):
    ckpt = _save_two_steps(tmp_path)
    try:
        assert ckpt.all_steps() == [1, 2]
        assert ckpt.verified_steps() == [1, 2]
        assert ckpt.host_state(2)["seed"] == 7
    finally:
        ckpt.close()


def test_corrupt_newest_falls_back_with_anomaly(tmp_path):
    """Truncating the newest checkpoint's largest payload file makes
    restore fall back to step 1 — with a structured ckpt_integrity
    anomaly, not a crash."""
    trace.configure(str(tmp_path / "trace"))
    ckpt = _save_two_steps(tmp_path)
    try:
        victim = max(_payload_files(tmp_path, 2), key=os.path.getsize)
        with open(victim, "r+b") as f:
            f.truncate(os.path.getsize(victim) // 2)
        assert ckpt.verify(2) == "corrupt"
        restored = ckpt.restore(_toy_state(0, 0.0))
        assert int(restored["step"]) == 1
        assert float(restored["w"][0]) == 1.0
        assert ckpt.last_restored_step == 1
    finally:
        ckpt.close()
    trace.flush()
    recs = trace.read_records(str(tmp_path / "trace" / "trace_rank0.jsonl"))
    anomalies = [r for r in recs if r.get("kind") == "anomaly"]
    assert any(a["name"] == "ckpt_integrity" and a["step"] == 2
               and a["action"] == "fallback" for a in anomalies)


def test_corrupt_manifest_is_unverified_but_restorable(tmp_path):
    """A torn/corrupt MANIFEST with an intact payload degrades to
    'unverified' — restore still succeeds on the newest step (the
    payload is fine; only the seal is gone)."""
    ckpt = _save_two_steps(tmp_path)
    try:
        with open(manifest_path(ckpt.directory, 2), "w") as f:
            f.write('{"files": {truncated garbage')
        assert ckpt.verify(2) == "unverified"
        restored = ckpt.restore(_toy_state(0, 0.0))
        assert int(restored["step"]) == 2
    finally:
        ckpt.close()


def test_missing_payload_file_is_corrupt(tmp_path):
    ckpt = _save_two_steps(tmp_path)
    try:
        os.unlink(max(_payload_files(tmp_path, 2), key=os.path.getsize))
        assert ckpt.verify(2) == "corrupt"
        restored = ckpt.restore(_toy_state(0, 0.0))
        assert int(restored["step"]) == 1
    finally:
        ckpt.close()


def test_explicit_step_restore_raises_on_corruption(tmp_path):
    """An EXPLICIT --step ask does not silently fall back: the caller
    named a checkpoint; handing them another would lie."""
    ckpt = _save_two_steps(tmp_path)
    try:
        victim = max(_payload_files(tmp_path, 2), key=os.path.getsize)
        with open(victim, "r+b") as f:
            f.truncate(1)
        with pytest.raises(OSError):
            ckpt.restore(_toy_state(0, 0.0), step=2)
    finally:
        ckpt.close()


def test_chaos_ckpt_truncate_fault(tmp_path):
    """ckpt_truncate@latest: the injected torn write fires once at the
    next restore, which then falls back to the previous verified
    step."""
    ckpt = _save_two_steps(tmp_path)
    try:
        chaos.configure("ckpt_truncate@latest")
        restored = ckpt.restore(_toy_state(0, 0.0))
        assert int(restored["step"]) == 1       # fell back
        assert ckpt.verify(2) == "corrupt"      # the fault really tore it
        # one-shot: a second restore does not re-truncate step 1
        restored = ckpt.restore(_toy_state(0, 0.0))
        assert int(restored["step"]) == 1
        assert ckpt.verify(1) == "ok"
    finally:
        ckpt.close()


def test_load_train_checkpoint_mid_write_dir(tmp_path):
    """A serving process pointed at a run whose newest step directory
    is mid-write (committed-looking name, unreadable content) falls
    back to the newest verified step instead of crashing."""
    ckpt = Checkpointer(str(tmp_path))
    ckpt.save({"step": np.asarray(4, np.int32),
               "params": {"w": np.ones((8,), np.float32)},
               "batch_stats": {}}, step=4, sync=True)
    ckpt.close()
    # fake a mid-write step 5: orbax sees a step-shaped dir with junk
    mid = tmp_path / "checkpoints" / "5"
    mid.mkdir()
    (mid / "half_written").write_bytes(b"\x00" * 10)
    out = load_train_checkpoint(str(tmp_path))
    assert out is not None
    np.testing.assert_array_equal(out["params"]["w"], np.ones((8,)))


def test_all_corrupt_resumes_from_scratch_not_crash(tmp_path):
    trace.configure(str(tmp_path / "trace"))
    ckpt = _save_two_steps(tmp_path)
    try:
        for step in (1, 2):
            for path in _payload_files(tmp_path, step):
                with open(path, "r+b") as f:
                    f.truncate(1)
        assert ckpt.restore(_toy_state(0, 0.0)) is None
    finally:
        ckpt.close()
    trace.flush()
    recs = trace.read_records(str(tmp_path / "trace" / "trace_rank0.jsonl"))
    assert any(r.get("name") == "ckpt_integrity"
               and r.get("verdict") == "none_usable" for r in recs)


# ---------------------------------------------------------------------------
# heartbeat_stall + ps_drop faults
# ---------------------------------------------------------------------------

def test_heartbeat_stall_fault(tmp_path):
    from dtf_tpu.obs.watchdog import Heartbeat
    chaos.configure("heartbeat_stall@step:5")
    hb = Heartbeat(str(tmp_path / "hb.json"), interval_s=0.0)
    assert hb.beat(step=1, force=True)          # before the stall: writes
    assert not hb.beat(step=5, force=True)      # stalled
    assert not hb.beat(step=7, force=True)      # latched — stays stalled
    assert not hb.beat(step=1, force=True)      # even for earlier steps


def test_ps_drop_fault_exercises_reconnect():
    from dtf_tpu.obs.registry import default_registry
    from dtf_tpu.parallel import ps as ps_lib
    default_registry().reset()
    srv = ps_lib.PsServer(port=0)
    try:
        chaos.configure("ps_drop@version:2")
        client = ps_lib.PsClient(f"127.0.0.1:{srv.port}",
                                 reconnect_timeout=30.0)
        client.init(np.zeros(8, np.float32))
        g = np.ones(8, np.float32)
        assert client.push(0.1, g) == 1
        assert client.push(0.1, g) == 2   # probe fires: socket severed
        # the next op hits the dead socket and rides the real
        # reconnect+backoff machinery to the same store
        assert client.push(0.1, g) == 3
        reconnects = default_registry().counter("ps_client_reconnects",
                                                unit="ops").value
        assert reconnects >= 1
        client.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# serve drain
# ---------------------------------------------------------------------------

def test_serve_drain_sheds_new_finishes_inflight():
    import jax
    import jax.numpy as jnp
    from dtf_tpu.models.transformer import TransformerLM
    from dtf_tpu.serve import Backpressure, ServeEngine
    model = TransformerLM(vocab_size=64, num_layers=1, d_model=32,
                          num_heads=2, d_ff=64, max_seq_len=16)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    engine = ServeEngine(model, params, max_batch=2, max_seq_len=16,
                         max_delay_s=0.0)
    h = engine.submit(np.array([1, 2, 3], np.int32), max_new_tokens=4)
    engine.begin_drain()
    assert engine.draining
    # drained admissions shed with retry_after, like a full queue
    with pytest.raises(Backpressure) as exc:
        engine.submit(np.array([1], np.int32), max_new_tokens=2)
    assert exc.value.retry_after > 0
    # in-flight work still finishes; stop(drain=True) then exits clean
    result = h.result(timeout=60)
    assert not result.cancelled and len(result.tokens) == 4
    engine.stop(drain=True)
    assert engine.shed_count == 1


# ---------------------------------------------------------------------------
# trace_main --allow
# ---------------------------------------------------------------------------

def test_trace_check_allowlist(tmp_path):
    from dtf_tpu.cli.trace_main import main as trace_main
    path = tmp_path / "trace_rank0.jsonl"
    recs = [
        {"kind": "span", "name": "step", "ts": 0.0, "dur_s": 0.1,
         "rank": 0, "step": 1},
        {"kind": "anomaly", "name": "injected_fault", "ts": 1.0,
         "rank": 0, "fault": "crash@step:1"},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    assert trace_main([str(tmp_path), "--check"]) == 1
    assert trace_main([str(tmp_path), "--check",
                       "--allow", "injected_fault"]) == 0
    # a second, NOT-allowed anomaly still fails the allowlisted check
    with path.open("a") as f:
        f.write(json.dumps({"kind": "anomaly", "name": "nan_loss",
                            "ts": 2.0, "rank": 0, "step": 2}) + "\n")
    assert trace_main([str(tmp_path), "--check",
                       "--allow", "injected_fault"]) == 1


# ---------------------------------------------------------------------------
# supervisor policy units (scripted ranks, no jax)
# ---------------------------------------------------------------------------

def test_preempted_restart_does_not_consume_budget(tmp_path):
    """preempt → crash → success on a crash budget of ONE: the
    preempted restart must not have consumed it."""
    marker = tmp_path / "count"
    script = (
        "import os, sys\n"
        f"p = {str(marker)!r}\n"
        "n = int(open(p).read()) if os.path.exists(p) else 0\n"
        "open(p, 'w').write(str(n + 1))\n"
        f"sys.exit([{launch.EXIT_PREEMPTED}, 3, 0][n])\n")
    rc = launch.launch_local([sys.executable, "-c", script],
                             num_processes=1, coordinator="localhost:0",
                             log_dir=str(tmp_path / "logs"),
                             devices_per_process=None, max_restarts=1,
                             restart_backoff_s=0.01)
    assert rc == 0
    ev = _events(str(tmp_path / "logs"))
    restarts = [e for e in ev if e["event"] == "restart"]
    assert [e["classification"] for e in restarts] == ["preempted",
                                                       "crash"]
    assert restarts[0]["crashes_in_window"] == 0
    assert restarts[1]["crashes_in_window"] == 1  # within budget 1


def test_unsupervised_preemption_does_not_restart(tmp_path):
    """No --max_restarts/--heartbeat_timeout = no supervision: an
    operator SIGTERMing their unsupervised launch must get an exit,
    not a job that resurrects itself."""
    marker = tmp_path / "ran"
    script = (f"import sys; open({str(marker)!r}, 'a').write('x'); "
              f"sys.exit({launch.EXIT_PREEMPTED})")
    rc = launch.launch_local([sys.executable, "-c", script],
                             num_processes=1, coordinator="localhost:0",
                             log_dir=str(tmp_path / "logs"),
                             devices_per_process=None, max_restarts=0)
    assert rc == launch.EXIT_PREEMPTED
    assert marker.read_text() == "x"  # ran exactly once
    ev = _events(str(tmp_path / "logs"))
    give_up = [e for e in ev if e["event"] == "give_up"]
    assert give_up and give_up[0]["reason"] == "unsupervised"


def test_preemption_loop_backstop(tmp_path):
    """max_preemptions bounds a pathological always-preempted job."""
    rc = launch.launch_local(
        [sys.executable, "-c",
         f"import sys; sys.exit({launch.EXIT_PREEMPTED})"],
        num_processes=1, coordinator="localhost:0",
        log_dir=str(tmp_path / "logs"), devices_per_process=None,
        max_restarts=1, max_preemptions=3)
    assert rc == launch.EXIT_PREEMPTED
    ev = _events(str(tmp_path / "logs"))
    give_up = [e for e in ev if e["event"] == "give_up"]
    assert give_up and give_up[0]["classification"] == "preempted"


def test_teardown_escalates_to_kill_for_stuck_rank(tmp_path):
    """A rank wedged past the teardown SIGTERM (dead collective, or a
    handler that latches the signal and never reaches a step boundary)
    is hard-killed after teardown_grace — the supervisor must not wait
    on it forever."""
    import time
    stuck = ("import signal, time\n"
             "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
             "print('armed', flush=True)\n"
             "time.sleep(600)\n")
    # rank 1 fails fast; rank 0 ignores the teardown SIGTERM
    script = ("import os, sys\n"
              "if os.environ['DTF_PROCESS_ID'] == '1':\n"
              "    sys.exit(3)\n"
              f"{stuck}")
    t0 = time.monotonic()
    rc = launch.launch_local([sys.executable, "-c", script],
                             num_processes=2, coordinator="localhost:0",
                             log_dir=str(tmp_path / "logs"),
                             devices_per_process=None, max_restarts=0,
                             teardown_grace=1.0)
    assert rc == 3
    assert time.monotonic() - t0 < 30
    ev = _events(str(tmp_path / "logs"))
    assert any(e["event"] == "teardown_kill" and e["rank"] == 0
               for e in ev)


def test_crash_budget_is_per_window_with_backoff(tmp_path):
    """Crashes are budgeted per sliding window with exponential
    backoff; exhausting the budget gives up with the first failure's
    code and a give_up event."""
    rc = launch.launch_local(
        [sys.executable, "-c", "import sys; sys.exit(3)"],
        num_processes=1, coordinator="localhost:0",
        log_dir=str(tmp_path / "logs"), devices_per_process=None,
        max_restarts=2, restart_window_s=3600.0,
        restart_backoff_s=0.01)
    assert rc == 3
    ev = _events(str(tmp_path / "logs"))
    restarts = [e for e in ev if e["event"] == "restart"]
    assert [e["classification"] for e in restarts] == ["crash", "crash"]
    assert restarts[1]["backoff_s"] == pytest.approx(0.02)
    give_up = [e for e in ev if e["event"] == "give_up"]
    assert give_up and give_up[0]["crashes_in_window"] == 2


def test_crash_window_expiry_restores_budget(tmp_path):
    """Old crashes age out of the sliding window: with a tiny window a
    twice-crashing job still completes on a budget of 1."""
    marker = tmp_path / "count"
    script = (
        "import os, sys\n"
        f"p = {str(marker)!r}\n"
        "n = int(open(p).read()) if os.path.exists(p) else 0\n"
        "open(p, 'w').write(str(n + 1))\n"
        "sys.exit(0 if n >= 2 else 3)\n")
    rc = launch.launch_local([sys.executable, "-c", script],
                             num_processes=1, coordinator="localhost:0",
                             log_dir=str(tmp_path / "logs"),
                             devices_per_process=None, max_restarts=1,
                             restart_window_s=0.001,
                             restart_backoff_s=0.05)
    assert rc == 0
    assert marker.read_text() == "3"
