"""Launcher supervision: restart-on-failure and hang detection — the
failure-recovery machinery the reference lacked entirely (SURVEY §5.3:
per-epoch checkpoints + a human running kill.sh was the whole story)."""

import sys

from dtf_tpu.cli.launch import launch_local, main as launch_main


def test_restart_recovers_from_transient_failure(tmp_path):
    """First attempt fails (marker file absent), relaunch succeeds."""
    marker = tmp_path / "attempted"
    script = (f"import os, sys; p = {str(marker)!r}\n"
              f"sys.exit(0) if os.path.exists(p) else "
              f"(open(p, 'w').close(), sys.exit(3))")
    rc = launch_local([sys.executable, "-c", script], num_processes=2,
                      coordinator="localhost:0",
                      log_dir=str(tmp_path / "logs"),
                      devices_per_process=None, max_restarts=2)
    assert rc == 0
    assert marker.exists()


def test_no_restart_without_flag(tmp_path):
    rc = launch_local([sys.executable, "-c", "import sys; sys.exit(5)"],
                      num_processes=2, coordinator="localhost:0",
                      log_dir=str(tmp_path / "logs"),
                      devices_per_process=None)
    assert rc == 5


def test_heartbeat_kills_hung_rank(tmp_path):
    """A rank that stops producing output past the timeout is killed and
    the job fails (instead of hanging forever)."""
    import time
    script = "import time; print('up', flush=True); time.sleep(600)"
    t0 = time.monotonic()
    rc = launch_local([sys.executable, "-c", script], num_processes=2,
                      coordinator="localhost:0",
                      log_dir=str(tmp_path / "logs"),
                      devices_per_process=None, heartbeat_timeout=2.0,
                      startup_grace=2.0)
    assert rc != 0
    assert time.monotonic() - t0 < 60


def test_startup_grace_spares_slow_starter(tmp_path):
    """A rank silent longer than heartbeat_timeout but inside the
    startup grace (XLA compile, checkpoint restore) is not killed."""
    script = ("import time; time.sleep(3); print('compiled', flush=True)")
    rc = launch_local([sys.executable, "-c", script], num_processes=1,
                      coordinator="localhost:0",
                      log_dir=str(tmp_path / "logs"),
                      devices_per_process=None, heartbeat_timeout=1.0,
                      startup_grace=30.0)
    assert rc == 0


def test_local_fanout_refuses_to_share_tpu_chips(tmp_path, monkeypatch):
    """Nothing assigns chips to local children, so on a host that
    exposes TPU chips a fan-out whose children could see them is
    refused before anything is spawned; children held to the CPU (the
    virtual-device mesh) and single-process launches pass."""
    import pytest

    from dtf_tpu.cli import launch

    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 4)
    for platforms in ("tpu,cpu", ""):
        with pytest.raises(RuntimeError, match="same chips"):
            launch.refuse_shared_chips(2, {"JAX_PLATFORMS": platforms},
                                       "launch")
    launch.refuse_shared_chips(2, {"JAX_PLATFORMS": "cpu"}, "launch")
    launch.refuse_shared_chips(1, {"JAX_PLATFORMS": "tpu"}, "launch")
    # the launcher itself, and the replica tier's spawner
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(RuntimeError, match="same chips"):
        launch_local([sys.executable, "-c", "pass"], 2, "localhost:1",
                     str(tmp_path), None)
    assert not list(tmp_path.iterdir())
    from dtf_tpu.serve.router import replica_spawner
    spawn = replica_spawner([sys.executable, "-c", "pass"], str(tmp_path))
    spawn(0, 0).wait(timeout=60)
    with pytest.raises(RuntimeError, match="same chips"):
        spawn(1, 0)


def test_hosts_mode_rejects_supervision_flags():
    import pytest
    with pytest.raises(ValueError, match="supervise"):
        launch_main(["--hosts", "h1,h2", "--max_restarts", "1", "--",
                     "echo", "hi"])
