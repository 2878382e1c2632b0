"""Test harness: 8 virtual CPU devices standing in for a TPU mesh.

This closes the reference's biggest testing gap (SURVEY §4): its
multi-worker paths had no automated tests at all — correctness was
validated by manually-run cluster logs (ps_server/log*.log).  Here every
distribution strategy is exercised on an
``--xla_force_host_platform_device_count=8`` CPU mesh in CI.
"""

import os

# Must be set before the JAX backend initializes.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8")
# Force, don't setdefault: the environment may preset JAX_PLATFORMS to a
# real accelerator platform, and runtime/mesh.py honors that env var —
# tests must win or the virtual 8-device CPU mesh silently becomes a
# 1-chip accelerator run with accelerator matmul precision.
os.environ["JAX_PLATFORMS"] = "cpu"
# Hermetic compiles: the mains place a persistent compile cache inside
# the checkout (runtime/compile_cache.py); a test run — and the CPU
# children it spawns — must neither fill it nor pass on a warm one.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import faulthandler  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from tools.dtflint.markers import DEFAULT_CEILING_S  # noqa: E402

# --- a clock of its own for every test ------------------------------------
# Under ``--dist loadfile`` a test that blocks for ever takes the rest of
# its file and the whole run's clock with it, and the log shows only dots.
# The limits derive from the marker audit's per-test ceiling: a test may
# run to 6x what the audit would flag (30x when marked slow) before it is
# failed here with every thread's stack.

TEST_LIMIT_S = 6 * DEFAULT_CEILING_S
SLOW_TEST_LIMIT_S = 30 * DEFAULT_CEILING_S


@pytest.fixture(autouse=True)
def _test_clock(request):
    """Fail a test that outlives its limit, with a dump of all threads.

    SIGALRM lands on the main thread, which is where pytest (and every
    xdist worker) runs the test, so it interrupts the blocking read or
    lock wait the test hangs in; the other tests of the file then run."""
    if (not hasattr(signal, "setitimer")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return
    limit = (SLOW_TEST_LIMIT_S if request.node.get_closest_marker("slow")
             else TEST_LIMIT_S)

    def on_alarm(signum, frame):
        faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
        pytest.fail(f"{request.node.nodeid} still running after its "
                    f"{limit:g} s limit (thread stacks on stderr)",
                    pytrace=False)

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


# --- thread sanitizer: record where every NON-DAEMON thread started -------
# The serving tier spawns a lot of threads (router dispatcher/prober/
# readers, replica accept/conn/writer/waiter, metrics servers).  All of
# them are daemons BY CONTRACT — a non-daemon thread that outlives its
# test would hang interpreter shutdown and serialize the whole suite
# behind a leak nobody can attribute.  The sanitizer fixture below
# enforces the contract after EVERY test; this start() wrapper is what
# lets it report the leaker's creation stack instead of just a name.
# Only non-daemon threads are recorded (the daemon flag is final by
# start() time), and only cheap (file, line, function) tuples — a
# format_stack here measurably slows thread-storm tests (the prom
# endpoint test starts hundreds of handler threads).

_orig_thread_start = threading.Thread.start


def _recording_start(self, *args, **kwargs):
    if not self.daemon and not hasattr(self, "_dtf_started_at"):
        frames, f = [], sys._getframe(1)
        while f is not None and len(frames) < 10:
            frames.append((f.f_code.co_filename, f.f_lineno,
                           f.f_code.co_name))
            f = f.f_back
        self._dtf_started_at = frames
    return _orig_thread_start(self, *args, **kwargs)


threading.Thread.start = _recording_start


def _format_creation_stack(thread) -> str:
    frames = getattr(thread, "_dtf_started_at", None)
    if not frames:
        return "    <creation stack not recorded>\n"
    return "".join(f"    {fn}:{ln} in {name}\n"
                   for fn, ln, name in frames)


@pytest.fixture(autouse=True)
def _thread_sanitizer():
    """After each test: no leaked non-daemon threads.

    Leaked DAEMON threads are tolerated (engines/routers under test
    run daemons that die with the process — the watchdog for those is
    the wall-clock budget), but a NON-daemon leak fails the leaking
    test with the thread's creation stack, while the culprit is still
    on screen."""
    import time as _time
    before = set(threading.enumerate())
    yield

    def leaked():
        return [t for t in threading.enumerate()
                if t.is_alive() and not t.daemon and t not in before]

    threads = leaked()
    deadline = _time.monotonic() + 2.0
    while threads and _time.monotonic() < deadline:
        _time.sleep(0.05)   # grace: teardown joins may still be racing
        threads = leaked()
    if threads:
        lines = [f"  {t.name} (alive, daemon=False), started at:\n"
                 f"{_format_creation_stack(t)}" for t in threads]
        pytest.fail(
            "leaked non-daemon thread(s) — they would hang interpreter "
            "shutdown; join them in the test/fixture teardown or mark "
            "them daemon:\n" + "\n".join(lines), pytrace=False)


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


# --- test-budget bookkeeping (tools/marker_audit.py) ----------------------
# Every run dumps {nodeid: {duration, slow}} so the marker audit can
# fail CI when an unmarked test exceeds the per-test time ceiling —
# the guard that keeps tier-1 under its wall-clock budget as the
# multi-device compile tests grow.

_durations: dict = {}


def pytest_runtest_logreport(report):
    if report.when == "call":
        _durations[report.nodeid] = {
            "duration": round(report.duration, 3),
            "slow": "slow" in getattr(report, "keywords", {}),
        }


def pytest_sessionfinish(session, exitstatus):
    if not _durations:
        return
    import json
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".last_durations.json")
    try:
        with open(path, "w") as f:
            json.dump(_durations, f, indent=1, sort_keys=True)
    except OSError:
        pass  # a read-only checkout must not fail the suite
