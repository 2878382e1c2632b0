"""The test harness's own contract (tests/conftest.py): a test that
blocks for ever costs one failure with every thread's stack, and the
rest of its file still runs."""

import os
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))

# the repo's clock fixture at a limit a test of it can afford: the
# limit is read from the module at each test's start
_CONFTEST = f"""
import importlib.util
spec = importlib.util.spec_from_file_location(
    "repo_conftest", {os.path.join(TESTS, "conftest.py")!r})
repo_conftest = importlib.util.module_from_spec(spec)
spec.loader.exec_module(repo_conftest)
repo_conftest.TEST_LIMIT_S = 1.0
_test_clock = repo_conftest._test_clock
"""

_TESTS = """
import os

def test_blocks_for_ever():
    r, w = os.pipe()
    os.read(r, 1)

def test_after_the_hang():
    pass
"""


def test_hung_test_fails_at_its_limit_and_the_file_goes_on(tmp_path):
    (tmp_path / "conftest.py").write_text(_CONFTEST)
    (tmp_path / "test_hang.py").write_text(_TESTS)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTEST_ADDOPTS", None)   # no xdist, no -m from the caller
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "--rootdir", str(tmp_path),
         str(tmp_path / "test_hang.py")],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=100)
    out = r.stdout + r.stderr
    assert r.returncode == 1, out
    assert "1 failed, 1 passed" in out, out
    assert "still running after its 1 s limit" in out, out
    # the dump names the frame the test hung in
    assert "most recent call first" in out, out
    assert "in test_blocks_for_ever" in out, out
