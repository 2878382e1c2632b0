"""chip_smoke.py must FAIL wherever it cannot prove anything: with no
TPU visible it exits non-zero naming the missing TPU (it does not train
on the CPU JAX fell back to), and alone in a directory — no repo around
it — it exits non-zero too.  Neither prints a result line."""

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cwd, env):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    # the conftest environment: JAX_PLATFORMS=cpu
    proc = _run(REPO, dict(os.environ))
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert "[lm]" not in proc.stdout          # no phase was started


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run(tmp_path, env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
