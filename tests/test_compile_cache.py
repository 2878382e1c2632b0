"""The compile cache can be placed from outside, and otherwise sits at
one fixed path inside the checkout (runtime/compile_cache.py)."""

import os
import subprocess
import sys

import jax

from dtf_tpu.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record_config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    return calls


def test_env_set_means_nothing_is_set_in_code(monkeypatch, tmp_path):
    calls = _record_config_updates(monkeypatch)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert calls == []


def test_env_unset_means_the_fixed_path_in_the_checkout(monkeypatch):
    calls = _record_config_updates(monkeypatch)
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure() == fixed
    assert compile_cache.configure() == fixed      # same on every call
    assert calls == [("jax_compilation_cache_dir", fixed)] * 2
    # ... and in every process, whatever its working directory
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, "-c",
         "from dtf_tpu.runtime import compile_cache; import jax; "
         "print(compile_cache.configure()); "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, cwd="/", capture_output=True, text=True, timeout=120,
        check=True).stdout.split()
    assert out == [fixed, fixed]
