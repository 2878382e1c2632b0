"""Where the persistent XLA compile cache lives.

Every machine the program meets may be a cold one, and the 12-layer LM
step, ResNet-50 and one serve body per chunk shape each compile for
tens of seconds.  JAX's persistent cache removes that from every run
after the first — provided the directory does not move: the path is
part of how a later process finds the entries, so it is either the one
the operator names or one fixed place inside the checkout, never a
temp dir, a pid or a timestamp.

  JAX_COMPILATION_CACHE_DIR set   — JAX already uses it; nothing is
                                    set in code.
  unset                           — ``<checkout>/.jax_cache`` (ignored
                                    by git), derived from the package
                                    location.

Every main calls :func:`configure` before its first compile.  JAX's
own write thresholds stay at their defaults (entries that took under
1 s to compile are not written): the bodies worth caching all take
longer.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Place the compile cache; returns the directory in use."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
