"""Determinism / JAX-hazard lint.

Two rule groups:

1. det-* — the modules that carry BIT-EXACTNESS contracts (train
   resume replays the identical loss trajectory; data-service batch n
   is a pure function of (seed, process, n); seeded decode replays
   token-exactly across failover; the canary gate compares greedy
   streams) must not consult non-deterministic sources.  Banned in
   DETERMINISM_MODULES:

     det-time      time.time()/time.time_ns() — wall clock feeding
                   data.  (monotonic/perf_counter stay legal: they
                   time work, they don't shape it.)
     det-random    the stdlib ``random`` module, and numpy GLOBAL-state
                   RNG (np.random.<fn>); explicitly-seeded generators
                   (np.random.default_rng / SeedSequence / Generator /
                   PCG64) and key-passing jax.random.* are the legal
                   forms
     det-entropy   os.urandom / uuid.uuid4 / secrets.*
     det-set-iter  iterating a set (``for x in {...}`` / ``in set(...)``)
                   — CPython iteration order is salted; a stream that
                   depends on it is not a pure function of its seed

2. host-sync — device→host syncs (np.asarray / jax.device_get /
   .item() / .block_until_ready()) inside the step loops listed in
   STEP_LOOPS stall the dispatch pipeline; the MFU ledger accounts for
   a fixed set of them (that sync IS its measurement point).  Every
   sync site must carry ``# dtflint: sync-point (reason)`` — so adding
   an unaccounted sync to the hot loop is a lint failure, not a silent
   MFU regression the benchmark catches three PRs later.
"""

from __future__ import annotations

import ast
from typing import List

from tools.dtflint import Context, Finding, Source

#: repo-relative modules under the bit-exactness contracts
DETERMINISM_MODULES = (
    "dtf_tpu/data/service/reader.py",
    "dtf_tpu/data/service/pool.py",
    "dtf_tpu/data/service/cache.py",
    "dtf_tpu/data/records.py",
    "dtf_tpu/serve/decode.py",
    "dtf_tpu/train/checkpoint.py",
)

#: (module, function names) holding device step loops whose syncs the
#: ledger accounts — the host-sync rule's scope
STEP_LOOPS = {
    "dtf_tpu/serve/engine.py": ("_step", "_advance_prefill",
                                "_loop_body"),
    "dtf_tpu/train/loop.py": ("fit",),
}

_SEEDED_NP_RANDOM = ("default_rng", "SeedSequence", "Generator",
                     "PCG64", "Philox", "bit_generator")
_SYNC_ATTRS = ("item", "block_until_ready")


def _dotted(node) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    else:
        return ""
    return ".".join(reversed(parts))


def _det_check(src: Source) -> List[Finding]:
    out: List[Finding] = []

    def flag(rule, node, msg):
        out.append(Finding(rule, src.path, node.lineno, msg))

    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name in ("time.time", "time.time_ns"):
                flag("det-time", node,
                     f"{name}() in a bit-exactness module — wall "
                     f"clock must not shape the deterministic stream")
            elif name in ("os.urandom", "uuid.uuid4") or \
                    name.startswith("secrets."):
                flag("det-entropy", node,
                     f"{name}() in a bit-exactness module")
            elif name.startswith("random."):
                flag("det-random", node,
                     f"stdlib {name}() in a bit-exactness module — "
                     f"use a seeded np.random.default_rng")
            elif (name.startswith("np.random.")
                  or name.startswith("numpy.random.")):
                leaf = name.rsplit(".", 1)[1]
                if leaf not in _SEEDED_NP_RANDOM:
                    flag("det-random", node,
                         f"{name}() uses numpy GLOBAL RNG state — "
                         f"use a seeded default_rng/Generator")
        iter_expr = None
        if isinstance(node, (ast.For, ast.comprehension)):
            iter_expr = node.iter
        if iter_expr is not None:
            if isinstance(iter_expr, ast.Set) or (
                    isinstance(iter_expr, ast.Call)
                    and _dotted(iter_expr.func) in ("set", "frozenset")):
                flag("det-set-iter", node if isinstance(node, ast.For)
                     else iter_expr,
                     "iterating a set in a bit-exactness module — "
                     "iteration order is hash-salted; sort it")
    return out


def _sync_check(src: Source, fn_names) -> List[Finding]:
    out: List[Finding] = []
    for fn in [n for n in ast.walk(src.tree)
               if isinstance(n, ast.FunctionDef) and n.name in fn_names]:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            is_sync = name in ("np.asarray", "numpy.asarray",
                               "jax.device_get")
            if not is_sync and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _SYNC_ATTRS \
                    and not node.args:
                is_sync = True
            if is_sync and not src.is_sync_point(node.lineno):
                out.append(Finding(
                    "host-sync", src.path, node.lineno,
                    f"{name or node.func.attr}() inside step loop "
                    f"'{fn.name}' without a '# dtflint: sync-point "
                    f"(reason)' annotation — unaccounted device sync "
                    f"on the hot path"))
    return out


def check(ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    det_modules = getattr(ctx, "det_modules", DETERMINISM_MODULES)
    step_loops = getattr(ctx, "step_loops", STEP_LOOPS)
    for src in ctx.sources:
        if src.path in det_modules:
            findings.extend(_det_check(src))
        fns = step_loops.get(src.path)
        if fns:
            findings.extend(_sync_check(src, fns))
    return findings
