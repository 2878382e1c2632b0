"""Always-on MFU/cost ledger — per-executable FLOPs, bytes, and
achieved-utilization gauges.

The attribution method: XLA's own
``compiled.cost_analysis()`` (flops, bytes accessed) for exactly the
program that runs, divided by measured wall time, against the chip's
peak FLOP/s and HBM bandwidth.  This module keeps that accounting
LIVE: the train loop and the serving decoder register each jitted
executable at compile time (the AOT ``lower().compile()`` object they
then EXECUTE — cost analysis is free, nothing compiles twice), feed it
their already-measured wall times, and the ledger exports

  ledger_<exec>_flops            gauge   XLA flop count (per device)
  ledger_<exec>_bytes            gauge   XLA bytes accessed (per device)
  ledger_<exec>_wall_s           gauge   running-mean measured wall time
  ledger_<exec>_calls            gauge   observations folded in
  ledger_<exec>_achieved_tflops  gauge   flops / mean wall / 1e12
  ledger_<exec>_mfu              gauge   achieved / peak FLOP/s
  ledger_<exec>_hbm_frac         gauge   achieved bytes/s / peak HBM b/s

into whatever registry owns the subsystem (the engine's
``engine.metrics``, train's default registry) — scraped live via the
Prometheus endpoint (``--metrics_port``), exported post-run through
``BenchmarkFileLogger.log_registry``.  Registration and summaries also
land in the trace stream (``ledger_exec`` / ``ledger_summary`` events),
so ``trace_main --ledger`` renders the table from trace files alone.
Beside the counts an entry names what the compiler left in the program:
its Pallas kernels and its collectives (``reduce-scatter`` / ``all-reduce``
/ ``all-gather`` call sites and operand bytes), logged once at compile.

Peaks come from the device kind (the public-spec tables below, the
program's one copy); unknown kinds (CPU) export no mfu/hbm_frac
rather than a made-up number.  ``DTF_PEAK_TFLOPS`` / ``DTF_PEAK_HBM_GBPS``
override both — deterministic tests, and chips the table hasn't learned.

Accuracy contract (documented tolerance): the train-step wall time is
the log-window mean (sync-inclusive, measured across a device_get), so
host dispatch overhead is IN the ledger's number, deliberately (it is
utilization the run actually achieves, not the kernel's best case;
tests/test_obs.py holds it within 20% of the same formula over the
loop's own step time).
Chunked-prefill entries are per chunk SHAPE; on the gather path several
window variants share one name and the latest compile's counts stand
for the family (serving's headline is the decode-step entry).
"""

from __future__ import annotations

import logging
import math
import os
import re
import threading
from typing import Dict, Optional

from dtf_tpu.obs import trace
from dtf_tpu.obs.registry import MetricsRegistry, default_registry

log = logging.getLogger("dtf_tpu")

# Public-spec peaks by TPU generation, matched case-insensitively
# against jax device_kind.  The program's one table: a program module
# does not import benchmark/, so tests/test_obs.py holds these against
# benchmark/lib/peaks.py for every kind that table lists.
PEAK_BF16_TFLOPS = {
    "v6e": 918.0, "v6": 918.0,
    "v5p": 459.0,
    "v5 lite": 197.0, "v5e": 197.0, "v5litepod": 197.0,
    "v4": 275.0,
    "v3": 123.0,
    "v2": 45.0,
}
PEAK_HBM_GBPS = {
    "v5 lite": 819.0, "v5e": 819.0, "v4": 1228.0, "v5p": 2765.0,
    "v6e": 1640.0,
}


def _lookup(table: dict, kind: str) -> Optional[float]:
    kind = kind.lower()
    for key, val in table.items():
        if key in kind:
            return val
    return None


def device_peaks() -> tuple:
    """(peak FLOP/s, peak HBM bytes/s) of the attached device — or
    (None, None) when unknown.  Env overrides DTF_PEAK_TFLOPS /
    DTF_PEAK_HBM_GBPS win (tests, unlisted chips); jax is imported
    lazily and failures degrade to unknown, never to a crash."""
    tflops = os.environ.get("DTF_PEAK_TFLOPS", "")
    gbps = os.environ.get("DTF_PEAK_HBM_GBPS", "")
    peak_f = float(tflops) * 1e12 if tflops else None
    peak_b = float(gbps) * 1e9 if gbps else None
    if peak_f is None or peak_b is None:
        try:
            import jax
            kind = getattr(jax.devices()[0], "device_kind", "")
        except Exception:  # noqa: BLE001 — diagnostics never crash a run
            kind = ""
        if peak_f is None:
            t = _lookup(PEAK_BF16_TFLOPS, kind)
            peak_f = t * 1e12 if t else None
        if peak_b is None:
            g = _lookup(PEAK_HBM_GBPS, kind)
            peak_b = g * 1e9 if g else None
    return peak_f, peak_b


def cost_of(compiled) -> tuple:
    """(flops, bytes accessed) from a compiled executable's
    cost_analysis."""
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
    return (float(ca.get("flops", 0.0) or 0.0),
            float(ca.get("bytes accessed", 0.0) or 0.0))


# the name in front of the call; where the kernel's library wraps the call
# in a jit of its own (megablox ``jit(gmm)``), the scope the caller named
_PALLAS_OP = re.compile(
    r'op_name="[^"]*?([\w.]+)(?:/jit\(\w+\))?/pallas_call')


def pallas_kernels(compiled) -> Dict[str, int]:
    """{kernel name: call sites} of the Mosaic (Pallas TPU) kernels in
    a compiled executable, read from its optimized HLO — what the
    device will run, not what a flag asked for: a reference
    formulation (blockwise, gather) or interpret mode lowers to plain
    HLO and shows up here as absent."""
    return _pallas_kernels(compiled.as_text())


def _pallas_kernels(hlo: str) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _PALLAS_OP.search(line)
        name = m.group(1) if m else "pallas_call"
        counts[name] = counts.get(name, 0) + 1
    return counts


# ``collective-permute``: a hop of the ZeRO scatter's ring (train/zero.py)
COLLECTIVE_OPS = ("reduce-scatter", "all-reduce", "all-gather",
                  "collective-permute")
_HLO_DEF = re.compile(r"^\s*(?:ROOT )?(%[\w.-]+) = (.*?) ([a-z][\w-]*)\((.*)$")
_HLO_ARRAY = re.compile(r"\b([a-z]+)(\d+)?\[([\d,]*)\]")


def _hlo_type_bytes(text: str) -> int:
    """Bytes of an HLO type as printed (an array, or a tuple of them)."""
    total = 0
    for kind, bits, dims in _HLO_ARRAY.findall(text):
        n = math.prod(int(d) for d in dims.split(",") if d)
        total += n * (int(bits) // 8 if bits else 1)   # pred: one byte
    return total


def collectives(compiled) -> Dict[str, Dict[str, int]]:
    """{op: {"ops": call sites, "bytes": bytes of their operands}} of
    the cross-device collectives in a compiled executable, read from its
    optimized HLO like ``pallas_kernels`` — what the device will run: a
    ``psum_scatter`` the compiler decomposed shows up as an
    ``all-reduce`` of the whole operand, not as the ``reduce-scatter``
    the program asked for.  An async pair counts once, at its
    ``-start``."""
    return _collectives(compiled.as_text())


def _collectives(hlo: str) -> Dict[str, Dict[str, int]]:
    sizes: Dict[str, int] = {}      # a computation defines before it uses
    out = {op: {"ops": 0, "bytes": 0} for op in COLLECTIVE_OPS}
    for line in hlo.splitlines():
        m = _HLO_DEF.match(line)
        if not m:
            continue
        name, result, op, rest = m.groups()
        sizes[name] = _hlo_type_bytes(result)
        op = op.removesuffix("-start")
        if op in COLLECTIVE_OPS:
            operands = re.findall(r"%[\w.-]+", rest.split(")")[0])
            out[op]["ops"] += 1
            out[op]["bytes"] += sum(sizes.get(o, 0) for o in operands)
    return out


class Ledger:
    """Per-executable cost ledger over one metrics registry.

    ``register(name, compiled=...)`` once per executable at compile
    time; ``observe(name, wall_s)`` with each measured wall time the
    caller already has (decode steps sync per step; the train loop's
    log windows span a real device sync).  ``emit_summary()`` flushes
    one ``ledger_summary`` trace event per executable — call it at
    run/engine teardown so ``trace_main --ledger`` works from the
    trace directory alone."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None \
            else default_registry()
        self._mu = threading.Lock()
        self._execs: Dict[str, dict] = {}
        self.peak_flops, self.peak_hbm = device_peaks()

    def register(self, name: str, compiled=None, flops: float = 0.0,
                 bytes_accessed: float = 0.0) -> None:
        """Record an executable's static cost.  ``compiled`` is an AOT
        ``lower().compile()`` object (cost pulled from XLA); without
        one, pass the counts directly.  Re-registering the same name
        (gather-path chunk window variants) updates the counts and
        keeps the accumulated timing."""
        kernels: Dict[str, int] = {}
        comms: Dict[str, Dict[str, int]] = {}
        if compiled is not None:
            try:
                flops, bytes_accessed = cost_of(compiled)
                hlo = compiled.as_text()    # megabytes: printed once
                kernels = _pallas_kernels(hlo)
                comms = _collectives(hlo)
            except Exception as e:  # noqa: BLE001 — a backend without
                # cost_analysis must not take down the step it measures,
                # but a step that runs without an entry must be visible
                log.warning("ledger: no entry for %s — cost_analysis "
                            "failed: %s: %s", name, type(e).__name__, e)
                return
        with self._mu:
            e = self._execs.get(name)
            if e is None:
                e = self._execs[name] = {"flops": 0.0, "bytes": 0.0,
                                         "count": 0, "total_s": 0.0,
                                         "kernels": {}, "collectives": {}}
            e["flops"] = float(flops)
            e["bytes"] = float(bytes_accessed)
            # a name shared by several bodies (a chunk shape's first and
            # continuation bodies) accumulates the kernels of all of them
            e["kernels"].update(kernels)
            kernels = dict(e["kernels"])
            e["collectives"] = comms
        if compiled is not None:
            log.info("ledger: %s compiled — %.4g flops, %.4g bytes, "
                     "pallas kernels %s, collectives %s", name, flops,
                     bytes_accessed, kernels or "none",
                     ", ".join(f"{c['ops']} {op} ({c['bytes']:.4g} B)"
                               for op, c in comms.items() if c["ops"])
                     or "none")
        self.registry.gauge(f"ledger_{name}_flops",
                            unit="flops").set(flops)
        self.registry.gauge(f"ledger_{name}_bytes",
                            unit="bytes").set(bytes_accessed)
        trace.event("ledger_exec", exec=name, flops=float(flops),
                    bytes=float(bytes_accessed), kernels=kernels,
                    collectives=comms,
                    peak_tflops=(self.peak_flops / 1e12
                                 if self.peak_flops else None),
                    peak_hbm_gbps=(self.peak_hbm / 1e9
                                   if self.peak_hbm else None))

    def observe(self, name: str, wall_s: float) -> None:
        """Fold one measured wall time into the executable's gauges.
        Unregistered names and non-positive times are ignored (the
        caller's timing sites outlive registration failures)."""
        if not wall_s or wall_s <= 0 or not math.isfinite(wall_s):
            return
        with self._mu:
            e = self._execs.get(name)
            if e is None:
                return
            e["count"] += 1
            e["total_s"] += float(wall_s)
            mean = e["total_s"] / e["count"]
            flops, nbytes, count = e["flops"], e["bytes"], e["count"]
        g = self.registry.gauge
        g(f"ledger_{name}_wall_s", unit="s").set(mean)
        g(f"ledger_{name}_calls", unit="calls").set(count)
        achieved = flops / mean if mean > 0 else 0.0
        g(f"ledger_{name}_achieved_tflops",
          unit="tflops").set(achieved / 1e12)
        if self.peak_flops:
            g(f"ledger_{name}_mfu", unit="mfu").set(
                achieved / self.peak_flops)
        if self.peak_hbm and mean > 0:
            g(f"ledger_{name}_hbm_frac", unit="fraction").set(
                nbytes / mean / self.peak_hbm)

    def summary(self) -> Dict[str, dict]:
        """{exec: {flops, bytes, kernels, collectives, count, mean_s,
        achieved_tflops, mfu, hbm_frac}} — mfu/hbm_frac None when the
        peak is unknown."""
        out: Dict[str, dict] = {}
        with self._mu:
            items = sorted(self._execs.items())
        for name, e in items:
            mean = e["total_s"] / e["count"] if e["count"] else 0.0
            achieved = e["flops"] / mean if mean > 0 else 0.0
            out[name] = {
                "flops": e["flops"], "bytes": e["bytes"],
                "kernels": dict(e["kernels"]),
                "collectives": dict(e["collectives"]),
                "count": e["count"], "mean_s": mean,
                "achieved_tflops": achieved / 1e12,
                "mfu": (achieved / self.peak_flops
                        if self.peak_flops and mean > 0 else None),
                "hbm_frac": (e["bytes"] / mean / self.peak_hbm
                             if self.peak_hbm and mean > 0 else None),
            }
        return out

    def emit_summary(self) -> None:
        """One ``ledger_summary`` trace event per executable — the
        record ``trace_main --ledger`` tabulates."""
        for name, s in self.summary().items():
            trace.event("ledger_summary", exec=name, **s)
