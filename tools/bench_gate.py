#!/usr/bin/env python
"""Perf-regression gate: the committed BENCH history as a CI contract.

Committed bench artifacts are trajectory documentation unless
something compares against them.  This tool compares a CANDIDATE bench
artifact against the committed history with noise-aware thresholds and
exits nonzero on regression, loudly naming the metric.  (The training
family, BENCH_r*.json, is empty at present: its records came from an
installation that no longer exists and were deleted; ROADMAP S0
replaces this gate with the driver's paired-run rule.)

What it reads (all committed at the repo root):
  BENCH_r*.json      — training benches ({"parsed": {...}} wrappers or
                       bare bench.py JSON): the headline metric plus
                       nested sub-benches ("lm", "input_pipeline"),
                       each with value / value_min / value_max (or
                       tps_min/tps_max) spread fields.
  BENCH_serve*.json  — bench_serve.py --out artifacts: a "metrics"
                       list of BenchmarkMetric lines + "bars_failed".
  BENCH_zero*.json   — tools/zero_smoke.py --out artifacts: the ZeRO
                       overlap/calibration gauges as a "metrics" list
                       + "bars_failed" (same shape as serve).

Thresholds (documented contract, deliberately simple):
  * baseline per metric = the newest HISTORICAL artifact carrying it
    (the value the repo currently claims — regressing vs an old peak a
    later PR knowingly traded away is not a failure; regressing vs the
    current claim is).
  * noise margin per metric = clamp(2 × worst relative spread seen in
    history, MARGIN_FLOOR, MARGIN_CAP).  The spread is the artifact's
    own value_min/value_max (min over windows vs max over windows) —
    the repeatability protocol every bench already records.  A metric
    with no recorded spread gets the floor.
  * direction from the unit/name: throughput ("…/sec…", "tokens/s",
    "mfu", hit counts) must not DROP below baseline × (1 − margin);
    latency/time ("s", "ms", names containing latency/gap/wait/lag)
    must not RISE above baseline × (1 + margin).  Unknown-direction
    metrics are reported, never gated.
  * a BENCH_serve candidate with a non-empty "bars_failed" fails
    outright — the bench's own acceptance bars outrank any margin.

Usage:
  python tools/bench_gate.py                      # newest committed
      artifact of EACH family (training BENCH_r*, serving BENCH_serve*)
      gated against that family's earlier history (the ci_check stage:
      proves the committed history is self-consistent)
  python tools/bench_gate.py --candidate NEW.json # gate a fresh run
  python tools/bench_gate.py --smoke              # the gate's own
      contract, per family: passes on the committed history AND fails
      on a synthetically degraded copy (ci_check asserts both)
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MARGIN_FLOOR = 0.05     # 5%: no recorded window spread has been tighter
MARGIN_CAP = 0.60       # a metric noisier than this gates in name only
SMOKE_DEGRADE = 0.50    # --smoke halves throughput / doubles latency

HIGHER_TOKENS = ("/sec", "/s/", "per_sec", "per_second", "tokens/s",
                 "images/s")
HIGHER_NAMES = ("mfu", "hit", "throughput", "ratio", "eff", "tflop")
LOWER_UNITS = ("s", "ms")
LOWER_NAMES = ("latency", "gap", "wait", "lag", "time_to", "ttft",
               "step_ms")


def direction(name: str, unit: str) -> Optional[str]:
    """'higher' / 'lower' / None (ungated)."""
    name_l, unit_l = name.lower(), (unit or "").lower()
    if any(k in unit_l for k in HIGHER_TOKENS):
        return "higher"
    if any(k in name_l for k in LOWER_NAMES):
        return "lower"
    if any(k in name_l for k in HIGHER_NAMES):
        return "higher"
    if unit_l in LOWER_UNITS:
        return "lower"
    return None


def _spread(rec: dict) -> Optional[float]:
    """Relative window spread from the artifact's own repeatability
    fields — (max − min) / value."""
    value = rec.get("value")
    lo = rec.get("value_min", rec.get("tps_min"))
    hi = rec.get("value_max", rec.get("tps_max"))
    if not isinstance(value, (int, float)) or not value:
        return None
    lo = lo if isinstance(lo, (int, float)) else value
    hi = hi if isinstance(hi, (int, float)) else value
    return abs(float(hi) - float(lo)) / abs(float(value))


def extract_metrics(obj, out: Dict[str, dict]):
    """Walk an artifact for dicts shaped {"metric": name, "value": v}.
    First occurrence of a name wins (the headline; nested re-runs of
    the same metric under alternative configs — input_pipeline's
    "default" arm — are measurement context, not tracked claims)."""
    if isinstance(obj, dict):
        name = obj.get("metric")
        if isinstance(name, str) and isinstance(obj.get("value"),
                                                (int, float)):
            if name not in out:
                out[name] = {"value": float(obj["value"]),
                             "unit": str(obj.get("unit", "")),
                             "spread": _spread(obj)}
        for v in obj.values():
            extract_metrics(v, out)
    elif isinstance(obj, list):
        for v in obj:
            extract_metrics(v, out)


def load_artifact(path: str) -> Tuple[Dict[str, dict], List[str]]:
    """(metrics, failed bars) from one artifact file.  Handles the
    committed {"parsed": {...}} wrapper, bare bench.py JSON, and the
    bench_serve {"metrics": [...], "bars_failed": [...]} shape."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict) and "parsed" in data:
        data = data["parsed"]
    metrics: Dict[str, dict] = {}
    extract_metrics(data, metrics)
    bars = list(data.get("bars_failed", [])) if isinstance(data, dict) \
        else []
    return metrics, bars


def default_history() -> List[str]:
    pats = (os.path.join(REPO, "BENCH_r*.json"),
            os.path.join(REPO, "BENCH_serve*.json"),
            os.path.join(REPO, "BENCH_zero*.json"))
    return sorted(p for pat in pats for p in glob.glob(pat))


def families(history: List[str]) -> Dict[str, List[str]]:
    """Group artifacts into tracked families (training BENCH_r*,
    serving BENCH_serve*, ZeRO-overlap BENCH_zero*) so the default/
    smoke modes gate the newest artifact of EACH family — a
    lexicographic history[-1] would permanently pick one family once
    committed and stop gating the others' claims entirely."""
    out: Dict[str, List[str]] = {}
    for path in history:
        base = os.path.basename(path)
        fam = ("serve" if base.startswith("BENCH_serve")
               else "zero" if base.startswith("BENCH_zero")
               else "train")
        out.setdefault(fam, []).append(path)
    return {fam: sorted(paths) for fam, paths in out.items()}


def gate(history: List[str], candidate: str,
         margin_floor: float = MARGIN_FLOOR) -> int:
    """0 = no regression; 1 = regression (or failed serve bars);
    2 = unusable inputs."""
    history = [os.path.abspath(p) for p in history]
    candidate = os.path.abspath(candidate)
    prior = [p for p in history if p != candidate]
    if not prior:
        print(f"bench_gate: no history to gate {candidate} against "
              f"(need at least one earlier BENCH artifact)",
              file=sys.stderr)
        return 2
    cand_metrics, cand_bars = load_artifact(candidate)
    if not cand_metrics:
        print(f"bench_gate: no gateable metrics in {candidate}",
              file=sys.stderr)
        return 2

    # baseline = newest prior artifact carrying the metric; noise =
    # worst relative spread seen anywhere in history (candidate incl.)
    baseline: Dict[str, dict] = {}
    worst_spread: Dict[str, float] = {}
    for path in prior:                     # sorted: newest last wins
        metrics, _ = load_artifact(path)
        for name, rec in metrics.items():
            baseline[name] = {**rec, "from": os.path.basename(path)}
            if rec["spread"] is not None:
                worst_spread[name] = max(worst_spread.get(name, 0.0),
                                         rec["spread"])
    for name, rec in cand_metrics.items():
        if rec["spread"] is not None:
            worst_spread[name] = max(worst_spread.get(name, 0.0),
                                     rec["spread"])

    failures: List[str] = []
    if cand_bars:
        failures.append(f"candidate bench bars failed: {cand_bars}")
    gated = reported = 0
    for name, rec in sorted(cand_metrics.items()):
        base = baseline.get(name)
        if base is None:
            continue          # a brand-new metric has no claim to keep
        d = direction(name, rec["unit"] or base["unit"])
        margin = min(max(2.0 * worst_spread.get(name, 0.0),
                         margin_floor), MARGIN_CAP)
        cur, ref = rec["value"], base["value"]
        if d is None or not ref:
            reported += 1
            print(f"  (report-only) {name}: {cur:g} vs {ref:g} "
                  f"[{base['from']}]")
            continue
        gated += 1
        if d == "higher":
            floor = ref * (1.0 - margin)
            verdict = cur >= floor
            bound = f">= {floor:g}"
        else:
            ceil = ref * (1.0 + margin)
            verdict = cur <= ceil
            bound = f"<= {ceil:g}"
        status = "ok" if verdict else "REGRESSION"
        print(f"  [{status}] {name}: {cur:g} (baseline {ref:g} from "
              f"{base['from']}, margin {margin:.0%}, need {bound})")
        if not verdict:
            failures.append(
                f"{name}: {cur:g} vs baseline {ref:g} "
                f"({base['from']}) outside the {margin:.0%} noise band")
    print(f"bench_gate: {gated} metric(s) gated, {reported} "
          f"report-only, candidate {os.path.basename(candidate)} vs "
          f"{len(prior)} historical artifact(s)")
    if failures:
        for f_ in failures:
            print(f"bench_gate: FAIL — {f_}", file=sys.stderr)
        return 1
    print("bench_gate: OK — no regression")
    return 0


def degrade(path: str, out_path: str, factor: float = SMOKE_DEGRADE):
    """Write a copy of an artifact with every gateable metric pushed
    the WRONG way (throughput × factor, latency ÷ factor) — the
    synthetic regression the gate smoke must catch."""
    with open(path) as f:
        data = json.load(f)

    def walk(obj):
        if isinstance(obj, dict):
            name = obj.get("metric")
            if isinstance(name, str) and isinstance(obj.get("value"),
                                                    (int, float)):
                d = direction(name, str(obj.get("unit", "")))
                if d == "higher":
                    obj["value"] = obj["value"] * factor
                elif d == "lower":
                    obj["value"] = obj["value"] / factor
            for v in obj.values():
                walk(v)
        elif isinstance(obj, list):
            for v in obj:
                walk(v)

    walk(data)
    with open(out_path, "w") as f:
        json.dump(data, f)


def smoke(history: List[str]) -> int:
    """The gate's own contract, PER FAMILY: the committed history
    passes, an injected regression fails.  Nonzero unless both hold
    for every family.  A family with a single artifact has no earlier
    history to pass; its artifact is then the baseline the injected
    regression must fail against."""
    for fam, paths in sorted(families(history).items()):
        candidate = paths[-1]
        if len(paths) >= 2:
            print(f"bench_gate --smoke [{fam} 1/2]: committed history "
                  f"must pass ({os.path.basename(candidate)})")
            if gate(paths, candidate) != 0:
                print(f"bench_gate --smoke: committed {fam} history "
                      f"FAILED its own gate — fix the artifacts or the "
                      f"thresholds", file=sys.stderr)
                return 1
        print(f"bench_gate --smoke [{fam} 2/2]: injected regression "
              f"must fail")
        with tempfile.TemporaryDirectory(prefix="bench_gate_") as tmp:
            degraded = os.path.join(tmp, os.path.basename(candidate))
            degrade(candidate, degraded)
            rc = gate(paths, degraded)
        if rc == 0:
            print(f"bench_gate --smoke: the gate PASSED a 2x-degraded "
                  f"{fam} artifact — thresholds are vacuous",
                  file=sys.stderr)
            return 1
    print("bench_gate --smoke: OK (history passes, regression caught)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/bench_gate.py",
        description="Gate a bench artifact against the committed "
                    "BENCH history (noise-aware thresholds).")
    ap.add_argument("--history", nargs="*", default=None,
                    help="history artifacts (default: the repo's "
                         "BENCH_r*.json + BENCH_serve*.json)")
    ap.add_argument("--candidate", default="",
                    help="artifact to gate (default: the newest "
                         "history artifact, gated vs the earlier ones)")
    ap.add_argument("--margin_floor", type=float, default=MARGIN_FLOOR,
                    help=f"minimum relative noise margin (default "
                         f"{MARGIN_FLOOR})")
    ap.add_argument("--smoke", action="store_true",
                    help="self-test: history passes AND an injected "
                         "regression fails")
    args = ap.parse_args(argv)
    history = args.history if args.history else default_history()
    if not history:
        print("bench_gate: no BENCH artifacts found", file=sys.stderr)
        return 2
    if args.smoke:
        return smoke(history)
    if args.candidate:
        return gate(history, args.candidate,
                    margin_floor=args.margin_floor)
    # default: gate the newest artifact of EACH family against its
    # earlier history (one regressed family fails the whole gate)
    rc = 0
    for fam, paths in sorted(families(history).items()):
        if len(paths) < 2:
            continue
        print(f"bench_gate: family {fam!r} — gating "
              f"{os.path.basename(paths[-1])}")
        rc = max(rc, gate(paths, paths[-1],
                          margin_floor=args.margin_floor))
    return rc


if __name__ == "__main__":
    sys.exit(main())
