"""test-marker — the test-budget contract as a dtflint rule.

Tier-1 runs ``-m 'not slow'`` under a hard wall-clock limit (1,470 s
over six xdist workers at PR 24; ROADMAP D8); that only holds if every
genuinely heavy test carries the ``slow`` marker.  tests/conftest.py
derives each test's own time limit from the ceiling below, and its
hook dumps per-test call durations to ``tests/.last_durations.json``;
this rule fails on any UNMARKED test over the ceiling.  Folded in from tools/marker_audit.py so CI runs ONE
analysis entrypoint (the old CLI remains as a thin shim over
:func:`audit`).

The rule is data-driven, not AST-driven: with no durations dump (the
suite hasn't run in this checkout) it skips silently — in ci_check the
dump always exists, because stage 1 writes it.
"""

from __future__ import annotations

import json
import os
from typing import List

from tools.dtflint import Context, Finding

DEFAULT_CEILING_S = 20.0


def audit(durations: dict, ceiling_s: float) -> list:
    """[(nodeid, duration), ...] of unmarked tests over the ceiling,
    slowest first.  (The function tools/marker_audit.py shims to.)"""
    offenders = [(nodeid, rec["duration"])
                 for nodeid, rec in durations.items()
                 if not rec.get("slow") and rec["duration"] > ceiling_s]
    return sorted(offenders, key=lambda kv: -kv[1])


def check(ctx: Context) -> List[Finding]:
    path = ctx.durations_path
    if not path or not os.path.exists(path):
        return []
    ceiling = getattr(ctx, "marker_ceiling_s", DEFAULT_CEILING_S)
    try:
        with open(path) as f:
            durations = json.load(f)
    except (OSError, ValueError):
        return [Finding("test-marker", os.path.basename(path), 1,
                        "durations dump exists but cannot be parsed")]
    out: List[Finding] = []
    for nodeid, dur in audit(durations, ceiling):
        testfile = nodeid.split("::", 1)[0]
        out.append(Finding(
            "test-marker", testfile, 1,
            f"unmarked test {nodeid} took {dur:.1f}s (> {ceiling:g}s "
            f"ceiling) — mark it @pytest.mark.slow or make it faster"))
    return out
