#!/usr/bin/env python
"""Marker audit — fail when an unmarked test exceeds the time ceiling.

THIN SHIM: the logic moved into the project-wide static-analysis suite
(tools/dtflint, rule ``test-marker``) so CI runs ONE analysis
entrypoint; this CLI remains for muscle memory and scripts.  Semantics
are unchanged: tier-1 runs `-m 'not slow'` under a hard wall-clock
limit (1,470 s over six xdist workers at PR 24; ROADMAP D8), which only
holds if every genuinely heavy test carries the `slow` marker.  The
conftest hook dumps per-test call durations to
``tests/.last_durations.json``; exit 1 (listing offenders) when any
UNMARKED test took longer than the ceiling.

    python tools/marker_audit.py [--ceiling 20] [--path tests/.last_durations.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# the single source of the audit logic + default ceiling
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from tools.dtflint.markers import DEFAULT_CEILING_S, audit  # noqa: E402

DEFAULT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", ".last_durations.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ceiling", type=float, default=DEFAULT_CEILING_S,
                    help="per-test call-time ceiling in seconds for "
                         "tests not marked slow (default %(default)s)")
    ap.add_argument("--path", default=DEFAULT_PATH,
                    help="durations dump written by the conftest hook")
    args = ap.parse_args(argv)

    try:
        with open(args.path) as f:
            durations = json.load(f)
    except OSError as e:
        print(f"marker_audit: cannot read {args.path} ({e}) — run the "
              f"test suite first (the conftest hook writes it)",
              file=sys.stderr)
        return 2

    offenders = audit(durations, args.ceiling)
    if offenders:
        print(f"marker_audit: {len(offenders)} unmarked test(s) over the "
              f"{args.ceiling:g}s ceiling — mark them "
              f"@pytest.mark.slow or make them faster:")
        for nodeid, dur in offenders:
            print(f"  {dur:8.1f}s  {nodeid}")
        return 1
    n = len(durations)
    print(f"marker_audit: OK — {n} tests, none unmarked over "
          f"{args.ceiling:g}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
