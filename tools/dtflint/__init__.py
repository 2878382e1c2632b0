"""dtflint — project-wide AST static analysis for the dtf_tpu tree.

The driver's benchmark is the no-silent-drift discipline for
PERFORMANCE; this is its correctness-side twin: the invariants
DESIGN.md states in prose — "under the router lock", "batch n is a
pure function of (seed, pid, n)", "every kind in KNOWN_EVENT_KINDS" —
are checked against the program text on every CI run, instead of
waiting for a chaos smoke to happen to trip them at runtime (the
reference repo's dominant bug class was exactly this invisible wiring
rot: vendored flags that parsed but drove nothing, PS races visible
only in 16-rank logs).

Rule families (one module per family; ids are stable):

  locks.py        lock-guard        guarded attribute touched outside
                                    its declared lock (``_GUARDED_BY``)
                  lock-decl         malformed ``_GUARDED_BY``
  determinism.py  det-time          wall-clock read in a bit-exactness
                                    module
                  det-random        unseeded/global RNG in one
                  det-entropy       os.urandom/uuid4/secrets in one
                  det-set-iter      iteration over a set (order-
                                    dependent) in one
                  host-sync         device→host sync in a step loop
                                    outside an accounted sync point
  vocab_rules.py  trace-unregistered  emitted trace kind missing from
                                      obs/vocab.py
                  trace-unemitted     registered kind nothing emits
                  metric-grammar      metric name outside the
                                      <subsystem>_<name> grammar
                  metric-dup          one metric name, two types/units
                  chaos-probe         chaos grammar kind without a
                                      probe point (or vice versa)
  flag_rules.py   flag-dead         Config field no code ever reads
                  flag-doc          ``--flag`` named in README/DESIGN
                                    that exists nowhere
                  plan-owned        PLAN_OWNED_FLAGS out of sync with
                                    config/flags.py
  cmd_rules.py    cmd-doc           a taught command or a slash path
                                    that names no file or module of
                                    the checkout
  markers.py      test-marker       unmarked test over the tier-1
                                    per-test time ceiling
  (core)          bad-suppression   a disable comment without a reason

Suppressions are inline and REQUIRE a reason::

    x = time.time()   # dtflint: disable=det-time (wall clock only logged)

A suppression on its own line applies to the next line.  Accounted
host syncs in step loops are annotated the same way::

    loss = jax.device_get(m)  # dtflint: sync-point (log-cadence copy)

The committed baseline (``tools/dtflint/baseline.json``) makes CI a
RATCHET: only NEW findings fail (`--update-baseline` re-records).  The
baseline is kept EMPTY — real findings get fixed or reason-suppressed,
not baselined; the file exists so an emergency landing is possible
without deleting the gate.

Usage:
  python -m tools.dtflint [--json] [--update-baseline]
                          [--durations tests/.last_durations.json]
Exit 0 = no new findings; 1 = new findings; 2 = usage/config error.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baseline.json")

#: directories whose .py files are scanned (repo-relative); root-level
#: scripts (chip_smoke.py, __graft_entry__.py) join via ROOT_GLOBS for
#: the usage-side scans (flag reads, doc flags)
SCAN_DIRS = ("dtf_tpu", "tools")
ROOT_GLOBS = (".py",)

# the reason may continue onto following comment lines: the opening
# paren with non-empty text suffices on the marker line
_SUPPRESS_RE = re.compile(
    r"#\s*dtflint:\s*disable=([a-z0-9-]+(?:\s*,\s*[a-z0-9-]+)*)"
    r"(?:\s*\(([^)]*)\)?)?")
_SYNC_RE = re.compile(
    r"#\s*dtflint:\s*sync-point(?:\s*\(([^)]*)\)?)?")
_CALLED_LOCKED_RE = re.compile(
    r"#\s*dtflint:\s*called-locked(?:\s*\(([^)]*)\)?)?")


@dataclasses.dataclass
class Finding:
    rule: str
    path: str        # repo-relative
    line: int
    message: str
    seq: int = 0     # Nth identical finding in this file (see key)

    @property
    def key(self) -> str:
        # line numbers are deliberately NOT part of the identity (a
        # baseline keyed on lines would churn on every unrelated
        # edit), but identical findings in one file are SEQUENCED so
        # a baselined occurrence never blankets new ones
        suffix = f"#{self.seq}" if self.seq else ""
        return f"{self.path}::{self.rule}::{self.message}{suffix}"

    def __str__(self) -> str:
        return f"{self.path}:{self.line} [{self.rule}] {self.message}"


class Source:
    """One parsed file: AST + the per-line suppression/annotation
    maps.  Parsing happens once; every rule family walks the same
    tree."""

    def __init__(self, abspath: str, repo_root: str = REPO_ROOT):
        self.abspath = abspath
        self.path = os.path.relpath(abspath, repo_root)
        with open(abspath, encoding="utf-8") as f:
            self.text = f.read()
        self.lines = self.text.splitlines()
        self.tree = ast.parse(self.text, filename=self.path)
        # line -> set of rule ids suppressed there; line -> reason
        self.suppressed: Dict[int, set] = {}
        self.sync_points: set = set()
        self.called_locked: set = set()
        self.bad_suppressions: List[Finding] = []
        self._scan_comments()

    def _scan_comments(self) -> None:
        import io
        import tokenize
        comments = {}
        try:
            for tok in tokenize.generate_tokens(
                    io.StringIO(self.text).readline):
                if tok.type == tokenize.COMMENT:
                    comments[tok.start[0]] = tok.string
        except tokenize.TokenError:
            pass
        for i, line in sorted(comments.items()):
            m = _SUPPRESS_RE.search(line)
            if m:
                rules = {r.strip() for r in m.group(1).split(",")}
                reason = (m.group(2) or "").strip()
                if not reason:
                    self.bad_suppressions.append(Finding(
                        "bad-suppression", self.path, i,
                        "suppression without a reason — write "
                        "'# dtflint: disable=RULE (why this is safe)'"))
                    continue
                self.suppressed.setdefault(i, set()).update(rules)
            m = _SYNC_RE.search(line)
            if m:
                if not (m.group(1) or "").strip():
                    self.bad_suppressions.append(Finding(
                        "bad-suppression", self.path, i,
                        "sync-point annotation without a reason — write "
                        "'# dtflint: sync-point (what accounts it)'"))
                else:
                    self.sync_points.add(i)
            if _CALLED_LOCKED_RE.search(line):
                self.called_locked.add(i)

    def _effective(self, store: Dict[int, set] | set, line: int):
        """A comment applies to its own line; a block of comment-only
        lines immediately above a code line applies to that line (so a
        reason too long for one line still anchors)."""
        def on(n):
            if isinstance(store, set):
                return store if n in store else None
            return store.get(n)
        hit = on(line)
        if hit:
            return hit
        prev = line - 1
        while 1 <= prev <= len(self.lines) and \
                self.lines[prev - 1].lstrip().startswith("#"):
            hit = on(prev)
            if hit:
                return hit
            prev -= 1
        return None

    def is_suppressed(self, rule: str, line: int) -> bool:
        rules = self._effective(self.suppressed, line)
        return bool(rules) and rule in rules

    def is_sync_point(self, line: int) -> bool:
        return bool(self._effective(self.sync_points, line))

    def is_called_locked(self, line: int) -> bool:
        """True when the def at ``line`` carries a called-locked
        annotation (same line or the comment line above)."""
        return bool(self._effective(self.called_locked, line))


class Context:
    """Everything the rule families need: the parsed sources plus the
    repo-level cross-reference paths.  Tests build one over a tmp tree
    to fixture a single rule."""

    def __init__(self, repo_root: str = REPO_ROOT,
                 py_files: Optional[Sequence[str]] = None,
                 doc_files: Optional[Sequence[str]] = None,
                 durations_path: Optional[str] = None):
        self.repo_root = repo_root
        if py_files is None:
            py_files = discover_py_files(repo_root)
        self.sources: List[Source] = []
        self.parse_errors: List[Finding] = []
        for p in py_files:
            try:
                self.sources.append(Source(p, repo_root))
            except SyntaxError as e:
                self.parse_errors.append(Finding(
                    "parse-error", os.path.relpath(p, repo_root),
                    e.lineno or 1, f"cannot parse: {e.msg}"))
        if doc_files is None:
            doc_files = [p for p in
                         (os.path.join(repo_root, "README.md"),
                          os.path.join(repo_root, "docs", "DESIGN.md"))
                         if os.path.exists(p)]
        self.doc_files = list(doc_files)
        self.durations_path = durations_path
        # cross-reference anchors (overridable in fixture tests)
        self.vocab_path = os.path.join(
            repo_root, "dtf_tpu", "obs", "vocab.py")
        self.chaos_path = os.path.join(
            repo_root, "dtf_tpu", "chaos", "__init__.py")
        self.flags_path = os.path.join(
            repo_root, "dtf_tpu", "config", "flags.py")
        self.plan_compile_path = os.path.join(
            repo_root, "dtf_tpu", "plan", "compile.py")

    def source(self, relpath: str) -> Optional[Source]:
        for s in self.sources:
            if s.path == relpath or s.abspath == relpath:
                return s
        return None


def discover_py_files(repo_root: str) -> List[str]:
    out: List[str] = []
    for d in SCAN_DIRS:
        base = os.path.join(repo_root, d)
        for root, dirs, files in os.walk(base):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in sorted(files):
                if f.endswith(".py"):
                    out.append(os.path.join(root, f))
    # root-level scripts join the usage-side scans
    if os.path.isdir(repo_root):
        for f in sorted(os.listdir(repo_root)):
            if f.endswith(ROOT_GLOBS) and \
                    os.path.isfile(os.path.join(repo_root, f)):
                out.append(os.path.join(repo_root, f))
    return out


def run_rules(ctx: Context) -> List[Finding]:
    """All rule families over ``ctx``; suppressions applied; findings
    sorted by (path, line)."""
    from tools.dtflint import (cmd_rules, determinism, flag_rules, locks,
                               markers, vocab_rules)
    findings: List[Finding] = list(ctx.parse_errors)
    for s in ctx.sources:
        findings.extend(s.bad_suppressions)
    for mod in (locks, determinism, vocab_rules, flag_rules, cmd_rules,
                markers):
        findings.extend(mod.check(ctx))
    kept = []
    for f in findings:
        src = ctx.source(f.path)
        if src is not None and src.is_suppressed(f.rule, f.line):
            continue
        kept.append(f)
    kept.sort(key=lambda f: (f.path, f.line, f.rule))
    counts: Dict[str, int] = {}
    for f in kept:
        ident = f"{f.path}::{f.rule}::{f.message}"
        f.seq = counts.get(ident, 0)
        counts[ident] = f.seq + 1
    return kept


def load_baseline(path: str = BASELINE_PATH) -> List[str]:
    try:
        with open(path) as f:
            data = json.load(f)
    except OSError:
        return []
    return list(data.get("findings", []))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.dtflint",
        description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="emit findings as one JSON object")
    ap.add_argument("--update-baseline", action="store_true",
                    help="record every current finding into the "
                         "baseline (the ratchet's emergency lever — "
                         "the target state is an EMPTY baseline)")
    ap.add_argument("--baseline", default=BASELINE_PATH,
                    help="baseline file (default %(default)s)")
    ap.add_argument("--durations", default=os.path.join(
                        REPO_ROOT, "tests", ".last_durations.json"),
                    help="per-test durations dump for the test-marker "
                         "rule (written by the tier-1 conftest hook; "
                         "the rule is skipped when the file is absent)")
    ap.add_argument("--ceiling", type=float, default=None,
                    help="test-marker per-test ceiling override (s)")
    ap.add_argument("--root", default=REPO_ROOT,
                    help="tree to analyze (default: this repo; fixture "
                         "tests point it at seeded-violation trees)")
    args = ap.parse_args(argv)

    ctx = Context(repo_root=os.path.abspath(args.root),
                  durations_path=args.durations)
    if args.ceiling is not None:
        ctx.marker_ceiling_s = args.ceiling
    findings = run_rules(ctx)
    baseline = set(load_baseline(args.baseline))
    new = [f for f in findings if f.key not in baseline]
    stale = sorted(baseline - {f.key for f in findings})

    if args.update_baseline:
        with open(args.baseline, "w") as f:
            json.dump({"findings": sorted({x.key for x in findings})},
                      f, indent=1)
            f.write("\n")
        print(f"dtflint: baseline updated with {len(findings)} "
              f"finding(s) -> {args.baseline}")
        return 0

    if args.json:
        print(json.dumps({
            "findings": [dataclasses.asdict(f) for f in findings],
            "new": [f.key for f in new],
            "baseline_stale": stale,
        }, indent=1))
    else:
        for f in findings:
            tag = "" if f.key in baseline else " NEW"
            print(f"{f}{tag}")
        for k in stale:
            print(f"dtflint: stale baseline entry (fixed? run "
                  f"--update-baseline): {k}", file=sys.stderr)
        n_src = len(ctx.sources)
        if new:
            print(f"dtflint: {len(new)} NEW finding(s) over {n_src} "
                  f"files — fix them or suppress WITH A REASON "
                  f"(# dtflint: disable=RULE (why))", file=sys.stderr)
        else:
            print(f"dtflint: OK — {n_src} files, "
                  f"{len(findings)} baselined finding(s), 0 new")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
