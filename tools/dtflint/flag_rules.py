"""Flag-wiring rules — the reference repo's dominant rot, made a gate.

flag-dead — every field of config/flags.py Config must be READ
somewhere in the tree (``cfg.<name>`` attribute access or
``getattr(x, "<name>", ...)``): a flag that parses but drives nothing
is the vendored-``official/`` failure mode.  Deliberate reference-
parity no-op shims stay, but each carries an inline suppression WITH
its reason — the no-op-ness becomes a declared contract instead of an
accident.

flag-doc — every ``--flag`` token in README.md / docs/DESIGN.md must
exist: as a Config field, or as a literal ``"--flag"`` string in some
CLI (argparse add_argument, manual argv handling).  Docs that teach
flags the binaries refuse are worse than no docs.

plan-owned — plan/compile.py PLAN_OWNED_FLAGS (the flags a plan
compiles into, which must sit at their defaults when ``--plan`` is
given) is cross-checked against Config: every key must be a real
field and the recorded default must equal the field's default — a
drifted default would let a hand-set flag slip past the conflict
check and be silently overridden, the exact ambiguity the planner
exists to remove.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Tuple

from tools.dtflint import Context, Finding

_DOC_FLAG_RE = re.compile(r"--([a-z][a-z0-9_]*)")

#: ``--tokens`` the docs may name although no CLI here defines them —
#: each entry carries its reason (the doc-side analog of an inline
#: suppression; markdown has no place to hang a comment)
DOC_FLAG_ALLOWLIST = {
    # XLA environment flag (lands in XLA_FLAGS, not our CLI)
    "xla_force_host_platform_device_count",
    # the TF reference repo's flag, cited in a parity note
    "num_gpus",
    # placeholders in flag-syntax prose ("--name value", "--flag=x")
    "name", "flag",
    # the benchmark's own CLI (benchmark/run.py, outside the scanned tree)
    "workload", "seconds",
}


def _config_fields(path: str) -> Dict[str, Tuple[int, object]]:
    """{field: (line, default-literal-or-Ellipsis)} of class Config."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    out: Dict[str, Tuple[int, object]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "Config":
            for stmt in node.body:
                if isinstance(stmt, ast.AnnAssign) \
                        and isinstance(stmt.target, ast.Name):
                    default: object = Ellipsis
                    if isinstance(stmt.value, ast.Constant):
                        default = stmt.value.value
                    elif isinstance(stmt.value, ast.UnaryOp) \
                            and isinstance(stmt.value.op, ast.USub) \
                            and isinstance(stmt.value.operand,
                                           ast.Constant):
                        default = -stmt.value.operand.value
                    out[stmt.target.id] = (stmt.lineno, default)
    return out


def _plan_owned(path: str) -> Tuple[Dict[str, object], int]:
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "PLAN_OWNED_FLAGS" \
                and isinstance(node.value, ast.Dict):
            out = {}
            for k, v in zip(node.value.keys, node.value.values):
                if isinstance(k, ast.Constant) \
                        and isinstance(v, ast.Constant):
                    out[k.value] = v.value
            return out, node.lineno
    return {}, 0


def check(ctx: Context) -> List[Finding]:
    findings: List[Finding] = []
    try:
        fields = _config_fields(ctx.flags_path)
    except (OSError, SyntaxError):
        return findings
    flags_rel = next((s.path for s in ctx.sources
                      if s.abspath == ctx.flags_path),
                     "dtf_tpu/config/flags.py")

    # -- usage scan: attribute reads + getattr literals + "--x" strings
    read: set = set()
    cli_literals: set = set()
    for src in ctx.sources:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Attribute):
                read.add(node.attr)
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id in ("getattr", "hasattr") \
                    and len(node.args) >= 2 \
                    and isinstance(node.args[1], ast.Constant) \
                    and isinstance(node.args[1].value, str):
                read.add(node.args[1].value)
            elif isinstance(node, ast.Constant) \
                    and isinstance(node.value, str) \
                    and node.value.startswith("--"):
                m = _DOC_FLAG_RE.match(node.value)
                if m:
                    cli_literals.add(m.group(1))
            elif isinstance(node, ast.Assign) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and "FLAGS" in node.targets[0].id \
                    and isinstance(node.value, ast.Dict):
                # CLI-local flag tables by convention carry FLAGS in
                # their name (plan_main._OWN_FLAGS & co): their keys
                # ARE accepted flags
                for k in node.value.keys:
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str):
                        cli_literals.add(k.value)

    for name, (line, _default) in fields.items():
        if name not in read:
            findings.append(Finding(
                "flag-dead", flags_rel, line,
                f"flag '--{name}' is defined in Config but nothing "
                f"reads it — wire it or delete it (declared no-op "
                f"parity shims carry an inline suppression)"))

    # -- docs closure
    known = set(fields) | cli_literals | set(DOC_FLAG_ALLOWLIST)
    for doc in ctx.doc_files:
        try:
            with open(doc, encoding="utf-8") as f:
                lines = f.read().splitlines()
        except (OSError, SyntaxError):
            continue
        rel = doc[len(ctx.repo_root) + 1:] if doc.startswith(
            ctx.repo_root) else doc
        seen_here: set = set()
        for i, text in enumerate(lines, start=1):
            for m in _DOC_FLAG_RE.finditer(text):
                name = m.group(1)
                if name in known or name in seen_here:
                    continue
                seen_here.add(name)
                findings.append(Finding(
                    "flag-doc", rel, i,
                    f"doc names '--{name}' but no Config field or CLI "
                    f"literal defines it"))

    # -- plan-owned cross-check
    try:
        owned, line = _plan_owned(ctx.plan_compile_path)
    except (OSError, SyntaxError):
        owned, line = {}, 0
    if owned:
        plan_rel = next((s.path for s in ctx.sources
                         if s.abspath == ctx.plan_compile_path),
                        "dtf_tpu/plan/compile.py")
        for name, default in owned.items():
            if name not in fields:
                findings.append(Finding(
                    "plan-owned", plan_rel, line,
                    f"PLAN_OWNED_FLAGS names '{name}' which is not a "
                    f"Config field"))
            elif fields[name][1] is not Ellipsis \
                    and fields[name][1] != default:
                findings.append(Finding(
                    "plan-owned", plan_rel, line,
                    f"PLAN_OWNED_FLAGS default for '{name}' "
                    f"({default!r}) != Config default "
                    f"({fields[name][1]!r}) — the --plan conflict "
                    f"check would mis-fire"))
    return findings
