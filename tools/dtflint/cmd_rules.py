"""Command-and-path closure — ``flag-doc``'s sibling for the binaries.

cmd-doc — docs that teach flags the binaries refuse are worse than no
docs, and the same holds for the binaries themselves.  Over every
scanned ``.py`` (docstrings, comments and strings alike), every ``.sh``
under the scanned directories, and the documents (README.md,
docs/DESIGN.md, docs/SETUP.md, PARITY.md):

  (a) every command taught — ``python[3]`` before a ``.py`` file or
      before ``-m pkg.mod``, ``bash`` or ``sh`` before a ``.sh`` file —
      names a file or module of the checkout.  A module whose top-level package is not a directory of
      the checkout (``pytest``, ``http.server``) resolves by import and
      passes when it is installed.
  (b) every token with a ``/`` whose first segment is a top-level
      directory of the checkout and which ends ``.py``, ``.sh``,
      ``.json``, ``.jsonl``, ``.md`` or ``.cpp`` exists.

Skipped by rule: a token holding a pattern character (``*``, ``{``,
``<``, ``$``), and whatever the checkout's ``.gitignore`` lists (run-time
artefacts: ``chiprun_out/``, ``_chip/``, ``benchmark/out/``).  BARE file
names are not checked: the sources name the reference's files
(``resnet_cifar_main.py``) and run-time artefacts
(``rollout_state.json``) by design.

A ``.py`` or ``.sh`` line is suppressed the usual way (``# dtflint:
disable=cmd-doc (why)`` on the line or on the comment lines above it).
"""

from __future__ import annotations

import fnmatch
import importlib.util
import os
import re
from typing import Iterable, List, Tuple

from tools.dtflint import SCAN_DIRS, _SUPPRESS_RE, Context, Finding

_PATTERN_CHARS = "*{<$"
_TOKEN = r"[\w.*{}<>$/-]+"
_PY_FILE_RE = re.compile(rf"\bpython3?\s+(?:-u\s+)?({_TOKEN}\.py)\b")
_PY_MOD_RE = re.compile(r"\bpython3?\s+(?:-u\s+)?-m\s+([A-Za-z_][\w.]*)")
_SH_FILE_RE = re.compile(rf"\b(?:bash|sh)\s+({_TOKEN}\.sh)\b")
_PATH_RE = re.compile(
    rf"(?<![\w/.$-])([A-Za-z_][\w.-]*/{_TOKEN}\.(?:py|sh|jsonl|json|md|cpp))"
    rf"(?![\w/])")

#: documents scanned beside ``ctx.doc_files`` (repo-relative)
EXTRA_DOCS = (os.path.join("docs", "SETUP.md"), "PARITY.md")


def _ignore_patterns(repo_root: str) -> List[str]:
    try:
        with open(os.path.join(repo_root, ".gitignore"),
                  encoding="utf-8") as f:
            lines = [ln.strip() for ln in f]
    except OSError:
        return []
    return [ln for ln in lines if ln and ln[0] not in "#!"]


def _ignored(token: str, patterns: Iterable[str]) -> bool:
    for pat in patterns:
        if pat.endswith(("/", "/*")):
            d = pat.rstrip("*").strip("/")
            if token.startswith(d + "/") or f"/{d}/" in token:
                return True
        elif fnmatch.fnmatch(token, pat) \
                or fnmatch.fnmatch(os.path.basename(token), pat):
            return True
    return False


def _module_resolves(repo_root: str, name: str) -> bool:
    parts = name.split(".")
    if os.path.isdir(os.path.join(repo_root, parts[0])) \
            or os.path.isfile(os.path.join(repo_root, parts[0] + ".py")):
        base = os.path.join(repo_root, *parts)
        return os.path.isfile(base + ".py") or os.path.isfile(
            os.path.join(base, "__main__.py"))
    try:
        return importlib.util.find_spec(parts[0]) is not None
    except (ImportError, ValueError):
        return False


def _dead_in_line(repo_root: str, text: str,
                  ignore: List[str]) -> List[Tuple[str, str]]:
    """[(token, message)] for one line of text."""
    out = []
    taught = set()

    def live_file(tok: str) -> bool:
        return (any(c in tok for c in _PATTERN_CHARS)
                or _ignored(tok, ignore)
                or os.path.exists(os.path.join(repo_root, tok)))

    for rx, what in ((_PY_FILE_RE, "python"), (_SH_FILE_RE, "sh")):
        for m in rx.finditer(text):
            tok = m.group(1)
            taught.add(tok)
            if not os.path.isabs(tok) and not live_file(tok):
                out.append((tok, f"teaches the command '{what} {tok}' but "
                                 f"the checkout has no such file"))
    for m in _PY_MOD_RE.finditer(text):
        name = m.group(1).rstrip(".")
        if not _module_resolves(repo_root, name):
            out.append((name, f"teaches the command 'python -m {name}' but "
                              f"no such module is in the checkout or "
                              f"installed"))
    for m in _PATH_RE.finditer(text):
        tok = m.group(1)
        if tok in taught:
            continue
        if os.path.isdir(os.path.join(repo_root, tok.split("/", 1)[0])) \
                and not live_file(tok):
            out.append((tok, f"names the path '{tok}' but the checkout "
                             f"has no such file"))
    return out


def _sh_files(repo_root: str) -> List[str]:
    out = []
    for d in SCAN_DIRS:
        for root, dirs, files in os.walk(os.path.join(repo_root, d)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            out.extend(os.path.join(root, f) for f in sorted(files)
                       if f.endswith(".sh"))
    return out


def _sh_suppressed(lines: List[str], i: int) -> bool:
    """The ``Source`` convention for a shell script: the marker, with a
    reason, on the line or on the comment-only lines above it."""
    n = i
    while True:
        m = _SUPPRESS_RE.search(lines[n - 1])
        if m and "cmd-doc" in m.group(1) and (m.group(2) or "").strip():
            return True
        n -= 1
        if n < 1 or not lines[n - 1].lstrip().startswith("#"):
            return False


def check(ctx: Context) -> List[Finding]:
    root = ctx.repo_root
    ignore = _ignore_patterns(root)
    # (path, lines, kind): a document reports a dead name once
    texts: List[Tuple[str, List[str], str]] = [
        (s.path, s.lines, "py") for s in ctx.sources]
    docs = list(ctx.doc_files) + [
        p for p in (os.path.join(root, d) for d in EXTRA_DOCS)
        if os.path.exists(p) and p not in ctx.doc_files]
    for path, kind in [(p, "sh") for p in _sh_files(root)] + \
            [(p, "doc") for p in docs]:
        try:
            with open(path, encoding="utf-8") as f:
                lines = f.read().splitlines()
        except OSError:
            continue
        texts.append((os.path.relpath(path, root), lines, kind))

    findings: List[Finding] = []
    for rel, lines, kind in texts:
        seen = set()
        for i, text in enumerate(lines, start=1):
            for tok, msg in _dead_in_line(root, text, ignore):
                if (kind == "doc" and tok in seen) \
                        or (kind == "sh" and _sh_suppressed(lines, i)):
                    continue
                seen.add(tok)
                findings.append(Finding("cmd-doc", rel, i, msg))
    return findings
