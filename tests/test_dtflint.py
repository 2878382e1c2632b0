"""tools/dtflint — fixture tests per rule family + the ratchet.

Every rule family gets a seeded violation that FIRES and a clean twin
that stays SILENT; the suppression/baseline/ratchet mechanics are
driven through the real CLI (``main(argv)`` with ``--root`` pointed at
a tmp tree); and the lock-discipline coverage of the five thread-heavy
production modules is PINNED: stripping one ``with <lock>:`` from any
of them must make the lock-guard rule fire — that is the test that
keeps ``_GUARDED_BY`` declarations from quietly rotting into comments.
"""

import json
import os
import textwrap

import pytest

from tools import dtflint
from tools.dtflint import Context, locks, determinism, vocab_rules, \
    flag_rules, markers


def _write(root, rel, content):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(content))
    return path


def _ctx(root, **kw):
    return Context(repo_root=str(root), **kw)


# ---------------------------------------------------------------------------
# lock discipline
# ---------------------------------------------------------------------------

LOCKED_SRC = """\
    import threading

    class Box:
        _GUARDED_BY = {"_items": "_mu"}

        def __init__(self):
            self._mu = threading.Lock()
            self._items = []

        def add(self, x):
            with self._mu:
                self._items.append(x)

        def _drain_locked(self):
            return list(self._items)

        def snapshot(self):
            with self._mu:
                return self._drain_locked()
    """


def test_lock_guard_clean_twin_is_silent(tmp_path):
    _write(tmp_path, "box.py", LOCKED_SRC)
    assert locks.check(_ctx(tmp_path)) == []


def test_lock_guard_fires_on_unguarded_touch(tmp_path):
    bad = LOCKED_SRC + textwrap.dedent("""\

        class Racy(Box):
            def peek(self):
                return len(self._items)   # no lock!
    """)
    _write(tmp_path, "box.py", bad)
    # the subclass does not redeclare _GUARDED_BY: guards are per
    # declaring class.  Seed the violation in the declaring class:
    bad2 = LOCKED_SRC.replace(
        "        def snapshot(self):\n"
        "            with self._mu:\n"
        "                return self._drain_locked()",
        "        def snapshot(self):\n"
        "            return list(self._items)")
    _write(tmp_path, "box.py", bad2)
    found = locks.check(_ctx(tmp_path))
    assert [f.rule for f in found] == ["lock-guard"]
    assert "_items" in found[0].message


def test_lock_guard_closure_inside_with_is_not_blessed(tmp_path):
    src = LOCKED_SRC.replace(
        "        def snapshot(self):\n"
        "            with self._mu:\n"
        "                return self._drain_locked()",
        "        def snapshot(self):\n"
        "            with self._mu:\n"
        "                def later():\n"
        "                    return list(self._items)\n"
        "                return later")
    _write(tmp_path, "box.py", src)
    found = locks.check(_ctx(tmp_path))
    assert [f.rule for f in found] == ["lock-guard"]


def test_lock_guard_checks_with_context_expressions(tmp_path):
    """A guarded touch INSIDE a with-statement's context expression
    runs before the lock is acquired — it must be judged by the OUTER
    held state, not blessed by the lock it is about to take."""
    src = LOCKED_SRC.replace(
        "        def snapshot(self):\n"
        "            with self._mu:\n"
        "                return self._drain_locked()",
        "        def snapshot(self):\n"
        "            with self._lock_for(self._items[0]):\n"
        "                return self._drain_locked()")
    _write(tmp_path, "box.py", src)
    found = locks.check(_ctx(tmp_path))
    assert [f.rule for f in found] == ["lock-guard"]
    assert "_items" in found[0].message


def test_lock_decl_must_be_literal(tmp_path):
    src = LOCKED_SRC.replace('_GUARDED_BY = {"_items": "_mu"}',
                             "_GUARDED_BY = dict(_items='_mu')")
    _write(tmp_path, "box.py", src)
    assert [f.rule for f in locks.check(_ctx(tmp_path))] == ["lock-decl"]


#: (module, the with-statement text whose removal must trip the rule)
PRODUCTION_LOCKS = [
    ("dtf_tpu/serve/router.py", "with self._mu:"),
    ("dtf_tpu/serve/engine.py", "with self._cond:"),
    ("dtf_tpu/serve/rollout.py", "with r._mu:"),
    ("dtf_tpu/serve/replica.py", "with self._lock:"),
    ("dtf_tpu/data/service/pool.py", "with self._close_lock:"),
]


@pytest.mark.parametrize("rel,lock_stmt", PRODUCTION_LOCKS,
                         ids=[p[0].rsplit("/", 1)[1]
                              for p in PRODUCTION_LOCKS])
def test_production_lock_discipline_is_pinned(tmp_path, rel, lock_stmt):
    """The five thread-heavy modules declare _GUARDED_BY, are clean as
    committed, and stripping their with-locks makes lock-guard FIRE —
    the declaration is live coverage, not a comment."""
    src_path = os.path.join(dtflint.REPO_ROOT, rel)
    with open(src_path) as f:
        src = f.read()
    assert "_GUARDED_BY" in src, f"{rel} lost its _GUARDED_BY"
    assert lock_stmt in src, f"{rel} lost its '{lock_stmt}'"

    name = os.path.basename(rel)
    _write(tmp_path, name, src)
    clean = [f for f in locks.check(_ctx(tmp_path))
             if not _ctx(tmp_path).source(name).is_suppressed(
                 f.rule, f.line)]
    assert clean == [], f"{rel} is not lock-clean as committed: {clean}"

    stripped = src.replace(lock_stmt, "if True:  # lock stripped")
    _write(tmp_path, name, stripped)
    ctx = _ctx(tmp_path)
    found = [f for f in locks.check(ctx)
             if not ctx.source(name).is_suppressed(f.rule, f.line)]
    assert found and all(f.rule == "lock-guard" for f in found), \
        f"stripping '{lock_stmt}' from {rel} did not trip lock-guard"


# ---------------------------------------------------------------------------
# determinism / host-sync
# ---------------------------------------------------------------------------

def test_det_rules_fire_and_clean_twin_silent(tmp_path):
    bad = _write(tmp_path, "reader.py", """\
        import os
        import time
        import numpy as np

        def batch(k):
            seed = time.time()
            noise = np.random.rand(4)
            salt = os.urandom(8)
            for x in set([3, 1, 2]):
                pass
            return seed, noise, salt
        """)
    good = _write(tmp_path, "clean.py", """\
        import time
        import numpy as np

        def batch(k, seed):
            rng = np.random.default_rng(seed)
            t0 = time.perf_counter()
            for x in sorted(set([3, 1, 2])):
                pass
            return rng.integers(10), time.perf_counter() - t0
        """)
    ctx = _ctx(tmp_path)
    ctx.det_modules = ("reader.py", "clean.py")
    rules = sorted(f.rule for f in determinism.check(ctx))
    assert rules == ["det-entropy", "det-random", "det-set-iter",
                     "det-time"]
    assert all(f.path == "reader.py"
               for f in determinism.check(ctx)), (bad, good)


def test_host_sync_requires_annotation(tmp_path):
    _write(tmp_path, "loop.py", """\
        import numpy as np

        def step_loop(xs):
            out = np.asarray(xs)          # unaccounted
            # dtflint: sync-point (EOS check needs host tokens)
            ok = np.asarray(out)
            return out, ok
        """)
    ctx = _ctx(tmp_path)
    ctx.step_loops = {"loop.py": ("step_loop",)}
    found = determinism.check(ctx)
    assert [f.rule for f in found] == ["host-sync"]
    assert found[0].line == 4


# ---------------------------------------------------------------------------
# vocabulary closure
# ---------------------------------------------------------------------------

VOCAB_SRC = """\
    KNOWN_ANOMALY_KINDS = ("boom",)
    KNOWN_EVENT_KINDS = ("tick", "ghost_kind")
    CHAOS_FAULT_KINDS = ("crash",)
    METRIC_SUBSYSTEMS = ("serve",)
    """


def test_trace_closure_both_directions(tmp_path):
    vocab = _write(tmp_path, "vocab.py", VOCAB_SRC)
    _write(tmp_path, "emitter.py", """\
        from obs import trace

        def go():
            trace.event("tick", n=1)
            trace.event("unregistered_kind")
            trace.anomaly("boom")
        """)
    ctx = _ctx(tmp_path)
    ctx.vocab_path = vocab
    found = vocab_rules.check(ctx)
    rules = sorted(f.rule for f in found)
    assert rules == ["trace-unemitted", "trace-unregistered"]
    byrule = {f.rule: f for f in found}
    assert "unregistered_kind" in byrule["trace-unregistered"].message
    assert "ghost_kind" in byrule["trace-unemitted"].message


def test_metric_grammar_and_dup(tmp_path):
    vocab = _write(tmp_path, "vocab.py", VOCAB_SRC)
    _write(tmp_path, "metrics.py", """\
        def build(m):
            ok = m.gauge("serve_queue_depth", unit="requests")
            bad = m.counter("CamelCaseName")
            alien = m.gauge("warp_core_temp", unit="K")
            dup = m.histogram("serve_queue_depth", unit="s")
            return ok, bad, alien, dup
        """)
    ctx = _ctx(tmp_path)
    ctx.vocab_path = vocab
    rules = sorted(f.rule for f in vocab_rules.check(ctx)
                   if f.rule.startswith("metric-"))
    assert rules == ["metric-dup", "metric-grammar", "metric-grammar"]


def test_chaos_probe_closure(tmp_path):
    vocab = _write(tmp_path, "vocab.py", VOCAB_SRC)
    chaos = _write(tmp_path, "chaos_mod.py", """\
        KINDS = ("crash", "gremlin")
        """)
    _write(tmp_path, "loop.py", """\
        import chaos

        def run(step):
            chaos.step(step)
        """)
    ctx = _ctx(tmp_path)
    ctx.vocab_path = vocab
    ctx.chaos_path = chaos
    found = [f for f in vocab_rules.check(ctx) if f.rule == "chaos-probe"]
    # 'crash' maps to the called probe step() and is alias-listed ->
    # silent; 'gremlin' has no probe mapping AND no vocab alias -> 2
    assert len(found) == 2
    assert all("gremlin" in f.message for f in found)


# ---------------------------------------------------------------------------
# flag wiring
# ---------------------------------------------------------------------------

def test_flag_rules(tmp_path):
    flags = _write(tmp_path, "flags.py", """\
        import dataclasses

        @dataclasses.dataclass
        class Config:
            used_flag: int = 3
            dead_flag: str = ""
            shimmed: bool = False  # dtflint: disable=flag-dead (declared no-op shim for the fixture)
        """)
    _write(tmp_path, "consumer.py", """\
        def run(cfg):
            return cfg.used_flag
        """)
    plan = _write(tmp_path, "plan_compile.py", """\
        PLAN_OWNED_FLAGS = {"used_flag": 99, "phantom_flag": 1}
        """)
    doc = _write(tmp_path, "README.md", """\
        Use `--used_flag 7` or `--imaginary_flag yes`.
        """)
    ctx = _ctx(tmp_path, doc_files=[doc])
    ctx.flags_path = flags
    ctx.plan_compile_path = plan
    found = flag_rules.check(ctx)
    # suppression filtering happens in run_rules; emulate it
    found = [f for f in found
             if not (ctx.source(f.path) or ctx.source("flags.py"))
             or not (ctx.source(f.path)
                     and ctx.source(f.path).is_suppressed(f.rule, f.line))]
    rules = sorted(f.rule for f in found)
    assert rules == ["flag-dead", "flag-doc", "plan-owned", "plan-owned"]
    msgs = " | ".join(f.message for f in found)
    assert "dead_flag" in msgs and "imaginary_flag" in msgs
    assert "phantom_flag" in msgs and "99" in msgs
    assert "shimmed" not in msgs, "reasoned suppression must silence"


# ---------------------------------------------------------------------------
# command-and-path closure
# ---------------------------------------------------------------------------

_LIVE = {"old_bench.py": "", "tools/old.sh": "", "tools/old_gate.py": "",
         "pkg/__init__.py": "", "pkg/gone.py": ""}

# case -> (files of the tree, the dead names the rule must report)
CMD_DOC_CASES = {
    "dead-python-in-a-docstring": (
        {"dtf_tpu/a.py": '"""Run it::\n\n    python old_bench.py --x\n"""\n'},
        ["python old_bench.py"]),
    "dead-python-in-a-markdown-fence": (
        {"README.md": "Measure:\n```bash\npython3 old_bench.py --out r.json\n```\n"},
        ["python old_bench.py"]),
    "dead-python-in-a-shell-script": (
        {"tools/ci.sh": "set -e\npython old_bench.py --out \"$D/x.json\"\n"},
        ["python old_bench.py"]),
    "dead-module": (
        {"pkg/__init__.py": "",
         "dtf_tpu/a.py": "# regenerate with: python -m pkg.gone --all\n"},
        ["python -m pkg.gone"]),
    "dead-bash": (
        {"tools/x.py": "", "README.md": "Then `bash tools/old.sh`.\n"},
        ["sh tools/old.sh"]),
    "dead-slash-path-in-a-comment": (
        {"tools/b.py": "# the bars live in tools/old_gate.py\nX = 1\n"},
        ["tools/old_gate.py"]),
    "live-twins-are-silent": (
        dict(_LIVE, **{
            "dtf_tpu/a.py": '"""python old_bench.py; python -m pkg.gone"""\n'
                            "# the bars live in tools/old_gate.py\n",
            "tools/ci.sh": "python old_bench.py\nbash tools/old.sh\n",
            "README.md": "```\npython3 old_bench.py\nsh tools/old.sh\n```\n"}),
        []),
    "installed-module-is-silent": (
        {"README.md": "`python -m pytest tests/ -q`, `python3 -m http.server`\n"},
        []),
    "uninstalled-module-is-dead": (
        {"README.md": "`python -m no_such_pkg_xyz.tool`\n"},
        ["python -m no_such_pkg_xyz.tool"]),
    "pattern-is-skipped": (
        {"docs/DESIGN.md": "see docs/pr42_*.jsonl, tools/<name>_smoke.py and "
                           "`python tools/{a,b}_smoke.py`\n",
         "tools/x.py": ""},
        []),
    "ignored-directory-is-skipped": (
        {".gitignore": "chiprun_out/\ntools/.cache.json\n",
         "chiprun_out/.keep": "", "tools/x.py": "",
         "README.md": "read chiprun_out/run1/trace.json and "
                      "tools/.cache.json\n"},
        []),
    "bare-reference-file-name-is-silent": (
        {"dtf_tpu/a.py": "# as resnet_cifar_main.py:104 does; state in "
                         "rollout_state.json, see official/resnet/common.py\n"},
        []),
    "suppression-with-a-reason-is-honoured": (
        {"dtf_tpu/a.py": "# python old_bench.py  # dtflint: disable=cmd-doc "
                         "(history: names the tool a PR deleted)\nX = 1\n",
         "tools/ci.sh": "# dtflint: disable=cmd-doc (kept to show the old "
                        "call)\n# python old_bench.py\n"},
        []),
    "suppression-without-a-reason-is-not": (
        {"tools/ci.sh": "python old_bench.py  # dtflint: disable=cmd-doc\n"},
        ["python old_bench.py"]),
}


@pytest.mark.parametrize("case", list(CMD_DOC_CASES))
def test_cmd_doc_rule(tmp_path, case):
    files, want = CMD_DOC_CASES[case]
    for rel, content in files.items():
        _write(tmp_path, rel, content)
    found = [f for f in dtflint.run_rules(_ctx(tmp_path))
             if f.rule == "cmd-doc"]
    assert len(found) == len(want), [str(f) for f in found]
    for f, name in zip(found, want):
        assert f"'{name}'" in f.message, str(f)


# ---------------------------------------------------------------------------
# test-marker (the folded-in marker audit)
# ---------------------------------------------------------------------------

def test_marker_rule_and_shim(tmp_path):
    dump = tmp_path / "durations.json"
    dump.write_text(json.dumps({
        "tests/test_slowpoke.py::test_big": {"duration": 45.0,
                                             "slow": False},
        "tests/test_marked.py::test_big": {"duration": 45.0,
                                           "slow": True},
        "tests/test_quick.py::test_ok": {"duration": 0.1, "slow": False},
    }))
    ctx = _ctx(tmp_path, durations_path=str(dump))
    found = markers.check(ctx)
    assert [f.rule for f in found] == ["test-marker"]
    assert "test_slowpoke" in found[0].message
    # the legacy CLI shims to the same logic
    from tools.marker_audit import main as shim_main
    assert shim_main(["--path", str(dump)]) == 1
    assert shim_main(["--path", str(dump), "--ceiling", "60"]) == 0


# ---------------------------------------------------------------------------
# suppression / baseline / ratchet mechanics, through the real CLI
# ---------------------------------------------------------------------------

def _seed_violation_tree(root):
    _write(root, "dtf_tpu/data/service/reader.py", """\
        import time

        def batch(k):
            return time.time()
        """)


def test_ratchet_cli(tmp_path, capsys):
    _seed_violation_tree(tmp_path)
    base = str(tmp_path / "baseline.json")
    argv = ["--root", str(tmp_path), "--baseline", base,
            "--durations", str(tmp_path / "no_durations.json")]

    # a seeded violation fails the gate
    assert dtflint.main(argv) == 1
    assert "det-time" in capsys.readouterr().out

    # --update-baseline records it; the gate goes green (ratchet)
    assert dtflint.main(argv + ["--update-baseline"]) == 0
    assert dtflint.main(argv) == 0

    # any NEW finding trips the ratchet again
    _write(tmp_path, "dtf_tpu/data/service/reader.py", """\
        import time

        def batch(k):
            return time.time()

        def batch2(k):
            return time.time()
        """)
    assert dtflint.main(argv) == 1

    # a reasoned suppression silences; a reasonless one is ITSELF a
    # finding
    _write(tmp_path, "dtf_tpu/data/service/reader.py", """\
        import time

        def batch(k):
            return time.time()

        def batch2(k):
            # dtflint: disable=det-time (fixture: wall clock only logged)
            return time.time()
        """)
    assert dtflint.main(argv) == 0
    _write(tmp_path, "dtf_tpu/data/service/reader.py", """\
        import time

        def batch(k):
            return time.time()

        def batch2(k):
            # dtflint: disable=det-time
            return time.time()
        """)
    assert dtflint.main(argv) == 1
    assert "bad-suppression" in capsys.readouterr().out


def test_json_output(tmp_path, capsys):
    _seed_violation_tree(tmp_path)
    rc = dtflint.main(["--root", str(tmp_path), "--json",
                       "--baseline", str(tmp_path / "baseline.json"),
                       "--durations", str(tmp_path / "none.json")])
    assert rc == 1
    out = json.loads(capsys.readouterr().out)
    assert out["new"] and out["findings"][0]["rule"] == "det-time"
    assert out["findings"][0]["line"] == 4


# ---------------------------------------------------------------------------
# the repo itself
# ---------------------------------------------------------------------------

def test_repo_is_clean():
    """The whole tree passes with the committed (empty) baseline —
    the executable form of 'fix or reason-suppress every finding'."""
    assert dtflint.main(["--durations", os.devnull + ".absent"]) == 0


def test_vocab_is_single_sourced():
    from dtf_tpu.cli import trace_main
    from dtf_tpu.obs import vocab
    assert trace_main.KNOWN_EVENT_KINDS is vocab.KNOWN_EVENT_KINDS
    assert trace_main.KNOWN_ANOMALY_KINDS is vocab.KNOWN_ANOMALY_KINDS


def test_thread_start_records_creation_stack():
    """conftest's sanitizer wrapper stamps the creation stack the leak
    report prints — for non-daemon threads, the only kind it reports
    (daemon threads skip the recording: they are the hot path)."""
    import threading
    t = threading.Thread(target=lambda: None)   # non-daemon
    t.start()
    t.join()
    frames = getattr(t, "_dtf_started_at", [])
    assert any("test_dtflint" in fn for fn, _ln, _name in frames)
    d = threading.Thread(target=lambda: None, daemon=True)
    d.start()
    d.join()
    assert not hasattr(d, "_dtf_started_at")
