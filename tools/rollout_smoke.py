#!/usr/bin/env python
"""CI rollout smoke: the zero-downtime model-rollout contract, driven
through REAL replica subprocesses serving REAL exported checkpoints
(ci_check.sh stage 11).

One tier, five stages, every assertion fatal (nonzero exit):

  1. CHECKPOINTS + BASELINE — three exported artifacts from one
     deterministic param set: A (the incumbent), B (a re-exported
     numerically-IDENTICAL copy — the token-exact rollout target), and
     C (a perturbed copy — a genuinely different model the canary gate
     must catch).  A 2-replica tier serves A; a shared-prefix burst's
     greedy tokens become the oracle.
  2. IDENTICAL ROLLOUT — mid-traffic rollout A→B.  Bars: final phase
     DONE, ZERO requests shed or lost, every request token-exact vs
     the baseline, zero mixed-model streams, both replicas healthy on
     the new checkpoint, and the prefix-affinity machinery still
     producing registry hits AFTER the rollout (owner-map handoff: a
     rollout must not go affinity-cold).
  3. GATED ROLLBACK — rollout B→C.  The canary compares mirrored live
     greedy traffic token-by-token, sees divergence, and auto-rolls-
     back.  Bars: phase ROLLED_BACK with a canary_divergence reason,
     >= 1 divergence recorded, zero lost, fleet token-exact on the OLD
     model, persisted state agrees.
  4. rollout_kill@phase:rolling — a replica SIGKILLed mid-rollout
     (after the gate passed).  Bars: phase ROLLED_BACK, zero lost,
     token-exact on the old model.
  5. ckpt_truncate vs the NEW checkpoint — the rollout target loses a
     payload file before the canary restart; the canary process cannot
     restore and the rollout rolls back.  Bars: phase ROLLED_BACK
     (canary_start_failed), zero lost, token-exact on the old model.
     `trace_main --check` with the rollout allowlist is green at the
     end — the run contained the injected faults + the rollouts'
     reactions and nothing else.

Usage: python tools/rollout_smoke.py [--keep DIR]
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

VOCAB = 64
PAGE = 16
BUDGET = 8
MODEL_FLAGS = [
    "--model", "transformer_small", "--num_classes", str(VOCAB),
    "--serve_max_seq_len", "48", "--serve_max_batch", "4",
    "--serve_queue_size", "32", "--heartbeat_secs", "0.2",
    "--seed", "7",
]


def build_checkpoints(root):
    """A (incumbent), B (identical re-export), C (perturbed)."""
    import jax
    import jax.numpy as jnp

    from dtf_tpu.models import build_model
    from dtf_tpu.train.checkpoint import export_model

    model, _ = build_model("transformer_small", num_classes=VOCAB)
    params = model.init(jax.random.key(7),
                        jnp.zeros((1, 48), jnp.int32))["params"]
    a, b, c = (os.path.join(root, d) for d in ("ckpt_a", "ckpt_b",
                                               "ckpt_c"))
    state = types.SimpleNamespace(params=params, batch_stats={})
    export_model(a, state)
    export_model(b, state)   # numerically identical, separate artifact
    # a genuinely different model: an independent init.  (NOT a global
    # sign flip — negating every weight turns out to be an exact
    # symmetry of the residual/LN stack, and greedy argmax survives
    # it: the first draft of this smoke proved that the hard way.)
    other = model.init(jax.random.key(1234),
                       jnp.zeros((1, 48), jnp.int32))["params"]
    export_model(c, types.SimpleNamespace(params=other,
                                          batch_stats={}))
    return a, b, c


def make_prompts():
    rng = np.random.default_rng(42)
    groups = [rng.integers(0, VOCAB, (2 * PAGE,)).astype(np.int32)
              for _ in range(2)]
    prompts = []
    for i in range(10):
        tail = rng.integers(0, VOCAB, (1 + i % 6,)).astype(np.int32)
        prompts.append(np.concatenate([groups[i % 2], tail]))
    return prompts


def build_tier(workdir, ckpt, trace_dir):
    from dtf_tpu.obs import trace
    from dtf_tpu.serve.router import Router, replica_spawner

    rendezvous = os.path.join(workdir, "rdv")
    cmd = [sys.executable, "-m", "dtf_tpu.cli.replica_main",
           "--rendezvous_dir", rendezvous, "--export_dir", ckpt,
           *MODEL_FLAGS]
    ckpt_map: dict = {}
    spawn = replica_spawner(cmd, rendezvous,
                            env_extra={"DTF_TRACE_DIR": trace_dir},
                            checkpoint_map=ckpt_map)
    router = Router(2, rendezvous, spawn=spawn, page_size=PAGE,
                    probe_interval_s=0.25, health_timeout_s=5.0,
                    deadline_s=180.0, replica_inflight=32,
                    respawn_backoff_s=0.2, max_respawns=4,
                    checkpoint_map=ckpt_map)
    trace.configure(trace_dir, stream="router")
    t0 = time.time()
    router.start(wait_s=600)
    print(f"  tier up in {time.time() - t0:.1f}s")
    return router


class Pump:
    """Continuous traffic through a rollout; resolves everything at
    exit — the zero-shed / zero-lost / token-exact ledger."""

    def __init__(self, router, prompts, interval=0.15):
        self.router = router
        self.prompts = prompts
        self.interval = interval
        self.handles = []
        self.shed = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        from dtf_tpu.serve.engine import Backpressure
        i = 0
        while not self._stop.wait(self.interval):
            p = self.prompts[i % len(self.prompts)]
            try:
                self.handles.append(
                    (i % len(self.prompts),
                     self.router.submit(p, max_new_tokens=BUDGET)))
            except Backpressure:
                self.shed += 1
            i += 1

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)

    def check(self, baseline, stage):
        if self.shed == 0 and not self.handles:
            raise SystemExit(f"{stage}: the pump submitted nothing")
        if self.shed:
            raise SystemExit(f"{stage}: {self.shed} requests SHED "
                             f"mid-rollout — zero shed is the bar")
        lost = 0
        for pi, h in self.handles:
            try:
                r = h.result(timeout=240)
            except Exception as e:  # noqa: BLE001
                print(f"  LOST: prompt {pi}: {e!r}", file=sys.stderr)
                lost += 1
                continue
            if r.tokens != baseline[pi]:
                raise SystemExit(
                    f"{stage}: prompt {pi} diverged from baseline\n"
                    f"  want {baseline[pi]}\n  got  {r.tokens} "
                    f"(replica {r.replica}, version {r.version!r})")
        if lost:
            raise SystemExit(f"{stage}: {lost} requests LOST — zero "
                             f"lost is the bar")
        print(f"  {stage}: {len(self.handles)} pumped requests, 0 "
              f"shed, 0 lost, token-exact")


def burst(router, prompts):
    handles = [router.submit(p, max_new_tokens=BUDGET) for p in prompts]
    return [h.result(timeout=240).tokens for h in handles]


def assert_mixed_zero(router, stage):
    mixed = router.metrics.get("router_mixed_model_total").value
    if mixed:
        raise SystemExit(f"{stage}: {mixed} MIXED-MODEL stream(s) — a "
                         f"client stream mixed two checkpoints")


def rollout(router, ckpt, old, **kw):
    from dtf_tpu.serve.rollout import RolloutController
    args = dict(old_checkpoint=old, canary_requests=3,
                mirror_fraction=1.0, warm_timeout_s=600.0,
                drain_timeout_s=120.0, gate_timeout_s=300.0)
    args.update(kw)
    return RolloutController(router, ckpt, **args).run()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", default="",
                    help="keep work dirs under this path (debug)")
    args = ap.parse_args()
    root = args.keep or tempfile.mkdtemp(prefix="dtf_rollout_smoke_")
    os.makedirs(root, exist_ok=True)
    trace_dir = os.path.join(root, "trace")
    os.makedirs(trace_dir, exist_ok=True)
    from dtf_tpu import chaos
    from dtf_tpu.serve.rollout import RolloutState, default_state_path

    print("rollout smoke [1/5]: checkpoints + baseline tier")
    ckpt_a, ckpt_b, ckpt_c = build_checkpoints(root)
    prompts = make_prompts()
    chaos.disable()
    router = build_tier(root, ckpt_a, trace_dir)
    try:
        baseline = burst(router, prompts)
        print(f"  baseline OK: {len(baseline)} requests on ckpt A")

        # -- 2. identical rollout: token-exact, zero shed ------------
        print("rollout smoke [2/5]: mid-traffic rollout A -> B "
              "(identical re-export)")
        hits0 = router.metrics.get("router_affinity_hits_total").value
        with Pump(router, prompts) as pump:
            state = rollout(router, ckpt_b, old=ckpt_a)
        if state.phase != "DONE":
            raise SystemExit(f"identical rollout ended {state.phase} "
                             f"({state.reason}) — expected DONE")
        if state.diverged:
            raise SystemExit(f"identical checkpoints diverged "
                             f"{state.diverged} time(s) — determinism "
                             f"is broken")
        pump.check(baseline, "identical-rollout")
        assert_mixed_zero(router, "identical-rollout")
        persisted = RolloutState.load(
            default_state_path(router.rendezvous_dir))
        if persisted.phase != "DONE":
            raise SystemExit("persisted rollout state does not say DONE")
        # prefix affinity survives the rollout: the same shared-prefix
        # burst, twice — the second pass must hit warm registries (the
        # owner-map handoff keeps groups together through replacement)
        post = burst(router, prompts)
        if post != baseline:
            raise SystemExit("post-rollout burst diverged from baseline")
        burst(router, prompts)
        hits1 = router.metrics.get("router_affinity_hits_total").value
        if hits1 - hits0 < len(prompts):
            raise SystemExit(
                f"affinity went cold through the rollout "
                f"(hits {hits0} -> {hits1})")
        reg_hits = 0
        for rid in range(2):
            stats = router.replica_stats(rid, timeout=10) or {}
            reg_hits += stats.get("serve_prefix_hit_pages_total", 0)
        if reg_hits < 1:
            raise SystemExit("no replica-side prefix-registry hits "
                             "after the rollout — the tier re-prefills "
                             "every shared prompt")
        print(f"  identical rollout OK: DONE, compared="
              f"{state.compared}, affinity hits +{hits1 - hits0}, "
              f"registry hits {reg_hits}")

        # -- 3. divergent rollout: canary gate fires -----------------
        print("rollout smoke [3/5]: rollout B -> C (perturbed) — "
              "canary gate must fire")
        with Pump(router, prompts) as pump:
            state = rollout(router, ckpt_c, old=ckpt_b)
        if state.phase != "ROLLED_BACK":
            raise SystemExit(f"divergent rollout ended {state.phase} — "
                             f"the canary gate never fired")
        if not state.reason.startswith("canary_divergence"):
            raise SystemExit(f"rollback reason {state.reason!r} — "
                             f"expected canary_divergence")
        if state.diverged < 1:
            raise SystemExit("gate fired without a recorded divergence")
        pump.check(baseline, "divergent-rollout")
        assert_mixed_zero(router, "divergent-rollout")
        post = burst(router, prompts)
        if post != baseline:
            raise SystemExit("post-rollback fleet is not token-exact "
                             "on the old model")
        print(f"  gated rollback OK: diverged={state.diverged}, "
              f"first_pos={state.first_divergence_pos}, fleet "
              f"token-exact on old")

        # -- 4. replica kill mid-rollout -----------------------------
        print("rollout smoke [4/5]: rollout_kill@phase:rolling "
              "(SIGKILL mid-rollout)")
        chaos.configure("rollout_kill@phase:rolling", rank=0)
        with Pump(router, prompts) as pump:
            state = rollout(router, ckpt_b, old=ckpt_b)
        chaos.disable()
        if state.phase != "ROLLED_BACK":
            raise SystemExit(f"kill-mid-rollout ended {state.phase} — "
                             f"expected ROLLED_BACK")
        pump.check(baseline, "rollout-kill")
        assert_mixed_zero(router, "rollout-kill")
        post = burst(router, prompts)
        if post != baseline:
            raise SystemExit("post-kill-rollback fleet is not "
                             "token-exact on the old model")
        print(f"  rollout-kill OK: ROLLED_BACK ({state.reason}), zero "
              f"lost, token-exact")

        # -- 5. truncated NEW checkpoint -----------------------------
        print("rollout smoke [5/5]: ckpt_truncate vs the NEW "
              "checkpoint")
        ckpt_d = os.path.join(root, "ckpt_d")
        shutil.copytree(ckpt_b, ckpt_d)
        chaos.configure("ckpt_truncate@latest", rank=0)
        with Pump(router, prompts) as pump:
            state = rollout(router, ckpt_d, old=ckpt_b,
                            warm_timeout_s=120.0)
        chaos.disable()
        if state.phase != "ROLLED_BACK":
            raise SystemExit(f"truncated-ckpt rollout ended "
                             f"{state.phase} — expected ROLLED_BACK")
        if state.reason != "canary_start_failed":
            raise SystemExit(f"rollback reason {state.reason!r} — "
                             f"expected canary_start_failed")
        pump.check(baseline, "ckpt-truncate")
        assert_mixed_zero(router, "ckpt-truncate")
        post = burst(router, prompts)
        if post != baseline:
            raise SystemExit("post-truncate-rollback fleet is not "
                             "token-exact on the old model")
        print("  truncate OK: ROLLED_BACK (canary_start_failed), zero "
              "lost, token-exact")
    finally:
        from dtf_tpu.obs import trace
        router.stop(drain=True)
        trace.disable()

    # trace cleanliness: the injected faults + the rollouts' reactions,
    # nothing else
    cmd = [sys.executable, "-m", "dtf_tpu.cli.trace_main", trace_dir,
           "--check"]
    for kind in ("injected_fault", "rollout_rollback",
                 "canary_divergence", "replica_lost"):
        cmd += ["--allow", kind]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          cwd=REPO, timeout=120)
    if proc.returncode != 0:
        print(proc.stdout[-3000:], file=sys.stderr)
        print(proc.stderr[-2000:], file=sys.stderr)
        raise SystemExit("trace check FAILED — the rollout runs "
                         "contained unexpected anomalies")
    print("  trace check OK")

    if not args.keep:
        shutil.rmtree(root, ignore_errors=True)
    print("rollout smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
