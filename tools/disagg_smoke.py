#!/usr/bin/env python
"""CI disaggregated-serving smoke: the prefill/decode pool-split
contract, driven through REAL replica subprocesses (ci_check.sh
stage 15).

Four stages, every assertion fatal (nonzero exit):

  1. BASELINE — a COLOCATED router over 2 replica processes completes
     two phases of shared-prefix traffic (cold burst, then exact
     repeats); the per-request greedy tokens become the oracle.
     Migration must move BITS, not meaning: any disaggregated tier
     must reproduce these tokens exactly.
  2. DISAGGREGATED — the same tier with --router_prefill_replicas 1:
     cold prompts land on the prefill pool (replica 0), finished
     chains migrate their KV pages over the wire (page_fetch /
     page_push), and the EXACT repeats re-home to the decode pool
     (replica 1) where the migrated pages serve as prefix hits.
     Bars: token-exact both phases, >= 1 chain migrated with zero
     failures, every repeat served by the decode pool, zero lost,
     `trace_main --check` clean (a successful migration is an event,
     never an anomaly).
  3. replica_kill@req:N — a PREFILL replica is SIGKILLed mid-burst
     holding in-flight work and chains mid-migration.  Bars: every
     accepted request completes TOKEN-EXACT vs baseline (the router
     fails over to the decode pool — role preference is a preference,
     not a partition), zero lost, the replica respawns, and the trace
     allows only the injected fault + the router's reaction
     (replica_lost, migration_failed: a kill mid-transfer fails that
     migration LOUDLY but costs no request).
  4. page_fetch_stall@replica1:S — the decode replica's migration
     client stalls before every fetch window (a congested fabric).
     Bars: token-exact, zero lost, chains STILL migrate (slow wire =
     efficiency loss, never a correctness event).

Usage: python tools/disagg_smoke.py [--keep DIR]
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

MODEL_FLAGS = [
    "--model", "transformer_small", "--num_classes", "64",
    "--serve_max_seq_len", "48", "--serve_max_batch", "4",
    "--serve_queue_size", "32", "--heartbeat_secs", "0.2",
    "--kv_page_size", "16", "--kv_pool_pages", "25",
    "--seed", "7",
]
PAGE = 16
BUDGET = 8
REQUESTS = 8


def make_prompts():
    """Shared-prefix cold burst: 2 'system prompts' of 2 full pages
    each, per-request tails — every chain distinct, every chain
    crossing page boundaries (pages must actually migrate)."""
    rng = np.random.default_rng(42)
    groups = [rng.integers(0, 64, (2 * PAGE,)).astype(np.int32)
              for _ in range(2)]
    prompts = []
    for i in range(REQUESTS):
        tail = rng.integers(0, 64, (1 + i % 6,)).astype(np.int32)
        prompts.append(np.concatenate([groups[i % 2], tail]))
    return prompts


def build_tier(workdir, *, prefill_replicas=0, fault_env=None,
               deadline_s=120.0):
    from dtf_tpu.serve.router import Router, replica_spawner
    rendezvous = os.path.join(workdir, "rdv")
    trace_dir = os.path.join(workdir, "trace")
    os.makedirs(trace_dir, exist_ok=True)
    cmd = [sys.executable, "-m", "dtf_tpu.cli.replica_main",
           "--serve_random_init", "--rendezvous_dir", rendezvous,
           *MODEL_FLAGS]
    env_extra = {"DTF_TRACE_DIR": trace_dir}
    if fault_env:
        env_extra["DTF_FAULT"] = fault_env
    spawn = replica_spawner(cmd, rendezvous, env_extra=env_extra)
    # health timeout 15s, not router_smoke's 5s: lazy chunk-shape
    # compiles stall the engine loop (and so its heartbeat) for ~5s on
    # a loaded CPU box, and a false replica_lost would dirty the
    # BASELINE trace.  The kill arm doesn't care — a SIGKILL drops the
    # wire connection, which the router notices immediately.
    router = Router(2, rendezvous, spawn=spawn, page_size=PAGE,
                    probe_interval_s=0.25, health_timeout_s=15.0,
                    deadline_s=deadline_s, replica_inflight=32,
                    respawn_backoff_s=0.2, max_respawns=4,
                    prefill_replicas=prefill_replicas,
                    migrate_timeout_s=60.0)
    from dtf_tpu.obs import trace
    trace.configure(trace_dir, stream="router")
    t0 = time.time()
    router.start(wait_s=600)
    print(f"  tier up in {time.time() - t0:.1f}s")
    return router, trace_dir


def run_traffic(router, prompts):
    from dtf_tpu.serve import Backpressure, DeadlineExceeded
    handles = [router.submit(p, max_new_tokens=BUDGET) for p in prompts]
    results, lost = [], 0
    for h in handles:
        try:
            results.append(h.result(timeout=router.deadline_s + 30))
        except (Backpressure, DeadlineExceeded) as e:
            results.append(e)
            lost += 1
    return results, lost


def wait_migrations(router, want, timeout_s=90.0):
    """Poll until >= ``want`` chains migrated and none are pending.
    Returns the final stats; the CALLER judges failures (a kill arm
    expects some)."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        ms = router.migration_stats()
        if ms["migrated"] >= want and ms["pending"] == 0:
            return ms
        time.sleep(0.25)
    return router.migration_stats()


def teardown(router, trace_dir):
    from dtf_tpu.obs import trace
    router.stop(drain=True)
    trace.disable()


def check_trace(trace_dir, allow=()):
    cmd = [sys.executable, "-m", "dtf_tpu.cli.trace_main", trace_dir,
           "--check"]
    for kind in allow:
        cmd += ["--allow", kind]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          cwd=REPO, timeout=120)
    if proc.returncode != 0:
        print(proc.stdout[-3000:], file=sys.stderr)
        print(proc.stderr[-2000:], file=sys.stderr)
        raise SystemExit(
            f"trace check FAILED for {trace_dir} (allow={allow})")


def assert_exact(results, oracle, stage):
    for i, (got, want) in enumerate(zip(results, oracle)):
        if isinstance(got, Exception):
            raise SystemExit(f"{stage}: request {i} was LOST "
                             f"({got!r}) — zero lost is the bar")
        if got.tokens != want:
            raise SystemExit(
                f"{stage}: request {i} diverged from the colocated "
                f"oracle\n  want {want}\n  got  {got.tokens} "
                f"(replica {got.replica})")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", default="",
                    help="keep work dirs under this path (debug)")
    args = ap.parse_args()
    root = args.keep or tempfile.mkdtemp(prefix="dtf_disagg_smoke_")
    os.makedirs(root, exist_ok=True)
    from dtf_tpu import chaos
    prompts = make_prompts()

    # -- 1. colocated oracle --------------------------------------------
    print("disagg smoke [1/4]: colocated baseline (the token oracle)")
    chaos.disable()
    router, tdir = build_tier(os.path.join(root, "colocated"))
    cold, lost = run_traffic(router, prompts)
    warm, lost2 = run_traffic(router, prompts)
    teardown(router, tdir)
    if lost or lost2:
        raise SystemExit("baseline: requests lost on a healthy "
                         "colocated tier")
    check_trace(tdir, allow=())
    oracle_cold = [r.tokens for r in cold]
    oracle_warm = [r.tokens for r in warm]
    if oracle_cold != oracle_warm:
        raise SystemExit("baseline: colocated repeats diverged — "
                         "greedy decode is not deterministic here?")
    print(f"  oracle OK: {len(oracle_cold)} requests")

    # -- 2. disaggregated tier ------------------------------------------
    print("disagg smoke [2/4]: disaggregated 1p:1d tier (migrate + "
          "re-home)")
    router, tdir = build_tier(os.path.join(root, "disagg"),
                              prefill_replicas=1)
    cold, lost = run_traffic(router, prompts)
    assert_exact(cold, oracle_cold, "disagg/cold")
    if any(r.replica != 0 for r in cold):
        raise SystemExit(
            f"disagg: cold prompts leaked past the prefill pool "
            f"(replicas {[r.replica for r in cold]})")
    ms = wait_migrations(router, want=1)
    if ms["migrated"] < 1 or ms["failed"] or ms["pending"]:
        raise SystemExit(f"disagg: migration never settled ({ms})")
    warm, lost2 = run_traffic(router, prompts)
    assert_exact(warm, oracle_warm, "disagg/warm")
    if lost or lost2:
        raise SystemExit("disagg: requests lost")
    off_pool = [r.replica for r in warm if r.replica == 0]
    if off_pool:
        raise SystemExit(
            f"disagg: {len(off_pool)} repeats served by the PREFILL "
            f"pool — re-homing never landed")
    teardown(router, tdir)
    check_trace(tdir, allow=())
    print(f"  disagg OK: token-exact, {ms['migrated']} chains "
          f"migrated, 0 failed, repeats on the decode pool")

    # -- 3. kill a prefill replica mid-burst ----------------------------
    print("disagg smoke [3/4]: replica_kill@req:4 on the prefill pool")
    chaos.configure("replica_kill@req:4", rank=0)
    router, tdir = build_tier(os.path.join(root, "kill"),
                              prefill_replicas=1)
    cold, lost = run_traffic(router, prompts)
    assert_exact(cold, oracle_cold, "kill/cold")
    if lost:
        raise SystemExit(f"kill: {lost} requests lost")
    failovers = router.metrics.get("router_failover_total").value
    if failovers < 1:
        raise SystemExit("kill: the SIGKILL stranded nothing — the "
                         "fault never fired?")
    deadline = time.time() + 300
    while time.time() < deadline and not all(
            router.replica_healthy(i) for i in range(2)):
        time.sleep(0.25)
    if not all(router.replica_healthy(i) for i in range(2)):
        raise SystemExit("kill: the prefill replica never respawned")
    warm, lost2 = run_traffic(router, prompts)
    assert_exact(warm, oracle_warm, "kill/warm")
    if lost2:
        raise SystemExit("kill: post-respawn repeats lost requests")
    teardown(router, tdir)
    chaos.disable()
    check_trace(tdir, allow=("injected_fault", "replica_lost",
                             "migration_failed"))
    print(f"  kill OK: token-exact, 0 lost, failovers={failovers}, "
          f"prefill replica respawned")

    # -- 4. stalled migration fabric ------------------------------------
    print("disagg smoke [4/4]: page_fetch_stall@replica1:0.05 "
          "(congested wire)")
    router, tdir = build_tier(os.path.join(root, "stall"),
                              prefill_replicas=1,
                              fault_env="page_fetch_stall@replica1:0.05")
    cold, lost = run_traffic(router, prompts)
    assert_exact(cold, oracle_cold, "stall/cold")
    ms = wait_migrations(router, want=1)
    if ms["migrated"] < 1 or ms["pending"]:
        raise SystemExit(f"stall: chains stopped migrating under a "
                         f"slow fabric ({ms}) — a stall is an "
                         f"efficiency loss, not a correctness event")
    warm, lost2 = run_traffic(router, prompts)
    assert_exact(warm, oracle_warm, "stall/warm")
    if lost or lost2:
        raise SystemExit("stall: requests lost")
    teardown(router, tdir)
    check_trace(tdir, allow=("injected_fault",))
    print(f"  stall OK: token-exact, {ms['migrated']} chains migrated "
          f"through the stalled fabric")

    if not args.keep:
        shutil.rmtree(root, ignore_errors=True)
    print("disagg smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
