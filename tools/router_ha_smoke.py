#!/usr/bin/env python
"""CI router-HA smoke: crash-exact takeover via request journal,
fenced leader lease, and in-flight re-adoption, driven through REAL
replica subprocesses (ci_check.sh stage 16).

Four stages, every assertion fatal (nonzero exit):

  1. BASELINE — an unfaulted router over 2 replica processes completes
     a burst; the per-request greedy tokens become the oracle.  A
     router death must move CONTROL, not meaning: any takeover must
     reproduce these tokens exactly.
  2. LEADER KILL — a journaling leader (epoch 1, fenced lease) dies
     via chaos ``router_kill@req:5`` mid-burst: dispatches in flight,
     requests still queued, journal tail un-synced.  The engines keep
     decoding into their retained tails while the warm standby waits
     out the lease ttl, acquires epoch 2, adopts the live tier
     (``adopt=True`` — no respawns) and replays the journal.  Bars:
     ZERO lost requests, ZERO replica respawns (same engine pids
     before and after), every client stream exactly-once token-exact
     vs baseline (acknowledged prefix + resumed tail, no token twice),
     and the trace allows only the injected fault.
  3. SPLIT BRAIN — an epoch-3 usurper force-takes the lease while the
     epoch-2 leader still runs.  Bars: the replicas reject the stale
     leader's ops (``stale_epoch``), the deposed router latches fenced
     (health not ok, submits refused), and the new leader serves
     token-exact — the race costs the old leader, never a stream.
  4. LEASE STALL — chaos ``lease_stall@4`` drops the leader's renewal
     writes (the deterministic GC-pause stand-in): the lease ages out,
     a standby acquires epoch+1, and the stalled leader's keeper
     fences it the moment it wakes up.

The router "SIGKILL" is the chaos crash hook freezing the router
in-process — loops stopped, sockets severed, nothing resolved, exactly
the state a killed process leaves behind — so this process can keep
acting as the surviving clients.  (The mid-rollout takeover resume is
pinned tier-1 in tests/test_router_ha.py + tests/test_rollout.py.)

Usage: python tools/router_ha_smoke.py [--keep DIR]
"""

from __future__ import annotations

import argparse
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
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
LEASE_TTL = 1.0


def make_prompts():
    """Shared-prefix burst: 2 'system prompts' of 2 full pages each,
    per-request tails — every chain distinct and page-crossing."""
    rng = np.random.default_rng(42)
    groups = [rng.integers(0, 64, (2 * PAGE,)).astype(np.int32)
              for _ in range(2)]
    prompts = []
    for i in range(REQUESTS):
        tail = rng.integers(0, 64, (1 + i % 6,)).astype(np.int32)
        prompts.append(np.concatenate([groups[i % 2], tail]))
    return prompts


def build_tier(workdir, *, journal=False, epoch=0, crash_hook=None):
    from dtf_tpu.obs import trace
    from dtf_tpu.serve import journal as journal_mod
    from dtf_tpu.serve.router import Router, replica_spawner
    rendezvous = os.path.join(workdir, "rdv")
    trace_dir = os.path.join(workdir, "trace")
    os.makedirs(trace_dir, exist_ok=True)
    cmd = [sys.executable, "-m", "dtf_tpu.cli.replica_main",
           "--serve_random_init", "--rendezvous_dir", rendezvous,
           *MODEL_FLAGS]
    spawn = replica_spawner(cmd, rendezvous,
                            env_extra={"DTF_TRACE_DIR": trace_dir})
    # health timeout 15s (disagg_smoke rationale): lazy chunk-shape
    # compiles stall the engine heartbeat for seconds on a loaded box
    router = Router(2, rendezvous, spawn=spawn, page_size=PAGE,
                    probe_interval_s=0.25, health_timeout_s=15.0,
                    deadline_s=120.0, replica_inflight=32,
                    respawn_backoff_s=0.2, max_respawns=4,
                    journal_path=(journal_mod.journal_path(rendezvous)
                                  if journal else None),
                    epoch=epoch, crash_hook=crash_hook)
    trace.configure(trace_dir, stream="router")
    t0 = time.time()
    router.start(wait_s=600)
    print(f"  tier up in {time.time() - t0:.1f}s")
    return router, rendezvous, trace_dir


def successor(rendezvous, *, epoch):
    """A standby's router over the SAME live tier: no spawner (a
    takeover must never respawn engines), adopt-start."""
    from dtf_tpu.serve import journal as journal_mod
    from dtf_tpu.serve.router import Router
    router = Router(2, rendezvous, page_size=PAGE,
                    probe_interval_s=0.25, health_timeout_s=15.0,
                    deadline_s=120.0, replica_inflight=32,
                    journal_path=journal_mod.journal_path(rendezvous),
                    epoch=epoch, role="leader")
    router.start(wait_s=60, adopt=True)
    return router


def freeze(router):
    """What a SIGKILL leaves behind, in-process: loops stopped, TCP
    severed mid-stream, nothing resolved, journal tail as-is."""
    with router._mu:
        router._stopping = True
        router._mu.notify_all()
    for rep in router._replicas:
        conn = rep.conn
        if conn is not None:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        router._close_conn(rep)


def collect_stream(handle, out):
    """Client thread: drain one stream until it resolves or goes
    silent (= the router died mid-stream)."""

    def run():
        try:
            for t in handle.stream(timeout=3.0):
                out.append(t)
        except (TimeoutError, RuntimeError):
            pass

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


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


def tier_pids(rendezvous):
    from dtf_tpu.serve.replica import read_announce
    return {rid: (read_announce(rendezvous, rid) or {}).get("pid")
            for rid in range(2)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", default="",
                    help="keep work dirs under this path (debug)")
    args = ap.parse_args()
    root = args.keep or tempfile.mkdtemp(prefix="dtf_router_ha_smoke_")
    os.makedirs(root, exist_ok=True)
    from dtf_tpu import chaos
    from dtf_tpu.obs import trace
    from dtf_tpu.serve import ha
    prompts = make_prompts()

    # -- 1. unfaulted baseline ------------------------------------------
    print("router_ha smoke [1/4]: unfaulted baseline (the token oracle)")
    chaos.disable()
    router, rdv, tdir = build_tier(os.path.join(root, "baseline"))
    handles = [router.submit(p, max_new_tokens=BUDGET) for p in prompts]
    oracle = [h.result(timeout=150).tokens for h in handles]
    router.stop(drain=True)
    trace.disable()
    check_trace(tdir, allow=())
    print(f"  oracle OK: {len(oracle)} requests")

    # -- 2. leader killed mid-burst → standby takeover ------------------
    print(f"router_ha smoke [2/4]: router_kill@req:5 mid-burst, "
          f"standby takeover (lease ttl {LEASE_TTL}s)")
    workdir = os.path.join(root, "takeover")
    crashed = threading.Event()
    router1, rdv, tdir = build_tier(workdir, journal=True, epoch=0,
                                    crash_hook=crashed.set)
    lease1 = ha.LeaderLease(rdv, ttl_s=LEASE_TTL, holder="leader")
    epoch1 = lease1.acquire()
    if epoch1 != 1:
        raise SystemExit(f"leader lease acquire returned {epoch1}")
    router1.epoch = epoch1
    keeper1 = ha.LeaseKeeper(lease1, on_fenced=router1.fence).start()
    pids_before = tier_pids(rdv)

    # the crash watcher IS the kill: the hook fires inside the
    # dispatch loop (under the router lock), so the freeze runs here
    def crash_watch():
        crashed.wait()
        freeze(router1)
        keeper1.stop()      # a dead process renews nothing

    watcher = threading.Thread(target=crash_watch, daemon=True)
    watcher.start()

    chaos.configure("router_kill@req:5", rank=0)
    handles = [router1.submit(p, max_new_tokens=BUDGET) for p in prompts]
    got = [[] for _ in prompts]
    streams = [collect_stream(h, g) for h, g in zip(handles, got)]
    if not crashed.wait(timeout=150):
        raise SystemExit("router_kill@req:5 never fired")
    t_kill = time.time()
    watcher.join(timeout=30)
    for s in streams:
        s.join(timeout=30)          # drain everything delivered pre-kill
    delivered = {h.request.id: list(g) for h, g in zip(handles, got)}
    resolved_pre = {h.request.id: h.result(timeout=0.001).tokens
                    for h in handles if h.done() and h._exc is None}
    print(f"  leader dead; {sum(map(len, got))} tokens delivered, "
          f"{len(resolved_pre)} requests fully resolved pre-kill")

    lease2 = ha.LeaderLease(rdv, ttl_s=LEASE_TTL, holder="standby")
    epoch2 = ha.wait_for_takeover(lease2, poll_s=0.1, timeout_s=60.0)
    if epoch2 != 2:
        raise SystemExit(f"standby takeover acquired epoch {epoch2}, "
                         f"want 2")
    router2 = successor(rdv, epoch=epoch2)
    summary = ha.take_over(router2, delivered=delivered)
    t_takeover = time.time() - t_kill
    print(f"  takeover in {t_takeover:.2f}s: "
          f"readopted={summary['readopted']} "
          f"redispatched={summary['redispatched']}")
    unresolved = set(summary["handles"]) | set(resolved_pre)
    if unresolved != {h.request.id for h in handles}:
        raise SystemExit(
            f"takeover lost requests: baseline ids "
            f"{sorted(h.request.id for h in handles)}, recovered "
            f"{sorted(unresolved)} — zero lost is the bar")
    for h, want in zip(handles, oracle):
        rid = h.request.id
        if rid in resolved_pre:
            if resolved_pre[rid] != want:
                raise SystemExit(f"request {rid}: pre-kill result "
                                 f"diverged from baseline")
            continue
        nh = summary["handles"][rid]
        tail = list(nh.stream(timeout=150.0))
        if delivered[rid] + tail != want:
            raise SystemExit(
                f"request {rid} NOT exactly-once token-exact across "
                f"the takeover:\n  want {want}\n  got  "
                f"{delivered[rid]} + {tail}")
        res = nh.result(timeout=30)
        if res.tokens != want or res.diverged:
            raise SystemExit(f"request {rid}: adopted result diverged "
                             f"(diverged={res.diverged})")
    respawns = router2.metrics.get("router_replica_respawns_total").value
    if respawns:
        raise SystemExit(f"takeover respawned {respawns} replica(s) — "
                         f"a router blip must not cold-start engines")
    pids_after = tier_pids(rdv)
    if pids_after != pids_before:
        raise SystemExit(f"engine pids changed across takeover: "
                         f"{pids_before} -> {pids_after}")
    if router2.metrics.get("router_takeover_total").value != 1:
        raise SystemExit("router_takeover_total != 1 on the successor")
    chaos.disable()
    print(f"  takeover OK: 0 lost, 0 respawns, exactly-once "
          f"token-exact, pids stable")

    # -- 3. split brain: the deposed leader is fenced at the replicas --
    print("router_ha smoke [3/4]: split brain (epoch-3 usurper vs the "
          "epoch-2 leader)")
    tdir3 = os.path.join(root, "splitbrain", "trace")
    os.makedirs(tdir3, exist_ok=True)
    trace.flush()   # seal stage-2's stream before re-pointing
    trace.configure(tdir3, stream="router")
    lease3 = ha.LeaderLease(rdv, ttl_s=LEASE_TTL, holder="usurper")
    epoch3 = lease3.acquire(force=True)
    if epoch3 != 3:
        raise SystemExit(f"force-acquire returned epoch {epoch3}")
    router3 = successor(rdv, epoch=epoch3)
    r = router3.generate(prompts[0], max_new_tokens=BUDGET)
    if r.tokens != oracle[0]:
        raise SystemExit("usurper's first request diverged")
    # the deposed epoch-2 leader keeps driving: replicas reject it
    try:
        router2.submit(prompts[1],
                       max_new_tokens=BUDGET).result(timeout=30)
        raise SystemExit("deposed leader's submit SUCCEEDED — replicas "
                         "accepted a stale epoch")
    except RuntimeError:
        pass
    deadline = time.time() + 15
    while time.time() < deadline and not router2.health()["fenced"]:
        time.sleep(0.1)
    h2 = router2.health()
    if not h2["fenced"] or h2["ok"]:
        raise SystemExit(f"deposed leader never latched fenced: {h2}")
    if router2.metrics.get("router_stale_epoch_total").value < 1:
        raise SystemExit("no stale_epoch rejection counted")
    # the real leader is untouched by the split-brain attempt
    r = router3.generate(prompts[2], max_new_tokens=BUDGET)
    if r.tokens != oracle[2]:
        raise SystemExit("leader diverged after the split-brain race")
    print(f"  split brain OK: stale epoch rejected, deposed leader "
          f"fenced, streams exact")

    # -- 4. lease stall: renewals drop, the keeper fences the leader ---
    print("router_ha smoke [4/4]: lease_stall@4 (renewal writes drop)")
    chaos.configure("lease_stall@4", rank=0)
    keeper3 = ha.LeaseKeeper(lease3, on_fenced=router3.fence).start()
    lease4 = ha.LeaderLease(rdv, ttl_s=LEASE_TTL, holder="standby2")
    epoch4 = ha.wait_for_takeover(lease4, poll_s=0.1, timeout_s=60.0)
    if epoch4 != 4:
        raise SystemExit(f"post-stall takeover acquired {epoch4}, want 4")
    deadline = time.time() + 30
    while time.time() < deadline and not router3.health()["fenced"]:
        time.sleep(0.1)
    if not router3.health()["fenced"]:
        raise SystemExit("stalled leader's keeper never fenced it")
    keeper3.stop()
    chaos.disable()
    print("  lease stall OK: standby acquired epoch 4, stalled leader "
          "fenced by its keeper")

    router3.stop(drain=True)
    router2.stop(drain=False)
    router1.stop(drain=False)   # owns the engine processes: ends the tier
    trace.disable()
    # the replica processes' DTF_TRACE_DIR is pinned at spawn, so the
    # stage-3/4 stale-epoch rejections they emit land in the stage-2
    # dir; the router-side fencing + lease_stall fault land in tdir3
    check_trace(tdir, allow=("injected_fault", "stale_epoch"))
    check_trace(tdir3, allow=("injected_fault", "router_fenced"))

    if not args.keep:
        shutil.rmtree(root, ignore_errors=True)
    print(f"router_ha smoke: OK (time-to-takeover {t_takeover:.2f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
