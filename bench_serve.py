"""Serving benchmark: engine scenarios + end-to-end latency.

Prints ONE JSON line per metric, bench.py contract ({"metric", "value",
"unit", "vs_baseline", ...}).  Measured:

  1. engine-level synthetic traffic (burst of varied-length prompts
     through submit/batch/decode/retire) — latency percentiles +
     delivered tokens/s, the serving-SLA view.
  2. MIXED-LENGTH scenario (short decodes + max-length prompts
     admitted mid-flight), chunked and un-chunked prefill, the pool at
     50% of one full reservation per slot.  Records delivered
     tokens/s, the p99 decode-step GAP of running slots (the
     head-of-line-blocking number chunked prefill bounds), peak
     concurrent slots, and the page-pool high-water mark.  Bar:
     chunked p99 gap < un-chunked p99 gap.
  3. SHARED-PREFIX scenario: N concurrent requests over one system
     prompt against a pool too small for N unshared copies, sharing
     on vs off, every handle consumed through its token stream.
     Bars: sharing fits ≥ 2× the concurrent sequences of no-sharing
     at equal page budget; first-streamed-token p50 < full-retire
     p50.
  4. REPLICA TIER (--router_replicas N; 0 skips): real replica
     subprocesses behind the serve/router.py front-end —
       · replica scaling: 1-replica vs N-replica tokens/s under the
         same burst (report-only: this container is core-bound);
       · OVERLOAD DEGRADES, NEVER HANGS: with every replica saturated,
         new submits resolve with Backpressure(retry_after) within a
         bounded time (bar: max time-to-Backpressure < 5 s, zero
         unresolved handles);
       · PREFIX-AFFINE vs RANDOM placement: the same shared-prompt
         traffic, measured by the replicas' own PrefixRegistry hit
         counters (bar: affinity hits > random hits);
       · KILL UNDER LOAD: SIGKILL a replica mid-burst (bar: zero lost
         requests, ≥ 1 failover, every request completes);
       · DISAGGREGATED vs COLOCATED at equal chips: bursty long-prompt
         traffic against a 1p:1d pool split (cold prompts on the
         prefill pool, chains migrating their KV pages over the wire,
         repeats re-homed to the decode pool) vs the same 2 replicas
         colocated (bar: the decode pool's decode-gap p99 STRICTLY
         below colocated — the split must buy the head-of-line tail
         it exists for).

--out writes every metric line into ONE BenchmarkMetric JSON artifact
(BENCH_serve_rNN.json shape) so the serving perf trajectory is tracked
across PRs like training's BENCH_r0N.json files.  The artifact carries
the MFU/cost-ledger gauges for the decode-step executable
(serve_ledger_decode_* — wall, achieved TFLOP/s, MFU/HBM fraction when
the chip's peaks are known), so tools/bench_gate.py gates serve
EFFICIENCY across PRs, not just throughput bars.

Run: python bench_serve.py [--model transformer_small] [--batch 8]
     [--seq 256] [--router_replicas 2] [--out FILE]
"""

import argparse
import datetime
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np


_RECORDS = []      # every metric line, for the --out artifact


def _jline(metric, value, unit, **extra):
    rec = {"metric": metric, "value": round(float(value), 4),
           "unit": unit, "vs_baseline": None, **extra}
    _RECORDS.append(rec)
    print(json.dumps(rec))


def write_artifact(path, model, bars):
    """The BENCH_serve artifact: every metric line of this run plus the
    bar verdicts, one JSON file — the serving perf trajectory's unit
    of comparison across PRs (BENCH_r0N.json's serving sibling)."""
    devices = jax.devices()
    payload = {
        "bench": "bench_serve",
        "run_date": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
        "model": model,
        "device_kind": devices[0].device_kind if devices else "unknown",
        "platform": devices[0].platform if devices else "unknown",
        "bars_failed": bars,
        "metrics": _RECORDS,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"# wrote {path} ({len(_RECORDS)} metrics, "
          f"{len(bars)} failed bars)")


# shared-prefix scenario shape, single-sourced: the pool sizing in
# main() (and tools/serve_smoke.py) must agree with the traffic the
# scenario generates, or the >=2x concurrency bar measures a wrong
# page budget
PREFIX_TAIL_LEN = 8        # per-request tokens after the system prompt
PREFIX_BUDGET = 24         # per-request max_new_tokens


def prefix_pool_pages(batch: int, sys_pages: int, page_size: int) -> int:
    """Total pool pages (incl. scratch) sized so ONE full prompt copy
    plus per-request tails fit, but `batch` unshared copies cannot."""
    tail_pages = (-(-(sys_pages * page_size + PREFIX_TAIL_LEN
                      + PREFIX_BUDGET) // page_size) - sys_pages)
    return 1 + (sys_pages + tail_pages) + (batch - 1) * tail_pages


def mixed_scenario(model, params, *, batch: int, seq: int, requests: int,
                   kv_page_size, kv_pool_pages, prefill_chunk,
                   label: str, n_long: int = 3):
    """Short decodes + ``n_long`` max-length prompts admitted
    mid-flight (staggered).  Several longs, not one: a single
    whole-prompt prefill is one outlier among ~100 gap samples and
    hides BELOW p99 by arithmetic — recurring long prompts are both
    the realistic long-context traffic and the shape where p99
    actually reflects the blocking.

    Returns the decode-gap snapshot."""
    from dtf_tpu.serve import ServeEngine, collect_stats
    eng = ServeEngine(model, params, max_batch=batch, max_seq_len=seq,
                      max_delay_s=0.0, queue_size=max(64, 2 * requests),
                      kv_page_size=kv_page_size,
                      kv_pool_pages=kv_pool_pages,
                      prefill_chunk=prefill_chunk)
    rng = np.random.default_rng(2)
    long_len = seq - 8
    # warmup: compile every shape the measured traffic will hit (short
    # first-chunk, long first/continuation chunks, decode step) — a
    # production engine warms at startup, so compile must not masquerade
    # as head-of-line blocking in the measured gap distribution
    warm = [eng.submit(rng.integers(0, model.vocab_size, (n,)).astype(
        np.int32), max_new_tokens=2) for n in (8, long_len)]
    for h in warm:
        h.result(timeout=600)
    n_warm = eng.reset_measurement()
    t0 = time.time()
    handles = []
    for _ in range(requests):
        plen = int(rng.integers(4, 17))
        handles.append(eng.submit(
            rng.integers(0, model.vocab_size, (plen,)).astype(np.int32),
            max_new_tokens=48))
    # let the short requests admit and reach steady-state decode, THEN
    # drop the max-length prompts on them — the head-of-line case
    time.sleep(0.3)
    for _ in range(n_long):
        handles.append(eng.submit(
            rng.integers(0, model.vocab_size,
                         (long_len,)).astype(np.int32),
            max_new_tokens=8))
        time.sleep(0.2)
    for h in handles:
        h.result(timeout=600)
    wall = time.time() - t0
    stats = collect_stats(eng.completed[n_warm:], eng.shed_count,
                          wall_time_s=wall)
    gap = eng.metrics.get("serve_decode_gap_s").snapshot()
    maxc = eng.max_concurrent
    high = eng.pool.high_water
    eng.stop()
    _jline(f"serve_mixed_tokens_per_s_{label}", stats.tokens_per_s,
           "tokens/s", requests=stats.num_requests, long_prompt=long_len)
    _jline(f"serve_mixed_decode_gap_p99_{label}", gap["p99"], "s",
           mean=round(gap["mean"], 5), samples=gap["count"])
    _jline(f"serve_mixed_max_concurrent_{label}", maxc, "slots")
    _jline(f"serve_kv_pages_high_water_{label}", high, "pages",
           pool_usable=eng.pool.usable_pages, page_size=eng.page_size)
    return gap


def shared_prefix_scenario(model, params, *, batch: int, seq: int,
                           requests: int, kv_page_size: int,
                           kv_pool_pages: int, sys_pages: int,
                           prefix_sharing: bool, label: str):
    """N concurrent requests sharing one system prompt, against a pool
    deliberately too small to hold N unshared copies.

    The warm request writes + registers the system prefix (sharing
    arm) and compiles every shape; the measured burst then admits with
    ``sys_pages`` of each prompt shared — so concurrency is bounded by
    the per-request TAIL pages, not the full prompt.  Every handle is
    consumed through its token STREAM by a client thread, recording
    first-streamed-token latency next to full-retire latency — the
    streaming win is the gap between those two columns.

    Returns (stats, max_concurrent, high_water, ttft_stream_p50,
    full_latency_p50)."""
    import concurrent.futures as cf
    import threading

    from dtf_tpu.serve import ServeEngine, collect_stats
    eng = ServeEngine(model, params, max_batch=batch, max_seq_len=seq,
                      max_delay_s=0.0, queue_size=max(64, 2 * requests),
                      kv_page_size=kv_page_size,
                      kv_pool_pages=kv_pool_pages,
                      prefix_sharing=prefix_sharing)
    ps = kv_page_size
    rng = np.random.default_rng(5)
    sys_prompt = rng.integers(0, model.vocab_size,
                              (sys_pages * ps,)).astype(np.int32)
    budget = PREFIX_BUDGET
    # warm: registers the system prefix (sharing arm) and compiles the
    # prefill/decode shapes for both arms
    eng.submit(sys_prompt, max_new_tokens=2).result(timeout=600)
    n_warm = eng.reset_measurement()
    first_times = {}
    lock = threading.Lock()

    def _consume(rid, handle, t_submit):
        for _ in handle.stream(timeout=600):
            with lock:
                if rid not in first_times:
                    first_times[rid] = time.perf_counter() - t_submit

    t0 = time.time()
    handles = []
    with cf.ThreadPoolExecutor(max_workers=requests) as ex:
        consumers = []
        for r in range(requests):
            tail = rng.integers(0, model.vocab_size,
                                (PREFIX_TAIL_LEN,)).astype(np.int32)
            h = eng.submit(np.concatenate([sys_prompt, tail]),
                           max_new_tokens=budget)
            handles.append(h)
            consumers.append(ex.submit(_consume, r, h,
                                       time.perf_counter()))
        results = [h.result(timeout=600) for h in handles]
        for c in consumers:
            c.result()       # propagate consumer-thread failures loudly
    wall = time.time() - t0
    stats = collect_stats(eng.completed[n_warm:], eng.shed_count,
                          wall_time_s=wall)
    maxc = eng.max_concurrent
    high = eng.pool.high_water
    hits = eng.metrics.get("serve_prefix_hit_pages_total").value
    eng.stop()
    lat = sorted(r.latency_s for r in results)
    ttft = sorted(first_times.values())
    if not ttft:
        # a 0.0 default would pass the ttft < full-retire bar VACUOUSLY
        raise SystemExit(
            f"shared-prefix scenario ({label}): no first-token times "
            f"recorded — the streaming path produced no tokens")
    ttft_p50 = ttft[len(ttft) // 2]
    full_p50 = lat[len(lat) // 2]
    _jline(f"serve_prefix_tokens_per_s_{label}", stats.tokens_per_s,
           "tokens/s", requests=stats.num_requests)
    _jline(f"serve_prefix_max_concurrent_{label}", maxc, "slots",
           pool_usable=kv_pool_pages - 1, sys_pages=sys_pages)
    _jline(f"serve_prefix_pages_high_water_{label}", high, "pages",
           shared_hit_pages=hits)
    _jline(f"serve_stream_ttft_p50_{label}", ttft_p50, "s",
           full_retire_p50=round(full_p50, 4), budget_tokens=budget)
    return stats, maxc, high, ttft_p50, full_p50


# ---------------------------------------------------------------------------
# replica tier (serve/router.py over real replica subprocesses)
# ---------------------------------------------------------------------------

ROUTER_SEED = 11
# the replica-tier scenarios pin their OWN model (replicas need seeded
# identical params; the in-process --model arg never reaches them) —
# every router_* metric line carries this so the --out artifact cannot
# mislabel them with args.model
ROUTER_MODEL = "transformer_small"
ROUTER_REPLICA_FLAGS = [
    "--serve_random_init", "--model", ROUTER_MODEL,
    "--num_classes", "256", "--serve_max_seq_len", "128",
    "--serve_max_batch", "4", "--serve_queue_size", "16",
    "--heartbeat_secs", "0.2", "--seed", str(ROUTER_SEED),
]


def router_tier(workdir, n, *, placement="affinity", admission=128,
                deadline_s=120.0, inflight=4, replica_flags=(),
                prefill_replicas=0, health_timeout=5.0):
    # inflight defaults to the replica SLOT count: bursts queue at the
    # ROUTER and trickle into replicas at their concurrency, so a
    # healthy-tier scenario never trips replica-level sheds.  The
    # overload scenario overrides it UP — and shrinks the replica
    # queue — precisely to trip them.
    from dtf_tpu.serve.router import Router, replica_spawner
    rdv = os.path.join(workdir, "rdv")
    cmd = [sys.executable, "-m", "dtf_tpu.cli.replica_main",
           "--rendezvous_dir", rdv, *ROUTER_REPLICA_FLAGS,
           *replica_flags]
    router = Router(n, rdv, spawn=replica_spawner(cmd, rdv),
                    page_size=16, probe_interval_s=0.25,
                    health_timeout_s=health_timeout, deadline_s=deadline_s,
                    admission_limit=admission, replica_inflight=inflight,
                    placement=placement, seed=3,
                    prefill_replicas=prefill_replicas,
                    migrate_timeout_s=60.0)
    router.start(wait_s=600)
    return router


def router_burst(router, requests, budget=24, seed=0, plen=(8, 33)):
    """Submit a burst, resolve everything.  Returns (tokens/s, lost,
    results)."""
    from dtf_tpu.serve import Backpressure, DeadlineExceeded
    rng = np.random.default_rng(seed)
    t0 = time.time()
    handles = [router.submit(
        rng.integers(0, 256, (int(rng.integers(*plen)),)).astype(np.int32),
        max_new_tokens=budget) for _ in range(requests)]
    tokens, lost = 0, 0
    for h in handles:
        try:
            tokens += len(h.result(timeout=router.deadline_s + 30).tokens)
        except (Backpressure, DeadlineExceeded):
            lost += 1
    wall = time.time() - t0
    return tokens / wall if wall > 0 else 0.0, lost, len(handles)


def router_scaling_and_kill(tmpdir, replicas, requests):
    """Replica scaling (1 vs N, report-only on a core-bound container)
    then kill-under-load on the N-replica tier.  Returns the list of
    failed bars."""
    bars = []
    tps1, lost1, _ = None, 0, 0
    r1 = router_tier(os.path.join(tmpdir, "tier1"), 1)
    try:
        router_burst(r1, 4, seed=9)    # warm the tier's steady state
        tps1, lost1, _ = router_burst(r1, requests, seed=10)
    finally:
        r1.stop(drain=True)
    rN = router_tier(os.path.join(tmpdir, "tierN"), replicas)
    try:
        router_burst(rN, 4, seed=9)
        tpsN, lostN, _ = router_burst(rN, requests, seed=10)
        scale = tpsN / tps1 if tps1 else 0.0
        _jline("router_replica_scaling", scale, "x", model=ROUTER_MODEL,
               replicas=replicas,
               tokens_per_s_1=round(tps1, 2),
               tokens_per_s_n=round(tpsN, 2),
               note="report-only: container is core-bound")
        if lost1 or lostN:
            bars.append(f"router scaling lost requests "
                        f"({lost1}+{lostN}) on a healthy tier")

        # kill under load: SIGKILL a replica mid-burst — zero lost,
        # >= 1 failover, every request completes.  64-token budgets +
        # an early kill: the burst must still be DECODING when the
        # kill lands (at 32 tokens a ~1k tok/s box drains the whole
        # burst in ~0.4s and the kill strands nothing — a vacuous bar)
        from dtf_tpu.serve import Backpressure, DeadlineExceeded
        rng = np.random.default_rng(21)
        handles = [rN.submit(
            rng.integers(0, 256, (12,)).astype(np.int32),
            max_new_tokens=64) for _ in range(requests)]
        time.sleep(0.2)                 # burst in flight on both
        rN.kill_replica(0)
        lost = 0
        for h in handles:
            try:
                h.result(timeout=rN.deadline_s + 30)
            except (Backpressure, DeadlineExceeded):
                lost += 1
        failovers = rN.metrics.get("router_failover_total").value
        _jline("router_kill_under_load_lost", lost, "requests",
               model=ROUTER_MODEL, requests=requests, failovers=failovers,
               respawns=rN.metrics.get(
                   "router_replica_respawns_total").value)
        if lost:
            bars.append(f"kill-under-load lost {lost}/{requests} "
                        f"requests (bar: zero)")
        if failovers < 1:
            bars.append("kill-under-load saw no failover — the kill "
                        "missed all in-flight work")
    finally:
        rN.stop(drain=True)
    return bars


def router_overload_bar(tmpdir, replicas):
    """All replicas saturated: new submits must resolve with
    Backpressure within a BOUNDED time (degrade, never hang)."""
    from dtf_tpu.serve import Backpressure, DeadlineExceeded
    bars = []
    router = router_tier(os.path.join(tmpdir, "overload"), replicas,
                         admission=10, inflight=32,
                         replica_flags=("--serve_queue_size", "2"))
    try:
        router_burst(router, 2, seed=1)   # warm
        rng = np.random.default_rng(13)
        outcomes = {"ok": 0, "bp_immediate": 0, "bp_async": 0,
                    "deadline": 0}
        bp_latency_max = 0.0
        pending = []
        # replicas hold 4 slots + 2 queued each; admission 10; 30
        # submits guarantee saturation at both levels
        for _ in range(30):
            t0 = time.monotonic()
            try:
                pending.append((t0, router.submit(
                    rng.integers(0, 256, (12,)).astype(np.int32),
                    max_new_tokens=48)))
            except Backpressure:
                outcomes["bp_immediate"] += 1
        for t0, h in pending:
            try:
                h.result(timeout=router.deadline_s + 30)
                outcomes["ok"] += 1
            except Backpressure:
                outcomes["bp_async"] += 1
                bp_latency_max = max(bp_latency_max,
                                     time.monotonic() - t0)
            except DeadlineExceeded:
                outcomes["deadline"] += 1
        shed = outcomes["bp_immediate"] + outcomes["bp_async"]
        _jline("router_overload_shed", shed, "requests",
               model=ROUTER_MODEL, **outcomes,
               bp_latency_max_s=round(bp_latency_max, 3))
        if shed == 0:
            bars.append("overload scenario never shed — it did not "
                        "saturate the tier (bench bug)")
        if bp_latency_max >= 5.0:
            bars.append(f"async Backpressure took {bp_latency_max:.1f}s "
                        f"(bar: < 5s) — overload must degrade FAST")
        if outcomes["deadline"]:
            bars.append(f"{outcomes['deadline']} requests hit their "
                        f"deadline under overload — sheds must happen "
                        f"at the door, not at the deadline")
    finally:
        router.stop(drain=True)
    return bars


def router_affinity_bar(tmpdir, replicas, requests_per_group=8):
    """Prefix-affine vs random placement over identical shared-prompt
    traffic, scored by the REPLICAS' own PrefixRegistry hit counters —
    the measured registry hit-rate win affinity exists for."""
    bars = []
    hits = {}
    for arm in ("affinity", "random"):
        router = router_tier(os.path.join(tmpdir, f"aff_{arm}"),
                             replicas, placement=arm)
        try:
            rng = np.random.default_rng(31)
            # MORE groups than replicas: with groups == replicas both
            # arms converge once every replica has registered every
            # prefix (first-touch misses are all either arm pays, and
            # 2 groups over 2 replicas can tie).  4 groups keep the
            # structural gap — random pays a first-touch miss per
            # (group, replica) pair, affinity one per group
            groups = [rng.integers(0, 256, (4 * 16,)).astype(np.int32)
                      for _ in range(4)]
            # one warmer per group (registers the prefix somewhere),
            # then the measured traffic in WAVES of one request per
            # group: a 32-deep burst spills past the per-replica
            # inflight cap and the spill misses land on BOTH arms as
            # noise — waves keep every affinity home eligible, so the
            # arms differ only by placement (the thing being measured)
            for g in groups:
                router.generate(g, max_new_tokens=2)
            for _ in range(requests_per_group):
                wave = []
                for g in groups:
                    tail = rng.integers(0, 256, (5,)).astype(np.int32)
                    wave.append(router.submit(
                        np.concatenate([g, tail]), max_new_tokens=8))
                for h in wave:
                    h.result(timeout=router.deadline_s + 30)
            total = 0
            for rid in range(replicas):
                stats = router.replica_stats(rid, timeout=10)
                total += int((stats or {}).get(
                    "serve_prefix_hit_pages_total", 0))
            hits[arm] = total
        finally:
            router.stop(drain=True)
    _jline("router_affinity_registry_hits", hits["affinity"], "pages",
           model=ROUTER_MODEL, random_placement=hits["random"],
           win=bool(hits["affinity"] > hits["random"]))
    if hits["affinity"] <= hits["random"]:
        bars.append(
            f"prefix-affine routing hit {hits['affinity']} registry "
            f"pages vs random's {hits['random']} — no measured win")
    return bars


DISAGG_PAGE = 16               # router/replica page size (migration unit)
DISAGG_GROUP_PAGES = 4         # shared system prompts: 4 FULL pages each


def router_disagg_arm(workdir, *, prefill_replicas, rounds=6):
    """One arm of the bursty long-prompt comparison at EQUAL chips
    (2 replicas): colocated (``prefill_replicas=0``) or a 1p:1d split.

    Seed phase registers two multi-page shared chains (and, in the
    split arm, waits for their KV pages to MIGRATE to the decode pool),
    then every decode-gap distribution is reset so compile stalls don't
    pollute the measurement.  The measured phase is ``rounds`` bursts
    of decode-heavy repeats (shared prefix + tail, 32-token budget)
    with two COLD ~500-token prompts dropped mid-decode each round —
    the head-of-line traffic disaggregation exists to absorb.

    Returns ``(p99, per_replica, migrated, lost)`` where ``p99`` is
    the decode-gap p99 experienced by the repeat traffic: max over the
    replicas that SERVE it — all replicas when colocated, only the
    decode pool when split (the prefill pool's gaps belong to the
    prefill-bound cold prompts by construction; a bounded tail on the
    decode pool is the number the split buys)."""
    from dtf_tpu.serve import Backpressure, DeadlineExceeded
    # seq cap raised to 512 for THIS scenario (last --flag wins): the
    # head-of-line effect needs prompts whose chunked prefill visibly
    # outweighs a decode step — at the tier default of 128 tokens the
    # whole prefill costs about one step and both arms measure noise
    router = router_tier(workdir, 2, prefill_replicas=prefill_replicas,
                         health_timeout=15.0, deadline_s=180.0,
                         inflight=8,
                         replica_flags=("--serve_max_seq_len", "512"))
    try:
        rng = np.random.default_rng(41)
        prefix_len = DISAGG_GROUP_PAGES * DISAGG_PAGE
        long_len = 500             # ~31 pages of cold prefill per burst
        groups = [rng.integers(0, 256, (prefix_len,)).astype(np.int32)
                  for _ in range(2)]
        # seed + warm: register the shared chains and compile every
        # shape the measured burst hits (repeat tails, the cold long
        # prompt, decode steps)
        warm = [router.submit(np.concatenate(
            [g, rng.integers(0, 256, (4,)).astype(np.int32)]),
            max_new_tokens=8) for g in groups]
        warm.append(router.submit(
            rng.integers(0, 256, (long_len,)).astype(np.int32),
            max_new_tokens=4))
        for h in warm:
            h.result(timeout=router.deadline_s + 30)
        migrated = 0
        if prefill_replicas:
            deadline = time.time() + 120
            while time.time() < deadline:
                ms = router.migration_stats()
                if ms["migrated"] >= len(groups) and not ms["pending"]:
                    break
                time.sleep(0.25)
            ms = router.migration_stats()
            if ms["migrated"] < len(groups) or ms["failed"]:
                raise SystemExit(
                    f"disagg bench: seed chains never migrated ({ms}) "
                    f"— the split arm cannot measure re-homed decode")
            migrated = ms["migrated"]
        for rid in range(2):
            if not router.reset_replica_measurement(rid):
                raise SystemExit(f"disagg bench: reset_measurement to "
                                 f"replica {rid} failed")
        lost = 0
        for r in range(rounds):
            handles = []
            for i in range(4):
                tail = rng.integers(0, 256, (3 + i,)).astype(np.int32)
                handles.append(router.submit(
                    np.concatenate([groups[i % 2], tail]),
                    max_new_tokens=32))
            time.sleep(0.15)   # repeats decoding when the longs land
            for _ in range(2):
                handles.append(router.submit(
                    rng.integers(0, 256, (long_len,)).astype(np.int32),
                    max_new_tokens=4))
                time.sleep(0.1)
            for h in handles:
                try:
                    h.result(timeout=router.deadline_s + 30)
                except (Backpressure, DeadlineExceeded):
                    lost += 1
        per_replica = {}
        for rid in range(2):
            stats = router.replica_stats(rid, timeout=10) or {}
            per_replica[rid] = {
                "p99": float(stats.get("serve_decode_gap_p99", 0.0)),
                "samples": int(stats.get("serve_decode_gap_count", 0))}
        decode_pool = [r for r in range(2) if r >= prefill_replicas]
        p99 = max(per_replica[r]["p99"] for r in decode_pool)
        if not any(per_replica[r]["samples"] for r in decode_pool):
            raise SystemExit(
                f"disagg bench: no decode-gap samples on the measured "
                f"pool ({per_replica}) — a 0.0 p99 would pass the bar "
                f"vacuously")
        return p99, per_replica, migrated, lost
    finally:
        router.stop(drain=True)


def router_disagg_bar(tmpdir, rounds=6):
    """Bursty long-prompt traffic, disaggregated vs colocated at equal
    chips.  Bar: the split's decode-pool gap p99 STRICTLY below the
    colocated p99 — migration must buy the tail it exists for."""
    bars = []
    colo_p99, colo_pr, _, lost_c = router_disagg_arm(
        os.path.join(tmpdir, "disagg_colo"), prefill_replicas=0,
        rounds=rounds)
    split_p99, split_pr, migrated, lost_s = router_disagg_arm(
        os.path.join(tmpdir, "disagg_split"), prefill_replicas=1,
        rounds=rounds)
    _jline("router_disagg_decode_gap_p99", split_p99, "s",
           model=ROUTER_MODEL, colocated_p99=round(colo_p99, 5),
           chains_migrated=migrated,
           split_per_replica=split_pr, colocated_per_replica=colo_pr)
    _jline("router_disagg_p99_ratio",
           (colo_p99 / split_p99) if split_p99 > 0 else 0.0, "x",
           split_beats_colocated=bool(split_p99 < colo_p99))
    if lost_c or lost_s:
        bars.append(f"disagg comparison lost requests (colocated "
                    f"{lost_c}, split {lost_s}) on healthy tiers")
    if split_p99 >= colo_p99:
        bars.append(
            f"disaggregation bar failed: decode-pool gap p99 "
            f"{split_p99:.4f}s is not below colocated {colo_p99:.4f}s "
            f"at equal chips — the pool split bought nothing")
    return bars


def _freeze_router(router):
    """What a SIGKILL leaves behind, in-process (the smoke's freeze):
    loops stopped, TCP severed mid-stream, nothing resolved."""
    import socket as socket_mod
    with router._mu:
        router._stopping = True
        router._mu.notify_all()
    for rep in router._replicas:
        conn = rep.conn
        if conn is not None:
            try:
                conn.shutdown(socket_mod.SHUT_RDWR)
            except OSError:
                pass
        router._close_conn(rep)


def router_takeover_bar(tmpdir, replicas, samples=4):
    """Time-to-takeover: the leader dies mid-burst, a standby waits out
    the fenced lease, adopts the live tier and replays the journal.
    Bar: p99 (max over samples) bounded, zero lost requests — an HA
    story whose takeover stalls or sheds is downtime with extra steps."""
    from dtf_tpu.serve import ha
    from dtf_tpu.serve import journal as journal_mod
    from dtf_tpu.serve.router import Router, replica_spawner
    bars = []
    lease_ttl = 0.5
    workdir = os.path.join(tmpdir, "takeover")
    rdv = os.path.join(workdir, "rdv")
    cmd = [sys.executable, "-m", "dtf_tpu.cli.replica_main",
           "--rendezvous_dir", rdv, *ROUTER_REPLICA_FLAGS]

    def make_router(epoch, spawn=None):
        r = Router(replicas, rdv, spawn=spawn, page_size=16,
                   probe_interval_s=0.25, health_timeout_s=5.0,
                   deadline_s=120.0, replica_inflight=4, seed=3,
                   journal_path=journal_mod.journal_path(rdv),
                   epoch=epoch)
        r.start(wait_s=600 if spawn else 60, adopt=spawn is None)
        return r

    owner = make_router(1, spawn=replica_spawner(cmd, rdv))
    routers = [owner]
    times, lost = [], 0
    rng = np.random.default_rng(29)
    try:
        router_burst(owner, 2, seed=40)     # warm the tier
        leader, epoch = owner, 1
        lease = ha.LeaderLease(rdv, ttl_s=lease_ttl, holder="bench-0")
        lease.acquire()
        for i in range(samples):
            keeper = ha.LeaseKeeper(lease, on_fenced=leader.fence)
            keeper.start()
            handles = [leader.submit(
                rng.integers(0, 256, (12,)).astype(np.int32),
                max_new_tokens=48) for _ in range(6)]
            time.sleep(0.3)                 # burst decoding in flight
            keeper.stop()
            _freeze_router(leader)
            t0 = time.monotonic()
            lease = ha.LeaderLease(rdv, ttl_s=lease_ttl,
                                   holder=f"bench-{i + 1}")
            epoch = ha.wait_for_takeover(lease, poll_s=0.05,
                                         timeout_s=60.0)
            leader = make_router(epoch)
            summary = ha.take_over(leader, resume_rollout=False)
            times.append(time.monotonic() - t0)
            routers.append(leader)
            for h in handles:
                if h.done() and h._exc is None:
                    continue                # resolved before the kill
                nh = summary["handles"].get(h.request.id)
                try:
                    if nh is None:
                        raise RuntimeError("not adopted")
                    nh.result(timeout=150)
                except Exception:
                    lost += 1
        p99 = max(times)
        _jline("router_takeover_p99", p99, "s", model=ROUTER_MODEL,
               samples=samples, lease_ttl_s=lease_ttl,
               mean=round(sum(times) / len(times), 4),
               lost_requests=lost)
        if lost:
            bars.append(f"takeover lost {lost} requests across "
                        f"{samples} leader kills (bar: zero)")
        if p99 >= 15.0:
            bars.append(f"time-to-takeover p99 {p99:.2f}s breaches the "
                        f"15s bound (lease ttl {lease_ttl}s)")
    finally:
        for r in routers[1:]:
            r.stop(drain=False)
        owner.stop(drain=False)   # owns the replica processes
    return bars


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="transformer_small")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--kv_page_size", type=int, default=16)
    # chunk for the mixed scenario's chunked arm.  Measured frontier
    # (CPU, transformer_small, seq 1024): whole-prompt flash prefill
    # 0.56 s vs 0.21 s max per 128-token chunk — the gap bound the
    # chunked arm must demonstrate; 64-token chunks bound tighter
    # (0.17 s) but pay 1.6x the total prefill work
    ap.add_argument("--prefill_chunk", type=int, default=128)
    # the mixed-length scenario runs at a LONGER context than the
    # engine-traffic section: chunked prefill exists for prompts
    # whose single-shot prefill visibly blocks running decodes, which
    # starts around 4x the step-shape sequence on this hardware
    # (at 512 the whole-prompt flash pass is already cheaper than one
    # chunk's gather-attend, and chunking can only add overhead)
    ap.add_argument("--mixed_seq", type=int, default=1024)
    # replica-tier scenarios (real replica subprocesses); 0 skips them
    ap.add_argument("--router_replicas", type=int, default=2)
    # BENCH_serve artifact: one JSON file holding every metric line of
    # this run (the serving trajectory's cross-PR unit)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    from dtf_tpu.models import build_model
    from dtf_tpu.serve import ServeEngine, collect_stats

    model, _ = build_model(args.model, dtype=jnp.bfloat16)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, args.seq), jnp.int32))["params"]

    # engine-level traffic: burst of requests, SLA percentiles
    eng = ServeEngine(model, params, max_batch=args.batch,
                      max_seq_len=args.seq, max_delay_s=0.005,
                      queue_size=max(64, 2 * args.requests))
    rng = np.random.default_rng(1)
    t0 = time.time()
    handles = []
    for _ in range(args.requests):
        plen = int(rng.integers(4, 17))
        handles.append(eng.submit(
            rng.integers(0, model.vocab_size, (plen,)).astype(np.int32),
            max_new_tokens=32))
    for h in handles:
        h.result(timeout=600)
    wall = time.time() - t0
    eng.stop()
    s = collect_stats(eng.completed, eng.shed_count, wall_time_s=wall)
    _jline("serve_engine_tokens_per_s", s.tokens_per_s, "tokens/s",
           requests=s.num_requests, batch=args.batch)
    _jline("serve_latency_p50", s.latency_p50_s, "s")
    _jline("serve_latency_p99", s.latency_p99_s, "s")
    _jline("serve_ttft_p50", s.ttft_p50_s, "s")
    # engine registry (obs.MetricsRegistry): operational signals that
    # used to be log lines at best — shed total, queue depth, slot
    # occupancy sampled per decode iteration
    shed = eng.metrics.get("serve_shed_total")
    occ = eng.metrics.get("serve_slot_occupancy_sampled").snapshot()
    qd = eng.metrics.get("serve_queue_depth_sampled").snapshot()
    _jline("serve_shed_total", shed.value, "requests")
    _jline("serve_slot_occupancy_mean", occ["mean"], "fraction",
           p90=round(occ["p90"], 4), samples=occ["count"])
    _jline("serve_queue_depth_p90", qd["p90"], "requests",
           max=qd["max"], mean=round(qd["mean"], 4))
    # MFU/cost ledger gauges for the decode-step executable: the --out
    # artifact then carries serve EFFICIENCY, not just throughput, so
    # tools/bench_gate.py gates achieved-TFLOP/s (and MFU/HBM fraction
    # where the chip's peaks are known) across PRs
    led = eng.ledger.summary().get("serve_decode_step")
    if led and led["count"]:
        _jline("serve_ledger_decode_step_wall_ms", led["mean_s"] * 1e3,
               "ms", calls=led["count"], batch=args.batch)
        _jline("serve_ledger_decode_achieved_tflops",
               led["achieved_tflops"], "tflops",
               gflops_per_step=round(led["flops"] / 1e9, 3))
        if led["mfu"] is not None:
            _jline("serve_ledger_decode_mfu", led["mfu"], "mfu")
        if led["hbm_frac"] is not None:
            _jline("serve_ledger_decode_hbm_frac", led["hbm_frac"],
                   "fraction")

    # mixed-length scenario: 50% pool, chunked vs un-chunked prefill
    ps = args.kv_page_size
    pages_full = args.batch * (-(-args.mixed_seq // ps))
    pool_half = 1 + pages_full // 2
    mixed_requests = min(args.requests, 12)
    if mixed_requests != args.requests:
        # no silent caps: the scenario bounds runtime at 12 requests —
        # say so, or the serve_mixed_* numbers read as --requests load
        print(f"# mixed-length scenario capped at {mixed_requests} "
              f"requests (--requests {args.requests}); section 1 "
              f"honored the flag")
    mixed = dict(batch=args.batch, seq=args.mixed_seq,
                 requests=mixed_requests)
    g_chunk = mixed_scenario(
        model, params, kv_page_size=ps, kv_pool_pages=pool_half,
        prefill_chunk=args.prefill_chunk, label="paged_chunked", **mixed)
    g_plain = mixed_scenario(
        model, params, kv_page_size=ps, kv_pool_pages=pool_half,
        prefill_chunk=0, label="paged_unchunked", **mixed)
    _jline("serve_mixed_chunked_gap_improvement",
           (g_plain["p99"] / g_chunk["p99"]) if g_chunk["p99"] > 0
           else 0.0, "x",
           chunked_below_unchunked=bool(g_chunk["p99"] < g_plain["p99"]))

    # shared-prefix scenario: N requests over one system prompt, pool
    # sized so unshared copies CANNOT all fit — prefix sharing must at
    # least double the concurrent sequences at equal page budget, and
    # streaming must deliver the first token well before full retire
    sys_pages = 8
    prefix_pool = prefix_pool_pages(args.batch, sys_pages, ps)
    _, c_share, hw_share, ttft_stream, full_p50 = shared_prefix_scenario(
        model, params, batch=args.batch, seq=args.seq,
        requests=args.batch, kv_page_size=ps, kv_pool_pages=prefix_pool,
        sys_pages=sys_pages, prefix_sharing=True, label="sharing")
    _, c_noshare, hw_noshare, _, _ = shared_prefix_scenario(
        model, params, batch=args.batch, seq=args.seq,
        requests=args.batch, kv_page_size=ps, kv_pool_pages=prefix_pool,
        sys_pages=sys_pages, prefix_sharing=False, label="nosharing")
    _jline("serve_prefix_concurrency_gain",
           (c_share / c_noshare) if c_noshare else 0.0, "x",
           sharing=c_share, nosharing=c_noshare,
           meets_2x_bar=bool(c_share >= 2 * c_noshare))
    _jline("serve_stream_first_token_gain",
           (full_p50 / ttft_stream) if ttft_stream > 0 else 0.0, "x",
           stream_ttft_p50=round(ttft_stream, 4),
           full_retire_p50=round(full_p50, 4),
           streaming_earlier=bool(ttft_stream < full_p50))

    # acceptance bars, enforced — a printed false boolean that exits 0
    # is not a contract.  Collected, not raised one-by-one: the --out
    # artifact records every verdict even when an early bar fails
    failed = []
    if g_chunk["p99"] >= g_plain["p99"]:
        failed.append(
            f"chunked prefill did not bound the decode gap: p99 "
            f"{g_chunk['p99']:.3f}s chunked vs {g_plain['p99']:.3f}s "
            f"un-chunked")
    if c_share < 2 * c_noshare:
        failed.append(
            f"prefix-sharing bar failed: {c_share} concurrent sequences "
            f"sharing vs {c_noshare} without (bar: >= 2x) at "
            f"{prefix_pool - 1} usable pages")
    if ttft_stream >= full_p50:
        failed.append(
            f"streaming bar failed: first streamed token p50 "
            f"{ttft_stream:.3f}s is not below full-retire p50 "
            f"{full_p50:.3f}s")

    # replica-tier scenarios: scaling + kill-under-load, overload
    # degrade bound, prefix-affine vs random placement
    if args.router_replicas > 0:
        import shutil
        tier_dir = tempfile.mkdtemp(prefix="dtf_bench_router_")
        clean = False
        try:
            failed += router_scaling_and_kill(
                tier_dir, args.router_replicas, requests=12)
            failed += router_overload_bar(tier_dir, args.router_replicas)
            failed += router_affinity_bar(tier_dir, args.router_replicas)
            failed += router_disagg_bar(tier_dir)
            failed += router_takeover_bar(tier_dir, args.router_replicas)
            clean = True
        finally:
            if clean and not failed:
                shutil.rmtree(tier_dir, ignore_errors=True)
            else:
                # ANY non-clean exit keeps the rendezvous + replica
                # logs — a tier that failed to start (exception, not a
                # bar) is exactly when replica0.log matters
                print(f"# replica-tier work dir kept for debugging: "
                      f"{tier_dir}")

    if args.out:
        write_artifact(args.out, args.model, failed)
    if failed:
        raise SystemExit("bench_serve bars FAILED:\n  "
                         + "\n  ".join(failed))


if __name__ == "__main__":
    main()
