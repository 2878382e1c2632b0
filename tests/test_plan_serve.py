"""Serving-capacity simulator tests (dtf_tpu/plan/serve_trace +
serve_model).

Three contracts, in rising order of expense:

  1. the TRACE-REPLAY PARSER reconstructs per-request records from
     recorded router/replica streams exactly — including the edge
     cases a real fleet writes: torn JSONL tails, records missing a
     trace id (counted, never guessed), router + replica views of one
     request merged across streams, a failover (requeue + second
     dispatch) counted ONCE;
  2. the SIMULATOR is exact where it claims exactness (a lone
     request's latency is chunk + step arithmetic) and moves the
     right direction under every lever (batching amortizes, the
     admission bound sheds, a starved pool queues FIFO without loss,
     prefix sharing cuts both pages and prefill work, TP follows the
     Amdahl split and scales the pool);
  3. the three documented WHAT-IFS — replicas for X req/s at a p99
     SLO, TP-vs-replicas at a fixed chip budget, page-pool size vs
     shed rate — answered from a RECORDED trace, pinned (the
     acceptance criterion); plus the calibration contract against a
     live traced engine run (slow-marked; ci_check stage 10 runs the
     same contract via the CLI).
"""

import dataclasses
import json
import math

import pytest

from dtf_tpu.plan.serve_model import (FleetConfig, ServeProfile,
                                      calibration_ratios,
                                      measured_tp_comm_frac, pool_split,
                                      pool_vs_shed,
                                      rank_tp_vs_replicas, ratios_within,
                                      replicas_for, simulate)
from dtf_tpu.plan.serve_trace import (RequestRecord, Workload,
                                      measured_stats, parse_workload,
                                      scale_workload, synthetic_workload,
                                      workload_from_records)

PROFILE = ServeProfile(decode_step_s=0.010, prefill_chunk_s=0.008,
                       chunk_tokens=64, page_size=16)
CONFIG = FleetConfig(replicas=1, slots=8, pool_pages=64, queue_size=64,
                     admission_limit=256, deadline_s=30.0,
                     replica_inflight=16)


def _req(i, arrival, prompt=32, decode=16, **kw):
    return RequestRecord(trace_id=f"t{i:04d}", arrival_s=arrival,
                         prompt_tokens=prompt, decode_tokens=decode,
                         **kw)


def _workload(reqs, duration=None):
    dur = duration if duration is not None else (
        max(r.arrival_s for r in reqs) + 60.0 if reqs else 1.0)
    return Workload(list(reqs), dur, "test")


# ---------------------------------------------------------------------------
# trace-replay parsing
# ---------------------------------------------------------------------------

def _write_jsonl(path, records):
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")


def _router_lifecycle(tid, t0, prompt=32, tokens=16, latency=0.5,
                      wait=0.02, replica=0):
    """The records serve/router.py writes for one completed request."""
    return [
        {"kind": "event", "name": "router_submit", "ts": t0,
         "rank": "router", "request": 1, "trace": tid,
         "prompt_len": prompt, "deadline_s": 120.0, "queue_depth": 1},
        {"kind": "event", "name": "router_dispatch", "ts": t0 + wait,
         "rank": "router", "request": 1, "trace": tid,
         "replica": replica, "attempt": 1, "queue_wait_s": wait},
        {"kind": "event", "name": "router_complete",
         "ts": t0 + latency, "rank": "router", "request": 1,
         "trace": tid, "replica": replica, "tokens": tokens,
         "redispatches": 0, "latency_s": latency},
    ]


def test_parse_router_trace_reconstructs_requests(tmp_path):
    recs = (_router_lifecycle("aaa", 100.0, prompt=48, tokens=24,
                              latency=0.8, wait=0.05)
            + _router_lifecycle("bbb", 100.3, prompt=16, tokens=8,
                                latency=0.4, wait=0.01))
    _write_jsonl(tmp_path / "trace_router.jsonl", recs)
    w = parse_workload([str(tmp_path)])
    assert len(w.requests) == 2 and w.skipped_no_trace == 0
    a, b = w.requests
    assert (a.trace_id, a.prompt_tokens, a.decode_tokens) == ("aaa", 48, 24)
    assert a.arrival_s == 0.0 and b.arrival_s == pytest.approx(0.3)
    assert a.queue_wait_s == pytest.approx(0.05)
    assert a.latency_s == pytest.approx(0.8)
    assert a.outcome == "complete"
    # the window spans first arrival -> last completion (request a:
    # 0.0 + 0.8 s outlives request b's 0.3 + 0.4 s)
    assert w.duration_s == pytest.approx(0.8)
    m = measured_stats(w)
    assert m["completed"] == 2 and m["shed_rate"] == 0.0
    assert m["tokens_per_s"] == pytest.approx(32 / 0.8)


def test_parse_tolerates_torn_tail_line(tmp_path):
    recs = _router_lifecycle("aaa", 10.0)
    path = tmp_path / "trace_router.jsonl"
    _write_jsonl(path, recs)
    with open(path, "a") as f:
        # a crash mid-write: half a router_submit for another request
        f.write('{"kind": "event", "name": "router_submit", "ts": 11.0,'
                ' "trace": "bb')
    w = parse_workload([str(tmp_path)])
    assert len(w.requests) == 1
    assert w.requests[0].trace_id == "aaa"


def test_parse_counts_records_missing_trace_id(tmp_path):
    recs = _router_lifecycle("aaa", 10.0)
    # an old-format record with no trace id: counted, not guessed
    recs.append({"kind": "event", "name": "router_submit", "ts": 11.0,
                 "rank": "router", "request": 9, "prompt_len": 8})
    _write_jsonl(tmp_path / "trace_router.jsonl", recs)
    w = parse_workload([str(tmp_path)])
    assert len(w.requests) == 1
    assert w.skipped_no_trace == 1


def test_parse_merges_router_and_replica_streams(tmp_path):
    """One request seen by BOTH tiers: router records own arrival/
    queue-wait/outcome, the replica's serve_admit contributes the
    prefix-share depth only the engine knows."""
    _write_jsonl(tmp_path / "trace_router.jsonl",
                 _router_lifecycle("ccc", 50.0, wait=0.04))
    _write_jsonl(tmp_path / "trace_rank0.jsonl", [
        {"kind": "event", "name": "serve_submit", "ts": 50.05,
         "rank": 0, "request": 3, "trace": "ccc", "prompt_len": 32},
        {"kind": "event", "name": "serve_admit", "ts": 50.1, "rank": 0,
         "request": 3, "trace": "ccc", "queue_wait_s": 0.05,
         "shared_tokens": 16},
        {"kind": "event", "name": "serve_retire", "ts": 50.4, "rank": 0,
         "request": 3, "trace": "ccc", "tokens": 16, "latency_s": 0.35},
    ])
    w = parse_workload([str(tmp_path)])
    assert len(w.requests) == 1
    r = w.requests[0]
    # router fields win; engine enriches the share depth
    assert r.queue_wait_s == pytest.approx(0.04)
    assert r.latency_s == pytest.approx(0.5)
    assert r.prefix_tokens == 16
    assert r.outcome == "complete"


def test_parse_failover_counted_once(tmp_path):
    """A requeue + second dispatch is ONE request with redispatches=1,
    not two requests."""
    tid = "ddd"
    recs = [
        {"kind": "event", "name": "router_submit", "ts": 10.0,
         "rank": "router", "request": 5, "trace": tid,
         "prompt_len": 24},
        {"kind": "event", "name": "router_dispatch", "ts": 10.02,
         "rank": "router", "request": 5, "trace": tid, "replica": 0,
         "attempt": 1, "queue_wait_s": 0.02},
        {"kind": "event", "name": "router_requeue", "ts": 10.3,
         "rank": "router", "request": 5, "trace": tid,
         "reason": "conn_lost", "redispatches": 1, "delivered": 3},
        {"kind": "event", "name": "router_dispatch", "ts": 10.35,
         "rank": "router", "request": 5, "trace": tid, "replica": 1,
         "attempt": 2},
        {"kind": "event", "name": "router_complete", "ts": 10.9,
         "rank": "router", "request": 5, "trace": tid, "replica": 1,
         "tokens": 12, "redispatches": 1, "latency_s": 0.9},
    ]
    _write_jsonl(tmp_path / "trace_router.jsonl", recs)
    w = parse_workload([str(tmp_path)])
    assert len(w.requests) == 1
    r = w.requests[0]
    assert r.redispatches == 1 and r.outcome == "complete"
    assert r.decode_tokens == 12
    # queue wait is submit -> FIRST dispatch; the failover leg is
    # service disruption, not queueing
    assert r.queue_wait_s == pytest.approx(0.02)


def test_parse_queue_wait_survives_lost_first_attempt(tmp_path):
    """A dead replica at first dispatch leaves NO attempt-1 record;
    the router latches the first-attempt wait and stamps it on every
    later dispatch record, so the ground truth survives."""
    recs = [
        {"kind": "event", "name": "router_submit", "ts": 10.0,
         "rank": "router", "request": 6, "trace": "xyz",
         "prompt_len": 24},
        # attempt 1's send failed — the first RECORD is attempt 2,
        # still carrying the latched first-attempt wait
        {"kind": "event", "name": "router_dispatch", "ts": 10.4,
         "rank": "router", "request": 6, "trace": "xyz", "replica": 1,
         "attempt": 2, "queue_wait_s": 0.03},
        {"kind": "event", "name": "router_complete", "ts": 10.8,
         "rank": "router", "request": 6, "trace": "xyz", "replica": 1,
         "tokens": 8, "redispatches": 1, "latency_s": 0.8},
    ]
    _write_jsonl(tmp_path / "trace_router.jsonl", recs)
    w = parse_workload([str(tmp_path)])
    assert w.requests[0].queue_wait_s == pytest.approx(0.03)


def test_parse_engine_only_stream(tmp_path):
    """A router-less traced engine run stands alone (the calibration
    path): serve_submit/admit/retire carry the whole lifecycle."""
    _write_jsonl(tmp_path / "trace_rank0.jsonl", [
        {"kind": "event", "name": "serve_submit", "ts": 5.0, "rank": 0,
         "request": 0, "trace": "eee", "prompt_len": 20},
        {"kind": "event", "name": "serve_admit", "ts": 5.2, "rank": 0,
         "request": 0, "trace": "eee", "queue_wait_s": 0.2},
        {"kind": "event", "name": "serve_retire", "ts": 5.6, "rank": 0,
         "request": 0, "trace": "eee", "tokens": 10, "latency_s": 0.6},
    ])
    w = parse_workload([str(tmp_path)])
    assert len(w.requests) == 1
    r = w.requests[0]
    assert (r.prompt_tokens, r.decode_tokens) == (20, 10)
    assert r.queue_wait_s == pytest.approx(0.2)
    assert r.outcome == "complete"


def test_parse_shed_and_deadline_outcomes(tmp_path):
    recs = _router_lifecycle("fff", 20.0)
    # an admission shed never reaches router_submit — the anomaly IS
    # the record
    recs.append({"kind": "anomaly", "name": "router_shed", "ts": 20.1,
                 "rank": "router", "reason": "admission limit 128",
                 "trace": "ggg", "retry_after": 0.5})
    recs += [
        {"kind": "event", "name": "router_submit", "ts": 20.2,
         "rank": "router", "request": 7, "trace": "hhh",
         "prompt_len": 8},
        {"kind": "anomaly", "name": "router_deadline", "ts": 25.2,
         "rank": "router", "request": 7, "trace": "hhh",
         "deadline_s": 5.0, "delivered": 2, "redispatches": 0},
    ]
    _write_jsonl(tmp_path / "trace_router.jsonl", recs)
    w = parse_workload([str(tmp_path)])
    outcomes = {r.trace_id: r.outcome for r in w.requests}
    assert outcomes == {"fff": "complete", "ggg": "shed",
                        "hhh": "deadline"}
    # the deadline-failed request's streamed tokens are real demand a
    # replay must pay for — not floored to nothing
    assert {r.trace_id: r.decode_tokens
            for r in w.requests}["hhh"] == 2
    m = measured_stats(w)
    assert m["shed"] == 1 and m["deadlined"] == 1 and m["completed"] == 1


# ---------------------------------------------------------------------------
# synthetic arrival generation
# ---------------------------------------------------------------------------

def test_synthetic_poisson_deterministic_and_in_window():
    a = synthetic_workload(rate_rps=20, duration_s=10, seed=7)
    b = synthetic_workload(rate_rps=20, duration_s=10, seed=7)
    assert [r.arrival_s for r in a.requests] == \
           [r.arrival_s for r in b.requests]
    assert all(0 <= r.arrival_s < 10 for r in a.requests)
    # mean rate in the statistical ballpark of the ask
    assert 0.6 * 20 <= a.rate_rps <= 1.4 * 20


def test_synthetic_burst_concentrates_arrivals():
    w = synthetic_workload(rate_rps=10, duration_s=16, seed=3,
                           process="burst", burst_factor=4.0,
                           burst_period_s=4.0)
    # every arrival lands in the leading 1/burst_factor of its period
    for r in w.requests:
        assert math.fmod(r.arrival_s, 4.0) <= 4.0 / 4.0 + 1e-9
    assert len(w.requests) > 0


def test_synthetic_shared_prefix_mix():
    w = synthetic_workload(rate_rps=30, duration_s=10, seed=1,
                           shared_fraction=0.5, shared_groups=3,
                           shared_prefix_tokens=64,
                           prompt_tokens=(4, 8))
    shared = [r for r in w.requests if r.prefix_group is not None]
    assert 0.3 * len(w.requests) <= len(shared) <= 0.7 * len(w.requests)
    assert {r.prefix_group for r in shared} <= {"g0", "g1", "g2"}
    for r in shared:
        assert r.prefix_tokens == 64 and r.prompt_tokens >= 64 + 4
    for r in w.requests:
        if r.prefix_group is None:
            assert 4 <= r.prompt_tokens <= 8


def test_synthetic_validation():
    with pytest.raises(ValueError):
        synthetic_workload(rate_rps=0, duration_s=5)
    with pytest.raises(ValueError):
        synthetic_workload(rate_rps=1, duration_s=5, process="stampede")
    with pytest.raises(ValueError):
        synthetic_workload(rate_rps=1, duration_s=5, shared_fraction=1.5)


def test_scale_workload_preserves_shape():
    w = synthetic_workload(rate_rps=10, duration_s=10, seed=2)
    s = scale_workload(w, 20.0)
    assert s.rate_rps == pytest.approx(20.0, rel=1e-6)
    # ordering and mix survive; relative spacing compresses uniformly
    assert len(s.requests) == len(w.requests)
    assert [r.prompt_tokens for r in s.requests] == \
           [r.prompt_tokens for r in w.requests]


# ---------------------------------------------------------------------------
# simulator
# ---------------------------------------------------------------------------

def test_single_request_latency_is_service_arithmetic():
    """A lone request's simulated latency is EXACT: one 64-token chunk
    plus (budget − 1) decode steps (the last prefill chunk emits the
    first token, the engine contract)."""
    w = _workload([_req(0, 0.0, prompt=64, decode=32)])
    pred = simulate(w, PROFILE, CONFIG)
    expected = 0.008 + 31 * 0.010
    assert pred.latency_p50_s == pytest.approx(expected, abs=1e-9)
    assert pred.completed == 1 and pred.loss_rate == 0.0
    assert pred.tokens_per_s == pytest.approx(32 / expected)


def test_full_prefix_hit_skips_prefill_pays_full_decode():
    """A request whose whole prompt is a recorded prefix hit (parsed
    trace, prefix_tokens == prompt) runs zero chunks and all budget
    decode steps — the engine's COW path."""
    w = _workload([_req(0, 0.0, prompt=64, decode=32, prefix_tokens=64)])
    pred = simulate(w, PROFILE, CONFIG)
    assert pred.latency_p50_s == pytest.approx(32 * 0.010, abs=1e-9)


def test_batching_amortizes_decode_steps():
    """8 simultaneous arrivals on 8 slots decode TOGETHER: ~8× the
    tokens/s of a lone request, p99 within ~2× of solo latency (the
    chunk round-robin staggers starts, it does not serialize them)."""
    solo = simulate(_workload([_req(0, 0.0, prompt=64, decode=32)]),
                    PROFILE, CONFIG)
    batch = simulate(
        _workload([_req(i, 0.0, prompt=64, decode=32)
                   for i in range(8)]), PROFILE, CONFIG)
    assert batch.completed == 8
    assert batch.tokens_per_s > 5.0 * solo.tokens_per_s
    assert batch.latency_p99_s < 2.0 * solo.latency_p50_s


def test_admission_limit_sheds():
    cfg = dataclasses.replace(CONFIG, admission_limit=4)
    w = _workload([_req(i, 0.0) for i in range(10)])
    pred = simulate(w, PROFILE, cfg)
    assert pred.shed == 6 and pred.completed == 4
    assert pred.shed_rate == pytest.approx(0.6)


def test_starved_pool_queues_fifo_without_loss():
    """A pool that fits ONE request at a time serializes admissions:
    everything completes, queue wait grows, nothing is lost."""
    # prompt 32 + budget 16 = 48 tokens = 3 pages; pool of 3 usable
    cfg = dataclasses.replace(CONFIG, pool_pages=3, slots=8)
    w = _workload([_req(i, 0.0, prompt=32, decode=16)
                   for i in range(4)])
    pred = simulate(w, PROFILE, cfg)
    assert pred.completed == 4 and pred.loss_rate == 0.0
    # the 4th request waited for three predecessors to retire
    assert pred.queue_wait_p99_s > 2.5 * pred.latency_p50_s / 4


def test_oversized_request_is_shed():
    cfg = dataclasses.replace(CONFIG, pool_pages=2)
    w = _workload([_req(0, 0.0, prompt=64, decode=32)])   # 6 pages
    pred = simulate(w, PROFILE, cfg)
    assert pred.shed == 1 and pred.completed == 0


def test_deadline_is_a_posthoc_verdict():
    cfg = dataclasses.replace(CONFIG, deadline_s=0.1)
    w = _workload([_req(0, 0.0, prompt=64, decode=32)])   # ~0.32 s
    pred = simulate(w, PROFILE, cfg)
    assert pred.deadlined == 1 and pred.completed == 0
    assert pred.deadline_rate == 1.0


def test_prefix_sharing_cuts_pages_and_prefill():
    """Shared-group traffic on a tight pool: the registry model admits
    more concurrently and skips shared-prefix chunks — strictly better
    p99 than the same traffic with group identity stripped."""
    reqs = [_req(i, 0.001 * i, prompt=128 + 16, decode=16,
                 prefix_group="g0", prefix_tokens=128)
            for i in range(8)]
    stripped = [dataclasses.replace(r, prefix_group=None,
                                    prefix_tokens=0) for r in reqs]
    cfg = dataclasses.replace(CONFIG, pool_pages=30, slots=8)
    shared = simulate(_workload(reqs), PROFILE, cfg)
    unshared = simulate(_workload(stripped), PROFILE, cfg)
    assert shared.completed == unshared.completed == 8
    assert shared.latency_p99_s < unshared.latency_p99_s
    assert shared.queue_wait_p99_s < unshared.queue_wait_p99_s


def test_eviction_never_frees_the_admitted_groups_held_chain():
    """Admitting a group whose own registered chain is the only
    evictable thing: only the chain BEYOND the held depth may be
    truncated — the `hit` pages stay (the engine holds shares before
    evicting).  Both requests complete; evicting the held chain would
    deadlock or grant phantom pages."""
    reqs = [
        # registers a 9-page chain (prompt 144 tokens), then retires
        _req(0, 0.0, prompt=144, decode=16, prefix_group="g0",
             prefix_tokens=144),
        # short prompt (2-page hit) + a decode budget that needs the
        # chain's deeper 7 pages truncated to fit the 12-page pool
        _req(1, 5.0, prompt=32, decode=160, prefix_group="g0",
             prefix_tokens=32),
    ]
    cfg = dataclasses.replace(CONFIG, pool_pages=12, slots=4)
    pred = simulate(_workload(reqs), PROFILE, cfg)
    assert pred.completed == 2 and pred.loss_rate == 0.0
    # the second request admitted immediately (its 2 held pages plus
    # 10 fresh after the truncation) — no head-of-line stall
    assert pred.queue_wait_p99_s == pytest.approx(0.0)


def test_tp_amdahl_split_and_pool_scaling():
    p = PROFILE
    assert p.decode_step_for(1) == p.decode_step_s
    t2 = p.decode_step_for(2)
    # faster than tp=1, slower than perfect halving (the comm fraction)
    assert p.decode_step_s / 2 < t2 < p.decode_step_s
    assert t2 == pytest.approx(0.010 * (0.15 + 0.85 / 2))
    cfg = dataclasses.replace(CONFIG, tp=2)
    assert cfg.usable_pages == 2 * CONFIG.pool_pages
    assert cfg.chips == 2
    assert dataclasses.replace(cfg, pool_scales_with_tp=False
                               ).usable_pages == CONFIG.pool_pages


def test_simulator_is_deterministic():
    w = synthetic_workload(rate_rps=25, duration_s=10, seed=5)
    a = simulate(w, PROFILE, CONFIG)
    b = simulate(w, PROFILE, CONFIG)
    assert a == b


def test_profile_validation():
    with pytest.raises(ValueError):
        ServeProfile(decode_step_s=0.0, prefill_chunk_s=0.01)
    with pytest.raises(ValueError):
        ServeProfile(decode_step_s=0.01, prefill_chunk_s=0.01,
                     tp_comm_frac=1.0)
    with pytest.raises(ValueError):
        FleetConfig(replicas=0)
    with pytest.raises(ValueError):
        FleetConfig(placement="telepathy")


def test_profile_from_records_medians_and_overrides():
    recs = ([{"kind": "span", "name": "serve_decode", "ts": 0.0,
              "dur_s": d} for d in (0.01, 0.012, 5.0)]   # 5.0 = compile
            + [{"kind": "span", "name": "serve_prefill_chunk",
                "ts": 0.0, "dur_s": d, "tokens": 64}
               for d in (0.008, 0.009, 0.009)]
            + [{"kind": "event", "name": "ledger_exec",
                "exec": "serve_decode_step", "ts": 0.0,
                "flops": 1.5e9, "bytes": 2e8}])
    p = ServeProfile.from_records(recs, page_size=8)
    assert p.decode_step_s == pytest.approx(0.012)   # median, not mean
    assert p.prefill_chunk_s == pytest.approx(0.009)
    assert p.chunk_tokens == 64 and p.page_size == 8
    assert p.decode_flops == pytest.approx(1.5e9)
    with pytest.raises(ValueError):
        ServeProfile.from_records([])                # nothing measured


# ---------------------------------------------------------------------------
# the three documented what-ifs, answered from a recorded trace (pinned)
# ---------------------------------------------------------------------------

def _recorded_trace(tmp_path, n=48, gap=0.05):
    """A plausible recorded router trace: n completed requests at a
    steady gap, prompt 64 / 24 generated tokens each."""
    recs = []
    for i in range(n):
        recs += _router_lifecycle(f"req{i:04d}", 1000.0 + i * gap,
                                  prompt=64, tokens=24, latency=0.6,
                                  wait=0.03, replica=i % 2)
    _write_jsonl(tmp_path / "trace_router.jsonl", recs)
    return parse_workload([str(tmp_path)])


def test_whatifs_from_recorded_trace_pinned(tmp_path):
    """The acceptance criterion: all three capacity questions answered
    from a recorded trace, deterministically."""
    w = _recorded_trace(tmp_path)
    assert len(w.requests) == 48
    assert w.rate_rps == pytest.approx(48 / w.duration_s)
    base = dataclasses.replace(CONFIG, slots=4, pool_pages=40)

    # 1. replicas for 40 req/s at p99 <= 1.5 s: one replica saturates
    # (p99 ~2.4 s), two serve it at ~0.8 s
    n, evaluated = replicas_for(w, PROFILE, base, target_rps=40.0,
                                slo_p99_s=1.5)
    assert n == 2
    # every evaluated count below the answer missed the SLO
    for r, pred in evaluated:
        if r < n:
            assert pred.latency_p99_s > 1.5 or pred.loss_rate > 0.01
    # the answering config meets it
    answer = dict(evaluated)[n]
    assert answer.latency_p99_s <= 1.5 and answer.loss_rate <= 0.01

    # 2. tp × replicas at 4 chips: TP's Amdahl win + bigger pools beat
    # more queues for this steady single-stream traffic
    ranked = rank_tp_vs_replicas(w, PROFILE, base, chips=4)
    assert [(c.tp, c.replicas) for c, _ in ranked] == \
           [(4, 1), (2, 2), (1, 4)]
    assert all(p.loss_rate == 0.0 for _, p in ranked)
    # ranking is by p99: strictly improving with TP here
    p99s = [p.latency_p99_s for _, p in ranked]
    assert p99s == sorted(p99s)

    # 3. page-pool size vs shed rate: the provisioning curve is
    # monotone and the smallest under-bar pool is pinned
    best, rows = pool_vs_shed(w, PROFILE, base, [4, 8, 16, 40])
    assert best == 8
    losses = [p.loss_rate for _, p in rows]
    assert losses[0] == 1.0         # 4 pages: every request oversized
    assert losses == sorted(losses, reverse=True)
    assert dict(rows)[40].loss_rate == 0.0
    # under the loss bar the curve is still a latency trade: 8 pages
    # serialize admissions (one 6-page request at a time)
    assert dict(rows)[8].latency_p99_s > 2 * dict(rows)[40].latency_p99_s


def test_replicas_for_can_fail_loudly():
    w = synthetic_workload(rate_rps=50, duration_s=5, seed=9,
                           decode_tokens=64)
    n, evaluated = replicas_for(w, PROFILE, CONFIG, target_rps=5000.0,
                                slo_p99_s=0.001, max_replicas=3)
    assert n is None and len(evaluated) == 3


def test_cost_per_token_ranking():
    """$/Mtoken at the SLO (capacity-sim follow-on #4): the dollar
    arithmetic is rate/throughput, halving service time ~halves
    $/token, and an SLO-missing config ranks below every meeting one
    no matter how cheap its tokens are."""
    from dtf_tpu.plan.serve_model import rank_cost_per_token

    w = synthetic_workload(rate_rps=20, duration_s=20, seed=5,
                           prompt_tokens=(16, 48), decode_tokens=24)
    base = dataclasses.replace(CONFIG, slots=4, pool_pages=40)
    rows = rank_cost_per_token(w, PROFILE, base, chips=4,
                               chip_cost_per_hour=3.6, slo_p99_s=5.0)
    assert [(r.config.tp, r.config.replicas) for r in rows] \
        == [(r.config.tp, r.config.replicas)
            for r in sorted(rows, key=lambda r: (not r.meets_slo,
                                                 r.usd_per_mtoken))]
    top = rows[0]
    assert top.meets_slo
    # the dollar arithmetic: chips × $/chip-hr / 3600 / tok/s × 1e6
    expect = 4 * 3.6 / 3600.0 / top.prediction.tokens_per_s * 1e6
    assert top.usd_per_mtoken == pytest.approx(expect)
    assert top.usd_per_hour == pytest.approx(4 * 3.6)
    # a faster profile cuts $/token — visible once the fleet (not the
    # arrival process) is the throughput bound, so saturate it
    sat = synthetic_workload(rate_rps=200, duration_s=10, seed=5,
                             prompt_tokens=(16, 48), decode_tokens=24)
    slow_sat = rank_cost_per_token(sat, PROFILE, base, chips=4,
                                   chip_cost_per_hour=3.6,
                                   slo_p99_s=1e9, loss_bar=1.0)
    fast = dataclasses.replace(PROFILE, decode_step_s=0.005)
    fast_sat = rank_cost_per_token(sat, fast, base, chips=4,
                                   chip_cost_per_hour=3.6,
                                   slo_p99_s=1e9, loss_bar=1.0)
    assert fast_sat[0].usd_per_mtoken < 0.7 * slow_sat[0].usd_per_mtoken
    # an impossible SLO: nothing meets it, everything ranked anyway
    none_meet = rank_cost_per_token(w, PROFILE, base, chips=4,
                                    chip_cost_per_hour=3.6,
                                    slo_p99_s=1e-4)
    assert not any(r.meets_slo for r in none_meet)
    # SLO dominance: the json form keeps strict-JSON costs
    assert all((r.to_dict()["usd_per_mtoken"] is None)
               == (r.usd_per_mtoken == float("inf"))
               for r in none_meet)
    with pytest.raises(ValueError, match="chip_cost_per_hour"):
        rank_cost_per_token(w, PROFILE, base, chips=4,
                            chip_cost_per_hour=0.0, slo_p99_s=5.0)
    with pytest.raises(ValueError, match="slo_p99_s"):
        rank_cost_per_token(w, PROFILE, base, chips=4,
                            chip_cost_per_hour=1.0, slo_p99_s=0.0)


# ---------------------------------------------------------------------------
# jitter + hedging (measured per-step spread in the simulator)
# ---------------------------------------------------------------------------

JITTER = (0.8, 0.9, 1.0, 1.0, 1.1, 1.5, 2.5)


def test_profile_from_records_extracts_jitter():
    durs = [0.010, 0.010, 0.011, 0.012, 0.009, 0.010, 0.013, 0.030]
    recs = ([{"kind": "span", "name": "serve_decode", "ts": 0.0,
              "dur_s": d} for d in durs]
            + [{"kind": "span", "name": "serve_prefill_chunk",
                "ts": 0.0, "dur_s": 0.008, "tokens": 64}])
    p = ServeProfile.from_records(recs)
    med = p.decode_step_s
    assert p.jitter == tuple(sorted(round(d / med, 6) for d in durs))
    assert p.jitter[-1] == pytest.approx(0.030 / med)   # tail survives
    # fewer than the minimum span count: no jitter claimed
    few = ServeProfile.from_records(recs[:3] + recs[-1:])
    assert few.jitter == ()


def test_jitter_validation_and_canonical_tuple():
    with pytest.raises(ValueError, match="jitter"):
        ServeProfile(decode_step_s=0.01, prefill_chunk_s=0.01,
                     jitter=(1.0, -0.5))
    p = ServeProfile(decode_step_s=0.01, prefill_chunk_s=0.01,
                     jitter=[1.0, 1.2])        # JSON round-trip shape
    assert p.jitter == (1.0, 1.2)


def test_jitter_is_deterministic_and_changes_the_tail():
    w = synthetic_workload(rate_rps=25, duration_s=10, seed=5)
    jittered = dataclasses.replace(PROFILE, jitter=JITTER)
    a = simulate(w, jittered, CONFIG)
    assert a == simulate(w, jittered, CONFIG)
    det = simulate(w, PROFILE, CONFIG)
    # the measured spread must actually reach the prediction
    assert a.latency_p99_s != det.latency_p99_s


def test_hedge_reroutes_stragglers_only_under_jitter():
    w = synthetic_workload(rate_rps=20, duration_s=20, seed=0,
                           process="burst", burst_factor=4.0,
                           prompt_tokens=(64, 256), decode_tokens=32)
    cfg = dataclasses.replace(CONFIG, replicas=2, pool_pages=128,
                              hedge_s=0.2)
    jittered = dataclasses.replace(PROFILE, jitter=JITTER)
    hedged = simulate(w, jittered, cfg)
    assert hedged.hedged > 0
    # same spread, no hedge bar: nothing moves
    assert simulate(w, jittered,
                    dataclasses.replace(cfg, hedge_s=0.0)).hedged == 0
    # hedge bar without measured jitter: deterministic service never
    # straggles, the knob stays a recorded no-op
    assert simulate(w, PROFILE, cfg).hedged == 0


# ---------------------------------------------------------------------------
# pool_split (disaggregated prefill/decode what-if)
# ---------------------------------------------------------------------------

def test_pool_split_rows_shape_and_wire_cost_pinned():
    w = synthetic_workload(rate_rps=30, duration_s=10, seed=0,
                           prompt_tokens=(64, 256), decode_tokens=32)
    cfg = dataclasses.replace(CONFIG, pool_pages=128)
    best, rows = pool_split(w, PROFILE, cfg, 4, page_bytes=1 << 18,
                            wire_gbps=20.0, wire_latency_s=0.001)
    assert [r.prefill_replicas for r in rows] == [0, 1, 2, 3]
    assert [r.decode_replicas for r in rows] == [4, 3, 2, 1]
    colo = rows[0]
    assert colo.is_colocated and colo.prefill is None
    assert colo.migrate_chunk_s == 0.0
    # one chunk = chunk_tokens/page_size pages over the wire + window
    want = 0.001 + (64 / 16) * (1 << 18) / (20.0 * 1e9 / 8.0)
    assert rows[1].migrate_chunk_s == pytest.approx(want)
    for row in rows[1:]:
        assert row.prefill is not None
        assert row.loss_rate >= row.decode.loss_rate
        assert "p:" in row.describe()
    d = rows[1].to_dict()
    assert d["prefill"]["completed"] == len(w.requests)
    # a fast wire at this load: some split beats colocated p99
    assert best is not None and not best.is_colocated
    assert best.decode.latency_p99_s < colo.decode.latency_p99_s


def test_pool_split_slow_wire_colocated_wins():
    w = synthetic_workload(rate_rps=30, duration_s=10, seed=0,
                           prompt_tokens=(64, 256), decode_tokens=32)
    cfg = dataclasses.replace(CONFIG, pool_pages=128)
    best, rows = pool_split(w, PROFILE, cfg, 4, page_bytes=1 << 20,
                            wire_gbps=0.01, wire_latency_s=0.05)
    assert best is None          # migration cost eats the split's win
    assert len(rows) == 4        # the rows still document why


def test_pool_split_validation():
    w = _workload([_req(0, 0.0)])
    with pytest.raises(ValueError, match="chips"):
        pool_split(w, PROFILE, CONFIG, 1)
    with pytest.raises(ValueError, match="multiple"):
        pool_split(w, PROFILE,
                   dataclasses.replace(CONFIG, tp=2), 5)
    with pytest.raises(ValueError, match="wire_gbps"):
        pool_split(w, PROFILE, CONFIG, 4, wire_gbps=0.0)


def test_measured_tp_comm_frac_solves_and_clamps():
    # t(2) = t(1)·(f + (1−f)/2): f=0.2 → 6 ms from a 10 ms base
    assert measured_tp_comm_frac(0.010, 0.006) == pytest.approx(0.2)
    # perfect halving = all compute; slowdown clamps pessimistic
    assert measured_tp_comm_frac(0.010, 0.005) == 0.0
    assert measured_tp_comm_frac(0.010, 0.012) == 0.95
    # tp_base generalization: 2→4 chips
    assert measured_tp_comm_frac(0.010, 0.007, tp_base=2,
                                 tp_scaled=4) == pytest.approx(0.4)
    with pytest.raises(ValueError):
        measured_tp_comm_frac(0.0, 0.01)
    with pytest.raises(ValueError):
        measured_tp_comm_frac(0.01, 0.01, tp_base=2, tp_scaled=2)


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------

def test_calibration_ratios_and_gauges():
    from dtf_tpu.obs.registry import MetricsRegistry
    w = _workload([
        dataclasses.replace(_req(i, 0.1 * i, prompt=64, decode=32),
                            latency_s=0.35, queue_wait_s=0.01)
        for i in range(6)], duration=2.0)
    measured = measured_stats(w)
    pred = simulate(w, PROFILE, CONFIG)
    reg = MetricsRegistry()
    ratios = calibration_ratios(measured, pred, registry=reg)
    assert reg.get("plan_serve_tokens_ratio").value == \
        pytest.approx(ratios["tokens_ratio"])
    assert reg.get("plan_serve_p99_ratio").value == \
        pytest.approx(ratios["p99_ratio"])
    # the simulated latency (~0.32 s) sits near the stipulated 0.35 s
    assert ratios_within(ratios, 2.0)
    assert not ratios_within({"r": 3.0}, 2.0)
    assert not ratios_within({"r": 0.2}, 2.0)


def test_calibration_refuses_empty_measurement():
    w = _workload([dataclasses.replace(_req(0, 0.0), outcome="shed")])
    pred = simulate(_workload([_req(0, 0.0)]), PROFILE, CONFIG)
    with pytest.raises(ValueError):
        calibration_ratios(measured_stats(w), pred)


@pytest.mark.slow
def test_calibration_contract_live_engine(tmp_path):
    """The ci_check stage-10 contract in-process: record a real traced
    engine run, reconstruct workload + profile from the trace alone,
    replay, and land inside the 2× ratio bar — with the gauges in the
    default obs registry."""
    from dtf_tpu.cli.plan_serve_main import main as plan_serve_main
    from dtf_tpu.obs import trace
    from dtf_tpu.obs.registry import default_registry

    bench_dir = tmp_path / "bench"
    try:
        rc = plan_serve_main(["--calibrate", "--calibrate_tolerance",
                              "2.0", "--benchmark_log_dir",
                              str(bench_dir)])
    finally:
        trace.disable()
    assert rc == 0
    reg = default_registry()
    for name in ("plan_serve_tokens_ratio", "plan_serve_p99_ratio"):
        g = reg.get(name)
        assert g is not None and 0.5 <= g.value <= 2.0
    assert (bench_dir / "metric.log").exists()


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------

def test_cli_synthetic_whatifs_and_artifact(tmp_path, capsys):
    from dtf_tpu.cli.plan_serve_main import main as plan_serve_main
    out = tmp_path / "art.json"
    rc = plan_serve_main([
        "--rate", "30", "--duration", "10", "--decode_step_ms", "10",
        "--prefill_chunk_ms", "8", "--chunk_tokens", "64",
        "--target_rps", "40", "--slo_p99", "2.0", "--chips", "4",
        "--pool_sweep", "16,64,128", "--out", str(out)])
    assert rc == 0
    art = json.loads(out.read_text())
    assert art["replicas_for"]["answer"] is not None
    assert len(art["tp_vs_replicas"]["ranked"]) == 3
    assert len(art["pool_vs_shed"]["rows"]) == 3
    text = capsys.readouterr().out
    assert "what-if: replicas for" in text
    assert "what-if: tp × replicas" in text
    assert "what-if: page-pool size" in text


def test_cli_synthetic_needs_a_profile(capsys):
    from dtf_tpu.cli.plan_serve_main import main as plan_serve_main
    assert plan_serve_main(["--rate", "5", "--duration", "5"]) == 2
    assert "decode_step_ms" in capsys.readouterr().err


def test_cli_trace_mode(tmp_path):
    from dtf_tpu.cli.plan_serve_main import main as plan_serve_main
    _recorded_trace(tmp_path)
    out = tmp_path / "art.json"
    rc = plan_serve_main([
        "--trace", str(tmp_path), "--decode_step_ms", "10",
        "--prefill_chunk_ms", "8", "--chunk_tokens", "64",
        "--chips", "2", "--out", str(out)])
    assert rc == 0
    art = json.loads(out.read_text())
    assert art["workload"]["requests"] == 48
    assert art["measured"]["completed"] == 48
    assert len(art["tp_vs_replicas"]["ranked"]) == 2


def test_cli_empty_trace_dir_is_loud(tmp_path):
    from dtf_tpu.cli.plan_serve_main import main as plan_serve_main
    assert plan_serve_main(["--trace", str(tmp_path)]) == 2


def test_cli_pool_split_whatif(tmp_path, capsys):
    from dtf_tpu.cli.plan_serve_main import main as plan_serve_main
    out = tmp_path / "art.json"
    rc = plan_serve_main([
        "--rate", "30", "--duration", "10", "--decode_step_ms", "10",
        "--prefill_chunk_ms", "12", "--chunk_tokens", "64",
        "--prompt_tokens", "64:256", "--decode_tokens", "32",
        "--pool_pages", "128", "--chips", "4", "--pool_split",
        "--migrate_page_bytes", str(1 << 18), "--migrate_wire_gbps",
        "20", "--migrate_latency_ms", "1", "--out", str(out)])
    assert rc == 0
    art = json.loads(out.read_text())
    rows = art["pool_split"]["rows"]
    assert [r["prefill_replicas"] for r in rows] == [0, 1, 2, 3]
    assert rows[0]["prefill"] is None
    assert art["pool_split"]["answer"] is not None
    assert "what-if: prefill:decode split" in capsys.readouterr().out


def test_cli_pool_split_needs_chips(capsys):
    from dtf_tpu.cli.plan_serve_main import main as plan_serve_main
    with pytest.raises(SystemExit, match="chips"):
        plan_serve_main(["--rate", "5", "--duration", "5",
                         "--decode_step_ms", "10",
                         "--prefill_chunk_ms", "8", "--pool_split"])


@pytest.mark.slow
def test_cli_measure_tp_comm_live(tmp_path):
    """Two live traced bursts (tp=1 vs tp=2 over virtual host devices)
    solve the Amdahl split; the gauge lands in the default registry and
    the measured value replaces the documented default."""
    from dtf_tpu.cli.plan_serve_main import main as plan_serve_main
    from dtf_tpu.obs import trace
    from dtf_tpu.obs.registry import default_registry

    out = tmp_path / "art.json"
    try:
        rc = plan_serve_main([
            "--measure_tp_comm", "--calibrate_requests", "6",
            "--calibrate_budget", "12", "--seq", "64",
            "--decode_step_ms", "10", "--prefill_chunk_ms", "8",
            "--rate", "10", "--duration", "5", "--out", str(out)])
    finally:
        trace.disable()
    assert rc == 0
    art = json.loads(out.read_text())
    meas = art["tp_comm_measurement"]
    assert 0.0 <= meas["tp_comm_frac"] <= 0.95
    assert meas["decode_step_s_tp1"] > 0
    assert meas["decode_step_s_tp2"] > 0
    # the what-ifs in the same run used the measured value
    assert art["profile"]["tp_comm_frac"] == meas["tp_comm_frac"]
    g = default_registry().get("plan_serve_tp_comm_frac")
    assert g is not None and g.value == meas["tp_comm_frac"]
