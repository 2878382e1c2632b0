#!/usr/bin/env bash
# One-command CI contract: tier-1 suite + test-budget audit + traced
# smoke run + anomaly cleanliness + chaos smoke (kill → resume →
# trajectory-exactness) + parallelism-planner contract (feasible plans
# compile; predicted step time within tolerance of measured).
#
# Before this script the repo had two CONVENTIONS instead of one
# command: "run tools/marker_audit.py after the suite" (the test-budget
# contract — no unmarked test over the per-test ceiling) and "run
# trace_main --check on a traced run" (the anomaly-cleanliness
# contract — no NaN/step-time/shed anomalies in a healthy smoke run).
# Conventions rot; this script is the executable form:
#
#   1. tier-1 pytest (ROADMAP command shape: CPU, -m 'not slow'),
#      which also writes tests/.last_durations.json via the conftest
#      hook.  Skip with CI_CHECK_SKIP_TESTS=1 when iterating on the
#      later stages.
#   2. tools/marker_audit.py over that durations dump.
#   3. a traced synthetic-data smoke train run (tiny step count) with
#      --trace_dir into a temp dir.
#   4. python -m dtf_tpu.cli.trace_main <dir> --check — exits nonzero
#      on ANY anomaly record (nan_loss, step_time_regression,
#      serve_shed, ...).
#   5. tools/chaos_smoke.py — the fault-tolerance contract: a run
#      killed by an injected crash (dtf_tpu/chaos) under the
#      cli/launch.py supervisor resumes to a BIT-IDENTICAL loss
#      trajectory, and `trace_main --check --allow injected_fault`
#      proves the trace contains the injected fault and nothing else.
#      (The long kill-matrix variants live in tests/test_chaos.py,
#      marked `slow`.)
#   6. the parallelism-planner contract (dtf_tpu/plan):
#      `plan_main --plan auto --out` reproduces the docs' ranked-plan
#      artifact (exits nonzero if the worked example loses
#      feasibility); `plan_main --check` compiles one smoke
#      train step per top feasible-marked plan on the LM and cifar
#      smoke configs (a cost model that blesses un-constructible plans
#      fails HERE, not on a pod); and a calibration smoke records
#      predicted-vs-measured step time + live bytes into the obs
#      registry — exported to metric.log via
#      BenchmarkFileLogger.log_registry — exiting nonzero when the
#      ratio leaves the 2x tolerance.
#   7. tools/data_service_smoke.py — the data-service contract
#      (dtf_tpu/data/service): the 2-worker sharded merged stream is
#      bit-identical to the inline stream, and an imagenet run on
#      synthetic JPEG shards killed by an injected crash resumes —
#      with a DIFFERENT worker count — to a bit-identical per-step
#      loss trajectory (the PR-4 guarantee, extended to the flagship
#      workload).
#   8. tools/serve_smoke.py — the distributed-serving contract
#      (dtf_tpu/serve) on a 4-virtual-device CPU mesh: TP=2 decode
#      (Megatron params + head-sharded KV page pool under shard_map)
#      is token-exact vs TP=1, and the shared-prefix scenario's
#      bars hold — prefix sharing fits >= 2x the concurrent sequences
#      of the no-sharing pool at equal page budget, and the first
#      STREAMED token lands before full retire.
#   9. tools/router_smoke.py — the serving REPLICA-TIER contract
#      (serve/router.py over real cli/replica_main.py subprocesses):
#      with replica_kill / net_partition / slow_replica chaos injected
#      mid-traffic, every accepted request completes TOKEN-EXACT vs an
#      unfaulted baseline, zero requests are lost, the dead replica
#      respawns (budgeted) and re-registers, the partitioned replica
#      re-registers WITHOUT a respawn when the partition heals, and
#      `trace_main --check --allow injected_fault --allow
#      replica_lost` proves the chaos run contained the injected fault
#      + the router's reaction and nothing else.
#  10. the capacity-simulator contract (dtf_tpu/plan/serve_model.py):
#      `plan_serve_main --calibrate` records a live traced engine run,
#      reconstructs the workload + service profile FROM THAT TRACE
#      ALONE (the trace-replay parser end to end), replays it through
#      the analytic fleet model, and exits nonzero when predicted
#      tokens/s or p99 latency leave the 2x ratio bar — with the
#      plan_serve_*_ratio gauges exported to metric.log like stage
#      6's plan_step_time_ratio.
#  11. tools/rollout_smoke.py — the zero-downtime-rollout contract
#      (serve/rollout.py over real replica subprocesses + real
#      exported checkpoints): a mid-traffic rollout to a re-exported
#      IDENTICAL checkpoint completes (DONE) with zero shed / lost /
#      mixed-model requests, token-exact vs baseline, prefix affinity
#      still warm after the whole fleet restarted; a rollout to a
#      genuinely different checkpoint is CAUGHT by the token-exact
#      canary gate and auto-rolls-back; rollout_kill chaos mid-rollout
#      and a truncated NEW checkpoint both resolve to ROLLED_BACK with
#      the fleet token-exact on the old model; and `trace_main
#      --check` with the rollout allowlist is green.
#  12. python -m tools.dtflint — the project-wide static-analysis
#      ratchet: the lock-
#      discipline race detector (_GUARDED_BY), determinism/JAX-hazard
#      lint (wall-clock/RNG/set-order in bit-exactness modules,
#      unaccounted host syncs in step loops), vocabulary closure
#      (trace kinds ↔ obs/vocab.py, metric-name grammar, chaos kinds
#      ↔ probe points), flag wiring (dead flags, doc'd flags that
#      don't exist, PLAN_OWNED_FLAGS drift), and the test-budget
#      audit folded in as the test-marker rule.  Fails on any NEW
#      finding vs the committed (EMPTY) baseline; suppressions
#      require a written reason.
#  13. tools/zero_smoke.py — the fully-sharded data-parallelism
#      contract (--zero_stage 2/3, train/zero.py): ZeRO-2/3 per-step
#      loss ≡ replicated within the documented float tolerance; the
#      planner marks a transformer config replicated-INFEASIBLE on a
#      simulated mesh while zero=3 fits, and that config trains under
#      ZeRO-3 matching a smaller-mesh replicated oracle; the measured
#      --zero_probe gauges show exposed comm strictly below the
#      serialized collective wall (the overlap is real, not modeled);
#      and plan_main --calibrate holds the 2x contract for zero ∈ {2,3}.
#  14. tools/elastic_smoke.py — the elastic-training contract
#      (train/elastic.py + the launch.py --elastic supervisor): a run
#      losing a host mid-training (host_loss chaos — an unprompted
#      SIGKILL) under --elastic resumes on HALF the devices at the
#      sealed checkpoint, with the shrunken window's per-step loss
#      trajectory BIT-IDENTICAL to an oracle launched fresh on N/2
#      from the same checkpoint; when capacity re-announces the
#      supervisor drains at a checkpoint boundary and grows the job
#      back to N; device_loss (exit 76) classifies + reshards too; and
#      `trace_main --check --allow injected_fault --allow
#      host_loss/device_loss` is green.
#  15. tools/disagg_smoke.py — the disaggregated-serving contract
#      (prefill/decode pool split + wire KV-page migration,
#      serve/migrate.py + router pool roles): a 1p:1d tier is
#      TOKEN-EXACT vs a colocated oracle with chains migrating their
#      KV pages over the wire and exact repeats re-homed to the
#      decode pool; SIGKILLing the prefill replica mid-burst loses
#      zero requests (mid-transfer migrations fail loudly, requests
#      fail over); and a page_fetch_stall chaos arm proves a
#      congested fabric is an efficiency loss, never a correctness
#      event.
#  16. tools/router_ha_smoke.py — the router high-availability
#      contract (serve/ha.py + the request journal, over real replica
#      subprocesses): the leader router is SIGKILLed mid-burst
#      (router_kill chaos — dispatches in flight, journal tail
#      un-synced), a warm standby waits out the fenced lease, adopts
#      the LIVE tier (zero replica respawns, engine pids stable),
#      replays the journal, and every client stream is exactly-once
#      TOKEN-EXACT vs an unfaulted baseline with zero lost requests;
#      a split-brain usurper fences the deposed leader at the
#      replicas (stale_epoch); and lease_stall chaos proves a
#      GC-paused leader discovers it is fenced instead of resuming.
#
# Usage: tools/ci_check.sh            # the full contract
#        CI_CHECK_SKIP_TESTS=1 tools/ci_check.sh   # stages 2-16 only

set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}

if [ "${CI_CHECK_SKIP_TESTS:-0}" != "1" ]; then
    echo "== ci_check [1/16]: tier-1 test suite =="
    # the shape of the driver's command (commands[0] of
    # /root/TESTS_LAST_RUN.json): six xdist workers, a file to a
    # worker, 1,470 s
    timeout -k 10 1470 python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider \
        -p xdist -n 6 --dist loadfile -p no:randomly
else
    echo "== ci_check [1/16]: SKIPPED (CI_CHECK_SKIP_TESTS=1) =="
fi

echo "== ci_check [2/16]: marker audit (test-budget contract) =="
python tools/marker_audit.py

echo "== ci_check [3/16]: traced smoke run =="
TRACE_DIR=$(mktemp -d)
trap 'rm -rf "$TRACE_DIR"' EXIT
python -m dtf_tpu.cli.lm_main --use_synthetic_data --train_steps 3 \
    --batch_size 4 --model transformer_small --seq_len 64 \
    --model_dir "$TRACE_DIR/run" --skip_checkpoint \
    --trace_dir "$TRACE_DIR" >/dev/null

echo "== ci_check [4/16]: anomaly cleanliness =="
python -m dtf_tpu.cli.trace_main "$TRACE_DIR" --check

echo "== ci_check [5/16]: chaos smoke (kill -> resume -> exactness) =="
python tools/chaos_smoke.py

echo "== ci_check [6/16]: parallelism planner (check + calibration) =="
python -m dtf_tpu.cli.plan_main --model transformer_tpu --dataset lm \
    --seq_len 2048 --batch_size 256 --dtype bf16 --optimizer adamw \
    --plan_mesh 4x4 --plan auto --top 12 \
    --out "$TRACE_DIR/PLAN_4x4.json" >/dev/null
python -m dtf_tpu.cli.plan_main --devices 8 --model transformer_small \
    --dataset lm --use_synthetic_data --seq_len 64 --batch_size 8 \
    --check --check_top 2 --top 0 >/dev/null
python -m dtf_tpu.cli.plan_main --devices 2 --model resnet20 \
    --dataset cifar10 --use_synthetic_data --batch_size 8 \
    --plan_mesh hosts=1,devices=2 --check --check_top 1 --top 0 >/dev/null
python -m dtf_tpu.cli.plan_main --model transformer_small --dataset lm \
    --use_synthetic_data --seq_len 64 --batch_size 4 --optimizer adamw \
    --calibrate --calibrate_tolerance 2.0 --top 0 \
    --benchmark_log_dir "$TRACE_DIR/plan_bench"
grep -q plan_step_time_ratio "$TRACE_DIR/plan_bench/metric.log"

echo "== ci_check [7/16]: data-service smoke (sharded determinism + imagenet resume exactness) =="
python tools/data_service_smoke.py

echo "== ci_check [8/16]: multi-device serve smoke (TP exactness + prefix-sharing/streaming bars) =="
python tools/serve_smoke.py

echo "== ci_check [9/16]: router smoke (replica tier: kill/partition/slow chaos -> token-exact failover) =="
python tools/router_smoke.py

echo "== ci_check [10/16]: capacity-simulator smoke (record -> replay -> calibrate) =="
python -m dtf_tpu.cli.plan_serve_main --calibrate --calibrate_tolerance 2.0 \
    --benchmark_log_dir "$TRACE_DIR/serve_plan_bench"
grep -q plan_serve_tokens_ratio "$TRACE_DIR/serve_plan_bench/metric.log"

echo "== ci_check [11/16]: rollout smoke (zero-downtime rollout: canary gate, rollback, rollout chaos) =="
python tools/rollout_smoke.py

echo "== ci_check [12/16]: dtflint (static analysis: lock discipline, determinism, vocab closure, flag wiring) =="
python -m tools.dtflint

echo "== ci_check [13/16]: zero smoke (ZeRO-2/3 ≡ replicated, infeasible-replicated config trains, measured overlap, 2x calibration) =="
python tools/zero_smoke.py

echo "== ci_check [14/16]: elastic smoke (host/device loss -> shrink resume oracle-exact -> grow back) =="
python tools/elastic_smoke.py

echo "== ci_check [15/16]: disagg smoke (prefill/decode split: migrate -> re-home token-exact, kill prefill replica -> zero lost, stalled fabric) =="
python tools/disagg_smoke.py

echo "== ci_check [16/16]: router HA smoke (leader kill -> journal takeover exactly-once, split brain fenced, lease stall) =="
python tools/router_ha_smoke.py

echo "ci_check: OK"
