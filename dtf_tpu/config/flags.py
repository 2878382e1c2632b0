"""Typed configuration + CLI flag system.

One config system covering both hyperparameters and cluster topology —
the unification SURVEY.md §5.6 calls for.  The reference splits this
between absl flags (`official.utils.flags.core` groups composed by
`common.define_keras_flags`, reference common.py:248-309) and the
`TF_CONFIG` env JSON / `--worker_hosts --task_index` pair
(reference resnet_imagenet_main.py:108-110, ps_server/*_ps_0.py:40-50).

Here everything is a single dataclass, every field is a CLI flag
(``--name value`` or ``-name value``, absl style), per-process identity
may come from env vars, and a ``TF_CONFIG``-format JSON is still
understood for drop-in parity.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional

# Strategy names accepted by --distribution_strategy.  Mirrors the
# reference's set (SURVEY.md §2.2) plus the TPU-native mode that
# BASELINE.json's north star names.
STRATEGIES = (
    "off",
    "one_device",
    "mirrored",
    "multi_worker_mirrored",
    "horovod",
    "parameter_server",
    "tpu",
)

DTYPES = ("fp32", "float32", "bf16", "bfloat16", "fp16", "float16")


@dataclasses.dataclass
class Config:
    """Every knob of a run.  Field comments cite the reference flag they
    provide parity for."""

    # --- base (official.utils.flags.core define_base) ---
    data_dir: str = ""                  # --data_dir
    model_dir: str = "/tmp/dtf_tpu"     # --model_dir
    clean: bool = False                 # model_helpers.apply_clean (imagenet_main.py:275)
    batch_size: int = 128               # global batch size, --batch_size
    train_epochs: int = 182             # --train_epochs (cifar default, cifar_main.py:226-230)
    epochs_between_evals: int = 1       # --epochs_between_evals
    stop_threshold: Optional[float] = None  # --stop_threshold
    export_dir: str = ""                # --export_dir (SavedModel equiv: orbax export)

    # --- performance (define_performance) ---
    dtype: str = "fp32"                 # --dtype; bf16 is the TPU-native mixed mode
    # --loss_scale: a number (static scale) or "dynamic" (TF2
    # LossScaleOptimizer semantics); only meaningful for fp16 parity
    loss_scale: Optional[Any] = None
    enable_xla: bool = True             # --enable_xla: always-on under JAX  # dtflint: disable=flag-dead (declared reference-parity no-op: XLA is unconditional under jax)
    all_reduce_alg: Optional[str] = None  # --all_reduce_alg (cifar_main.py:104)  # dtflint: disable=flag-dead (declared reference-parity no-op: XLA picks the collective on TPU)
    num_packs: int = 1                  # --num_packs gradient packing  # dtflint: disable=flag-dead (declared reference-parity no-op: XLA fuses collectives)
    datasets_num_private_threads: Optional[int] = None  # input pipeline threads
    # DCT-space 1/2–1/8 scaled decode (libjpeg scale_denom) for train
    # crops >=2x the output size: skips most IDCT work on large crops.
    # Changes the downsampling filter chain (scaled decode + bilinear
    # vs pure bilinear) — a throughput opt-in, never a default
    input_scaled_decode: bool = False
    # Host→device batch wire for the real-data pipelines.  "uint8"
    # (default, TPU-native): raw pixels over the wire — 4x fewer bytes
    # than f32 (the measured bottleneck of the r3 recorded runs) — with
    # normalization as the first op inside the compiled step (the
    # reference keeps it in-graph too, imagenet_preprocessing.py:
    # 397-430).  "float32": host-side normalization (r1-r3 wire).
    input_wire: str = "uint8"
    # --- host-side data service (dtf_tpu/data/service) ---
    # Imagenet TRAIN batches come from the sharded deterministic
    # multi-process service by default: batch n is a pure function of
    # (seed, process, n), so killed-at-K resume is bit-exact and decode
    # scales past the single-process GIL ceiling.  False = the legacy
    # threaded pipeline (fused native decode; NOT position-exact — a
    # mid-stream resume is refused loudly).
    input_service: bool = True
    # static shard count of the TFRecord file set.  Part of the stream's
    # identity: the merged batch order depends on it, so a resumed run
    # must keep the value the checkpoint was written with (validated
    # from host_state).  Size it >= input_workers; the default (16)
    # suits the production 1024-file layout — toy directories with
    # fewer files than shards fail loudly with the flag to lower.
    input_num_shards: int = 16
    # spawned shard-worker processes; -1 (default) = auto: one per
    # host core, capped by input_num_shards (inline when the host has
    # a single core); 0 = run every shard inline (no subprocess —
    # tests, benchmark baselines).  Worker count NEVER changes the
    # stream — workers only decide who computes a batch, not what the
    # batch is — so auto-sizing (and changing it across a resume) is
    # safe by construction.
    input_workers: int = -1
    # decode-once cache tier: directory for the per-shard mmap-backed
    # cache of decoded images ("" = off).  Epoch >= 2 and co-hosted
    # replicas skip JPEG decode entirely; cached and uncached runs are
    # bit-identical by construction.
    input_cache_dir: str = ""
    input_cache_limit_mb: int = 0       # per-shard cache byte bound; 0 = unbounded
    per_gpu_thread_count: int = 0       # no-op compat (common.py:143-166 is CUDA-only)  # dtflint: disable=flag-dead (declared no-op: CUDA-only knob, kept for reference CLI parity)
    tf_gpu_thread_mode: Optional[str] = None  # no-op compat  # dtflint: disable=flag-dead (declared no-op: CUDA-only knob, kept for reference CLI parity)
    batchnorm_spatial_persistent: bool = False  # no-op compat (cuDNN-only, common.py:368-377)  # dtflint: disable=flag-dead (declared no-op: cuDNN-only knob, kept for reference CLI parity)

    # --- image / data ---
    # --data_format (reference resnet_cifar_main.py:94-98): channels_first
    # means batches are fed NCHW; the train/eval steps transpose to NHWC
    # (compute is always NHWC — the MXU layout)
    data_format: str = "channels_last"
    use_synthetic_data: bool = False    # --use_synthetic_data (common.py:311-359)
    # Eval partial-batch handling.  False (default): eval pipelines pad
    # the final partial batch and mask the padding, so eval covers the
    # reference's exact full set (imagenet_preprocessing.py:259-323) with
    # static shapes.  True: drop it (every eval batch full — benchmark
    # purity).  Training always drops the remainder for static shapes
    # (imagenet_main.py:143-145 XLA parity).
    drop_remainder: bool = False
    image_bytes_as_serving_input: bool = False  # compat  # dtflint: disable=flag-dead (declared no-op: TF serving-signature knob with no orbax analog; kept for reference CLI parity)

    # --- keras-flags extras (common.py:248-309) ---
    enable_eager: bool = False          # no-op: JAX is eager outside jit by construction  # dtflint: disable=flag-dead (declared no-op by construction; kept for reference CLI parity)
    skip_eval: bool = False             # --skip_eval
    eval_only: bool = False             # evaluate (a restored checkpoint) and exit
    use_trivial_model: bool = False     # --use_trivial_model (imagenet_main.py:189-191)
    report_accuracy_metrics: bool = True  # --report_accuracy_metrics (common.py:277-278)
    use_tensor_lr: bool = False         # --use_tensor_lr → PiecewiseConstantDecayWithWarmup
    enable_tensorboard: bool = False    # --enable_tensorboard (common.py:187-190)
    train_steps: Optional[int] = None   # --train_steps cap (common.py)
    profile_steps: Optional[str] = None  # --profile_steps "start,stop" (common.py:289-296)
    # partial-batch handling (reference resnet_cifar_main.py:108-141):
    # True forces drop_remainder=False (eval covers the partial batch)
    enable_get_next_as_optional: bool = False
    log_steps: int = 100                # --log_steps for BenchmarkMetric cadence
    skip_checkpoint: bool = False       # rank-0 checkpoints off (horovod mains default on)
    resume: bool = False                # restore latest checkpoint from model_dir
    # preemption-granularity checkpointing: additionally save (sync,
    # sealed with an integrity manifest) every N global steps.  0 = the
    # reference's per-epoch-only cadence.  On preemptible pods the
    # epoch is far too coarse a recovery unit — a rank lost mid-epoch
    # re-trains the whole epoch
    checkpoint_steps: int = 0

    # --- benchmark (define_benchmark) ---
    benchmark_log_dir: str = ""         # --benchmark_log_dir
    benchmark_test_id: str = ""         # --benchmark_test_id

    # --- model / dataset selection ---
    model: str = ""                     # resnet50 | resnet56|resnet20|resnet32|resnet110 | trivial
    dataset: str = ""                   # cifar10 | imagenet
    num_classes: Optional[int] = None   # override (imagenet: 1001, cifar: 10)
    seq_len: Optional[int] = None       # override the LM dataset's sequence length

    # --- distribution / topology (TF_CONFIG successor) ---
    distribution_strategy: str = "mirrored"  # --distribution_strategy
    ps_mode: str = "sync"               # parameter_server flavor: sync SPMD
                                        # (north star) | async (C++ param
                                        # store, capability-exact, parallel/ps)
    ps_wire: str = "fp32"               # async-PS wire format: fp32 | bf16
                                        # (bf16 halves pull/push traffic;
                                        # store math stays fp32)
    # async-PS fault tolerance (r5): the PS rank restores from
    # <dir>/ps_store.snap at startup when present, snapshots
    # params+velocity+version there every ps_snapshot_secs (atomic
    # tmp+rename), and workers reconnect with backoff instead of dying
    # with the store.  None = the reference's behavior (in-memory only,
    # "Workers will need to restart training", ps_server/log1.log).
    ps_snapshot_dir: Optional[str] = None
    ps_snapshot_secs: float = 30.0
    ps_reconnect_secs: float = 300.0    # how long workers retry a dead
                                        # PS before giving up (only with
                                        # ps_snapshot_dir — reconnecting
                                        # to an unrestored store hangs)
    # how many store versions a restarted PS may trail what a worker
    # already saw before the worker refuses to continue (guard against
    # silently resuming a mid-schedule run on a store that lost its
    # state).  Size >= cluster pushes/sec x ps_snapshot_secs + margin.
    # Default single-sourced from parallel/ps.py DEFAULT_RESEED_TOLERANCE
    # (10,000); kept as a literal here because Config must import
    # without pulling the ps module — parity asserted by test_ps.
    ps_reseed_tolerance: int = 10_000
    num_devices: Optional[int] = None   # ≈ --num_gpus: local chips to use; None = all
    worker_hosts: Optional[str] = None  # --worker_hosts "h1:p,h2:p" (imagenet_main.py:108-110)
    task_index: int = -1                # --task_index
    coordinator_address: Optional[str] = None  # jax.distributed coordinator
    process_id: Optional[int] = None
    process_count: Optional[int] = None
    # mesh axis sizes; data axis is inferred from the rest (SURVEY §5.7:
    # keep model/seq axes open even though the reference is DP-only)
    model_parallelism: int = 1          # size of the 'model' mesh axis
    seq_parallelism: int = 1            # size of the 'seq' mesh axis (ring attention)
    # column-parallel lm_head over 'model' (Megatron vocab-parallel
    # softmax): local logits + collective CE; transformer family only
    shard_lm_head: bool = False
    sync_bn: bool = False               # cross-replica BN (reference default: per-replica)

    # --- mixture-of-experts (moe_transformer family) ---
    # None = the model preset's own default (e.g. moe_transformer_small
    # ships 4 experts); set a value to override it
    num_experts: Optional[int] = None   # total experts; sharded over 'data' (EP)
    moe_capacity_factor: Optional[float] = None  # per-expert capacity multiplier
    moe_aux_weight: Optional[float] = None  # load-balance aux-loss weight
    moe_top_k: Optional[int] = None     # router choices: 1=Switch, 2=GShard
    # --- pipeline parallelism (pipeline_transformer family) ---
    num_microbatches: Optional[int] = None  # GPipe microbatches per step
    # 2 = two virtual stages per device (Megatron interleaving): halves
    # the fill/drain bubble at equal num_microbatches for the cost of
    # 2x ppermute hops (models/pipeline_lm.py docstring)
    pipeline_interleave: int = 1

    # --- optimizer ---
    optimizer: str = "sgd"              # sgd (reference, common.py:169-172)
                                        # | adamw (transformer LM recipe)
    # gradient accumulation: each step runs this many sequential
    # microbatch fwd/bwd passes per replica before one update — trains
    # reference-scale global batches on fewer chips
    grad_accum_steps: int = 1
    # rematerialization (jax.checkpoint) around each transformer block:
    # trade recompute FLOPs for HBM — the long-context memory lever
    remat: bool = False
    # selective remat (implies --remat): "dots" saves matmul/attention
    # outputs and recomputes only elementwise ops in the backward — a
    # cheaper memory lever than full remat (no MXU recompute), for
    # contexts where activations don't fit without remat
    # (models/transformer.py remat_policy has the measured frontier)
    remat_policy: Optional[str] = None
    # clip gradients to this global L2 norm (computed across every
    # shard of every parameter); None = no clipping
    clip_grad_norm: Optional[float] = None
    # ZeRO-1 / weight-update sharding (Xu et al. 2020, "Automatic
    # Cross-Replica Sharding of Weight Update in Data-Parallel
    # Training"): reduce-scatter gradients, update a 1/N parameter
    # slice per data shard with 1/N optimizer state, all-gather the
    # updated params — optimizer memory and update FLOPs drop by the
    # data-parallel degree at equal communication volume.  Kept as the
    # stage-1 shorthand; --zero_stage is the full lever
    optimizer_sharding: bool = False
    # ZeRO stage on the data axis (train/loop.py, train/zero.py):
    #   0 = replicated everything (plain DP)
    #   1 = sharded optimizer state (≡ --optimizer_sharding)
    #   2 = + sharded gradients: each microbatch's grads reduce-scatter
    #       into 1/N slices as the backward produces them (per-leaf, so
    #       XLA's latency-hiding scheduler overlaps the collectives
    #       with compute); the grad-accumulation buffer shrinks by the
    #       data-parallel degree
    #   3 = + sharded parameters: params live as 1/N column slices and
    #       are all-gathered per leaf at the top of each step — a model
    #       whose replicated state does not fit one device trains
    # Every stage is mathematically identical to plain DP (test-pinned
    # within the documented float tolerance); checkpoints are written
    # in the canonical stage-0 layout, so any stage restores into any
    # other and into serving via the bridge
    zero_stage: int = 0
    # ZeRO-2/3 grad reduce-scatter WIRE format: fp32 (default) | bf16.
    # bf16 halves the per-microbatch scatter volume — the collective
    # then also sums in bf16 (the --ps_wire bf16 trade, applied to the
    # FSDP path); the slices and the cross-microbatch accumulation
    # stay f32 (train/zero.py scatter_leaf).  Documented loss
    # tolerance vs the f32 wire is pinned by tests/test_zero_stages.py
    zero_wire: str = "fp32"
    # measure the ZeRO collective cost (stages >= 2): time standalone
    # reduce-scatter/all-gather probes plus a comm-stubbed twin of the
    # compiled step, and export train_zero_*_wall_s +
    # train_exposed_comm_frac gauges through the MFU ledger.  Costs one
    # extra step compile — a smoke lever, not a production
    # default
    zero_probe: bool = False

    # --- serving (cli/serve_main.py over dtf_tpu/serve) ---
    serve_max_batch: int = 8            # decode slots = max concurrent sequences
    serve_max_delay_ms: float = 5.0     # batch-fill window after first arrival
    serve_queue_size: int = 64          # bounded admission queue (backpressure)
    serve_max_seq_len: Optional[int] = None  # cache capacity; None = model max
    serve_max_new_tokens: int = 32      # per-request generation budget (demo)
    serve_temperature: float = 0.0      # 0 = greedy
    serve_requests: int = 16            # synthetic-traffic demo request count
    serve_prompt_len: int = 8           # synthetic prompt length (max; varied)
    # KV page pool (serve/engine.py, ops/paged_attention.py): tokens
    # per KV page (>= 1).  HBM admission is bounded by tokens in
    # flight, not num_slots x max_seq_len
    kv_page_size: int = 16
    # total pool pages INCLUDING the scratch page; 0 = one full
    # max_seq_len reservation per slot (1 + slots x pages-per-slot).
    # Size it down (e.g. 50%) when mean request length << max_seq_len
    kv_pool_pages: int = 0
    # chunked-prefill unit in tokens (multiple of kv_page_size): long
    # prompts prefill one chunk per engine iteration, interleaved with
    # decode steps for running slots; 0 = whole-prompt single chunk;
    # None (default) = 4 pages, valid at ANY page size
    serve_prefill_chunk: Optional[int] = None
    # serving tensor parallelism: shard decode params (Megatron
    # column/row layout) and every layer's KV page pool (head dim)
    # over a 'model' mesh axis of this many chips — the bridge
    # restores train/export/ZeRO checkpoints DIRECTLY into the sharded
    # layout, so a model that trains sharded never has to fit on one
    # chip to serve.  Needs the paged cache (kv_page_size > 0)
    serve_tp: int = 1
    # prefix sharing (paged cache): refcounted pages + a token-id-hash
    # registry of full prompt-prefix pages — N requests sharing a
    # system prompt cost ONE physical copy; copy-on-write protects the
    # one shared-page write (serve/engine.py module docs)
    serve_prefix_sharing: bool = True

    # --- serving replica tier (serve/router.py over cli/replica_main) ---
    # replica serve processes behind the router (cli/router_main.py);
    # each is a full ServeEngine (optionally TP-sharded via --serve_tp)
    router_replicas: int = 2
    # default per-request deadline: the router resolves every accepted
    # request — tokens, Backpressure, or DeadlineExceeded — within it
    router_deadline_s: float = 120.0
    # router-level admission bound: outstanding (queued + in-flight)
    # requests beyond this shed loudly with Backpressure(retry_after)
    router_admission: int = 128
    # health-probe cadence (reads each replica's heartbeat_rank{K}.json)
    router_probe_s: float = 0.5
    # heartbeat silence past this = the replica is declared lost (its
    # in-flight re-dispatches; must be comfortably > --heartbeat_secs)
    router_health_timeout_s: float = 15.0
    # per-replica in-flight dispatch cap; 0 = auto (serve_queue_size)
    router_replica_inflight: int = 0
    # replica respawn budget: at most this many respawns per sliding
    # window, exponential backoff between them, then loud give-up —
    # the launcher supervisor's crash discipline, per replica
    router_max_respawns: int = 8
    router_respawn_window_s: float = 300.0
    router_respawn_backoff_s: float = 0.5
    # hedge: re-dispatch a request to a second replica when its first
    # makes no progress for this long (greedy decode makes the copies
    # token-identical; first done wins).  0 = off
    router_hedge_s: float = 0.0
    # placement policy: prefix-affine (route by chained prompt-page
    # digest to the replica whose PrefixRegistry is warm, least-loaded
    # fallback) | least_loaded | random (the comparison arm)
    router_placement: str = "affinity"
    # disaggregation: replicas 0..N-1 form a prefill-specialized pool,
    # the rest a decode pool — cold prompts prefill in the first,
    # their KV-page chains migrate over the wire (serve/migrate.py)
    # and re-home to the second, so warm shared-prefix traffic decodes
    # prefill-free.  Needs router_placement=affinity.  0 = colocated
    # (the default: every replica does both, no migration)
    router_prefill_replicas: int = 0
    # rendezvous directory for announce + heartbeat files (router +
    # cli/replica_main); "" = router_main picks a temp dir.  Put it on
    # SHARED storage and the tier goes cross-host: replicas announce
    # host:port (--serve_host) and register/heal identically to local
    # ones — the wire is plain TCP
    rendezvous_dir: str = ""
    # replica identity for cli/replica_main; -1 = from DTF_PROCESS_ID
    replica_id: int = -1
    # address a replica binds AND announces (replica_rank{K}.json
    # "host" field): 127.0.0.1 = single-host loopback (default); a
    # routable address makes the replica reachable from a router on
    # another host
    serve_host: str = "127.0.0.1"
    # --- router high availability (serve/journal.py + serve/ha.py) ---
    # journal every request's lifecycle to router_journal.jsonl in the
    # rendezvous dir and take the shared-storage leader lease: a
    # successor router (restart or warm standby) replays the journal
    # and re-adopts in-flight requests exactly-once.  Off by default —
    # a single-router tier pays zero overhead.
    router_ha: bool = False
    # run router_main as the WARM STANDBY: wait for the leader's lease
    # to expire, then take over under the next fencing epoch (implies
    # router_ha; never spawns replicas — the leader owns them)
    router_standby: bool = False
    # leader-lease time-to-live: the standby takes over after the
    # leader misses ~1 TTL of renewals (renewal cadence is TTL/3)
    router_lease_ttl_s: float = 2.0
    # bounded journal fsync cadence: a HOST crash loses at most this
    # much journal tail (a process crash loses nothing — every append
    # is flushed)
    router_journal_fsync_s: float = 0.05

    # --- zero-downtime rollout (serve/rollout.py over the router) ---
    # rollout the tier onto this checkpoint (a model_dir or
    # export_dir path) mid-traffic: drain one replica at a time,
    # canary-gate the first against the old model token-by-token,
    # auto-rollback on breach.  "" = no rollout
    rollout_checkpoint: str = ""
    # completed old-vs-new comparisons the canary gate requires
    rollout_canary_requests: int = 4
    # slice of live greedy traffic mirrored to the canary (0, 1]
    rollout_mirror_fraction: float = 1.0
    # gate threshold on diverged/compared; 0.0 = token-exact (any
    # single divergence rolls back: identical models compare EQUAL,
    # so a mismatch is signal)
    rollout_max_divergence: float = 0.0
    # how long a restarted replica gets to warm + re-register before
    # the rollout declares the new checkpoint unserveable + rolls back
    rollout_warm_timeout_s: float = 600.0
    # persisted rollout state file; "" = <rendezvous>/rollout_state
    # .json — a router restarted mid-rollout resumes or rolls back
    # deterministically from it
    rollout_state: str = ""

    # --- parallelism planner (dtf_tpu/plan) ---
    # "" = off (hand-set flags rule, the pre-planner behavior);
    # "auto" = search the feasible plan lattice on --plan_mesh and
    # compile the fastest predicted plan into the parallelism flags;
    # <path> = a plan JSON (plan_main --out artifact, a {"plan": ...}
    # wrapper, or a bare plan object).  A plan-selected run is
    # bit-identical to the same flags set by hand (tests/test_plan.py);
    # plan-owned flags (--model_parallelism & co.) must stay at their
    # defaults when --plan is given — conflicts are loud errors.
    plan: str = ""
    # mesh descriptor the planner costs against: "" = the live runtime
    # topology, a preset (cpu | v4-8 | v5e-4 | 4x4), or an explicit
    # "hosts=4,devices=4,hbm=32g,flops=140t,intra=100g,inter=25g"
    plan_mesh: str = ""
    # ranked-lattice memoization sidecar (plan/cache.py): a JSON file
    # keyed by (workload, mesh descriptor, batch) — repeated
    # `--plan auto` resolves (launcher restarts!) and plan_main
    # rankings skip the search on a hit.  "" = off
    plan_cache: str = ""
    # cross-run checkpoint GC by verified-set (train/checkpoint.py
    # Checkpointer.gc): after training, delete all but the newest N
    # sha256-VERIFIED steps (steps newer than the newest verified one —
    # e.g. an in-flight unsealed save — are never touched; with no
    # verified step at all nothing is deleted).  0 = off (orbax's
    # in-run max_to_keep still applies)
    checkpoint_keep: int = 0

    # --- observability (dtf_tpu/obs) ---
    # structured JSONL tracing: each process writes
    # <trace_dir>/trace_rank{N}.jsonl (step/compile/checkpoint/ps/serve
    # spans + anomaly events); summarize with
    # `python -m dtf_tpu.cli.trace_main <trace_dir>`.  "" = off (the
    # DTF_TRACE_DIR env var — forwarded by the launcher — also enables)
    trace_dir: str = ""
    # abort loudly (structured anomaly + TrainingAnomaly) on the first
    # non-finite loss that reaches the host; checked at --log_steps
    # cadence on the value the loop already syncs — no extra device
    # round-trip
    nan_guard: bool = True
    # flag a log window taking > factor x the rolling median of recent
    # windows (input-pipeline stall / straggler signature); reports,
    # never aborts.  0 disables.
    step_time_guard_factor: float = 3.0
    # heartbeat file rewrite interval (launcher supervision); the file
    # is only written when the launcher exports DTF_HEARTBEAT_DIR
    heartbeat_secs: float = 5.0
    # live scrape endpoint: the owning registry as Prometheus text
    # over stdlib http.server on this port (GET /metrics) plus a
    # GET /healthz JSON probe (200/503).  Train: rank 0, the default
    # registry.  router_main: the router registry on this port and
    # replica K's engine registry on port+1+K (one flag makes the
    # whole tier scrapable).  replica_main standalone: the engine
    # registry.  0 = off (the default)
    metrics_port: int = 0
    # poll the GCE/TPU metadata preemption endpoint every N seconds in
    # a daemon thread; a pending preemption feeds the SIGTERM latch
    # (train/preemption.py), so the emergency-checkpoint path runs even
    # when the scheduler signals via metadata before the SIGTERM lands.
    # 0 = off (the default — most schedulers do deliver SIGTERM).
    # DTF_METADATA_URL overrides the endpoint (tests, other clouds)
    preemption_poll_s: float = 0.0

    # --- chaos (dtf_tpu/chaos: deterministic fault injection) ---
    # comma-separated fault specs, e.g. "crash@step:120",
    # "sigterm@rank1:step:80", "ps_drop@version:50",
    # "heartbeat_stall@step:60", "ckpt_truncate@latest"; serving
    # replica tier: "replica_kill@req:6" (router SIGKILLs the Nth
    # dispatch's replica), "net_partition@replica1:12" (drop replica
    # 1's health probes for 12 prober ticks), "slow_replica@replica1:4"
    # (4x decode steps in replica 1).  "" = off (the DTF_FAULT env var
    # also arms it).  Provably zero-cost when unset: every probe is a
    # module-level None check (tests/test_chaos.py)
    fault: str = ""

    # --- misc ---
    seed: int = 0
    verbose: int = 2                    # keras fit verbose parity (rank-gated)

    def __post_init__(self):
        if self.data_format not in ("channels_last", "channels_first"):
            raise ValueError(
                f"unknown data_format {self.data_format!r}; choose "
                f"channels_last or channels_first")
        if self.enable_get_next_as_optional and self.drop_remainder:
            # reference semantics: get_next_as_optional exists to handle
            # the partial final batch — forcing drop would contradict it
            self.drop_remainder = False
        if self.distribution_strategy not in STRATEGIES:
            raise ValueError(
                f"unknown distribution_strategy {self.distribution_strategy!r}; "
                f"choose from {STRATEGIES}")
        if self.dtype not in DTYPES:
            raise ValueError(f"unknown dtype {self.dtype!r}; choose from {DTYPES}")
        if self.pipeline_interleave not in (1, 2):
            raise ValueError(
                f"pipeline_interleave must be 1 or 2, got "
                f"{self.pipeline_interleave}")
        if self.input_wire not in ("uint8", "float32"):
            raise ValueError(
                f"unknown input_wire {self.input_wire!r}; choose uint8 "
                f"or float32")
        if self.ps_wire not in ("fp32", "bf16"):
            raise ValueError(
                f"unknown ps_wire {self.ps_wire!r}; choose fp32 or bf16")
        if self.ps_mode not in ("sync", "async"):
            raise ValueError(
                f"unknown ps_mode {self.ps_mode!r}; choose sync or async")
        if self.optimizer not in ("sgd", "momentum", "adamw"):
            raise ValueError(
                f"unknown optimizer {self.optimizer!r}; choose sgd or adamw")
        if self.loss_scale is not None:
            if str(self.loss_scale).lower() != "dynamic":
                try:
                    val = float(self.loss_scale)
                except (TypeError, ValueError):
                    raise ValueError(
                        f"loss_scale must be a number or 'dynamic', got "
                        f"{self.loss_scale!r}") from None
                import math
                if not math.isfinite(val) or val <= 0:
                    raise ValueError(
                        f"loss_scale must be a positive finite number, "
                        f"got {val}")
        if self.zero_stage not in (0, 1, 2, 3):
            raise ValueError(
                f"zero_stage must be 0, 1, 2 or 3, got {self.zero_stage}")
        if self.optimizer_sharding and self.zero_stage >= 2:
            raise ValueError(
                "--optimizer_sharding is the ZeRO stage-1 shorthand and "
                "contradicts --zero_stage >= 2 — pass only --zero_stage")
        if self.zero_probe and self.zero_stage < 2:
            raise ValueError(
                "--zero_probe measures the stage-2/3 collectives; it "
                "needs --zero_stage 2 or 3")
        if self.zero_wire not in ("fp32", "bf16"):
            raise ValueError(
                f"unknown zero_wire {self.zero_wire!r}; choose fp32 "
                f"or bf16")
        if self.zero_wire == "bf16" and self.zero_stage_effective < 2:
            raise ValueError(
                "--zero_wire bf16 rides the stage-2/3 grad "
                "reduce-scatter; it needs --zero_stage 2 or 3")
        if self.clip_grad_norm is not None:
            import math
            if (not math.isfinite(self.clip_grad_norm)
                    or self.clip_grad_norm <= 0):
                raise ValueError(
                    f"clip_grad_norm must be a positive finite number, "
                    f"got {self.clip_grad_norm}")
        if self.eval_only and self.skip_eval:
            raise ValueError("--eval_only contradicts --skip_eval")
        if self.stop_threshold is not None and not self.report_accuracy_metrics:
            raise ValueError(
                "--stop_threshold needs eval top-1, which "
                "--report_accuracy_metrics false disables — early "
                "stopping would silently never fire")
        if self.moe_top_k is not None and self.moe_top_k < 1:
            raise ValueError(f"moe_top_k must be >= 1, got {self.moe_top_k}")
        if self.serve_max_batch < 1 or self.serve_queue_size < 1:
            raise ValueError(
                "serve_max_batch and serve_queue_size must be >= 1")
        if self.kv_page_size < 1:
            raise ValueError(
                f"kv_page_size must be >= 1, got {self.kv_page_size}")
        if self.kv_pool_pages < 0 or (
                self.serve_prefill_chunk is not None
                and self.serve_prefill_chunk < 0):
            raise ValueError(
                "kv_pool_pages and serve_prefill_chunk must be >= 0")
        if (self.serve_prefill_chunk
                and self.serve_prefill_chunk % self.kv_page_size):
            raise ValueError(
                f"serve_prefill_chunk ({self.serve_prefill_chunk}) must "
                f"be a multiple of kv_page_size ({self.kv_page_size})")
        if self.serve_tp < 1:
            raise ValueError(f"serve_tp must be >= 1, got {self.serve_tp}")
        if self.router_replicas < 1:
            raise ValueError(
                f"router_replicas must be >= 1, got {self.router_replicas}")
        if self.router_deadline_s <= 0 or self.router_admission < 1:
            raise ValueError(
                "router_deadline_s must be > 0 and router_admission >= 1")
        if self.router_probe_s <= 0 or (
                self.router_probe_s >= self.router_health_timeout_s):
            raise ValueError(
                f"router_probe_s ({self.router_probe_s}) must be > 0 and "
                f"< router_health_timeout_s "
                f"({self.router_health_timeout_s}) — a health verdict "
                f"needs multiple probe ticks")
        if self.router_health_timeout_s <= 0:
            raise ValueError(
                f"router_health_timeout_s must be > 0, got "
                f"{self.router_health_timeout_s}")
        # NOTE: health_timeout vs heartbeat_secs is cross-checked in
        # cli/router_main.py, not here — a training-only run raising
        # --heartbeat_secs must not be rejected over router defaults
        # it never uses
        # literal copy of serve/router.py PLACEMENTS: Config must import
        # without pulling the serve stack (flax models); parity is
        # pinned by tests/test_router.py
        if self.router_placement not in ("affinity", "least_loaded",
                                         "random"):
            raise ValueError(
                f"unknown router_placement {self.router_placement!r}; "
                f"choose from ('affinity', 'least_loaded', 'random')")
        if self.router_prefill_replicas < 0 or (
                self.router_prefill_replicas >= self.router_replicas
                and self.router_prefill_replicas > 0):
            raise ValueError(
                f"router_prefill_replicas "
                f"({self.router_prefill_replicas}) must leave at least "
                f"one decode replica (router_replicas="
                f"{self.router_replicas})")
        if (self.router_prefill_replicas
                and self.router_placement != "affinity"):
            raise ValueError(
                "router_prefill_replicas needs router_placement="
                "affinity — chain re-homing rides the prefix-owner map")
        if (self.router_replica_inflight < 0 or self.router_max_respawns
                < 0 or self.router_respawn_backoff_s < 0
                or self.router_hedge_s < 0):
            raise ValueError(
                "router_replica_inflight/router_max_respawns/"
                "router_respawn_backoff_s/router_hedge_s must be >= 0")
        if not self.serve_host:
            raise ValueError(
                "serve_host must be a bindable address (127.0.0.1 for "
                "single-host, a routable address for cross-host)")
        if self.router_lease_ttl_s <= 0:
            raise ValueError(
                f"router_lease_ttl_s must be > 0, got "
                f"{self.router_lease_ttl_s}")
        if self.router_journal_fsync_s < 0:
            raise ValueError(
                f"router_journal_fsync_s must be >= 0, got "
                f"{self.router_journal_fsync_s}")
        if self.router_standby and not self.rendezvous_dir:
            raise ValueError(
                "router_standby needs an explicit --rendezvous_dir — "
                "the standby finds the leader's lease, journal and "
                "replicas there (a temp dir of its own would watch "
                "an empty tier)")
        if self.rollout_canary_requests < 1:
            raise ValueError(
                f"rollout_canary_requests must be >= 1, got "
                f"{self.rollout_canary_requests}")
        if not 0.0 < self.rollout_mirror_fraction <= 1.0:
            raise ValueError(
                f"rollout_mirror_fraction must be in (0, 1], got "
                f"{self.rollout_mirror_fraction}")
        if not 0.0 <= self.rollout_max_divergence <= 1.0:
            raise ValueError(
                f"rollout_max_divergence must be in [0, 1], got "
                f"{self.rollout_max_divergence}")
        if self.rollout_warm_timeout_s <= 0:
            raise ValueError(
                f"rollout_warm_timeout_s must be > 0, got "
                f"{self.rollout_warm_timeout_s}")
        if self.rollout_checkpoint and self.serve_temperature > 0:
            raise ValueError(
                "rollout_checkpoint needs greedy demo traffic "
                "(--serve_temperature 0): the canary gate compares "
                "mirrored GREEDY requests token-by-token — sampled "
                "traffic is never mirrored, so the gate would starve "
                "and every rollout would time out into a rollback")
        if self.rollout_checkpoint and self.router_replicas < 2:
            raise ValueError(
                "rollout_checkpoint needs >= 2 router_replicas — the "
                "shadow-only canary must not be the tier's only "
                "replica")
        if self.step_time_guard_factor and self.step_time_guard_factor <= 1.0:
            raise ValueError(
                f"step_time_guard_factor must be > 1.0 (or 0 to disable), "
                f"got {self.step_time_guard_factor}")
        if self.heartbeat_secs <= 0:
            raise ValueError(
                f"heartbeat_secs must be positive, got {self.heartbeat_secs}")
        if not 0 <= self.metrics_port <= 65535:
            raise ValueError(
                f"metrics_port must be in [0, 65535] (0 = off), got "
                f"{self.metrics_port}")
        if self.preemption_poll_s < 0:
            raise ValueError(
                f"preemption_poll_s must be >= 0 (0 = off), got "
                f"{self.preemption_poll_s}")
        if self.input_num_shards < 1:
            raise ValueError(
                f"input_num_shards must be >= 1, got "
                f"{self.input_num_shards}")
        if self.input_workers < -1:
            raise ValueError(
                f"input_workers must be >= -1 (-1 = auto, 0 = inline), "
                f"got {self.input_workers}")
        if self.input_cache_limit_mb < 0:
            raise ValueError(
                f"input_cache_limit_mb must be >= 0 (0 = unbounded), "
                f"got {self.input_cache_limit_mb}")
        if self.input_cache_limit_mb and not self.input_cache_dir:
            raise ValueError(
                "input_cache_limit_mb needs --input_cache_dir (the "
                "decode-once cache is off without a directory)")
        if self.checkpoint_steps < 0:
            raise ValueError(
                f"checkpoint_steps must be >= 0 (0 = per-epoch only), "
                f"got {self.checkpoint_steps}")
        if self.checkpoint_keep < 0:
            raise ValueError(
                f"checkpoint_keep must be >= 0 (0 = no cross-run GC), "
                f"got {self.checkpoint_keep}")
        if self.plan and self.plan != "auto" and not os.path.exists(self.plan):
            # fail at flag-parse time, not after dataset/model setup
            raise ValueError(
                f"--plan {self.plan!r}: no such plan file (pass 'auto' "
                f"to search, or a plan_main --out JSON artifact)")
        if self.plan_mesh:
            # typo'd presets/descriptors fail at flag-parse time, not
            # mid-resolution (mesh_spec never touches jax for a
            # non-empty spec, so this stays import-light)
            from dtf_tpu.plan.mesh_spec import mesh_spec
            mesh_spec(self.plan_mesh)
        if self.fault:
            # fail at flag-parse time, not at the step the typo'd fault
            # silently never fires
            from dtf_tpu import chaos
            chaos.parse_spec(self.fault)
        if self.eval_only and not self.resume:
            raise ValueError(
                "--eval_only evaluates a restored checkpoint; pass "
                "--resume (and --model_dir) or there is nothing to "
                "evaluate but random init")

    @property
    def zero_stage_effective(self) -> int:
        """The ZeRO stage a run executes: --zero_stage when set,
        else 1 under the --optimizer_sharding shorthand, else 0."""
        return self.zero_stage or (1 if self.optimizer_sharding else 0)

    # -- dtype helpers -------------------------------------------------
    @property
    def compute_dtype(self):
        import jax.numpy as jnp
        if self.dtype in ("bf16", "bfloat16"):
            return jnp.bfloat16
        if self.dtype in ("fp16", "float16"):
            return jnp.float16
        return jnp.float32

    @property
    def loss_scale_value(self):
        """Parity with flags_core.get_loss_scale: fp16 defaults to a
        static 128; ``--loss_scale dynamic`` returns the string
        "dynamic" (TF2 LossScaleOptimizer semantics, handled by the
        train loop)."""
        if self.loss_scale is not None:
            if str(self.loss_scale).lower() == "dynamic":
                return "dynamic"
            return float(self.loss_scale)
        return 128.0 if self.dtype in ("fp16", "float16") else 1.0

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _coerce(field: dataclasses.Field, raw: str) -> Any:
    t = field.type
    if raw.lower() in ("none", "null"):
        return None
    if t in ("bool", bool):
        return raw.lower() in ("true", "1", "yes", "t")
    if "int" in str(t):
        return int(raw)
    if "float" in str(t):
        return float(raw)
    return raw


def define_flags() -> dict:
    """Returns {flag_name: default} — the full registry, for docs/tests."""
    return {f.name: f.default for f in dataclasses.fields(Config)}


def parse_flags(argv=None, defaults: Optional[dict] = None) -> Config:
    """absl-style parsing: accepts ``--flag value``, ``--flag=value``,
    ``-flag value`` and bare boolean flags (``--skip_eval``).

    ``defaults`` plays the role of ``flags_core.set_defaults`` — the
    per-dataset defaults each main sets (reference cifar_main.py:226-230).
    """
    names = {f.name: f for f in dataclasses.fields(Config)}
    kw = dict(defaults or {})
    argv = list(argv or [])
    i = 0
    while i < len(argv):
        tok = argv[i]
        if not tok.startswith("-"):
            raise ValueError(f"unexpected argument {tok!r}")
        name = tok.lstrip("-")
        val = None
        if "=" in name:
            name, val = name.split("=", 1)
        if name not in names:
            raise ValueError(f"unknown flag --{name}")
        fld = names[name]
        if val is None:
            nxt = argv[i + 1] if i + 1 < len(argv) else None
            if fld.type in ("bool", bool) and (
                    nxt is None or nxt.startswith("-") or
                    nxt.lower() not in ("true", "false", "1", "0", "yes", "no", "t", "f")):
                val, step = "true", 1
            else:
                if nxt is None:
                    raise ValueError(f"flag --{name} needs a value")
                val, step = nxt, 2
        else:
            step = 1
        kw[name] = _coerce(fld, val)
        i += step
    cfg = Config(**kw)
    return apply_env_topology(cfg)


def topology_from_env() -> dict:
    """Read per-process identity from the environment.

    Two sources, in priority order:
      1. DTF_COORDINATOR / DTF_PROCESS_ID / DTF_PROCESS_COUNT — native.
      2. TF_CONFIG JSON — drop-in parity with the reference's cluster
         contract (ps_server/resnet_imagenet_main_dist_ps_0.py:40-50):
         {"cluster": {"worker": [host:port, ...]}, "task": {"type","index"}}.
         The first worker doubles as the coordination-service host.
    """
    out: dict = {}
    if os.environ.get("DTF_COORDINATOR"):
        out["coordinator_address"] = os.environ["DTF_COORDINATOR"]
    if os.environ.get("DTF_PROCESS_ID"):
        out["process_id"] = int(os.environ["DTF_PROCESS_ID"])
    if os.environ.get("DTF_PROCESS_COUNT"):
        out["process_count"] = int(os.environ["DTF_PROCESS_COUNT"])
    if out:
        return out

    tf_config = os.environ.get("TF_CONFIG")
    if tf_config:
        try:
            spec = json.loads(tf_config)
        except json.JSONDecodeError:
            return out
        cluster = spec.get("cluster", {})
        task = spec.get("task", {})
        workers = list(cluster.get("worker", []))
        ps = list(cluster.get("ps", []))
        # Flatten: ps ranks first then workers, matching the reference's
        # rank numbering where ps_0 is rank 0 (SURVEY §3.4).
        all_procs = ps + workers
        if all_procs:
            out["coordinator_address"] = all_procs[0]
            out["process_count"] = len(all_procs)
            ttype, tidx = task.get("type"), int(task.get("index", 0))
            out["process_id"] = tidx if ttype == "ps" else len(ps) + tidx
    return out


def apply_env_topology(cfg: Config) -> Config:
    """Fill unset topology fields from the environment; explicit flags win."""
    env = topology_from_env()
    kw = {}
    for k, v in env.items():
        if getattr(cfg, k) is None:
            kw[k] = v
    # --worker_hosts/--task_index parity (imagenet_main.py:108-110)
    if cfg.worker_hosts and cfg.coordinator_address is None and "coordinator_address" not in kw:
        hosts = [h.strip() for h in cfg.worker_hosts.split(",") if h.strip()]
        kw["coordinator_address"] = hosts[0]
        kw["process_count"] = len(hosts)
        if cfg.task_index >= 0:
            kw["process_id"] = cfg.task_index
    return cfg.replace(**kw) if kw else cfg
