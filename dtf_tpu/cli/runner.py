"""The `run(flags_obj) -> stats` equivalent — shared body of every main.

Mirrors the canonical reference call stack (SURVEY §3.1):
session config → perf knobs → strategy → datasets → model →
compile → callbacks → fit/evaluate → build_stats.  Returns the stats
dict (logged as "Run stats:" like resnet_imagenet_main.py:278).
"""

from __future__ import annotations

import itertools
import logging
import os
import shutil

import jax

from dtf_tpu.config import Config
from dtf_tpu.data import DatasetSpec, get_dataset_spec, synthetic_input_fn
from dtf_tpu.data.pipeline import DevicePrefetcher
from dtf_tpu.models import build_model
from dtf_tpu.runtime import initialize, is_coordinator
from dtf_tpu.runtime.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS
from dtf_tpu.train import Trainer

log = logging.getLogger("dtf_tpu")


def effective_global_batch(cfg: Config, runtime) -> int:
    """Batch-size semantics across strategies (SURVEY §3.3/§3.4):
    mirrored/MWM treat --batch_size as global (Keras-fit semantics);
    horovod/parameter_server treat it as per-replica — each reference
    rank drove exactly one GPU with its own --batch_size, so the global
    batch is batch × hvd.size() ≡ batch × num_replicas.  Scaling by
    replicas (not processes) keeps the horovod LR rule consistent when
    one process drives several chips: LR ramps to 0.1 × num_replicas
    and the batch scales by the same factor."""
    if cfg.distribution_strategy in ("horovod", "parameter_server"):
        return cfg.batch_size * runtime.num_replicas
    return cfg.batch_size


def make_input_fns(cfg: Config, spec: DatasetSpec, global_batch: int):
    """Returns (train_iter_factory, eval_iter_factory).

    Each process produces its 1/process_count share of the global batch
    (the loop assembles the global array from process-local shards), so
    the per-host batch is global // process_count.
    """
    if global_batch % jax.process_count():
        raise ValueError(
            f"global batch_size {global_batch} must be divisible by the "
            f"process count ({jax.process_count()})")
    host_batch = global_batch // jax.process_count()
    # The TRAIN factory accepts start_step: crash-exact resume rebuilds
    # the stream positioned at the restored step (position-derived RNGs
    # make batch n a pure function of (seed, n) in every pipeline —
    # cifar/synthetic natively, imagenet via the sharded data service).
    if cfg.use_synthetic_data or not cfg.data_dir:
        fns = (
            lambda start_step=0: synthetic_input_fn(
                spec, True, host_batch, cfg.seed, start_step=start_step),
            lambda: synthetic_input_fn(spec, False, host_batch, cfg.seed + 1),
        )
    elif spec.name == "cifar10":
        from dtf_tpu.data.cifar import cifar_input_fn
        fns = (
            lambda start_step=0: cifar_input_fn(
                cfg.data_dir, True, host_batch, seed=cfg.seed,
                wire=cfg.input_wire, start_step=start_step),
            lambda: cifar_input_fn(cfg.data_dir, False, host_batch,
                                   drop_remainder=cfg.drop_remainder,
                                   wire=cfg.input_wire),
        )
    elif spec.name == "imagenet":
        from dtf_tpu.data.imagenet import imagenet_input_fn
        if cfg.input_service:
            # sharded deterministic multi-process service (the default):
            # batch n is a pure function of (seed, process, n), so
            # killed-at-K resume replays bit-exactly and decode scales
            # across worker PROCESSES.  Eval stays on the threaded
            # pipeline — one ordered unaugmented pass, nothing to make
            # deterministic.
            from dtf_tpu.data.service import service_input_fn
            train_fn = lambda start_step=0: service_input_fn(
                cfg.data_dir, host_batch, seed=cfg.seed,
                num_shards=cfg.input_num_shards,
                num_workers=cfg.input_workers,
                wire=cfg.input_wire, cache_dir=cfg.input_cache_dir,
                cache_limit_mb=cfg.input_cache_limit_mb,
                start_step=start_step)
        else:
            # legacy threaded pipeline: fused native decode, NOT
            # position-exact — a mid-stream resume refuses loudly
            # inside imagenet_input_fn
            train_fn = lambda start_step=0: imagenet_input_fn(
                cfg.data_dir, True, host_batch, seed=cfg.seed,
                num_threads=cfg.datasets_num_private_threads,
                scaled_decode=cfg.input_scaled_decode,
                wire=cfg.input_wire, start_step=start_step)
        fns = (
            train_fn,
            lambda: imagenet_input_fn(cfg.data_dir, False, host_batch,
                                      drop_remainder=cfg.drop_remainder,
                                      wire=cfg.input_wire),
        )
    else:
        raise ValueError(f"no input pipeline for dataset {spec.name!r}")
    if cfg.data_format == "channels_first" and not spec.is_sequence:
        # --data_format parity (resnet_cifar_main.py:94-98): batches flow
        # NCHW from here on; the compiled steps transpose back to NHWC
        fns = tuple(_channels_first_factory(fn) for fn in fns)
    return fns


def _channels_first_factory(fn):
    import numpy as np

    def wrapped(*args, **kw):
        for batch in fn(*args, **kw):
            images = np.ascontiguousarray(
                np.asarray(batch[0]).transpose(0, 3, 1, 2))
            yield (images,) + tuple(batch[1:])
    return wrapped


def run(cfg: Config) -> dict:
    """Entry wrapper: arms tracing/chaos, installs the preemption
    guard, and translates a graceful preemption (SIGTERM → emergency
    checkpoint at the step boundary) into the distinct EXIT_PREEMPTED
    exit code the launch.py supervisor restarts without consuming the
    crash budget."""
    from dtf_tpu import chaos
    from dtf_tpu.obs import trace
    from dtf_tpu.runtime import compile_cache
    from dtf_tpu.train import preemption
    compile_cache.configure()
    trace.maybe_configure(cfg)
    # run-scoped trace id: the launcher mints one (DTF_TRACE_ID) so
    # every rank's records — steps, checkpoints, eval, data service,
    # PS — share it and `trace_main --request <id>` joins them into
    # one timeline; a standalone run mints its own
    trace.set_default_trace(os.environ.get("DTF_TRACE_ID")
                            or trace.new_trace_id())
    chaos.maybe_configure(cfg)
    preemption.install()
    poller = None
    if cfg.preemption_poll_s:
        # metadata-server preemption signal (GCE/TPU-VM): a pending
        # preemption visible on the metadata endpoint feeds the same
        # SIGTERM latch the guard just installed
        poller = preemption.MetadataPoller(cfg.preemption_poll_s).start()
    metrics_server = None
    if cfg.metrics_port and not (cfg.process_id or 0):
        # rank 0 only (cfg.process_id is None for single-process runs
        # and env-filled by the launcher otherwise — co-hosted ranks
        # must not race for one port); stdlib server, daemon threads
        from dtf_tpu.obs.prom import MetricsServer
        metrics_server = MetricsServer(cfg.metrics_port)
    try:
        return _run(cfg)
    except preemption.Preempted as p:
        log.warning("run preempted at step %d — emergency checkpoint "
                    "written; exiting %d", p.step, preemption.EXIT_PREEMPTED)
        trace.flush()
        raise SystemExit(preemption.EXIT_PREEMPTED)
    except Exception as e:  # noqa: BLE001 — device-loss classification
        from dtf_tpu.train import elastic
        if not (isinstance(e, elastic.DeviceLost)
                or elastic.is_device_loss(e)):
            raise
        step = getattr(e, "step", -1)
        log.warning("accelerators lost at step %d (%s) — exiting %d so "
                    "an --elastic supervisor reshards onto the "
                    "surviving topology", step, e,
                    elastic.EXIT_DEVICE_LOST)
        trace.flush()
        raise SystemExit(elastic.EXIT_DEVICE_LOST)
    finally:
        if metrics_server is not None:
            metrics_server.shutdown()
        if poller is not None:
            poller.stop()
        preemption.restore()


def _run(cfg: Config) -> dict:
    if cfg.plan:
        # --plan auto|<file>: compile the chosen plan into the ordinary
        # parallelism flags BEFORE anything reads them — from here on
        # the run is indistinguishable from the same flags set by hand
        # (bit-identical, tests/test_plan.py).  Infeasible plans die
        # here, loudly, not as an OOM mid-compile.
        # resolve_plan queries the live topology (mesh_spec("") and the
        # attached-device guard), which initializes the jax backend —
        # in a multi-process run the distributed rendezvous must come
        # first, or process_count() reports 1 and the later
        # jax.distributed.initialize refuses an initialized backend
        from dtf_tpu.runtime.mesh import _maybe_init_distributed
        _maybe_init_distributed(cfg)
        from dtf_tpu.plan import resolve_plan
        cfg = resolve_plan(cfg)
    # structured tracing: --trace_dir, or DTF_TRACE_DIR forwarded by the
    # launcher to every rank (idempotent when a main already configured)
    from dtf_tpu.obs import trace
    from dtf_tpu.obs.registry import default_registry
    # metric.log exports are per-run: a second run() in the same
    # process (tests, notebooks) must not inherit the previous run's
    # process-global counters (e.g. PS wire tallies)
    default_registry().reset()
    export_model = None
    if cfg.export_dir:
        # fail fast: don't discover a missing orbax install only after
        # training completes
        from dtf_tpu.train.checkpoint import export_model
    if cfg.clean and cfg.model_dir and os.path.isdir(cfg.model_dir):
        # model_helpers.apply_clean parity (resnet_imagenet_main.py:275)
        shutil.rmtree(cfg.model_dir, ignore_errors=True)
    if cfg.model_dir:
        os.makedirs(cfg.model_dir, exist_ok=True)
    if cfg.distribution_strategy == "parameter_server" and cfg.ps_mode == "async":
        # true-async push/pull against the C++ parameter store; no mesh,
        # no collective rendezvous — each worker steps independently
        # (SURVEY §3.4 semantics)
        from dtf_tpu.parallel import ps
        return ps.run_async(cfg)

    rt = initialize(cfg)
    spec = get_dataset_spec(cfg.dataset)
    import dataclasses
    if cfg.num_classes:
        spec = dataclasses.replace(spec, num_classes=cfg.num_classes)
    if cfg.seq_len and spec.is_sequence:
        spec = dataclasses.replace(spec, seq_len=cfg.seq_len)

    global_batch = effective_global_batch(cfg, rt)
    cfg = cfg.replace(batch_size=global_batch)

    rt.shard_seq = spec.is_sequence
    model_name = "trivial" if cfg.use_trivial_model else cfg.model
    is_moe = model_name.startswith("moe_transformer")
    is_pipeline = model_name.startswith("pipeline_transformer")
    seq_axis = (SEQ_AXIS if spec.is_sequence and cfg.seq_parallelism > 1
                else None)
    model_axis = (MODEL_AXIS if model_name.startswith("transformer")
                  and cfg.model_parallelism > 1 else None)
    # the 'model' axis doubles as the pipeline-stage axis for the
    # stacked-block family
    pipe_axis = (MODEL_AXIS if is_pipeline and cfg.model_parallelism > 1
                 else None)
    # experts ride the batch-splitting axis by default (classic
    # DeepSpeed-MoE/GShard placement — all_to_all token exchange);
    # --model_parallelism with a MoE family instead places them on the
    # 'model' axis (group size decoupled from dp; batch replicated
    # across it, partial-output psum — models/moe.py docstring)
    expert_axis = None
    expert_on_model = is_moe and cfg.model_parallelism > 1
    if is_moe:
        expert_axis = MODEL_AXIS if expert_on_model else DATA_AXIS
    if is_pipeline and cfg.seq_parallelism > 1:
        raise ValueError(
            "pipeline_transformer does not compose with seq_parallelism; "
            "use the plain transformer for ring attention")
    # None flags defer to the model preset's own defaults (the registry
    # partials, e.g. moe_transformer_small's 4 experts)
    model_kw = {}
    if is_moe:
        model_kw = {k: v for k, v in dict(
            num_experts=cfg.num_experts,
            capacity_factor=cfg.moe_capacity_factor,
            aux_weight=cfg.moe_aux_weight,
            router_top_k=cfg.moe_top_k).items() if v is not None}
        if expert_on_model:
            model_kw["expert_axis_along_batch"] = False
    elif is_pipeline:
        if cfg.pipeline_interleave > 1:
            if pipe_axis is None:
                raise ValueError(
                    "--pipeline_interleave > 1 needs pipeline stages: "
                    "set --model_parallelism > 1")
            model_kw["interleave"] = cfg.pipeline_interleave
        if cfg.num_microbatches is not None:
            model_kw = dict(model_kw, num_microbatches=cfg.num_microbatches)
        else:
            # auto-scale the GPipe schedule: bubble fraction is
            # (pp-1)/(M+pp-1), so target M = 4·pp (≤20% bubble) and
            # halve until it divides the per-shard batch
            pp = max(cfg.model_parallelism, 1)
            per_shard = global_batch // rt.num_replicas
            m = 4 * pp
            while m > 1 and per_shard % m:
                m //= 2
            model_kw = dict(model_kw, num_microbatches=max(m, 1))
    if cfg.remat or cfg.remat_policy:
        if model_name == "resnet50":
            # vision remat is the selective conv_out/bn_stats policy
            # (models/resnet.py RESNET_REMAT_POLICY) — there is no
            # full-remat or "dots" variant to select
            if cfg.remat_policy:
                raise ValueError(
                    "--remat_policy applies to the transformer families; "
                    "resnet50 takes plain --remat (selective "
                    "conv_out/bn_stats policy)")
            model_kw = dict(model_kw, remat=True)
        elif not model_name.startswith(
                ("transformer", "moe_transformer", "pipeline_transformer")):
            flag = "--remat" if cfg.remat else "--remat_policy"
            raise ValueError(
                f"{flag} is implemented for the transformer families and "
                f"resnet50, not {model_name!r}")
        else:
            model_kw = dict(model_kw, remat=True)
            if cfg.remat_policy:
                model_kw = dict(model_kw, remat_policy=cfg.remat_policy)
    shard_vocab = bool(cfg.shard_lm_head and model_axis is not None)
    if cfg.shard_lm_head and model_axis is None:
        raise ValueError(
            "--shard_lm_head needs the plain transformer family with "
            "--model_parallelism > 1")
    if shard_vocab:
        model_kw = dict(model_kw, shard_vocab=True)
    model, l2 = build_model(
        model_name, num_classes=spec.num_classes, dtype=cfg.compute_dtype,
        bn_axis=DATA_AXIS if cfg.sync_bn else None, seq_axis=seq_axis,
        model_axis=model_axis, expert_axis=expert_axis, pipe_axis=pipe_axis,
        **model_kw)
    if spec.is_sequence and spec.seq_len > model.max_seq_len:
        # --seq_len past the presets' 2048-row position table: grow the
        # table with it (never shrink — serving restores checkpoints
        # into the preset's shape)
        model = model.clone(max_seq_len=spec.seq_len)

    import functools
    param_spec_fn = None
    if model_axis is not None:
        from dtf_tpu.models.transformer import param_partition_specs
        param_spec_fn = functools.partial(param_partition_specs,
                                          model_axis=model_axis,
                                          shard_vocab=shard_vocab)
    elif is_moe:
        from dtf_tpu.models.moe import moe_param_partition_specs
        param_spec_fn = functools.partial(moe_param_partition_specs,
                                          expert_axis=expert_axis)
    elif pipe_axis is not None:
        from dtf_tpu.models.pipeline_lm import pipeline_param_partition_specs
        param_spec_fn = functools.partial(pipeline_param_partition_specs,
                                          pipe_axis=pipe_axis)
    # uint8 wire: normalization runs inside the compiled step; the
    # wire→normalize decision is single-sourced in for_config (the
    # async-PS path calls the same function)
    from dtf_tpu.data.normalize import for_config
    trainer = Trainer(cfg, rt, model, l2, spec, param_spec_fn=param_spec_fn,
                      vocab_axis=MODEL_AXIS if shard_vocab else None,
                      normalize_fn=for_config(cfg, spec))
    train_fn, eval_fn = make_input_fns(cfg, spec, global_batch)

    train_iter = train_fn()
    first = next(train_iter)
    state = trainer.init_state(jax.random.key(cfg.seed), first)

    callbacks = []
    ckpt_mod = None
    ckpt_cb = None
    if (not cfg.skip_checkpoint or cfg.resume) and cfg.model_dir:
        try:
            from dtf_tpu.train import checkpoint as ckpt_mod
        except ImportError:
            if cfg.resume:
                raise ImportError(
                    "--resume needs orbax-checkpoint; install it or drop "
                    "the flag")
            log.warning("checkpointing disabled: orbax-checkpoint not "
                        "installed (pass --skip_checkpoint to silence)")
    resumed_step = 0
    if ckpt_mod is not None:
        # all processes participate (orbax coordinates the collective
        # write of the replicated state — the rank-0-write equivalent).
        # The manifest carries the host half of crash-exact resume:
        # data position + the seed that derives the pipeline RNGs.
        spe = max(trainer.steps_per_epoch, 1)
        # mirror make_input_fns' branch order: synthetic/no-data_dir runs
        # never touch the service, so their manifests must not claim its
        # host_state (or resume would enforce num_shards against a
        # stream that has no shards)
        service_on = (spec.name == "imagenet" and cfg.input_service
                      and bool(cfg.data_dir)
                      and not cfg.use_synthetic_data)

        def host_state_fn(step):
            data = {"scheme": "position-derived", "dataset": cfg.dataset,
                    "start_step": step}
            if service_on:
                # per-shard next-batch positions: derivable from the
                # step alone, carried so the manifest is self-describing
                # and the resume contract auditable — and num_shards,
                # which is part of the stream's IDENTITY (the merged
                # order depends on it), validated below on restore
                from dtf_tpu.data.service import shard_positions
                data["num_shards"] = cfg.input_num_shards
                data["shard_positions"] = shard_positions(
                    step, cfg.input_num_shards)
            return {"seed": cfg.seed, "global_step": step,
                    "epoch": step // spe, "step_in_epoch": step % spe,
                    # which mesh WROTE this step — informational for
                    # elastic post-mortems, never validated on restore
                    # (topology is exactly what an elastic resume may
                    # change; the canonical layout is topology-free)
                    "topology": {"devices": rt.num_devices,
                                 "replicas": rt.num_replicas,
                                 "processes": jax.process_count()},
                    "data": data}
        ckpt_cb = ckpt_mod.CheckpointCallback(
            cfg.model_dir, every_steps=cfg.checkpoint_steps,
            host_state_fn=host_state_fn, keep=cfg.checkpoint_keep,
            # ZeRO runs save the canonical stage-0 layout (full-shaped
            # params + optimizer state): the checkpoint is
            # stage-portable — restore into any --zero_stage, or into
            # serving via the bridge
            state_transform=(trainer.canonical_state if trainer.zero
                             else None))
        if cfg.resume:
            if trainer.zero:
                # ZeRO: checkpoints hold the canonical form; restore
                # against the stage-independent template, then place
                # into this run's stage layout (sliced params/opt
                # state with their shardings)
                restored = ckpt_cb.ckpt.restore(
                    trainer.canonical_template())
                if restored is None and ckpt_cb.ckpt.verified_steps():
                    # steps that VERIFY (sha256-intact) but restore
                    # into the canonical template for none of the
                    # candidates are a layout mismatch, not corruption
                    # — almost certainly a pre-canonical-format
                    # --optimizer_sharding run (sliced optimizer
                    # state on disk).  Restarting from scratch here
                    # would silently discard the whole run.
                    raise ValueError(
                        f"--resume: checkpoints under "
                        f"{cfg.model_dir}/checkpoints pass integrity "
                        f"verification but do not match the canonical "
                        f"ZeRO checkpoint layout (full-shaped params + "
                        f"optimizer state).  They likely predate the "
                        f"stage-portable format (older "
                        f"--optimizer_sharding runs saved sliced "
                        f"state).  Resume them with the code revision "
                        f"that wrote them, or restart without --resume")
                if restored is not None:
                    restored = trainer.staged_state(restored)
            else:
                # restore with the state's own per-leaf shardings
                # (TP/EP/PP states are not replicated — a blanket
                # replicated sharding would silently unshard them)
                state_shardings = jax.tree_util.tree_map(
                    lambda x: x.sharding, state)
                restored = ckpt_cb.ckpt.restore(state,
                                                sharding=state_shardings)
            if restored is not None:
                state = restored
                resumed_step = int(jax.device_get(state.step))
                host = ckpt_cb.ckpt.host_state(
                    ckpt_cb.ckpt.last_restored_step)
                if host and host.get("seed") is not None \
                        and host["seed"] != cfg.seed:
                    # a different seed re-derives a DIFFERENT data
                    # stream: the resumed run would silently train on
                    # other batches than the run it claims to continue
                    raise ValueError(
                        f"--resume seed mismatch: checkpoint was written "
                        f"with seed {host['seed']}, this run has "
                        f"--seed {cfg.seed}; crash-exact resume needs the "
                        f"same seed (pass --seed {host['seed']})")
                ckpt_shards = (host or {}).get("data", {}).get("num_shards")
                if service_on and ckpt_shards is not None \
                        and int(ckpt_shards) != cfg.input_num_shards:
                    # num_shards is part of the merged stream's identity
                    # (batch n = shard n%S, local batch n//S): resuming
                    # with a different count would silently continue on
                    # a DIFFERENT stream than the run it claims to be
                    raise ValueError(
                        f"--resume input_num_shards mismatch: checkpoint "
                        f"was written with {ckpt_shards} shard(s), this "
                        f"run has --input_num_shards "
                        f"{cfg.input_num_shards}; the merged batch order "
                        f"depends on the shard count (pass "
                        f"--input_num_shards {ckpt_shards}).  Worker "
                        f"count, by contrast, may change freely")
            elif cfg.eval_only:
                # evaluating random init as if it were a checkpoint would
                # silently report garbage — fail instead
                raise FileNotFoundError(
                    f"--eval_only --resume: no checkpoint found under "
                    f"{cfg.model_dir}/checkpoints; point --model_dir at a "
                    f"trained run")
            else:
                log.warning(
                    "--resume: no checkpoint found under %s/checkpoints — "
                    "training from scratch", cfg.model_dir)
        if not cfg.skip_checkpoint:
            callbacks.append(ckpt_cb)
    # elastic supervision (DTF_ELASTIC_DEVICES exported by launch.py
    # --elastic): verify the attached topology matches the
    # supervisor's surviving-capacity accounting, and stamp the resume
    # point + topology into the trace (no-op otherwise)
    from dtf_tpu.train import elastic
    elastic.note_elastic_resume(rt, resumed_step)
    if cfg.enable_tensorboard and cfg.model_dir and is_coordinator():
        from dtf_tpu.utils.tensorboard import TensorBoardCallback
        callbacks.append(TensorBoardCallback(cfg.model_dir))

    if cfg.eval_only:
        # before the prefetcher: no training batches are consumed, so
        # no background transfer thread should start
        from dtf_tpu.utils.logs import build_stats
        eval_output = trainer.evaluate(state, eval_fn())
        stats = build_stats({}, eval_output, None)
        log.info("Run stats (eval only): %s", stats)
        return stats

    if resumed_step > 0:
        # crash-exact resume: rebuild the stream POSITIONED at the
        # restored step (the probe iterator above consumed batch 0 of a
        # step-0 stream — close it so its worker threads/buffers don't
        # idle alongside the real pipeline for the whole run; the loop
        # starts at batch resumed_step and must see exactly that batch)
        if hasattr(train_iter, "close"):
            train_iter.close()
        first = None
        prefetched = DevicePrefetcher(train_fn(start_step=resumed_step),
                                      rt, buffer_size=2)
    else:
        prefetched = DevicePrefetcher(itertools.chain([first], train_iter),
                                      rt, buffer_size=2)

    # logger.benchmark_context parity (resnet_cifar_main.py:234)
    from dtf_tpu.utils.benchmark_logger import benchmark_context
    try:
        with benchmark_context(cfg) as bench_log:
            state, stats = trainer.fit(
                state, prefetched,
                eval_iter_fn=None if cfg.skip_eval else eval_fn,
                callbacks=callbacks)
            if bench_log is not None:
                step_now = int(jax.device_get(state.step))
                bench_log.log_stats(stats, global_step=step_now)
                # process-global obs registry (PS wire counters etc.)
                # rides the same metric.log; empty registries write
                # nothing
                bench_log.log_registry(default_registry(),
                                       global_step=step_now)
    finally:
        # EVERY exit — normal, watchdog TrainingAnomaly abort,
        # preemption — lands the in-flight async orbax save and seals
        # its manifest; an orphaned write is exactly the truncated
        # checkpoint the integrity fallback exists to catch
        if ckpt_cb is not None:
            ckpt_cb.ckpt.close()

    if export_model is not None:
        # --export_dir parity: final inference variables, written once
        # (replicated state ⇒ the collective write is coordinator-led);
        # ZeRO states export their canonical full-shaped params
        export_model(cfg.export_dir, trainer.canonical_state(state)
                     if trainer.zero else state)

    log.info("Run stats: %s",
             {k: v for k, v in stats.items() if k != "step_timestamp_log"})
    return stats
