"""The SPMD training/eval loop — the `model.fit` equivalent.

Re-expresses the reference's Keras-fit semantics (SURVEY §7.3) in a
custom jitted loop:
  - per-step LR schedule inside the compiled step (replacing
    LearningRateBatchScheduler, common.py:36-73)
  - TimeHistory BenchmarkMetric cadence (utils.logs)
  - `epochs_between_evals`, `train_steps` cap, `skip_eval`
    (reference resnet_cifar_main.py:176-214)
  - build_stats-compatible result dict (common.py:202-245)
  - fp16 static loss scaling parity (resnet_imagenet_main.py:182-187);
    bf16 (the TPU-native mixed mode) needs none

Parallelism: one SPMD core for every strategy (SURVEY §2.2).  The step
is `jit(shard_map(...))` over the runtime mesh: each data-shard computes
a local forward/backward (per-replica BatchNorm statistics — the
reference's implicit MirroredStrategy choice), gradients and metrics are
`lax.pmean`-ed over the 'data' axis (XLA emits the ICI/DCN all-reduce —
the NCCL-ring / collective-allreduce / grpc-push-pull equivalent), and
every replica applies an identical update.  Params live replicated;
state buffers are donated so updates are in-place in HBM.
"""

from __future__ import annotations

import logging
import os
import time
from functools import partial
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from dtf_tpu import chaos
from dtf_tpu.config import Config
from dtf_tpu.data.base import DatasetSpec
from dtf_tpu.models.partition import spec_axes as _spec_axes
from dtf_tpu.models.registry import l2_weight_penalty
from dtf_tpu.obs import trace
from dtf_tpu.obs.watchdog import (Heartbeat, NanLossWatchdog,
                                  StepTimeWatchdog)
from dtf_tpu.runtime.mesh import (DATA_AXIS, MODEL_AXIS, SEQ_AXIS,
                                  MeshRuntime)
from dtf_tpu.train import preemption
from dtf_tpu.train import schedules as sched_lib
from dtf_tpu.train import zero as zero_lib
from dtf_tpu.train.optimizer import build_optimizer
from dtf_tpu.utils.logs import TimeHistory, build_stats

log = logging.getLogger("dtf_tpu")


@struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any
    # dynamic loss scaling only (--loss_scale dynamic): the live scale
    # and the count of consecutive finite steps; None under static
    # scaling (None is an empty pytree — costs nothing)
    loss_scale: Any = None
    good_steps: Any = None


# TF2 LossScaleOptimizer dynamic defaults (reference
# resnet_imagenet_main.py:182-187 wraps the optimizer in one)
DYNAMIC_SCALE_INIT = 2.0 ** 15
DYNAMIC_GROWTH_INTERVAL = 2000


# ZeRO slice layout + per-leaf collective helpers live in
# dtf_tpu/train/zero.py (shared with the canonical-checkpoint
# conversions); loop.py only orchestrates them per stage.


def per_example_cross_entropy(logits, labels):
    """Un-reduced CE with integer labels — one value per position."""
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


def cross_entropy(logits, labels):
    """Mean CE with integer labels; numerically identical to the
    reference's categorical CE over one-hot labels."""
    return jnp.mean(per_example_cross_entropy(logits, labels))


def sharded_per_example_cross_entropy(local_logits, labels, axis: str):
    """Un-reduced CE over vocab-sharded logits (Megatron's vocab-parallel
    softmax): a collective logsumexp over the model axis — the full
    vocab dimension never materializes on one shard.

    The two reductions are g-operator psums (`tp_psum`: sum forward,
    identity backward), which yields exactly the gradient of one loss
    replica; the max is stop-gradiented (it cancels analytically)."""
    from dtf_tpu.parallel.collectives import tp_psum

    vloc = local_logits.shape[-1]
    offset = lax.axis_index(axis) * vloc
    # stop_gradient *before* pmax: pmax has no differentiation rule,
    # and the max shift cancels analytically in the CE gradient anyway
    m = lax.pmax(jnp.max(lax.stop_gradient(local_logits), -1), axis)
    sumexp = tp_psum(
        jnp.sum(jnp.exp(local_logits - m[..., None]), -1), axis)
    lse = jnp.log(sumexp) + m
    local_label = labels - offset
    in_range = jnp.logical_and(local_label >= 0, local_label < vloc)
    safe = jnp.clip(local_label, 0, vloc - 1)
    picked = jnp.take_along_axis(local_logits, safe[..., None], -1)[..., 0]
    correct = tp_psum(jnp.where(in_range, picked, 0.0), axis)
    return lse - correct


def sharded_cross_entropy(local_logits, labels, axis: str):
    """Mean CE over vocab-sharded logits."""
    return jnp.mean(
        sharded_per_example_cross_entropy(local_logits, labels, axis))


def sharded_argmax(local_logits, axis: str):
    """Global argmax over vocab-sharded logits (metrics only — not
    differentiated).  Ties resolve to the lowest global index, matching
    jnp.argmax on the equivalent unsharded logits (within a shard
    jnp.argmax already picks the lowest; across shards the pmin does)."""
    # callers may sit inside a differentiated function (train-step
    # metrics) and pmax/pmin have no differentiation rule
    local_logits = lax.stop_gradient(local_logits)
    vloc = local_logits.shape[-1]
    offset = lax.axis_index(axis) * vloc
    local_max = jnp.max(local_logits, -1)
    local_arg = jnp.argmax(local_logits, -1) + offset
    best = lax.pmax(local_max, axis)
    sentinel = jnp.iinfo(local_arg.dtype).max
    cand = jnp.where(local_max == best, local_arg, sentinel)
    return lax.pmin(cand, axis)


class Trainer:
    """Builds jitted SPMD train/eval steps and runs the fit loop."""

    def __init__(self, cfg: Config, runtime: MeshRuntime, model,
                 l2_weight: float, spec: DatasetSpec,
                 schedule: Optional[Callable] = None,
                 param_spec_fn: Optional[Callable] = None,
                 vocab_axis: Optional[str] = None,
                 normalize_fn: Optional[Callable] = None):
        self.cfg = cfg
        self.rt = runtime
        self.model = model
        self.l2_weight = l2_weight
        self.spec = spec
        # uint8 wire: pipelines ship raw uint8 pixels and this runs as
        # the FIRST op inside the compiled train/eval step (f32 math
        # on-chip, fused by XLA into the first conv's input) — the
        # TPU-native placement of the reference's in-graph
        # normalization (imagenet_preprocessing.py:397-430).  None =
        # host-normalized f32 wire.
        self.normalize_fn = normalize_fn
        # vocab-sharded lm_head: logits arrive [B, S, V/mp] and the
        # loss/metrics go through the collective softmax forms
        self.vocab_axis = vocab_axis
        # tensor parallelism: fn(params) -> PartitionSpec tree sharding
        # params over the 'model' axis (e.g. transformer.
        # param_partition_specs).  The L2 penalty is sharding-aware
        # (l2_weight_penalty psums each sharded leaf over its axes).
        self.param_spec_fn = param_spec_fn

        # ---- epoch math (SURVEY §3.3/3.4 steps//size semantics) ----
        # cfg.batch_size is the GLOBAL batch. In horovod/parameter_server
        # parity modes the reference flag was per-worker; the CLI layer
        # multiplies by process count before we get here.
        self.global_batch = cfg.batch_size
        if self.global_batch % runtime.num_replicas:
            raise ValueError(
                f"global batch_size {self.global_batch} must be divisible by "
                f"the number of data-parallel replicas "
                f"({runtime.num_replicas}); pick a batch size that is a "
                f"multiple, or reduce --num_devices")
        self.grad_accum = max(int(cfg.grad_accum_steps or 1), 1)
        if (self.global_batch // runtime.num_replicas) % self.grad_accum:
            raise ValueError(
                f"per-replica batch "
                f"{self.global_batch // runtime.num_replicas} must be "
                f"divisible by grad_accum_steps ({self.grad_accum})")
        if spec.is_sequence:
            sp = runtime.mesh.shape[SEQ_AXIS]
            if spec.seq_len % sp:
                raise ValueError(
                    f"seq_len {spec.seq_len} must be divisible by "
                    f"seq_parallelism ({sp})")
        self.steps_per_epoch = spec.num_train // self.global_batch
        if self.steps_per_epoch == 0:
            raise ValueError(
                f"batch_size {self.global_batch} exceeds the training set "
                f"({spec.num_train} examples): zero steps per epoch")
        self.train_epochs = cfg.train_epochs
        if cfg.train_steps:
            # reference mains: train_steps caps to 1 epoch of that length
            self.steps_per_epoch = min(cfg.train_steps, self.steps_per_epoch)
            self.train_epochs = 1
        # --data_format: the reference honors channels_first by setting
        # the Keras image data format (resnet_cifar_main.py:94-98).
        # Here NCHW batches are accepted and transposed to NHWC inside
        # the compiled step (free: XLA folds the transpose into the
        # first conv's layout assignment); compute stays NHWC for the
        # MXU either way.
        self.channels_first = (cfg.data_format == "channels_first"
                               and not spec.is_sequence)

        if schedule is not None:
            self.schedule = schedule
        elif cfg.distribution_strategy == "horovod":
            # horovod-parity: constant size-scaled LR with 3-epoch warmup
            # replaces the piecewise schedule (SURVEY §3.3)
            self.schedule = sched_lib.horovod_schedule(
                runtime.num_replicas, max(self.steps_per_epoch, 1))
        else:
            self.schedule = sched_lib.for_dataset(
                spec.name, self.global_batch, max(self.steps_per_epoch, 1),
                spec.num_train, use_tensor_lr=cfg.use_tensor_lr,
                train_epochs=self.train_epochs)
        self.tx = build_optimizer(cfg.optimizer, self.schedule)
        self.dynamic_scale = cfg.loss_scale_value == "dynamic"
        self.loss_scale = (1.0 if self.dynamic_scale
                           else float(cfg.loss_scale_value))

        # ZeRO weight-update sharding (PAPERS.md: Xu et al. 2020),
        # stages 1-3 on the data axis (train/zero.py has the layout
        # contract).  Stage 1: optimizer state sliced, grads
        # reduce-scatter, updated slices all-gather back.  Stage 2: the
        # grad-accumulation carry holds 1/nd slices — each microbatch's
        # grads scatter as the backward produces them.  Stage 3: params
        # themselves live sliced and all-gather per leaf at the top of
        # the step.  Composes with TP/EP/PP param sharding:
        # model-sharded leaves slice their *local* shard over 'data'
        # (spec ('data','model')); expert leaves riding 'data' keep
        # locally-shaped state (zero_lib.zero_leaf_spec).
        self.zero_stage = cfg.zero_stage_effective
        self.zero = self.zero_stage >= 1
        # --zero_wire bf16: the per-microbatch grad reduce-scatter
        # crosses the wire (and sums) in bf16, halving stage-2/3
        # scatter volume; the returned slices and the cross-microbatch
        # accumulation carry stay f32 (the --ps_wire bf16 trade,
        # applied to the FSDP path — documented loss tolerance pinned
        # by tests/test_zero_stages.py)
        self.zero_wire = (jnp.bfloat16
                          if getattr(cfg, "zero_wire", "fp32") == "bf16"
                          else jnp.float32)

        if self.param_spec_fn is None and not self.zero:
            self._build_steps()
        # else: the state spec tree needs the concrete param structure —
        # steps are built in init_state

    # ------------------------------------------------------------------
    def init_state(self, rng: jax.Array, sample_batch) -> TrainState:
        """Seed-synced replicated init — the Horovod
        BroadcastGlobalVariablesCallback(0) equivalent (SURVEY §2.2):
        every process initializes from the same seed, so params are
        identical without a broadcast."""
        images = jnp.asarray(sample_batch[0][:1])
        if self.channels_first:
            images = jnp.transpose(images, (0, 2, 3, 1))
        if self.normalize_fn is not None:
            images = self.normalize_fn(images)
        # a seq- or model-sharded module calls collectives and can only
        # run inside shard_map; param *shapes* don't depend on those
        # axes (TP shards arrive by sharding the full arrays), so init
        # with an unsharded twin
        init_model = self.model
        clone_kw = {k: None
                    for k in ("seq_axis", "model_axis", "expert_axis",
                              "pipe_axis")
                    if getattr(init_model, k, None) is not None}
        if clone_kw and getattr(init_model, "interleave", 1) != 1:
            # param shapes don't depend on the visitation order either
            clone_kw["interleave"] = 1
        if clone_kw:
            init_model = init_model.clone(**clone_kw)
        variables = jax.jit(init_model.init, static_argnames=("train",))(
            rng, images, train=False)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        if self.zero:
            # optimizer state over each leaf's column-sliced 2-D view
            # [rows, nd·k] (a column block per (data, model) coordinate
            # when the param is model-sharded; locally-shaped for expert
            # leaves — zero_lib.slice_view / zero_leaf_spec).
            # Init under jit with sharded out_shardings so the full
            # state never materializes on one device (the transient
            # spike would OOM exactly the model sizes this targets)
            from dtf_tpu.train.optimizer import (ZEROS_INIT_OPTIMIZERS,
                                                 opt_state_specs)
            # This proto trick only holds for value-independent inits
            # (state is zeros whatever the params are) — enforced so a
            # future optimizer can't silently get wrong ZeRO state.
            assert self.cfg.optimizer in ZEROS_INIT_OPTIMIZERS, (
                f"ZeRO init uses zero-valued protos; optimizer "
                f"{self.cfg.optimizer!r} is not registered as having a "
                f"value-independent init (optimizer.ZEROS_INIT_OPTIMIZERS)")
            is_p = lambda x: isinstance(x, P)
            nd = self.rt.mesh.shape[DATA_AXIS]
            mesh_shape = dict(self.rt.mesh.shape)
            pspecs = (self.param_spec_fn(params)
                      if self.param_spec_fn is not None
                      else jax.tree_util.tree_map(lambda _: P(), params))
            # elastic shrink/grow resumes land here with an ARBITRARY
            # surviving mesh: leaves whose model spec pins a tensor dim
            # to a mesh axis (experts over 'data', TP/PP over 'model')
            # must refuse a non-dividing topology loudly — the ZeRO
            # slice layout itself reshards onto any nd by construction
            # (as_view zero-pads to the new nd's tile grid)
            from dtf_tpu.train import elastic as elastic_lib
            problems = elastic_lib.check_reshardable(
                pspecs, params, mesh_shape)
            if problems:
                raise ValueError(
                    "model cannot shard onto this mesh (an elastic "
                    "resume must refuse, not garble): "
                    + "; ".join(problems))
            opt_pspecs = jax.tree_util.tree_map(zero_lib.zero_leaf_spec,
                                                pspecs, is_leaf=is_p)

            def proto_leaf(spec, p):
                axes = _spec_axes(spec)
                if DATA_AXIS in axes:
                    return jax.ShapeDtypeStruct(p.shape, p.dtype)
                msz = 1
                for a in axes:
                    msz *= mesh_shape[a]
                rows, k = zero_lib.slice_shape(
                    zero_lib.local_shape(spec, p.shape, mesh_shape), nd)
                return jax.ShapeDtypeStruct((rows, nd * msz * k), p.dtype)

            protos = jax.tree_util.tree_map(proto_leaf, pspecs, params,
                                            is_leaf=is_p)
            ospecs = opt_state_specs(self.cfg.optimizer, opt_pspecs, P())
            oshard = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.rt.mesh, s), ospecs,
                is_leaf=is_p)
            opt_state = jax.jit(
                lambda: self.tx.init(jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), protos)),
                out_shardings=oshard)()
        else:
            opt_state = self.tx.init(params)
        state = TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            batch_stats=batch_stats, opt_state=opt_state,
            loss_scale=(jnp.float32(DYNAMIC_SCALE_INIT)
                        if self.dynamic_scale else None),
            good_steps=(jnp.zeros((), jnp.int32)
                        if self.dynamic_scale else None))
        if self.zero:
            # static trees the stage-3 gather and the canonical-
            # checkpoint conversions close over: the model partition
            # specs, and each leaf's shard_map-LOCAL full shape
            self._zero_pspecs = pspecs
            self._zero_local_sds = jax.tree_util.tree_map(
                lambda spec, p: jax.ShapeDtypeStruct(
                    zero_lib.local_shape(spec, p.shape, mesh_shape),
                    p.dtype),
                pspecs, params, is_leaf=is_p)
            param_state_specs = pspecs
            if self.zero_stage == 3:
                # params themselves live as ZeRO slices
                param_state_specs = jax.tree_util.tree_map(
                    zero_lib.zero_leaf_spec, pspecs, is_leaf=is_p)
            state_specs = self._make_zero_state_specs(
                state, param_state_specs, opt_pspecs)
            self._state_specs = state_specs
            self._build_canonical(state, pspecs, opt_pspecs, state_specs)
            if self.zero_stage == 3:
                # move the seed-synced replicated init into the sliced
                # layout (the replicated copy is a transient of init;
                # restores go through staged_state and never rebuild it)
                state = state.replace(params=self._slice_params(params))
            self._build_steps(state_specs)
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.rt.mesh, s), state_specs,
                is_leaf=lambda x: isinstance(x, P))
            return jax.device_put(state, shardings)
        if self.param_spec_fn is None:
            # replicate across the mesh
            return jax.device_put(state, self.rt.replicated())
        # tensor parallelism: per-leaf shardings; kernels/moments split
        # over the 'model' axis, everything else replicated
        state_specs = self._make_state_specs(state)
        self._build_steps(state_specs)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.rt.mesh, s), state_specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(state, shardings)

    def _make_zero_state_specs(self, state: TrainState, param_specs,
                               opt_pspecs):
        from dtf_tpu.train.optimizer import opt_state_specs
        rep = P()
        return TrainState(
            step=rep,
            params=param_specs,
            batch_stats=jax.tree_util.tree_map(lambda _: rep,
                                               state.batch_stats),
            opt_state=opt_state_specs(self.cfg.optimizer, opt_pspecs, rep),
            loss_scale=rep if self.dynamic_scale else None,
            good_steps=rep if self.dynamic_scale else None)

    def _make_state_specs(self, state: TrainState):
        from dtf_tpu.train.optimizer import opt_state_specs
        pspecs = self.param_spec_fn(state.params)
        rep = P()
        return TrainState(
            step=rep,
            params=pspecs,
            batch_stats=jax.tree_util.tree_map(lambda _: rep,
                                               state.batch_stats),
            opt_state=opt_state_specs(self.cfg.optimizer, pspecs, rep),
            loss_scale=rep if self.dynamic_scale else None,
            good_steps=rep if self.dynamic_scale else None)

    # ------------------------------------------------------------------
    # Canonical checkpoint form (ZeRO stages).  Checkpoints are always
    # WRITTEN in the stage-0 layout — full-shaped params and optimizer
    # state — so a checkpoint saved at any ZeRO stage restores into any
    # other stage and into serving via the bridge's structure-free
    # loader.  The conversions are pure per-leaf reshapes/collectives
    # (train/zero.py): gather-trim-reshape out, pad-view-slice back
    # in.  Padding elements are zeros in every supported optimizer's state
    # (optimizer.ZEROS_INIT_OPTIMIZERS), so dropping them on save and
    # re-creating them on restore is exact — the round trip is
    # bit-identical, which is what keeps killed-at-K resume trajectory-
    # exact under ZeRO-3 (tests/test_zero_stages.py).
    # ------------------------------------------------------------------
    def _build_canonical(self, state: TrainState, pspecs, opt_pspecs,
                         state_specs):
        from dtf_tpu.train.optimizer import opt_state_specs
        mesh = self.rt.mesh
        nd = mesh.shape[DATA_AXIS]
        is_p = zero_lib.is_spec
        stage3 = self.zero_stage == 3
        local_sds = self._zero_local_sds
        # canonical spec/shape trees: params carry the model partition
        # specs; optimizer leaves mirror their params, with genuinely
        # replicated leaves (the adam step count) marked REP so the
        # converters know there is nothing to slice
        opt_canon_specs = opt_state_specs(self.cfg.optimizer, pspecs,
                                          zero_lib.REP)
        opt_local_sds = opt_state_specs(
            self.cfg.optimizer, local_sds,
            jax.ShapeDtypeStruct((), jnp.int32))
        canon_specs = TrainState(
            step=P(), params=zero_lib.concrete_specs(pspecs),
            batch_stats=jax.tree_util.tree_map(lambda _: P(),
                                               state.batch_stats),
            opt_state=zero_lib.concrete_specs(opt_canon_specs),
            loss_scale=P() if self.dynamic_scale else None,
            good_steps=P() if self.dynamic_scale else None)
        self._canon_specs = canon_specs

        def gather_opt_leaf(spec, sds, leaf):
            return zero_lib.gather_leaf(spec, leaf, sds.shape, sds.dtype,
                                        nd)

        def to_canonical_local(st: TrainState) -> TrainState:
            p = st.params
            if stage3:
                p = zero_lib.tree_map_specs(gather_opt_leaf, pspecs,
                                            local_sds, p)
            opt = zero_lib.tree_map_specs(gather_opt_leaf,
                                          opt_canon_specs, opt_local_sds,
                                          st.opt_state)
            return st.replace(params=p, opt_state=opt)

        def to_staged_local(st: TrainState) -> TrainState:
            idx = lax.axis_index(DATA_AXIS)
            p = st.params
            if stage3:
                p = zero_lib.tree_map_specs(
                    lambda spec, leaf: zero_lib.slice_leaf(spec, leaf, nd,
                                                           idx),
                    pspecs, p)
            opt = zero_lib.tree_map_specs(
                lambda spec, leaf: zero_lib.slice_leaf(spec, leaf, nd,
                                                       idx),
                opt_canon_specs, st.opt_state)
            return st.replace(params=p, opt_state=opt)

        self._to_canonical = jax.jit(jax.shard_map(
            to_canonical_local, mesh=mesh, in_specs=(state_specs,),
            out_specs=canon_specs, check_vma=False))
        self._to_staged = jax.jit(jax.shard_map(
            to_staged_local, mesh=mesh, in_specs=(canon_specs,),
            out_specs=state_specs, check_vma=False))

        def slice_params_local(p):
            idx = lax.axis_index(DATA_AXIS)
            return zero_lib.tree_map_specs(
                lambda spec, leaf: zero_lib.slice_leaf(spec, leaf, nd,
                                                       idx),
                pspecs, p)

        self._slice_params = jax.jit(jax.shard_map(
            slice_params_local, mesh=mesh,
            in_specs=(zero_lib.concrete_specs(pspecs),),
            out_specs=state_specs.params, check_vma=False))

        template = TrainState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            params=jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
                state.params),
            batch_stats=jax.tree_util.tree_map(
                lambda b: jax.ShapeDtypeStruct(b.shape, b.dtype),
                state.batch_stats),
            opt_state=jax.eval_shape(self.tx.init, state.params),
            loss_scale=(jax.ShapeDtypeStruct((), jnp.float32)
                        if self.dynamic_scale else None),
            good_steps=(jax.ShapeDtypeStruct((), jnp.int32)
                        if self.dynamic_scale else None))
        # restore places directly into the canonical shardings (a TP
        # leaf never materializes replicated on one device)
        canon_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), canon_specs,
            is_leaf=lambda x: isinstance(x, P))
        self._canonical_template = jax.tree_util.tree_map(
            lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                                 sharding=sh),
            template, canon_shardings)

    def canonical_state(self, state: TrainState) -> TrainState:
        """The stage-0 (checkpoint wire) form of a live TrainState —
        identity for non-ZeRO runs."""
        if not self.zero:
            return state
        return self._to_canonical(state)

    def staged_state(self, canonical: TrainState) -> TrainState:
        """A restored canonical TrainState placed into THIS run's stage
        layout (sliced params/optimizer state, proper shardings)."""
        if not self.zero:
            return canonical
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.rt.mesh, s), self._canon_specs,
            is_leaf=lambda x: isinstance(x, P))
        return self._to_staged(jax.device_put(canonical, shardings))

    def canonical_template(self):
        """ShapeDtypeStruct tree of the canonical checkpoint form (the
        restore template — stage-independent).  Only meaningful after
        init_state on a ZeRO run; non-ZeRO runs restore against the
        live state directly."""
        assert self.zero, "canonical_template is the ZeRO restore path"
        return self._canonical_template

    # ------------------------------------------------------------------
    def _apply(self, params, batch_stats, images, train):
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        if train:
            # "aux_loss" collects regularizers sown by modules (MoE
            # load-balance); empty for every dense model
            mutable = (["batch_stats"] if batch_stats else []) + ["aux_loss"]
            out, mutated = self.model.apply(
                variables, images, train=True, mutable=mutable)
            new_stats = mutated.get("batch_stats", batch_stats) if batch_stats else batch_stats
            aux_leaves = jax.tree_util.tree_leaves(
                mutated.get("aux_loss", {}))
            aux = (jnp.sum(jnp.stack([a.astype(jnp.float32)
                                      for a in aux_leaves]))
                   if aux_leaves else jnp.zeros((), jnp.float32))
            return out, new_stats, aux
        return self.model.apply(variables, images, train=False), batch_stats

    def _build_steps(self, state_specs=None, comm_off=False):
        """Builds the jitted SPMD train/eval steps.  ``comm_off=True``
        builds and RETURNS the ``--zero_probe`` timing twin instead of
        installing it: the same step with every data-axis ZeRO
        collective replaced by a shape-right local stub (train/zero.py)
        — its wall time is the step minus those collectives, which is
        what turns exposed-comm into a measured number.  Twin results
        are garbage by construction and must never become state."""
        mesh = self.rt.mesh
        # token data shards [B, S] over (data, seq); vision shards dim 0
        if self.spec.is_sequence:
            data_spec = P(DATA_AXIS, SEQ_AXIS)
        else:
            data_spec = P(DATA_AXIS)
        # gradients/metrics average over every axis the batch is split
        # across; 'seq' has size 1 (identity) for vision runs
        reduce_axes = (DATA_AXIS, SEQ_AXIS)
        rep = P()
        loss_scale = self.loss_scale
        l2w = self.l2_weight

        # Per-leaf gradient reduction.  Replicated leaves pmean over
        # every batch-splitting axis (the NCCL-ring / collective
        # allreduce equivalent).  Leaves *sharded over* a batch axis
        # (MoE experts ride 'data') must not be pmean-ed there — that
        # would average different experts' grads; reverse-mode
        # all_to_all already summed their true grads across the group,
        # so they are divided by the axis size to match the global-mean
        # loss convention instead.
        param_specs = None if state_specs is None else state_specs.params
        if self.zero_stage == 3 and state_specs is not None:
            # state_specs.params is the SLICED layout; the step's grad
            # reduction / clipping / L2 reason about the gathered full
            # params, whose layout is the model partition specs
            param_specs = self._zero_pspecs
        local_sds = getattr(self, "_zero_local_sds", None)
        mesh_shape = dict(mesh.shape)
        nd = mesh_shape[DATA_AXIS]
        zero_stage = self.zero_stage
        zero_wire = self.zero_wire
        ring = zero_lib.ring_order(mesh) if self.zero else None

        def reduce_grads(grads):
            if param_specs is None:
                return jax.lax.pmean(grads, reduce_axes)

            def red(spec, g):
                sharded = _spec_axes(spec)
                axes = tuple(a for a in reduce_axes if a not in sharded)
                if axes:
                    g = jax.lax.pmean(g, axes)
                denom = 1
                for a in reduce_axes:
                    if a in sharded:
                        denom *= mesh_shape[a]
                if denom > 1:
                    g = (g / denom).astype(g.dtype)
                return g

            return jax.tree_util.tree_map(
                red, param_specs, grads,
                is_leaf=lambda x: isinstance(x, P))

        clip_norm = self.cfg.clip_grad_norm

        def clip_grads(grads):
            """Clip to the TRUE global L2 norm: a leaf sharded over a
            mesh axis holds distinct elements per shard, so its local
            sum-of-squares is psum-ed over that axis; replicated leaves
            contribute their full sum once.  Every shard computes the
            same norm, so the scaling stays replica-consistent."""
            if not clip_norm:
                return grads

            def leaf_sumsq(spec, g):
                ss = jnp.sum(jnp.square(g.astype(jnp.float32)))
                axes = tuple(_spec_axes(spec)) if spec is not None else ()
                if axes:
                    ss = lax.psum(ss, axes)
                return ss

            if param_specs is None:
                parts = jax.tree_util.tree_map(
                    lambda g: leaf_sumsq(None, g), grads)
            else:
                parts = jax.tree_util.tree_map(
                    leaf_sumsq, param_specs, grads,
                    is_leaf=lambda x: isinstance(x, P))
            sumsq = sum(jax.tree_util.tree_leaves(parts))
            norm = jnp.sqrt(sumsq)
            factor = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))
            return jax.tree_util.tree_map(
                lambda g: (g * factor).astype(g.dtype), grads)

        dynamic = self.dynamic_scale
        vocab_axis = self.vocab_axis
        zero = self.zero
        channels_first = self.channels_first
        # --report_accuracy_metrics false (reference common.py:277-278):
        # drop the in-step accuracy compute entirely for benchmark purity
        report_acc = self.cfg.report_accuracy_metrics

        def compute_ce(logits, labels):
            if vocab_axis is not None:
                return sharded_cross_entropy(logits, labels, vocab_axis)
            return cross_entropy(logits, labels)

        def compute_per_example_ce(logits, labels):
            if vocab_axis is not None:
                return sharded_per_example_cross_entropy(
                    logits, labels, vocab_axis)
            return per_example_cross_entropy(logits, labels)

        def compute_correct(logits, labels):
            """Per-position 0/1 correctness, float32."""
            if vocab_axis is not None:
                preds = sharded_argmax(logits, vocab_axis)
            else:
                preds = jnp.argmax(logits, -1)
            return (preds == labels).astype(jnp.float32)

        def compute_acc(logits, labels):
            if not report_acc:
                return jnp.zeros((), jnp.float32)
            return jnp.mean(compute_correct(logits, labels))

        accum = self.grad_accum
        normalize = self.normalize_fn

        def local_train_step(state: TrainState, images, labels):
            if channels_first:
                images = jnp.transpose(images, (0, 2, 3, 1))
            if normalize is not None:
                images = normalize(images)
            scale = state.loss_scale if dynamic else loss_scale

            is_p = zero_lib.is_spec
            zspecs = param_specs
            if zero:
                idx = lax.axis_index(DATA_AXIS)
                hops = zero_lib.ring_hops(ring, idx)

            # ZeRO-3: the params the model computes with are gathered
            # PER LEAF from their 1/nd slices at the top of the step —
            # each leaf's all_gather is an independent op feeding that
            # leaf's first use, so XLA's latency-hiding scheduler can
            # overlap later layers' gathers with earlier layers' compute
            if zero_stage == 3:
                model_params = jax.tree_util.tree_map(
                    lambda spec, sds, s: zero_lib.gather_leaf(
                        spec, s, sds.shape, sds.dtype, nd, comm_off),
                    zspecs, local_sds, state.params, is_leaf=is_p)
            else:
                model_params = state.params

            def grad_of_chunk(params, batch_stats, imgs, lbls):
                def loss_fn(p):
                    logits, new_stats, aux = self._apply(
                        p, batch_stats, imgs, train=True)
                    ce = compute_ce(logits, lbls)
                    loss = ce + l2_weight_penalty(p, l2w, param_specs) + aux
                    return loss * scale, (loss, compute_acc(logits, lbls),
                                          new_stats)
                return jax.grad(loss_fn, has_aux=True)(params)

            def scatter_tree(grads):
                return jax.tree_util.tree_map(
                    lambda spec, g: zero_lib.scatter_leaf(
                        spec, g, nd, reduce_axes, mesh_shape, comm_off,
                        idx, wire=zero_wire, ring=hops),
                    zspecs, grads, is_leaf=is_p)

            g_slices_acc = None
            if accum == 1:
                grads, (loss, acc, new_stats) = grad_of_chunk(
                    model_params, state.batch_stats, images, labels)
            elif zero_stage >= 2:
                # ZeRO-2/3 sharded gradient accumulation: each chunk's
                # grads scatter into f32 slices inside the scan's body
                # (per leaf, right after its producing op), so the scan
                # carry holds 1/nd-sized slices instead of a second
                # full gradient buffer.  What overlaps: a large leaf's
                # ring of collective-permutes runs beside the weight-
                # gradient matmuls under zero.TPU_STEP_OPTIONS; a
                # reduce-scatter (small leaves) never does — the TPU
                # compiler runs it synchronously (train/zero.py)
                chunks = jax.tree_util.tree_map(
                    lambda x: x.reshape((accum, x.shape[0] // accum)
                                        + x.shape[1:]), (images, labels))

                def body(carry, chunk):
                    gacc, stats, lacc, aacc = carry
                    g, (l, a, stats) = grad_of_chunk(
                        model_params, stats, *chunk)
                    gacc = jax.tree_util.tree_map(jnp.add, gacc,
                                                  scatter_tree(g))
                    return (gacc, stats, lacc + l, aacc + a), None

                zeros = jax.tree_util.tree_map(
                    lambda spec, p: zero_lib.slice_zeros(spec, p, nd),
                    zspecs, model_params, is_leaf=is_p)
                (gsum, new_stats, lsum, asum), _ = lax.scan(
                    body, (zeros, state.batch_stats,
                           jnp.zeros((), jnp.float32),
                           jnp.zeros((), jnp.float32)), chunks)
                g_slices_acc = jax.tree_util.tree_map(
                    lambda s: s / accum, gsum)
                grads = None
                loss, acc = lsum / accum, asum / accum
            else:
                # sequential microbatches: grads accumulate in the scan
                # carry (one buffer, not A stacked copies); BN stats
                # thread through exactly as A consecutive steps would
                chunks = jax.tree_util.tree_map(
                    lambda x: x.reshape((accum, x.shape[0] // accum)
                                        + x.shape[1:]), (images, labels))

                def body(carry, chunk):
                    gacc, stats, lacc, aacc = carry
                    g, (l, a, stats) = grad_of_chunk(
                        model_params, stats, *chunk)
                    gacc = jax.tree_util.tree_map(jnp.add, gacc, g)
                    return (gacc, stats, lacc + l, aacc + a), None

                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.promote_types(
                        p.dtype, jnp.float32)), model_params)
                (gsum, new_stats, lsum, asum), _ = lax.scan(
                    body, (zeros, state.batch_stats,
                           jnp.zeros((), jnp.float32),
                           jnp.zeros((), jnp.float32)), chunks)
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g / accum).astype(p.dtype),
                    gsum, model_params)
                loss, acc = lsum / accum, asum / accum
            if dynamic or loss_scale != 1.0:
                # linear, so unscaling slices ≡ unscaling full grads
                if g_slices_acc is not None:
                    g_slices_acc = jax.tree_util.tree_map(
                        lambda g: g / scale, g_slices_acc)
                else:
                    grads = jax.tree_util.tree_map(lambda g: g / scale,
                                                   grads)
            # per-replica BN stats averaged on update — MirroredStrategy's
            # variable aggregation semantics
            new_stats = jax.lax.pmean(new_stats, reduce_axes)

            if zero:
                # ZeRO weight-update sharding: the gradient all-reduce
                # becomes a reduce-scatter (same ICI volume), each data
                # shard updates its 1/nd slice with its 1/nd optimizer
                # state, and (stages 1-2) the updated slices all-gather
                # back — stage 3 keeps them sliced for the next step's
                # per-leaf gather.  Composed with model sharding: a
                # TP/PP leaf slices its LOCAL shard (scatter/gather
                # stay pure-'data' collectives); an expert leaf riding
                # 'data' updates in place (its grads were already
                # summed by the all_to_all transpose — divide to the
                # global-mean convention like reduce_grads does).
                g_slices = (g_slices_acc if g_slices_acc is not None
                            else scatter_tree(grads))
                if clip_norm:
                    def slice_sumsq(spec, s):
                        # each slice holds distinct elements across
                        # 'data' (and 'model' for model-sharded leaves)
                        axes = {DATA_AXIS} | (_spec_axes(spec)
                                              & {MODEL_AXIS})
                        return lax.psum(jnp.sum(jnp.square(s)),
                                        tuple(sorted(axes)))
                    parts = jax.tree_util.tree_map(slice_sumsq, zspecs,
                                                   g_slices, is_leaf=is_p)
                    sumsq = sum(jax.tree_util.tree_leaves(parts))
                    norm = jnp.sqrt(sumsq)
                    factor = jnp.minimum(
                        1.0, clip_norm / jnp.maximum(norm, 1e-12))
                    g_slices = jax.tree_util.tree_map(
                        lambda s: s * factor, g_slices)

                if zero_stage == 3:
                    # params already live as slices — no re-slicing
                    p_slices = state.params
                else:
                    p_slices = jax.tree_util.tree_map(
                        lambda spec, p: zero_lib.slice_leaf(spec, p, nd,
                                                            idx),
                        zspecs, state.params, is_leaf=is_p)
                updates, new_opt = self.tx.update(
                    g_slices, state.opt_state, p_slices, step=state.step)
                new_slices = optax.apply_updates(p_slices, updates)

                if zero_stage == 3:
                    # stay sliced: the NEXT step's per-leaf gather is
                    # this stage's one param collective
                    params = new_slices
                else:
                    params = jax.tree_util.tree_map(
                        lambda spec, ns, p: zero_lib.gather_leaf(
                            spec, ns, p.shape, p.dtype, nd, comm_off),
                        zspecs, new_slices, state.params, is_leaf=is_p)
                grads = g_slices  # the dynamic-scale finite check below
            else:
                # DEVICE/NETWORK BOUNDARY: gradient all-reduce over the
                # batch-splitting axes (≡ NCCL ring / collective
                # allreduce / PS push-pull, SURVEY §3); includes 'seq'
                # when the sequence dimension is sharded
                grads = reduce_grads(grads)
                grads = clip_grads(grads)
                updates, new_opt = self.tx.update(
                    grads, state.opt_state, state.params, step=state.step)
                params = optax.apply_updates(state.params, updates)
            new_scale, new_good = state.loss_scale, state.good_steps
            if dynamic:
                # TF2 LossScaleOptimizer semantics: skip the update on
                # non-finite grads and halve the scale; double it after
                # DYNAMIC_GROWTH_INTERVAL consecutive finite steps
                finite = jnp.array(True)
                for g in jax.tree_util.tree_leaves(grads):
                    finite = jnp.logical_and(finite,
                                             jnp.all(jnp.isfinite(g)))
                # every shard must reach the same verdict: a leaf
                # sharded over an axis (experts over 'data', TP/PP
                # stacks over 'model') can overflow on one shard only,
                # and a split decision would silently desynchronize
                # the replicated leaves and the scale itself
                finite = jax.lax.pmin(
                    finite.astype(jnp.int32),
                    (DATA_AXIS, SEQ_AXIS, MODEL_AXIS)).astype(bool)
                keep = lambda new, old: jax.tree_util.tree_map(
                    lambda n, o: jnp.where(finite, n, o), new, old)
                params = keep(params, state.params)
                new_opt = keep(new_opt, state.opt_state)
                new_stats = keep(new_stats, state.batch_stats)
                grew = state.good_steps + 1 >= DYNAMIC_GROWTH_INTERVAL
                new_scale = jnp.where(
                    finite,
                    jnp.where(grew, scale * 2.0, scale),
                    jnp.maximum(scale * 0.5, 1.0))
                new_good = jnp.where(jnp.logical_and(finite,
                                                     jnp.logical_not(grew)),
                                     state.good_steps + 1, 0)
            metrics = {
                "loss": jax.lax.pmean(loss, reduce_axes),
                "learning_rate": self.schedule(state.step),
            }
            if report_acc:
                metrics["accuracy"] = jax.lax.pmean(acc, reduce_axes)
            if dynamic:
                metrics["loss_scale"] = new_scale
            return TrainState(step=state.step + 1, params=params,
                              batch_stats=new_stats, opt_state=new_opt,
                              loss_scale=new_scale,
                              good_steps=new_good), metrics

        def local_eval_step(state: TrainState, images, labels, mask):
            """Masked sums, not batch means: eval pipelines pad the final
            partial batch (shapes stay static for XLA) and flag padding
            with mask=0, so eval covers exactly the real examples once —
            the reference's full-set eval (imagenet_preprocessing.py:
            259-323), which a drop-remainder loop silently under-covers.
            Units: examples for vision, tokens for sequence data."""
            if channels_first:
                images = jnp.transpose(images, (0, 2, 3, 1))
            if normalize is not None:
                images = normalize(images)
            if zero_stage == 3:
                eval_params = jax.tree_util.tree_map(
                    lambda spec, sds, s: zero_lib.gather_leaf(
                        spec, s, sds.shape, sds.dtype, nd, comm_off),
                    param_specs, local_sds, state.params,
                    is_leaf=zero_lib.is_spec)
            else:
                eval_params = state.params
            logits, _ = self._apply(eval_params, state.batch_stats,
                                    images, train=False)
            per = compute_per_example_ce(logits, labels)  # [B] | [B,S/sp]
            w = mask[:, None] * jnp.ones_like(per) if per.ndim == 2 else mask
            loss_sum = lax.psum(jnp.sum(per * w), reduce_axes)
            if report_acc:
                correct = lax.psum(
                    jnp.sum(compute_correct(logits, labels) * w),
                    reduce_axes)
            else:
                correct = jnp.zeros((), jnp.float32)
            count = lax.psum(jnp.sum(w), reduce_axes)
            return loss_sum, correct, count

        # replicated prefix by default; a full per-leaf tree under TP
        state_spec = rep if state_specs is None else state_specs

        train_sharded = jax.shard_map(
            local_train_step, mesh=mesh,
            in_specs=(state_spec, data_spec, data_spec),
            out_specs=(state_spec, rep),
            check_vma=False)
        # the mask is per-example [B]: sharded over 'data' only, even
        # when token data additionally shards dim 1 over 'seq'
        eval_sharded = jax.shard_map(
            local_eval_step, mesh=mesh,
            in_specs=(state_spec, data_spec, data_spec, P(DATA_AXIS)),
            out_specs=(rep, rep, rep),
            check_vma=False)

        # a step that scatters over the ring is built with the cap that
        # lays the hops under the backward (zero.TPU_STEP_OPTIONS); the
        # CPU's compiler does not know the option, and a step without a
        # ZeRO stage compiles exactly as before
        on_tpu = mesh.devices.flat[0].platform == "tpu"
        xla = zero_lib.TPU_STEP_OPTIONS if ring and on_tpu else None
        if comm_off:
            # the --zero_probe timing twin: returned, never installed,
            # never donated (its caller reuses the live state)
            return jax.jit(train_sharded)
        self.train_step = jax.jit(train_sharded, donate_argnums=(0,),
                                  compiler_options=xla)
        self.eval_step = jax.jit(eval_sharded)
        return None

    # ------------------------------------------------------------------
    def _compile_with_ledger(self, ledger, state, sharded):
        """AOT-compile the train step and register its XLA flop/byte
        counts with the MFU ledger.  Returns the compiled executable —
        the SAME program the jit path would run (donation included), so
        cost analysis is free rather than a second compile.  Any
        failure degrades to the plain jit path with no registration
        (observability must never change whether a run trains), and
        says so: a step that trains without an MFU entry is a finding."""
        try:
            compiled = self.train_step.lower(state, *sharded).compile()
        except Exception as e:  # noqa: BLE001 — see docstring
            log.warning("ledger: train-step AOT compile failed (%s: %s) "
                        "— using the jit path, no MFU entry",
                        type(e).__name__, e)
            return self.train_step
        ledger.register("train_step", compiled=compiled)
        return compiled

    # ------------------------------------------------------------------
    def _zero_overlap_probe(self, state: TrainState, batch, ledger,
                            window_step_s) -> None:
        """--zero_probe: turn the ZeRO-2/3 overlap claim into measured
        numbers (obs/ledger + registry gauges, tools/zero_smoke.py's inputs).

        Three measurements, all on the live mesh after training:
          1. standalone per-leaf reduce-scatter / all-gather of the
             param-shaped trees — the SERIALIZED collective wall, what
             the step would pay if nothing overlapped;
          2. a comm-stubbed twin of the compiled step (the same program
             minus the data-axis ZeRO collectives) — its wall is the
             step's compute+everything-else floor;
          3. the run's own median clean-window step time.

        exposed = max(0, step − twin) is the communication time the
        schedule failed to hide; exposed / serialized is the
        ``train_exposed_comm_frac`` gauge — strictly below 1.0 means
        the overlap is real, not a cost-model assumption."""
        mesh = self.rt.mesh
        nd = mesh.shape[DATA_AXIS]
        pspecs = self._zero_pspecs
        local_sds = self._zero_local_sds
        mesh_shape = dict(mesh.shape)
        grad_slice_specs = jax.tree_util.tree_map(
            zero_lib.zero_leaf_spec, pspecs, is_leaf=zero_lib.is_spec)
        reduce_axes = (DATA_AXIS, SEQ_AXIS)
        ring = zero_lib.ring_order(mesh)

        def scatter_local(p):
            idx = lax.axis_index(DATA_AXIS)
            hops = zero_lib.ring_hops(ring, idx)
            # same wire dtype as the live step: the probe must price
            # the collectives the run actually emits (--zero_wire)
            return zero_lib.tree_map_specs(
                lambda spec, g: zero_lib.scatter_leaf(
                    spec, g.astype(jnp.float32), nd, reduce_axes,
                    mesh_shape, False, idx, wire=self.zero_wire,
                    ring=hops),
                pspecs, p)

        def gather_local(s):
            return zero_lib.tree_map_specs(
                lambda spec, sds, leaf: zero_lib.gather_leaf(
                    spec, leaf, sds.shape, sds.dtype, nd),
                pspecs, local_sds, s)

        scatter_fn = jax.jit(jax.shard_map(
            scatter_local, mesh=mesh,
            in_specs=(zero_lib.concrete_specs(pspecs),),
            out_specs=zero_lib.concrete_specs(grad_slice_specs),
            check_vma=False))
        gather_fn = jax.jit(jax.shard_map(
            gather_local, mesh=mesh,
            in_specs=(zero_lib.concrete_specs(grad_slice_specs),),
            out_specs=zero_lib.concrete_specs(pspecs),
            check_vma=False))
        # full-param-shaped probe input (values irrelevant): global
        # shapes from the canonical template, placed per model specs
        pshard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            zero_lib.concrete_specs(pspecs),
            is_leaf=lambda x: isinstance(x, P))
        template = self._canonical_template.params
        full = jax.jit(
            lambda: jax.tree_util.tree_map(
                lambda sds: jnp.zeros(sds.shape, sds.dtype), template),
            out_shardings=pshard)()

        def timed(fn, arg, repeats: int = 5) -> float:
            jax.block_until_ready(fn(arg))  # compile outside the clock
            walls = []
            for _ in range(repeats):
                t0 = time.monotonic()
                jax.block_until_ready(fn(arg))
                # dtflint: sync-point (probe timing — the measurement IS
                # the sync)
                walls.append(time.monotonic() - t0)
            return sorted(walls)[len(walls) // 2]

        scatter_s = timed(scatter_fn, full)
        gather_s = timed(gather_fn, scatter_fn(full))
        twin = self._build_steps(self._state_specs, comm_off=True)
        twin_fn = lambda st: twin(st, *batch)[1]["loss"]
        nocomm_s = timed(twin_fn, state, repeats=3)
        step_s = sorted(window_step_s)[len(window_step_s) // 2]
        # stage >= 2 pays one reduce-scatter per microbatch plus one
        # param all-gather per step (stage 2: post-update; stage 3:
        # pre-compute) — the wall those would cost SERIALIZED
        serialized_s = self.grad_accum * scatter_s + gather_s
        exposed_s = max(0.0, step_s - nocomm_s)
        param_bytes = sum(
            int(np.prod(sds.shape)) * jnp.dtype(sds.dtype).itemsize
            for sds in jax.tree_util.tree_leaves(template))
        ledger.register("zero_scatter", flops=0.0,
                        bytes_accessed=float(param_bytes))
        ledger.observe("zero_scatter", scatter_s)
        ledger.register("zero_gather", flops=0.0,
                        bytes_accessed=float(param_bytes))
        ledger.observe("zero_gather", gather_s)
        from dtf_tpu.obs.registry import default_registry
        reg = default_registry()
        reg.gauge("train_zero_scatter_wall_s", unit="s").set(scatter_s)
        reg.gauge("train_zero_gather_wall_s", unit="s").set(gather_s)
        reg.gauge("train_zero_comm_serialized_s",
                  unit="s").set(serialized_s)
        reg.gauge("train_zero_step_nocomm_s", unit="s").set(nocomm_s)
        reg.gauge("train_exposed_comm_s", unit="s").set(exposed_s)
        frac = exposed_s / serialized_s if serialized_s > 0 else 0.0
        reg.gauge("train_exposed_comm_frac").set(frac)
        trace.event("zero_overlap", zero_stage=self.zero_stage,
                    scatter_wall_s=scatter_s, gather_wall_s=gather_s,
                    serialized_s=serialized_s, step_s=step_s,
                    nocomm_step_s=nocomm_s, exposed_s=exposed_s,
                    exposed_frac=frac)
        log.info("zero_probe: step %.2f ms, comm-off twin %.2f ms, "
                 "exposed comm %.2f ms vs %.2f ms serialized "
                 "(frac %.2f)", step_s * 1e3, nocomm_s * 1e3,
                 exposed_s * 1e3, serialized_s * 1e3, frac)

    # ------------------------------------------------------------------
    def evaluate(self, state: TrainState, eval_iter: Iterator,
                 heartbeat=None):
        """Weighted-exact eval: batches are (images, labels[, mask]);
        a missing mask means every example is real.  Returns
        (mean loss, top-1) over exactly the unmasked examples, or None
        when the iterator is empty.  top-1 is None under
        --report_accuracy_metrics false.  ``heartbeat``: beaten per
        batch so a long eval under the launcher supervisor stays
        visibly alive (the step loop — the usual beat site — is idle
        here)."""
        loss_sums, correct_sums, counts = [], [], []
        for batch in eval_iter:
            if heartbeat is not None:
                heartbeat.beat()
            if len(batch) == 2:
                images, labels = batch
                mask = np.ones((np.asarray(labels).shape[0],), np.float32)
            else:
                images, labels, mask = batch
            sharded = self.rt.shard_batch((images, labels, mask))
            ls, cs, n = self.eval_step(state, *sharded)
            loss_sums.append(ls)
            correct_sums.append(cs)
            counts.append(n)
        if not counts:
            return None
        total = float(np.sum(jax.device_get(counts)))
        if total == 0:
            return None
        loss = float(np.sum(jax.device_get(loss_sums))) / total
        if not self.cfg.report_accuracy_metrics:
            return (loss, None)
        return (loss,
                float(np.sum(jax.device_get(correct_sums))) / total)

    # ------------------------------------------------------------------
    def fit(self, state: TrainState, train_iter: Iterator,
            eval_iter_fn: Optional[Callable[[], Iterator]] = None,
            callbacks: Optional[list] = None):
        """Runs training; returns (state, stats-dict) where the stats dict
        is key-compatible with common.build_stats output."""
        cfg = self.cfg
        # dtflint: sync-point (one-time resume-position read, pre-loop)
        resumed_step = int(jax.device_get(state.step))
        time_cb = TimeHistory(self.global_batch, cfg.log_steps,
                              initial_global_step=resumed_step)
        # watchdogs (obs/watchdog): the NaN check reads the loss value
        # this loop already syncs at log cadence; the step-time guard
        # watches the same per-window wall time TimeHistory reports; the
        # heartbeat only exists when the launcher exported
        # DTF_HEARTBEAT_DIR.  All host-side, all off unless configured.
        nan_guard = NanLossWatchdog(enabled=getattr(cfg, "nan_guard", True))
        guard_factor = getattr(cfg, "step_time_guard_factor", 0.0) or 0.0
        step_guard = (StepTimeWatchdog(factor=guard_factor)
                      if guard_factor else None)
        heartbeat = Heartbeat.from_env(
            interval_s=getattr(cfg, "heartbeat_secs", 5.0))
        compile_pending = True
        window_t0 = time.monotonic()
        # calibration hook (dtf_tpu/plan): clean per-step wall times —
        # one sample per unskewed log window, so compile and epoch-
        # boundary work never contaminate the measurement the planner's
        # predicted-vs-measured ratio is computed against
        window_step_s: list = []
        # a skewed window covers non-step time (first-compile, or an
        # epoch boundary's eval/checkpoint) or fewer than log_steps
        # steps (post-boundary partial): emitting it would misreport
        # step_s and pollute the watchdog's rolling median — skip it
        window_skewed = True
        callbacks = [time_cb] + list(callbacks or [])
        acc_key = ("categorical_accuracy" if self.spec.one_hot
                   else "sparse_categorical_accuracy")
        history: dict = {"loss": [], acc_key: []}
        profile_range = _parse_profile_steps(cfg.profile_steps)
        profiling = False
        # ">= with a started flag" rather than "==": a resumed run whose
        # start step already passed profile_range[0] must still trace the
        # remaining in-range steps (--profile_steps contract under --resume).
        profile_started = False
        # profiler output goes to the TRACE dir when one is configured
        # — the XLA dump is observability artifact, not run state, and
        # mixing it into model_dir buries checkpoints under trace
        # protos (model_dir stays the fallback for untraced runs)
        profile_dir = (getattr(cfg, "trace_dir", "")
                       or os.environ.get("DTF_TRACE_DIR", "")
                       or cfg.model_dir)
        # MFU/cost ledger (obs/ledger.py): the train step registers its
        # XLA flop/byte counts at compile time — from the AOT
        # lower().compile() executable the loop then RUNS (no second
        # compile) — and every clean log window feeds its synced
        # per-step wall time.  DTF_LEDGER=0 is the kill switch (and
        # restores the pre-AOT jit dispatch path wholesale).
        from dtf_tpu.obs.ledger import Ledger
        ledger = Ledger()
        ledger_on = os.environ.get("DTF_LEDGER", "1") != "0"
        step_fn = self.train_step

        for cb in callbacks:
            _call(cb, "on_train_begin", None)
        eval_output = None
        metrics = None
        last_sharded = None
        global_step = resumed_step
        start_epoch = (global_step // self.steps_per_epoch
                       if self.steps_per_epoch else 0)
        # crash-exact mid-epoch resume: a run restored at step K of
        # epoch E continues at batch K%spe — it must neither re-train
        # the epoch prefix nor consume those batches from the (already
        # repositioned) data stream
        start_batch = (global_step % self.steps_per_epoch
                       if self.steps_per_epoch else 0)
        if global_step:
            log.info("resuming at step %d (epoch %d, batch %d)",
                     global_step, start_epoch, start_batch)
        t0 = time.time()
        try:
            for epoch in range(start_epoch, self.train_epochs):
                for cb in callbacks:
                    _call(cb, "on_epoch_begin", epoch, None)
                for batch_idx in range(
                        start_batch if epoch == start_epoch else 0,
                        self.steps_per_epoch):
                    for cb in callbacks:
                        _call(cb, "on_batch_begin", batch_idx, None)
                    if (profile_range and not profile_started
                            and global_step >= profile_range[0]
                            and global_step <= profile_range[1]):
                        jax.profiler.start_trace(profile_dir)
                        # surfaced by trace_main's summary: where the
                        # profiler dump for this run actually lives
                        trace.event("profiler_trace", path=profile_dir,
                                    start_step=global_step,
                                    stop_step=profile_range[1])
                        profiling = True
                        profile_started = True
                    images, labels = next(train_iter)
                    if hasattr(images, "device"):  # already sharded by prefetcher
                        sharded = (images, labels)
                    else:
                        sharded = self.rt.shard_batch((images, labels))
                    last_sharded = sharded
                    # NOTE: jit dispatch is async — a "step" span measures
                    # host-side dispatch (sub-ms once compiled), which is
                    # what makes it cheap enough to emit every step.  It
                    # exists for counting/attribution and host-stall
                    # detection; SYNCED wall-clock timing comes from the
                    # "log_window" spans below (and the "compile" span,
                    # whose first call blocks on trace+compile).
                    try:
                        if compile_pending:
                            compile_pending = False
                            with trace.span("compile", step=global_step):
                                if ledger_on:
                                    step_fn = self._compile_with_ledger(
                                        ledger, state, sharded)
                                with trace.span("step", step=global_step):
                                    state, metrics = step_fn(state,
                                                             *sharded)
                        else:
                            with trace.span("step", step=global_step):
                                state, metrics = step_fn(state, *sharded)
                    except Exception as e:  # noqa: BLE001 — classify,
                        # never swallow: only a recognized accelerator
                        # loss is translated; everything else keeps its
                        # ordinary crash path (traceback + crash budget)
                        from dtf_tpu.train import elastic as elastic_lib
                        if elastic_lib.is_device_loss(e):
                            trace.anomaly("device_lost", step=global_step,
                                          error=f"{type(e).__name__}: {e}")
                            raise elastic_lib.DeviceLost(global_step,
                                                         e) from e
                        raise
                    global_step += 1
                    if global_step % cfg.log_steps == 0:
                        # dtflint: sync-point (log-cadence host copy —
                        # the ledger's log_window wall time accounts it)
                        loss_val = jax.device_get(metrics["loss"])
                        nan_guard.check(global_step, float(loss_val))
                        # the loss trajectory record: Python floats
                        # round-trip JSON exactly, so the chaos suite's
                        # crash-exactness asserts compare these
                        # bit-identically across killed+resumed runs
                        trace.event("train_loss", step=global_step,
                                    loss=float(loss_val))
                        now = time.monotonic()
                        if not window_skewed:
                            # the one host-measured duration that spans a
                            # real device sync: log_steps steps of true
                            # wall time — the per-step timing signal
                            window_s = now - window_t0
                            trace.span_completed(
                                "log_window", window_s, step=global_step,
                                steps=cfg.log_steps,
                                step_s=window_s / cfg.log_steps)
                            window_step_s.append(window_s / cfg.log_steps)
                            # MFU ledger: the one per-step duration that
                            # spans a real device sync
                            ledger.observe("train_step",
                                           window_s / cfg.log_steps)
                            if step_guard is not None:
                                step_guard.observe(global_step, window_s)
                        window_t0 = now
                        window_skewed = False
                    if heartbeat is not None:
                        heartbeat.beat(step=global_step)
                    if profiling and global_step > profile_range[1]:
                        jax.profiler.stop_trace()
                        profiling = False
                    # interval checkpointing reads state/step from the
                    # logs dict (CheckpointCallback.every_steps)
                    for cb in callbacks:
                        _call(cb, "on_batch_end", batch_idx,
                              {"state": state, "step": global_step})
                    ckpt_every = getattr(cfg, "checkpoint_steps", 0) or 0
                    if ckpt_every and global_step % ckpt_every == 0:
                        # an interval save just ran inside this log
                        # window (synchronous seal — and under ZeRO
                        # the canonical param/opt gather): skip the
                        # window from the step-time signal like epoch
                        # boundaries are, or train_step_s, the
                        # step-time watchdog and the --zero_probe
                        # exposed-comm number all absorb checkpoint
                        # I/O as "step time"
                        window_skewed = True
                    # chaos probe AFTER the interval checkpoint sealed:
                    # crash@step:K with checkpoint_steps dividing K is
                    # the deterministic kill-after-durable-save
                    # experiment (tests/test_chaos.py)
                    chaos.step(global_step)
                    signum = preemption.triggered()
                    if signum is not None:
                        # preemption (SIGTERM/SIGINT): emergency
                        # checkpoint at this step boundary — save +
                        # wait + integrity manifest — then the distinct
                        # preempted exit the supervisor restarts
                        # without consuming the crash budget
                        for cb in callbacks:
                            _call(cb, "on_preempt",
                                  {"state": state, "step": global_step})
                        trace.event("preempted", step=global_step,
                                    signum=int(signum))
                        trace.flush()
                        raise preemption.Preempted(global_step, signum)
                # epoch end: materialize the last step's metrics (keras history
                # records per-epoch training metrics)
                # dtflint: sync-point (epoch-boundary metrics copy,
                # outside the step-time guard's measured window)
                m = jax.device_get(metrics)
                nan_guard.check(global_step, float(m["loss"]))
                trace.event("epoch_end", epoch=epoch, step=global_step,
                            loss=float(m["loss"]))
                history["loss"].append(float(m["loss"]))
                if "accuracy" in m:
                    history[acc_key].append(float(m["accuracy"]))
                for cb in callbacks:
                    _call(cb, "on_epoch_end", epoch,
                          {"state": state, "history": history})
                if heartbeat is not None:
                    # epoch-boundary work (checkpoint save above, eval
                    # below) runs outside the step loop's beat site — beat
                    # here so a slow save doesn't read as a dead rank
                    heartbeat.beat(step=global_step)
                if cfg.verbose and (jax.process_index() == 0):
                    log.info("epoch %d/%d: loss=%.4f top1=%s lr=%.5f",
                             epoch + 1, self.train_epochs, history["loss"][-1],
                             ("%.4f" % m["accuracy"]) if "accuracy" in m
                             else "n/a", float(m["learning_rate"]))
                run_eval = (not cfg.skip_eval and eval_iter_fn is not None and
                            ((epoch + 1) % cfg.epochs_between_evals == 0 or
                             epoch + 1 == self.train_epochs))
                if run_eval:
                    with trace.span("eval", epoch=epoch, step=global_step):
                        eval_output = self.evaluate(state, eval_iter_fn(),
                                                    heartbeat=heartbeat)
                    if eval_output and jax.process_index() == 0:
                        log.info("eval: loss=%.4f top1=%s", eval_output[0],
                                 ("%.4f" % eval_output[1])
                                 if eval_output[1] is not None else "n/a")
                    # --stop_threshold parity (model_helpers.past_stop_threshold
                    # via flags_core.define_base): end training once eval top-1
                    # reaches the threshold
                    if (eval_output and cfg.stop_threshold is not None
                            and eval_output[1] is not None
                            and eval_output[1] >= cfg.stop_threshold):
                        if jax.process_index() == 0:
                            log.info("stop_threshold %.4f reached (top1=%.4f) — "
                                     "stopping early at epoch %d",
                                     cfg.stop_threshold, eval_output[1], epoch + 1)
                        break
                # the epoch boundary just spent wall time on non-step work
                # (metrics sync, eval — incl. its one-time compile —
                # checkpoint-save callbacks): restart the step-time guard's
                # window here, or the next log window would measure that
                # work as a step-time regression on a healthy run
                window_t0 = time.monotonic()
                window_skewed = True  # next boundary closes a partial window
                if heartbeat is not None:
                    heartbeat.beat(step=global_step)
        finally:
            # one teardown for every exit — normal completion, the
            # stop_threshold break, and watchdog aborts
            # (TrainingAnomaly) alike: an in-flight profiler trace is
            # stopped and flushed, not orphaned mid-dump
            if profiling:
                jax.profiler.stop_trace()
        if (start_epoch >= self.train_epochs and not cfg.skip_eval
                and eval_iter_fn is not None):
            # resumed a fully-trained checkpoint: still honor the eval ask
            eval_output = self.evaluate(state, eval_iter_fn(),
                                        heartbeat=heartbeat)
            if eval_output and jax.process_index() == 0:
                log.info("eval (resumed, no further training): loss=%.4f "
                         "top1=%s", eval_output[0],
                         ("%.4f" % eval_output[1])
                         if eval_output[1] is not None else "n/a")
        for cb in callbacks:
            _call(cb, "on_train_end", {"state": state, "history": history})
        if metrics is not None:
            # host copy of the last loss: the completion barrier
            # dtflint: sync-point (final completion barrier, post-loop)
            jax.device_get(metrics["loss"])
        log.info("train wall time: %.1fs (%d steps)",
                 time.time() - t0, global_step)
        trace.event("train_end", step=global_step,
                    wall_s=time.time() - t0)
        if (self.zero_stage >= 2 and getattr(cfg, "zero_probe", False)
                and window_step_s and last_sharded is not None):
            try:
                self._zero_overlap_probe(state, last_sharded, ledger,
                                         window_step_s)
            except Exception:  # noqa: BLE001 — a probe must not fail a run
                log.exception("zero_probe failed — overlap gauges skipped")
        ledger.emit_summary()
        trace.flush()
        # calibration gauges (dtf_tpu/plan reads these after a measured
        # smoke): the median clean-window step time, and the live
        # device bytes at train end — params + optimizer state + grads
        # + pipeline buffers, the persistent portion of the planner's
        # predicted peak.  One live_arrays walk per fit: negligible.
        from dtf_tpu.obs.registry import default_registry
        if window_step_s:
            mid = sorted(window_step_s)[len(window_step_s) // 2]
            default_registry().gauge("train_step_s", unit="s").set(mid)
        try:
            # PER-DEVICE bytes (the planner's predicted peak is
            # per-device): sum physical shard bytes on the local
            # devices, averaged over them — a.size alone counts the
            # global logical array, which overstates sharded tensors
            # by the shard count and misstates replicated ones
            live = 0
            for a in jax.live_arrays():
                shards = getattr(a, "addressable_shards", None)
                if shards:
                    live += sum(int(np.prod(s.data.shape))
                                * a.dtype.itemsize for s in shards)
                else:
                    live += a.size * a.dtype.itemsize
            live //= max(jax.local_device_count(), 1)
        except Exception:  # noqa: BLE001 — diagnostics must not fail a run
            live = 0
        if live:
            default_registry().gauge("train_live_bytes",
                                     unit="bytes").set(live)
        stats = build_stats(history, eval_output, time_cb)
        return state, stats


def _call(cb, name, *args):
    fn = getattr(cb, name, None)
    if fn is not None:
        fn(*args)


def _parse_profile_steps(profile_steps: Optional[str]):
    """--profile_steps "start,stop" parity (common.py:289-296)."""
    if not profile_steps:
        return None
    parts = [p.strip() for p in str(profile_steps).split(",")]
    if len(parts) != 2:
        raise ValueError("profile_steps must be 'start,stop'")
    return int(parts[0]), int(parts[1])
