"""Process + device initialization and mesh construction.

TPU-native successor of the reference's L1/L2 layers (SURVEY.md §1):
``TF_CONFIG`` parsing + grpc server rendezvous + strategy objects
(reference distribution_utils call sites, resnet_cifar_main.py:100-105)
become: ``jax.distributed.initialize`` for multi-host rendezvous over
DCN, and a ``jax.sharding.Mesh`` whose axes carry the parallelism:

    ('data', 'seq', 'model')

The reference is data-parallel only (SURVEY §2.2) so 'seq' and 'model'
default to size 1, but the mesh keeps them open — adding tensor or
sequence (ring-attention) parallelism is a config change, not a
redesign.

Rank-concept mapping (SURVEY §5.8):
    hvd.rank()        → jax.process_index()
    hvd.local_rank()  → local device ordinal
    hvd.size()        → jax.process_count() / device_count()
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dtf_tpu.config import Config

log = logging.getLogger("dtf_tpu")

DATA_AXIS = "data"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"
MESH_AXES = (DATA_AXIS, SEQ_AXIS, MODEL_AXIS)

_distributed_initialized = False


def _maybe_init_distributed(cfg: Config) -> None:
    """Multi-host rendezvous — the grpc-server/Distribute-Coordinator
    equivalent (evidence in reference ps_server/log0.log)."""
    global _distributed_initialized
    if _distributed_initialized:
        return
    if cfg.process_count and cfg.process_count > 1:
        if not cfg.coordinator_address or cfg.process_id is None:
            raise ValueError(
                "multi-process run needs coordinator_address and process_id "
                "(set flags, DTF_* env vars, or TF_CONFIG)")
        jax.distributed.initialize(
            coordinator_address=cfg.coordinator_address,
            num_processes=cfg.process_count,
            process_id=cfg.process_id,
        )
        _distributed_initialized = True


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def local_device_count() -> int:
    return jax.local_device_count()


def is_coordinator() -> bool:
    """The hvd-rank-0 predicate used to gate checkpoints/verbosity
    (reference resnet_imagenet_main_horovod.py:255-260)."""
    return jax.process_index() == 0


@dataclasses.dataclass
class MeshRuntime:
    """A constructed device mesh plus the sharding helpers the train
    loop needs.  This is the strategy-scope equivalent: variables are
    replicated, the batch is sharded over 'data' (× 'seq' for long
    sequences)."""

    mesh: Mesh
    strategy: str
    # Token datasets shard dim 1 (sequence) over the 'seq' axis as well;
    # set by the runner from DatasetSpec.is_sequence.  Harmless when the
    # seq axis has size 1.
    shard_seq: bool = False

    @property
    def num_replicas(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    @property
    def num_devices(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    # -- shardings -----------------------------------------------------
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def data_sharding(self, ndim: int = 1) -> NamedSharding:
        """Batch dim sharded over 'data'; for sequence data dim 1 is
        additionally sharded over 'seq'; rest replicated."""
        return NamedSharding(self.mesh, self.batch_spec(ndim))

    def batch_spec(self, ndim: int = 1) -> P:
        if self.shard_seq and ndim >= 2:
            return P(DATA_AXIS, SEQ_AXIS, *([None] * (ndim - 2)))
        return P(DATA_AXIS, *([None] * (ndim - 1)))

    def shard_batch(self, batch):
        """Place a host-global batch onto the mesh, sharded on dim 0.

        Accepts numpy or jax arrays (a pytree); in multi-process runs the
        per-host array is the local shard and we assemble a global array
        via make_array_from_process_local_data.
        """
        def put(x):
            x = np.asarray(x)
            sh = self.data_sharding(x.ndim)
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sh, x)
            return jax.device_put(x, sh)
        return jax.tree_util.tree_map(put, batch)


def initialize(cfg: Config) -> MeshRuntime:
    """Build the runtime for a named distribution strategy.

    Strategy → mesh mapping (SURVEY §2.2 table, right column):
      off/one_device         — 1 device, mesh (1,1,1): plain jit
      mirrored               — all local devices on the data axis
      tpu                    — alias of mirrored over every addressable chip
      multi_worker_mirrored  — global mesh across processes (ICI within a
                               slice, DCN across), sync allreduce
      horovod                — same SPMD path; horovod-parity semantics
                               (broadcast-init ≡ seed-synced replicated init,
                               metric averaging ≡ pmean, rank-0 I/O)
      parameter_server       — SPMD reinterpretation (BASELINE.json north
                               star): synchronous data parallelism; the
                               async push/pull semantics of the reference
                               (ps_server/, SURVEY §3.4) do not map to the
                               TPU execution model and are provided as a
                               separate opt-in host-side mode (parallel/ps).
    """
    _maybe_init_distributed(cfg)
    strategy = cfg.distribution_strategy
    devices = jax.devices()
    if strategy == "tpu" and devices[0].platform != "tpu":
        # with no chip visible JAX warns and hands back the CPU; a run
        # that asked for the TPU by name must not train there
        raise RuntimeError(
            f"--distribution_strategy tpu: JAX found no TPU (platform "
            f"{devices[0].platform!r}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); use 'mirrored' to "
            f"run on whatever devices are attached")

    if strategy in ("off", "one_device"):
        devices = devices[:1]
    elif cfg.num_devices:
        if strategy in ("mirrored",):
            devices = jax.local_devices()[: cfg.num_devices]
        else:
            devices = devices[: cfg.num_devices]
    elif strategy == "mirrored":
        devices = jax.local_devices()

    n = len(devices)
    mp, sp = cfg.model_parallelism, cfg.seq_parallelism
    if n % (mp * sp):
        raise ValueError(
            f"{n} devices not divisible by model_parallelism*seq_parallelism={mp * sp}")
    dp = n // (mp * sp)
    dev_array = np.array(devices).reshape(dp, sp, mp)
    mesh = Mesh(dev_array, MESH_AXES)
    log.info(
        "mesh initialized: strategy=%s devices=%d data=%d seq=%d model=%d "
        "process=%d/%d", strategy, n, dp, sp, mp,
        jax.process_index(), jax.process_count())
    return MeshRuntime(mesh=mesh, strategy=strategy)


def topology() -> dict:
    """The live process/device topology as the parallelism planner's
    mesh descriptor sees it: hosts (= processes), local devices per
    host, and the backend platform.  Read-only, but the device query
    initializes the jax backend — in a multi-process run call
    :func:`_maybe_init_distributed` first (runner._run does), or
    ``process_count()`` reports 1 and the later distributed
    rendezvous refuses an already-initialized backend."""
    dev = jax.devices()[0]
    # bytes_limit is what the allocator will actually hand out; the CPU
    # backend reports no memory stats
    stats = dev.memory_stats() or {}
    return {
        "num_hosts": jax.process_count(),
        "devices_per_host": jax.local_device_count(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "hbm_bytes": stats.get("bytes_limit"),
    }


def make_mesh(devices: Optional[Sequence] = None, data: int = -1,
              seq: int = 1, model: int = 1) -> Mesh:
    """Direct mesh constructor for tests and advanced use."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if data == -1:
        data = n // (seq * model)
    arr = np.array(devices[: data * seq * model]).reshape(data, seq, model)
    return Mesh(arr, MESH_AXES)
