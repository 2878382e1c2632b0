"""Elastic training: shrink/grow resume across topology loss.

A preempted pod slice or a dead host used to mean waiting (or a crash
loop burning the restart budget on a fault no restart-at-size can
fix).  The pieces that make resuming SMALLER safe were deliberately
pre-staged and this module is the thin layer that binds them:

  - ZeRO checkpoints are written in the canonical stage-0 layout
    (full-shaped params + optimizer state, train/loop.py
    ``canonical_state``), so a checkpoint is TOPOLOGY-FREE: restoring
    it onto an arbitrary surviving mesh is ``staged_state`` — each
    leaf re-slices through the train/zero.py layout contract
    (``as_view`` zero-pads to the NEW nd's tile grid, so a
    non-dividing new dp costs padding that provably stays zero, not
    correctness).
  - The data stream is a pure function of position (PR 6): per-shard
    data-service positions are derived from the restored step alone,
    and worker count is a non-identity — so the stream remaps to the
    surviving host set with no bookkeeping.
  - Parallelization is re-resolved against whatever the relaunch
    attaches: ``--plan auto`` re-ranks the lattice for the surviving
    mesh (per-shard batch + grad-accum recomputed, GLOBAL batch and
    step semantics invariant); plain mirrored re-meshes over the local
    devices.

The supervisor half lives in ``cli/launch.py`` (stdlib-only by design
— it keeps copies of the contracts below; parity is pinned by
tests/test_elastic.py): device/host loss is CLASSIFIED apart from
ordinary crashes (EXIT_DEVICE_LOST, heartbeat-lost kills, unprompted
SIGKILLs), an ``--elastic`` policy shrinks the topology instead of
burning the restart budget, a ``--min_devices`` floor refuses loudly,
and a re-announced capacity (``elastic_rejoin.json``) grows the job
back at a checkpoint boundary.

The headline contract (tools/elastic_smoke.py, ci_check stage 14):
train on N devices, lose a host at step K, resume on N/2 with the
per-step loss trajectory BIT-IDENTICAL to an oracle launched fresh on
N/2 from the same checkpoint — then grow back to N.
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
from typing import List

import jax

from dtf_tpu.obs import trace
from dtf_tpu.train import zero as zero_lib

log = logging.getLogger("dtf_tpu")

# Exit-code / env / rendezvous contracts shared with cli/launch.py and
# dtf_tpu/chaos (both keep stdlib-only copies so the supervisor never
# imports the package it supervises; parity is test-pinned).
EXIT_DEVICE_LOST = 76
DEVICES_ENV = "DTF_ELASTIC_DEVICES"
REJOIN_FILE = "elastic_rejoin.json"

# XLA runtime error-text markers that mean THE ACCELERATORS ARE GONE
# (slice preemption, PCIe/ICI fault, TPU driver reset) rather than a
# bug in the step: jaxlib surfaces them as XlaRuntimeError with a
# status-code prefix.  Matched case-insensitively against both the
# exception type name and its message — jaxlib moves the exception
# class between releases (jax.errors / jaxlib.xla_extension), so the
# classifier keys on the STABLE parts: the runtime's status vocabulary.
_DEVICE_LOSS_MARKERS = (
    "device_lost", "device lost", "data_loss",
    "failed_precondition: device", "device or resource busy",
    "tpu driver", "device is in an invalid state",
)


class DeviceLost(RuntimeError):
    """An XLA runtime failure classified as accelerator loss: the host
    survives but its chips are gone.  The train loop converts the
    runtime's exception into this, and the runner exits
    ``EXIT_DEVICE_LOST`` so an ``--elastic`` supervisor RESHARDS onto
    the surviving topology instead of burning the crash budget on a
    fault no same-size restart can fix."""

    def __init__(self, step: int, cause: BaseException):
        super().__init__(
            f"device loss at step {step}: "
            f"{type(cause).__name__}: {cause}")
        self.step = int(step)
        self.cause = cause


def is_device_loss(exc: BaseException) -> bool:
    """True when ``exc`` is the XLA runtime reporting accelerator loss
    (vs an ordinary step-function error, which must keep crashing the
    normal way — misclassifying a NaN-shaped bug as device loss would
    make the supervisor shrink a healthy topology forever)."""
    name = type(exc).__name__.lower()
    if "xlaruntimeerror" not in name and "runtimeerror" not in name:
        return False
    text = f"{type(exc).__name__}: {exc}".lower()
    return any(m in text for m in _DEVICE_LOSS_MARKERS)


def announce_rejoin(log_dir: str, devices: int) -> str:
    """Re-announce capacity to a shrunken job's supervisor: a healed
    host's agent (or an operator, or the elastic smoke) writes
    ``{"devices": N}`` atomically into the supervisor's log dir.  The
    supervisor's grow-back probe consumes it — once the announced count
    covers the full topology, the job drains at a checkpoint boundary
    and relaunches at full size."""
    path = os.path.join(log_dir, REJOIN_FILE)
    fd, tmp = tempfile.mkstemp(dir=log_dir, suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        json.dump({"devices": int(devices)}, f)
    os.replace(tmp, path)
    log.info("elastic: re-announced %d device(s) at %s", devices, path)
    return path


def check_reshardable(pspecs, leaves, mesh_shape: dict) -> List[str]:
    """Violation messages for leaves that CANNOT shard onto a mesh of
    ``mesh_shape`` — empty when the whole tree reshards.

    The ZeRO column-slice layout reshards onto ANY data-parallel degree
    by construction (``as_view`` zero-pads to the new nd's tile grid:
    a leaf's view, and with it its slices' shapes, follow nd), so the
    only real constraints are the leaves whose MODEL partition spec
    pins a tensor dimension to a mesh axis: expert leaves riding
    'data' need the new dp to divide their expert dimension, and
    TP/PP-sharded dims need the (usually unchanged) model axis to
    divide theirs.  A violating resume must refuse with the leaf path,
    not garble state or die in a device_put stack trace."""
    problems: List[str] = []

    def visit(path, spec, leaf):
        if isinstance(spec, zero_lib.Replicated) or spec is None:
            return
        shape = tuple(leaf.shape)
        for d, part in enumerate(spec):
            if part is None:
                continue
            ways = 1
            for a in (part if isinstance(part, (tuple, list)) else (part,)):
                ways *= int(mesh_shape[a])
            if ways > 1 and shape[d] % ways:
                problems.append(
                    f"{jax.tree_util.keystr(path)}: dim {d} "
                    f"({shape[d]}) is not divisible by its mesh axes "
                    f"{part!r} (size {ways})")

    jax.tree_util.tree_map_with_path(visit, pspecs, leaves,
                                     is_leaf=zero_lib.is_spec)
    return problems


def note_elastic_resume(runtime, resumed_step: int) -> None:
    """Under elastic supervision (DEVICES_ENV exported): verify the
    attached topology matches the supervisor's surviving-capacity
    accounting, and stamp the resume point into the trace so the smoke
    (and post-mortems) can reconstruct which steps ran on which
    topology.  A no-op outside elastic supervision."""
    want = os.environ.get(DEVICES_ENV)
    if not want:
        return
    have = jax.device_count()
    if int(want) != have:
        raise RuntimeError(
            f"elastic supervisor sized this attempt for {want} "
            f"device(s) but the runtime attached {have} — the relaunch "
            f"topology does not match the supervisor's accounting "
            f"(stale XLA_FLAGS? a partially-healed slice?); refusing "
            f"to train mis-sharded")
    if resumed_step:
        trace.event("elastic_resume", step=int(resumed_step),
                    devices=have, replicas=runtime.num_replicas)
        log.info("elastic resume: step %d on %d device(s) "
                 "(%d data replicas)", resumed_step, have,
                 runtime.num_replicas)


def replan_for_surviving(cfg, surviving_devices: int):
    """Re-resolve a ``--plan auto`` config against a surviving device
    count — the reshard-time planning step a shrunken relaunch
    performs implicitly (the relaunched runner's ``resolve_plan`` sees
    only the surviving devices).  Exposed as a pure function so the
    invariants are test-pinnable without relaunching anything: the
    GLOBAL batch never changes (a plan compiles parallelism flags,
    never the batch), and an infeasible surviving mesh dies loudly at
    resolve time, not as an OOM mid-compile."""
    from dtf_tpu.plan import resolve_plan
    from dtf_tpu.plan.mesh_spec import mesh_spec
    mesh = mesh_spec("", live_devices=int(surviving_devices))
    out = resolve_plan(cfg, mesh=mesh)
    if out.batch_size != cfg.batch_size:
        raise AssertionError(
            f"plan re-resolution changed the global batch "
            f"({cfg.batch_size} -> {out.batch_size}) — step semantics "
            f"would silently differ across the shrink")
    return out
