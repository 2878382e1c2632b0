"""Elastic training: shrink/grow resume across topology loss
(dtf_tpu/train/elastic.py + the cli/launch.py --elastic supervisor).

Covers the supervisor classification matrix (crash vs preempt vs
device-loss vs host-loss), the elastic shrink/grow/floor/cap policy
with scripted ranks (no jax in the children), the reshard edge cases
(zero-pad rows under a non-dividing new dp, expert/TP leaves, loud
refusal), plan re-resolution under shrink, and the chaos grammar for
the two new kinds.  The end-to-end headline (host loss at step K on N
devices → resume on N/2 trajectory-exact vs a fresh oracle → grow
back) lives in tools/elastic_smoke.py, wrapped here as a slow test.
"""

import dataclasses
import json
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import dtf_tpu.data.base as data_base
from dtf_tpu import chaos
from dtf_tpu.cli import launch
from dtf_tpu.config import Config
from dtf_tpu.models import build_model
from dtf_tpu.runtime import initialize
from dtf_tpu.train import Trainer, elastic
from dtf_tpu.train import zero as zero_lib

TINY = dataclasses.replace(data_base.CIFAR10, image_size=8, num_train=96,
                           num_eval=16)


@pytest.fixture(autouse=True)
def tiny_specs(monkeypatch):
    monkeypatch.setitem(data_base._SPECS, "cifar10", TINY)


def _events(log_dir):
    with open(os.path.join(log_dir, "supervisor_events.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------------
# contracts: the stdlib-only supervisor copies must match the canonical
# constants (the same parity discipline as EXIT_PREEMPTED)
# ---------------------------------------------------------------------------

def test_contract_parity():
    assert (elastic.EXIT_DEVICE_LOST == chaos.EXIT_DEVICE_LOST
            == launch.EXIT_DEVICE_LOST == 76)
    assert elastic.REJOIN_FILE == launch.REJOIN_FILE
    assert elastic.DEVICES_ENV == launch.ELASTIC_DEVICES_ENV


def test_device_loss_classifier():
    """The XLA runtime's device-loss exception — recognized by its
    type NAME and status-text markers (jaxlib moves the class between
    releases, so the classifier must not import it) — classifies as
    device loss; ordinary step bugs do not."""
    # the real exception type is jaxlib's XlaRuntimeError; fake one by
    # name, exactly as a version-skewed jaxlib would present it
    XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
    lost = XlaRuntimeError(
        "INTERNAL: DEVICE_LOST: TPU driver reset detected")
    assert elastic.is_device_loss(lost)
    assert elastic.is_device_loss(
        XlaRuntimeError("DATA_LOSS: core halted unexpectedly"))
    # same type, ordinary failure text: NOT device loss — shrinking a
    # healthy topology on a shape bug would be a policy disaster
    assert not elastic.is_device_loss(
        XlaRuntimeError("INVALID_ARGUMENT: shapes do not match"))
    # right text, wrong exception family (a ValueError from user code
    # quoting logs): NOT device loss
    assert not elastic.is_device_loss(ValueError("DEVICE_LOST"))
    wrapped = elastic.DeviceLost(17, lost)
    assert wrapped.step == 17 and wrapped.cause is lost
    assert "DEVICE_LOST" in str(wrapped)
    # the runner's handler recognizes the wrapper as already-classified
    assert isinstance(wrapped, RuntimeError)


def test_chaos_grammar_device_and_host_loss():
    specs = chaos.parse_spec("device_loss@step:3,host_loss@rank1:step:5")
    assert [str(s) for s in specs] == ["device_loss@step:3",
                                      "host_loss@rank1:step:5"]
    assert specs[1].rank == 1
    with pytest.raises(ValueError, match="device_loss"):
        chaos.parse_spec("device_loss@latest")
    with pytest.raises(ValueError, match="host_loss"):
        chaos.parse_spec("host_loss@req:3")


# ---------------------------------------------------------------------------
# supervisor classification matrix (scripted ranks, no jax)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("script,want", [
    ("import sys; sys.exit(3)", "crash"),
    (f"import sys; sys.exit({launch.EXIT_PREEMPTED})", "preempted"),
    (f"import sys; sys.exit({launch.EXIT_DEVICE_LOST})", "device_loss"),
    ("import os, signal; os.kill(os.getpid(), signal.SIGKILL)",
     "host_loss"),
])
def test_classification_matrix(tmp_path, script, want):
    """crash vs preempt vs device-loss vs host-loss: exit 77-style
    codes stay crashes, 75 preempted, 76 device loss, and an
    UNPROMPTED SIGKILL — which no python crash produces by itself —
    reads as host loss."""
    launch.launch_local([sys.executable, "-c", script], num_processes=1,
                        coordinator="localhost:0",
                        log_dir=str(tmp_path / "logs"),
                        devices_per_process=None)
    exits = [e for e in _events(str(tmp_path / "logs"))
             if e["event"] == "rank_exit"]
    assert exits and exits[0]["classification"] == want


def test_heartbeat_lost_classifies_as_host_loss(tmp_path):
    """A rank the supervisor kills for heartbeat silence classifies as
    host loss (a dead host stops beating long before any exit code) —
    without --elastic the restart POLICY is still the budgeted crash,
    so existing behavior is unchanged."""
    script = "import time; print('up', flush=True); time.sleep(600)"
    rc = launch.launch_local([sys.executable, "-c", script],
                             num_processes=1, coordinator="localhost:0",
                             log_dir=str(tmp_path / "logs"),
                             devices_per_process=None,
                             heartbeat_timeout=1.0, startup_grace=1.0)
    assert rc != 0
    exits = [e for e in _events(str(tmp_path / "logs"))
             if e["event"] == "rank_exit"]
    assert exits and exits[0]["classification"] == "host_loss"


# ---------------------------------------------------------------------------
# elastic policy: shrink, floor, cap, grow (scripted ranks)
# ---------------------------------------------------------------------------

def test_elastic_shrink_halves_devices_and_exports_env(tmp_path):
    """device loss under --elastic: relaunch on half the devices with
    DTF_ELASTIC_DEVICES carrying the surviving total — outside the
    crash budget (max_restarts=0 and the job still completes)."""
    marker = tmp_path / "m"
    script = (
        "import os, sys\n"
        f"p = {str(marker)!r}\n"
        "if not os.path.exists(p):\n"
        "    open(p, 'w').write(os.environ['DTF_ELASTIC_DEVICES'])\n"
        f"    sys.exit({launch.EXIT_DEVICE_LOST})\n"
        "open(p + '2', 'w').write(os.environ['DTF_ELASTIC_DEVICES'])\n"
        "sys.exit(0)\n")
    rc = launch.launch_local([sys.executable, "-c", script],
                             num_processes=1, coordinator="localhost:0",
                             log_dir=str(tmp_path / "logs"),
                             devices_per_process=4, elastic=True,
                             min_devices=1)
    assert rc == 0
    assert marker.read_text() == "4"
    assert (tmp_path / "m2").read_text() == "2"
    shrinks = [e for e in _events(str(tmp_path / "logs"))
               if e["event"] == "elastic_shrink"]
    assert shrinks and shrinks[0]["total_devices"] == 2
    assert shrinks[0]["classification"] == "device_loss"


def test_elastic_host_loss_drops_one_process(tmp_path):
    """host loss in a multi-process job: the lost host's rank is
    dropped (N processes → N−1), not a device halving."""
    script = (
        "import os, signal, sys, time\n"
        "if os.environ['DTF_PROCESS_COUNT'] == '1':\n"
        "    sys.exit(0)\n"
        "if os.environ['DTF_PROCESS_ID'] == '1':\n"
        "    os.kill(os.getpid(), signal.SIGKILL)\n"
        "time.sleep(60)\n")
    rc = launch.launch_local([sys.executable, "-c", script],
                             num_processes=2, coordinator="localhost:0",
                             log_dir=str(tmp_path / "logs"),
                             devices_per_process=None, elastic=True,
                             min_devices=1, teardown_grace=5.0)
    assert rc == 0
    shrinks = [e for e in _events(str(tmp_path / "logs"))
               if e["event"] == "elastic_shrink"]
    assert shrinks and shrinks[0]["procs"] == 1
    assert shrinks[0]["classification"] == "host_loss"


def test_shrink_below_min_devices_refuses_loudly(tmp_path):
    """The --min_devices floor: a loss that would shrink below it
    gives up with a structured reason instead of resuming that
    small."""
    rc = launch.launch_local(
        [sys.executable, "-c",
         f"import sys; sys.exit({launch.EXIT_DEVICE_LOST})"],
        num_processes=1, coordinator="localhost:0",
        log_dir=str(tmp_path / "logs"), devices_per_process=2,
        elastic=True, min_devices=2)
    assert rc == launch.EXIT_DEVICE_LOST
    give_up = [e for e in _events(str(tmp_path / "logs"))
               if e["event"] == "give_up"]
    assert give_up and give_up[0]["reason"] == "min_devices"
    assert give_up[0]["surviving_devices"] == 1


def test_max_elastic_caps_flapping_topology(tmp_path):
    """A flapping fabric (losses forever) is bounded by --max_elastic,
    not by the crash budget."""
    rc = launch.launch_local(
        [sys.executable, "-c",
         f"import sys; sys.exit({launch.EXIT_DEVICE_LOST})"],
        num_processes=1, coordinator="localhost:0",
        log_dir=str(tmp_path / "logs"), devices_per_process=64,
        elastic=True, min_devices=1, max_elastic=2)
    assert rc == launch.EXIT_DEVICE_LOST
    ev = _events(str(tmp_path / "logs"))
    assert sum(1 for e in ev if e["event"] == "elastic_shrink") == 2
    give_up = [e for e in ev if e["event"] == "give_up"]
    assert give_up and give_up[0]["losses"] == 3


def test_elastic_requires_a_shrinkable_topology():
    with pytest.raises(ValueError, match="elastic"):
        launch.launch_local(["true"], num_processes=1,
                            coordinator="localhost:0", log_dir="/tmp/x",
                            devices_per_process=None, elastic=True)


def test_grow_back_on_reannounce(tmp_path):
    """Capacity re-announce (elastic_rejoin.json) while shrunken:
    the supervisor drains the job (SIGTERM → the ranks' preemption
    path) and relaunches at the FULL topology."""
    phase = tmp_path / "phase"
    shrunk = tmp_path / "shrunk"
    log_dir = tmp_path / "logs"
    os.makedirs(log_dir, exist_ok=True)
    script = (
        "import os, signal, sys, time\n"
        f"phase = {str(phase)!r}; shrunk = {str(shrunk)!r}\n"
        "if os.environ['DTF_ELASTIC_DEVICES'] == '4':\n"
        "    if os.path.exists(phase):\n"
        "        sys.exit(0)\n"
        "    open(phase, 'w').write('x')\n"
        f"    sys.exit({launch.EXIT_DEVICE_LOST})\n"
        "signal.signal(signal.SIGTERM, lambda *a: sys.exit(75))\n"
        "open(shrunk, 'w').write('x')\n"
        "for _ in range(1200):\n"
        "    time.sleep(0.05)\n"
        "sys.exit(1)\n")

    def announcer():
        while not shrunk.exists():
            time.sleep(0.05)
        elastic.announce_rejoin(str(log_dir), 4)

    th = threading.Thread(target=announcer, daemon=True)
    th.start()
    rc = launch.launch_local([sys.executable, "-c", script],
                             num_processes=1, coordinator="localhost:0",
                             log_dir=str(log_dir),
                             devices_per_process=4, elastic=True,
                             min_devices=1)
    th.join(timeout=10)
    assert rc == 0
    names = [e["event"] for e in _events(str(log_dir))]
    for expected in ("elastic_shrink", "grow_triggered", "elastic_grow",
                     "job_done"):
        assert expected in names, names
    # the announce was consumed — a later shrink must not instantly grow
    assert not (log_dir / launch.REJOIN_FILE).exists()


# ---------------------------------------------------------------------------
# reshard edge cases (train/elastic.py + the zero.py layout contract)
# ---------------------------------------------------------------------------

def test_check_reshardable_units():
    """Expert (data-sharded) leaves need the new dp to divide their
    expert dim; TP leaves their model dim; replicated and ZeRO-sliced
    leaves always reshard (as_view pads to ANY nd's tile grid)."""
    sds = jax.ShapeDtypeStruct
    pspecs = {"expert": P("data"), "tp": P(None, "model"),
              "rep": P(), "sent": zero_lib.REP}
    leaves = {"expert": sds((4, 8), np.float32),
              "tp": sds((8, 6), np.float32),
              "rep": sds((7,), np.float32),
              "sent": sds((), np.int32)}
    ok = elastic.check_reshardable(
        pspecs, leaves, {"data": 2, "seq": 1, "model": 2})
    assert ok == []
    bad = elastic.check_reshardable(
        pspecs, leaves, {"data": 8, "seq": 1, "model": 4})
    assert len(bad) == 2
    assert any("expert" in b and "8" in b for b in bad)
    assert any("tp" in b for b in bad)
    # composed axes: ('data','model') needs the PRODUCT to divide
    bad2 = elastic.check_reshardable(
        {"x": P(("data", "model"))}, {"x": sds((8,), np.float32)},
        {"data": 8, "seq": 1, "model": 2})
    assert len(bad2) == 1 and "size 16" in bad2[0]


def _zero3_trainer(num_devices, batch=12):
    cfg = Config(model="resnet20", dataset="cifar10", batch_size=batch,
                 train_steps=1, use_synthetic_data=True, skip_eval=True,
                 model_dir="", skip_checkpoint=True, log_steps=1,
                 distribution_strategy="mirrored",
                 num_devices=num_devices, zero_stage=3)
    rt = initialize(cfg)
    model, l2 = build_model("resnet20")
    trainer = Trainer(cfg, rt, model, l2, TINY, schedule=lambda s: 0.1)
    rng = np.random.default_rng(0)
    images = rng.normal(120, 50, (batch, 8, 8, 3)).astype(np.float32)
    labels = rng.integers(0, 10, (batch,)).astype(np.int32)
    state = trainer.init_state(jax.random.key(0), (images, labels))
    return trainer, rt, state, (images, labels)


@pytest.mark.slow  # reshard-resume is pinned e2e every CI by elastic_smoke (stage 14)
def test_zero3_reshard_across_non_dividing_dp(eight_devices):
    """The reshard headline at the layout level: a canonical (stage-0)
    state from an nd=4 mesh re-slices onto nd=3 — a dp that divides
    almost NO leaf size, so every pad row is exercised — and the
    canonical form read back from the nd=3 layout is BIT-identical
    (the padding provably stays zero)."""
    t4, _, s4, _ = _zero3_trainer(4)
    canon = jax.device_get(t4.canonical_state(s4))
    t3, rt3, _, batch = _zero3_trainer(3)
    staged = t3.staged_state(canon)
    for leaf in jax.tree_util.tree_leaves(staged.params):
        assert leaf.ndim == 2 and leaf.shape[1] % (3 * 128) == 0
    back = jax.device_get(t3.canonical_state(staged))
    for a, b in zip(jax.tree_util.tree_leaves(canon),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the resharded state trains
    state, metrics = t3.train_step(staged, *rt3.shard_batch(batch))
    assert np.isfinite(float(jax.device_get(metrics["loss"])))


def test_zero3_reshard_4_to_2_to_4_round_trips(eight_devices):
    """Shrink and grow back: the column-slice view depends on nd (a
    leaf is padded to whole rows of nd x 128), so a state from nd=4
    re-slices onto nd=2 into other shapes — and back onto nd=4 into the
    very slices it left, bit for bit; the canonical form is the same at
    every hop and the shrunken state trains."""
    import flax.linen as nn

    class MLP(nn.Module):
        # [192, 512]: leaf-shaped at both nd; [512, 24], [24, 10], the
        # biases: flat views whose padding differs with nd
        @nn.compact
        def __call__(self, x, train=False):
            x = nn.relu(nn.Dense(512)(x.reshape((x.shape[0], -1))))
            return nn.Dense(10)(nn.relu(nn.Dense(24)(x)))

    def trainer_at(nd):
        cfg = Config(model="resnet20", dataset="cifar10", batch_size=8,
                     train_steps=1, use_synthetic_data=True, skip_eval=True,
                     model_dir="", skip_checkpoint=True, log_steps=1,
                     distribution_strategy="mirrored", num_devices=nd,
                     zero_stage=3)
        rt = initialize(cfg)
        trainer = Trainer(cfg, rt, MLP(), 0.0, TINY, schedule=lambda s: 0.1)
        rng = np.random.default_rng(0)
        batch = (rng.normal(0, 1, (8, 8, 8, 3)).astype(np.float32),
                 rng.integers(0, 10, (8,)).astype(np.int32))
        state = trainer.init_state(jax.random.key(0), batch)
        return trainer, rt, state, batch

    def same(a, b):
        la, lb = (jax.tree_util.tree_leaves(jax.device_get(t))
                  for t in (a, b))
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            assert x.shape == y.shape
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    t4, rt4, s4, batch = trainer_at(4)
    s4, _ = t4.train_step(s4, *rt4.shard_batch(batch))   # momentum != 0
    canon4 = jax.device_get(t4.canonical_state(s4))
    t2, rt2, _, _ = trainer_at(2)
    s2 = t2.staged_state(canon4)
    shapes4 = [x.shape for x in jax.tree_util.tree_leaves(s4.params)]
    shapes2 = [x.shape for x in jax.tree_util.tree_leaves(s2.params)]
    assert shapes2 != shapes4                   # the view moved with nd
    canon2 = jax.device_get(t2.canonical_state(s2))
    same(canon2, canon4)
    same(t4.staged_state(canon2), s4)           # and back: the same slices
    _, metrics = t2.train_step(s2, *rt2.shard_batch(batch))
    assert np.isfinite(float(jax.device_get(metrics["loss"])))


def test_replan_for_surviving_keeps_global_batch(eight_devices):
    """--plan auto re-resolution against the surviving topology: the
    GLOBAL batch is invariant, the data-parallel degree follows the
    surviving device count, and per-shard batch/grad-accum are
    recomputed by the same search that planned the full mesh."""
    cfg = Config(model="transformer_small", dataset="lm", seq_len=64,
                 batch_size=8, use_synthetic_data=True, plan="auto")
    full = elastic.replan_for_surviving(cfg, 4)
    half = elastic.replan_for_surviving(cfg, 2)
    assert full.batch_size == half.batch_size == 8
    assert full.num_devices == 4 and half.num_devices == 2
    assert not full.plan and not half.plan  # compiled into flags


@pytest.mark.slow
def test_zero3_tp_composed_shrink(eight_devices):
    """TP/PP-composed shrink: a zero3 + model_parallelism=2 state from
    a (dp=2, mp=2) mesh reshards onto (dp=1, mp=2) — the model axis
    survives, only 'data' re-slices — canonical round trip exact."""
    import functools
    from dtf_tpu.data.base import LM
    from dtf_tpu.models.transformer import param_partition_specs

    def trainer_at(n):
        cfg = Config(model="transformer_small", dataset="lm",
                     batch_size=4, seq_len=32, train_steps=1,
                     use_synthetic_data=True, skip_eval=True,
                     model_dir="", skip_checkpoint=True, log_steps=1,
                     distribution_strategy="mirrored", num_devices=n,
                     model_parallelism=2, zero_stage=3,
                     optimizer="adamw")
        rt = initialize(cfg)
        model, l2 = build_model("transformer_small", seq_axis=None,
                                model_axis="model")
        spec = dataclasses.replace(LM, seq_len=32)
        tr = Trainer(cfg, rt, model, l2, spec, schedule=lambda s: 1e-3,
                     param_spec_fn=functools.partial(
                         param_partition_specs, model_axis="model"))
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 100, (4, 32)).astype(np.int32)
        state = tr.init_state(jax.random.key(0), (tokens, tokens))
        return tr, state
    t4, s4 = trainer_at(4)
    canon = jax.device_get(t4.canonical_state(s4))
    t2, _ = trainer_at(2)
    staged = t2.staged_state(canon)
    back = jax.device_get(t2.canonical_state(staged))
    for a, b in zip(jax.tree_util.tree_leaves(canon),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_elastic_smoke_tool():
    """tools/elastic_smoke.py — the ci_check stage-14 contract — as a
    slow-marked test so the suite exercises it too."""
    import subprocess
    r = subprocess.run([sys.executable, "tools/elastic_smoke.py"],
                       capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
