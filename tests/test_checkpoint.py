"""Checkpoint/resume + TensorBoard writer tests (reference parity:
rank-0 per-epoch ModelCheckpoint + restore-rebroadcast, SURVEY §5.4;
--enable_tensorboard, common.py:187-190)."""

import os

import jax
import numpy as np
import pytest

from dtf_tpu.cli import run
from dtf_tpu.config import Config
from dtf_tpu.data import records
from dtf_tpu.models import build_model
from dtf_tpu.runtime import initialize
from dtf_tpu.train import Trainer
from dtf_tpu.train.checkpoint import Checkpointer
from dtf_tpu.utils.tensorboard import SummaryWriter

import dataclasses
import dtf_tpu.data.base as data_base

TINY = dataclasses.replace(data_base.CIFAR10, image_size=8, num_train=64,
                           num_eval=16)


@pytest.fixture(autouse=True)
def tiny_specs(monkeypatch):
    monkeypatch.setitem(data_base._SPECS, "cifar10", TINY)


def _make(tmp_path, **kw):
    cfg = Config(model="resnet20", dataset="cifar10", batch_size=8,
                 train_steps=2, use_synthetic_data=True, skip_eval=True,
                 model_dir=str(tmp_path), log_steps=1,
                 distribution_strategy="off", **kw)
    rt = initialize(cfg)
    model, l2 = build_model("resnet20")
    trainer = Trainer(cfg, rt, model, l2, TINY)
    return cfg, rt, trainer


@pytest.mark.slow
def test_save_restore_roundtrip(tmp_path):
    cfg, rt, trainer = _make(tmp_path)
    images = np.zeros((8, 8, 8, 3), np.float32)
    labels = np.zeros((8,), np.int32)
    state = trainer.init_state(jax.random.key(0), (images, labels))
    batch = rt.shard_batch((images, labels))
    state, _ = trainer.train_step(state, *batch)

    ckpt = Checkpointer(str(tmp_path))
    ckpt.save(state)
    ckpt.wait()
    assert ckpt.latest_step() == 1

    restored = ckpt.restore(state, sharding=rt.replicated())
    assert int(restored.step) == 1
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ckpt.close()


@pytest.mark.slow
def test_resume_preserves_tensor_parallel_sharding(tmp_path, eight_devices):
    """Resume of a TP run must restore the model-axis shardings, not
    flatten them to replicated (the CLI passes the live state's own
    per-leaf shardings)."""
    import dtf_tpu.data.base as db
    lm_tiny = dataclasses.replace(db.LM, num_classes=64, seq_len=16,
                                  num_train=32, num_eval=16)
    import functools
    from unittest import mock
    from dtf_tpu.models import registry
    from dtf_tpu.models.transformer import TransformerLM
    with mock.patch.dict(db._SPECS, {"lm": lm_tiny}), \
         mock.patch.dict(registry._REGISTRY, {"transformer": (
             functools.partial(TransformerLM, num_layers=2, d_model=32,
                               num_heads=4, d_ff=64, max_seq_len=16),
             64, 0.0)}):
        base = dict(model="transformer", dataset="lm", batch_size=8,
                    train_steps=2, use_synthetic_data=True, skip_eval=True,
                    model_dir=str(tmp_path), log_steps=1,
                    optimizer="adamw", model_parallelism=2, num_devices=4)
        run(Config(**base))
        assert os.path.isdir(tmp_path / "checkpoints")
        run(Config(**base, resume=True))  # restores sharded; must not crash


@pytest.mark.slow
def test_resume_zero_tp_composed(tmp_path, eight_devices):
    """ZeRO×TP: ('data','model')-column-sliced optimizer state and
    TP-sharded params round-trip through save+resume with their
    shardings intact."""
    import dtf_tpu.data.base as db
    lm_tiny = dataclasses.replace(db.LM, num_classes=64, seq_len=16,
                                  num_train=32, num_eval=16)
    import functools
    from unittest import mock
    from dtf_tpu.models import registry
    from dtf_tpu.models.transformer import TransformerLM
    with mock.patch.dict(db._SPECS, {"lm": lm_tiny}), \
         mock.patch.dict(registry._REGISTRY, {"transformer": (
             functools.partial(TransformerLM, num_layers=2, d_model=32,
                               num_heads=4, d_ff=64, max_seq_len=16),
             64, 0.0)}):
        base = dict(model="transformer", dataset="lm", batch_size=8,
                    use_synthetic_data=True, skip_eval=True,
                    model_dir=str(tmp_path), log_steps=1,
                    optimizer="adamw", model_parallelism=2, num_devices=4,
                    optimizer_sharding=True)
        s1 = run(Config(**base, train_steps=2))
        # resume with a longer budget: restores the ('data','model')-
        # sliced opt state + TP params, then trains 2 more steps
        s2 = run(Config(**base, train_steps=4, resume=True))
        assert np.isfinite(s1["loss"]) and np.isfinite(s2["loss"])


def test_zero3_checkpoint_written_under_the_flat_layout_resumes(
        tmp_path, eight_devices):
    """A ZeRO-3 checkpoint recorded from the parent of PR 35, whose live
    slices were contiguous 1/nd runs of the flattened leaf
    (tests/data/zero3_ckpt_flat_layout.py wrote it).  On disk it is the
    canonical stage-0 form, which the slice layout never touches: the
    column-sliced tree restores it to bit-equal parameters and optimizer
    state (through ``staged_state`` and back), and trains on to the
    parent's own loss at step 4."""
    import functools
    import json
    import tarfile
    from unittest import mock
    import dtf_tpu.data.base as db
    from dtf_tpu.models import registry
    from dtf_tpu.models.transformer import TransformerLM
    from dtf_tpu.train.checkpoint import load_train_checkpoint
    here = os.path.dirname(os.path.abspath(__file__))
    with tarfile.open(os.path.join(
            here, "data", "zero3_ckpt_flat_layout.tar.gz")) as tar:
        tar.extractall(tmp_path, filter="data")
    with open(tmp_path / "parent_losses.json") as f:
        parent = json.load(f)
    stored = load_train_checkpoint(str(tmp_path))["params"]
    lm_tiny = dataclasses.replace(db.LM, num_classes=64, seq_len=16,
                                  num_train=64, num_eval=16)
    tiny = functools.partial(TransformerLM, num_layers=1, d_model=16,
                             num_heads=2, d_ff=32, max_seq_len=16,
                             use_pallas=False)
    base = dict(model="transformer", dataset="lm", batch_size=8,
                use_synthetic_data=True, skip_eval=True, log_steps=1,
                optimizer="adamw", num_devices=4, zero_stage=3,
                distribution_strategy="mirrored", checkpoint_steps=2,
                seed=7, model_dir=str(tmp_path))
    with mock.patch.dict(db._SPECS, {"lm": lm_tiny}), \
         mock.patch.dict(registry._REGISTRY,
                         {"transformer": (tiny, 64, 0.0)}):
        cfg = Config(**base, train_steps=4, resume=True)
        rt = initialize(cfg)
        rt.shard_seq = True
        trainer = Trainer(cfg, rt, tiny(vocab_size=64), 0.0, lm_tiny)
        tokens = np.zeros((8, 16), np.int32)
        trainer.init_state(jax.random.key(7), (tokens, tokens))
        ckpt = Checkpointer(str(tmp_path))
        canon = ckpt.restore(trainer.canonical_template())
        ckpt.close()
        assert int(canon.step) == 2
        staged = trainer.staged_state(canon)
        for leaf in jax.tree_util.tree_leaves(staged.params):
            assert leaf.ndim == 2                    # this tree's slices
        back = jax.device_get(trainer.canonical_state(staged))
        want = dict(jax.tree_util.tree_leaves_with_path(stored))
        got = dict(jax.tree_util.tree_leaves_with_path(back.params))
        assert set(got) == set(want) and len(got) >= 10
        for path, a in want.items():
            np.testing.assert_array_equal(
                np.asarray(got[path]), np.asarray(a),
                err_msg=jax.tree_util.keystr(path))
        for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(canon)),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        out = run(cfg)
    np.testing.assert_allclose(out["loss"], parent["loss_step4"], rtol=1e-5)
    assert out["loss"] < parent["loss_step2"]


def test_restore_none_when_empty(tmp_path):
    cfg, rt, trainer = _make(tmp_path)
    state = trainer.init_state(
        jax.random.key(0),
        (np.zeros((8, 8, 8, 3), np.float32), np.zeros((8,), np.int32)))
    ckpt = Checkpointer(str(tmp_path / "empty"))
    assert ckpt.restore(state) is None
    ckpt.close()


@pytest.mark.slow
def test_run_with_checkpoint_and_resume(tmp_path):
    """e2e: run saves per-epoch; second run with --resume continues from
    the saved step (and trains zero additional steps here)."""
    base = dict(model="resnet20", dataset="cifar10", batch_size=8,
                train_steps=2, use_synthetic_data=True, skip_eval=True,
                model_dir=str(tmp_path), log_steps=1,
                distribution_strategy="off")
    stats1 = run(Config(**base))
    assert os.path.isdir(tmp_path / "checkpoints")
    stats2 = run(Config(**base, resume=True))
    # resumed past the single capped epoch: no new train history
    assert "loss" not in stats2 or stats2.get("train_finish_time")


@pytest.mark.slow
def test_profile_steps_honored_under_resume(tmp_path, monkeypatch):
    """--profile_steps "0,10" on a resumed run whose start step (2) already
    passed the range start must still trace the remaining in-range steps
    (loop.py used `== range[0]`, which silently skipped the trace)."""
    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.__setitem__(
                            "start", calls["start"] + 1))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda *a, **k: calls.__setitem__(
                            "stop", calls["stop"] + 1))
    base = dict(model="resnet20", dataset="cifar10", batch_size=8,
                use_synthetic_data=True, skip_eval=True,
                model_dir=str(tmp_path), log_steps=1,
                distribution_strategy="off")
    run(Config(**base, train_steps=2))
    assert calls["start"] == 0  # no profile_steps on the first run
    run(Config(**base, train_steps=4, resume=True, profile_steps="0,10"))
    assert calls["start"] == 1 and calls["stop"] == 1


@pytest.mark.slow
def test_eval_only_from_checkpoint(tmp_path):
    """Train + save, then --eval_only --resume evaluates the restored
    state without training."""
    base = dict(model="resnet20", dataset="cifar10", batch_size=8,
                train_steps=2, use_synthetic_data=True, skip_eval=True,
                model_dir=str(tmp_path), log_steps=1,
                distribution_strategy="off")
    run(Config(**base))
    stats = run(Config(**dict(base, skip_eval=False, resume=True,
                              eval_only=True)))
    assert np.isfinite(stats["eval_loss"])
    assert "loss" not in stats  # no training happened


def test_tensorboard_event_file(tmp_path):
    w = SummaryWriter(str(tmp_path))
    w.scalar("loss", 1.5, step=10)
    w.scalar("loss", 1.2, step=20)
    w.close()
    files = [f for f in os.listdir(tmp_path) if "tfevents" in f]
    assert len(files) == 1
    # the event file is valid TFRecord framing with valid CRCs
    events = list(records.read_tfrecord_file(
        str(tmp_path / files[0]), verify_crc=True))
    assert len(events) == 3  # file_version + 2 scalars
    assert b"brain.Event:2" in events[0]
    assert b"loss" in events[1]


def test_tensorboard_e2e(tmp_path):
    run(Config(model="resnet20", dataset="cifar10", batch_size=8,
               train_steps=1, use_synthetic_data=True, skip_eval=True,
               model_dir=str(tmp_path), enable_tensorboard=True,
               skip_checkpoint=True, distribution_strategy="off"))
    train_dir = tmp_path / "train"
    files = [f for f in os.listdir(train_dir) if "tfevents" in f]
    assert files, "no event file written"
    payload = b"".join(records.read_tfrecord_file(str(train_dir / files[0])))
    assert b"epoch_loss" in payload


# ---------------------------------------------------------------------------
# cross-run GC by verified-set (--checkpoint_keep)
# ---------------------------------------------------------------------------

from dtf_tpu.train.checkpoint import CheckpointCallback, manifest_path


def _sealed_steps(tmp_path, steps, name="gc"):
    """A Checkpointer with sha256-sealed saves at the given steps."""
    ckpt = Checkpointer(str(tmp_path / name), max_to_keep=50)
    for s in steps:
        ckpt.save({"w": np.full((4,), float(s), np.float32)}, step=s)
    ckpt.wait()
    return ckpt


def _dirs(ckpt):
    return sorted(int(n) for n in os.listdir(ckpt.directory)
                  if n.isdigit())


def test_gc_keeps_newest_verified(tmp_path):
    ckpt = _sealed_steps(tmp_path, [1, 2, 3, 4, 5])
    assert ckpt.gc(keep=2) == [1, 2, 3]
    assert _dirs(ckpt) == [4, 5]
    assert ckpt.verify(4) == "ok" and ckpt.verify(5) == "ok"
    # the deleted steps' manifests went with them
    for s in (1, 2, 3):
        assert not os.path.exists(manifest_path(ckpt.directory, s))
    ckpt.close()


def test_gc_never_deletes_newer_than_newest_verified(tmp_path):
    """An unverified step NEWER than the newest verified one may be
    another process's in-flight save — GC must neither count it toward
    `keep` nor delete it."""
    ckpt = _sealed_steps(tmp_path, [1, 2, 3])
    os.makedirs(os.path.join(ckpt.directory, "9"))  # in-flight, no manifest
    assert ckpt.gc(keep=1) == [1, 2]
    assert _dirs(ckpt) == [3, 9]
    ckpt.close()


def test_gc_all_unverified_deletes_nothing(tmp_path):
    """GC must never convert 'all unverified' into 'nothing left'."""
    ckpt = Checkpointer(str(tmp_path / "u"), max_to_keep=50)
    for s in (1, 2, 3):
        os.makedirs(os.path.join(ckpt.directory, str(s)))
    assert ckpt.gc(keep=1) == []
    assert _dirs(ckpt) == [1, 2, 3]
    ckpt.close()


def test_gc_disabled_and_validated(tmp_path):
    ckpt = _sealed_steps(tmp_path, [1, 2])
    assert ckpt.gc(keep=0) == []
    assert _dirs(ckpt) == [1, 2]
    ckpt.close()
    with pytest.raises(ValueError, match="checkpoint_keep"):
        Config(model="resnet20", dataset="cifar10", checkpoint_keep=-1)


def test_gc_spans_previous_runs_via_callback(tmp_path):
    """The --checkpoint_keep wiring: a resume chain's earlier-run
    checkpoints live in the same model_dir; the callback's final GC
    (on_train_end, after wait() seals this run's saves) prunes them
    down to the newest `keep` verified."""
    # "previous run": three sealed steps
    prev = CheckpointCallback(str(tmp_path), max_to_keep=50, keep=0)
    for s in (1, 2, 3):
        prev.ckpt.save({"w": np.zeros((2,), np.float32)}, step=s)
    prev.on_train_end()
    prev.ckpt.close()
    # "this run": two more, with the GC budget armed
    cb = CheckpointCallback(str(tmp_path), max_to_keep=50, keep=2)
    for s in (4, 5):
        cb.ckpt.save({"w": np.zeros((2,), np.float32)}, step=s)
    cb.on_train_end()  # wait -> seal -> gc(2)
    assert _dirs(cb.ckpt) == [4, 5]
    cb.ckpt.close()
