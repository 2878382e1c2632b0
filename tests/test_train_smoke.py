"""End-to-end smoke matrix with the synthetic backend — the equivalent
of the reference's resnet_cifar_test.py / resnet_imagenet_test.py
(SURVEY §4 tier 2/3): each cell drives the real `run()` with
`--use_synthetic_data --train_steps 1 --batch_size small`, across
{strategy} × {dtype} × {device count} on the 8-virtual-device CPU mesh —
including the multi-device cells the reference could only run manually
on a GPU cluster.

A tiny 8×8 dataset spec keeps 1-core CI fast; the models are fully
convolutional so the architecture under test is unchanged.
"""

import dataclasses

import jax
import numpy as np
import pytest

import dtf_tpu.data.base as data_base
from dtf_tpu.cli import run
from dtf_tpu.cli.cifar_main import main as cifar_main
from dtf_tpu.config import Config

TINY_CIFAR = dataclasses.replace(
    data_base.CIFAR10, image_size=8, num_train=64, num_eval=16)
TINY_IMAGENET = dataclasses.replace(
    data_base.IMAGENET, image_size=8, num_train=64, num_eval=16,
    num_classes=13)


@pytest.fixture(autouse=True)
def tiny_specs(monkeypatch):
    monkeypatch.setitem(data_base._SPECS, "cifar10", TINY_CIFAR)
    monkeypatch.setitem(data_base._SPECS, "imagenet", TINY_IMAGENET)


def base_cfg(**kw):
    kw.setdefault("model", "resnet20")
    kw.setdefault("dataset", "cifar10")
    kw.setdefault("use_synthetic_data", True)
    kw.setdefault("train_steps", 1)
    kw.setdefault("batch_size", 8)
    kw.setdefault("skip_eval", True)
    kw.setdefault("skip_checkpoint", True)
    kw.setdefault("log_steps", 1)
    kw.setdefault("model_dir", "")
    return Config(**kw)


def check_stats(stats, eval_ran=False):
    assert np.isfinite(stats["loss"])
    assert "training_accuracy_top_1" in stats
    if eval_ran:
        assert np.isfinite(stats["eval_loss"])
        assert 0.0 <= stats["accuracy_top_1"] <= 1.0


# --- strategy × device-count matrix (reference resnet_cifar_test.py) ---

@pytest.mark.slow
def test_no_dist_strat():
    check_stats(run(base_cfg(distribution_strategy="off")))


def test_one_device():
    check_stats(run(base_cfg(distribution_strategy="one_device")))


def test_mirrored_2_devices():
    check_stats(run(base_cfg(distribution_strategy="mirrored", num_devices=2)))


@pytest.mark.slow
def test_mirrored_8_devices():
    check_stats(run(base_cfg(distribution_strategy="mirrored")))


def test_tpu_strategy_refuses_the_cpu():
    """'tpu' names the device, not just the mirrored layout: with no
    chip visible the run fails instead of training on the CPU JAX fell
    back to (the mesh mapping it shares with 'mirrored' is covered by
    the mirrored cells above)."""
    with pytest.raises(RuntimeError, match="found no TPU"):
        run(base_cfg(distribution_strategy="tpu"))


@pytest.mark.slow
def test_horovod_parity_mode():
    check_stats(run(base_cfg(distribution_strategy="horovod")))


@pytest.mark.slow  # PS coverage stays tier-1 via test_ps.py
def test_parameter_server_spmd_mode():
    check_stats(run(base_cfg(distribution_strategy="parameter_server")))


# --- dtype cells (reference resnet_imagenet_test.py:164-235) ---

def test_bf16():
    check_stats(run(base_cfg(dtype="bf16")))


def test_fp16_with_loss_scale():
    stats = run(base_cfg(dtype="fp16", loss_scale=64))
    check_stats(stats)


# --- workload cells ---

@pytest.mark.slow
def test_imagenet_resnet50_tiny():
    check_stats(run(base_cfg(model="resnet50", dataset="imagenet",
                             batch_size=8, num_devices=2)))


def test_trivial_model_switch():
    """--use_trivial_model parity (resnet_imagenet_main.py:189-191)."""
    check_stats(run(base_cfg(use_trivial_model=True, dataset="imagenet")))


@pytest.mark.slow
def test_eval_path():
    stats = run(base_cfg(skip_eval=False, train_steps=2))
    check_stats(stats, eval_ran=True)


@pytest.mark.slow
def test_sync_bn():
    check_stats(run(base_cfg(sync_bn=True)))


@pytest.mark.slow
def test_tensor_lr():
    check_stats(run(base_cfg(dataset="imagenet", use_tensor_lr=True)))


# --- determinism / correctness ---

@pytest.mark.slow
def test_same_seed_same_loss():
    s1 = run(base_cfg(seed=3))
    s2 = run(base_cfg(seed=3))
    np.testing.assert_allclose(s1["loss"], s2["loss"], rtol=1e-5)


@pytest.mark.slow
def test_data_parallel_matches_single_device():
    """The SPMD invariant: global batch B on 1 device ≡ B split over 4
    replicas (per-replica BN differs only if batch statistics differ —
    synthetic data repeats one batch, but the split changes per-replica
    stats, so compare with sync_bn to make them mathematically equal)."""
    s1 = run(base_cfg(distribution_strategy="off", sync_bn=False, train_steps=2))
    s4 = run(base_cfg(distribution_strategy="mirrored", num_devices=4,
                      sync_bn=True, train_steps=2))
    np.testing.assert_allclose(s1["loss"], s4["loss"], rtol=2e-3)


@pytest.mark.slow
def test_cli_main_smoke():
    """The reference's own smoke invocation (resnet_cifar_test.py:36-40)."""
    stats = cifar_main(["--use_synthetic_data", "--train_steps", "1",
                        "--batch_size", "8", "--skip_eval",
                        "--skip_checkpoint", "--model", "resnet20",
                        "--model_dir", ""])
    check_stats(stats)


def test_train_steps_cap():
    cfg = base_cfg(train_steps=3)
    from dtf_tpu.runtime import initialize
    from dtf_tpu.models import build_model
    from dtf_tpu.train import Trainer
    rt = initialize(cfg)
    model, l2 = build_model("resnet20")
    tr = Trainer(cfg, rt, model, l2, TINY_CIFAR)
    assert tr.steps_per_epoch == 3
    assert tr.train_epochs == 1


@pytest.mark.slow
def test_stop_threshold_early_stop(caplog):
    """--stop_threshold parity: training halts once eval top-1 passes
    the threshold (threshold 0.0 ⇒ stop after the first eval epoch)."""
    import logging
    cfg = base_cfg(skip_eval=False, train_steps=None, train_epochs=3,
                   stop_threshold=0.0, epochs_between_evals=1)
    with caplog.at_level(logging.INFO, logger="dtf_tpu"):
        stats = run(cfg)
    check_stats(stats, eval_ran=True)
    assert any("stop_threshold" in r.message for r in caplog.records)


@pytest.mark.slow
def test_export_dir_roundtrip(tmp_path):
    """--export_dir parity: final inference variables written and
    restorable."""
    from dtf_tpu.train.checkpoint import load_exported_model
    export_dir = str(tmp_path / "export")
    run(base_cfg(export_dir=export_dir))
    restored = load_exported_model(export_dir)
    assert "params" in restored and restored["params"]
    assert "batch_stats" in restored


@pytest.mark.slow
def test_benchmark_log_dir(tmp_path):
    """logger.benchmark_context parity: benchmark_run.log metadata +
    metric.log JSON lines."""
    import json
    log_dir = str(tmp_path / "bench")
    run(base_cfg(benchmark_log_dir=log_dir, benchmark_test_id="t1"))
    with open(f"{log_dir}/benchmark_run.log") as f:
        info = json.load(f)
    assert info["model_name"] == "resnet20"
    assert info["dataset"]["name"] == "cifar10"
    assert info["test_id"] == "t1"
    assert info["machine_config"]["device_count"] >= 1
    with open(f"{log_dir}/metric.log") as f:
        metrics = [json.loads(line) for line in f]
    names = {m["name"] for m in metrics}
    assert "loss" in names and "training_accuracy_top_1" in names
    assert all(isinstance(m["value"], float) for m in metrics)


def test_horovod_lr_schedule_selected():
    """Horovod mode uses the constant size-scaled warmup LR, not the
    piecewise schedule."""
    from dtf_tpu.models import build_model
    from dtf_tpu.runtime import initialize
    from dtf_tpu.train import Trainer
    import jax.numpy as jnp
    cfg = base_cfg(distribution_strategy="horovod")
    rt = initialize(cfg)
    model, l2 = build_model("resnet20")
    tr = Trainer(cfg, rt, model, l2, TINY_CIFAR)
    big_step = jnp.asarray(10_000)
    assert float(tr.schedule(big_step)) == pytest.approx(0.1 * rt.num_replicas)
