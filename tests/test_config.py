"""Config/flag system tests (reference parity: common.define_keras_flags
flag surface + TF_CONFIG cluster contract)."""

import json

import pytest

from dtf_tpu.config import Config, define_flags, parse_flags
from dtf_tpu.config.flags import topology_from_env


def test_defaults():
    cfg = Config()
    assert cfg.batch_size == 128
    assert cfg.distribution_strategy == "mirrored"
    assert cfg.compute_dtype.__name__ == "float32"


def test_flag_registry_covers_reference_surface():
    flags = define_flags()
    # the load-bearing reference flags (SURVEY §2.3 flags_core row)
    for name in ("data_dir", "model_dir", "batch_size", "train_epochs",
                 "epochs_between_evals", "dtype", "loss_scale", "enable_xla",
                 "distribution_strategy", "all_reduce_alg", "num_packs",
                 "worker_hosts", "task_index", "use_synthetic_data",
                 "data_format", "log_steps", "train_steps", "profile_steps",
                 "skip_eval", "use_trivial_model", "use_tensor_lr",
                 "enable_tensorboard", "report_accuracy_metrics",
                 "batchnorm_spatial_persistent", "enable_get_next_as_optional",
                 "stop_threshold", "export_dir"):
        assert name in flags, name


def test_parse_styles():
    cfg = parse_flags(["--batch_size", "64", "-train_epochs=2",
                       "--skip_eval", "--dtype", "bf16"])
    assert cfg.batch_size == 64
    assert cfg.train_epochs == 2
    assert cfg.skip_eval is True
    assert cfg.compute_dtype.__name__ == "bfloat16"


def test_parse_bool_with_value():
    cfg = parse_flags(["--use_synthetic_data", "true", "--batch_size", "4"])
    assert cfg.use_synthetic_data is True
    assert cfg.batch_size == 4


def test_unknown_flag():
    with pytest.raises(ValueError):
        parse_flags(["--not_a_flag", "1"])


def test_bad_strategy():
    with pytest.raises(ValueError):
        Config(distribution_strategy="nope")


def test_kv_page_size_zero_is_refused_by_name():
    """There is one KV cache; --kv_page_size 0 selects nothing."""
    with pytest.raises(ValueError, match="kv_page_size"):
        parse_flags(["--kv_page_size", "0"])
    assert parse_flags(["--kv_pool_pages", "0"]).kv_pool_pages == 0


def test_loss_scale_default_fp16():
    assert Config(dtype="fp16").loss_scale_value == 128.0
    assert Config(dtype="bf16").loss_scale_value == 1.0
    assert Config(dtype="fp16", loss_scale=256).loss_scale_value == 256.0


def test_tf_config_parity(monkeypatch):
    """The reference's cluster contract (ps_server/*_ps_0.py:40-50) maps
    onto coordinator/process topology: ps rank first, then workers."""
    tf_config = {
        "cluster": {"ps": ["h0:1111"],
                    "worker": ["h0:1112", "h1:1111", "h1:1112"]},
        "task": {"type": "worker", "index": 2},
    }
    monkeypatch.setenv("TF_CONFIG", json.dumps(tf_config))
    topo = topology_from_env()
    assert topo["coordinator_address"] == "h0:1111"
    assert topo["process_count"] == 4
    assert topo["process_id"] == 3  # 1 ps + worker index 2


def test_dtf_env_overrides_tf_config(monkeypatch):
    monkeypatch.setenv("TF_CONFIG", json.dumps(
        {"cluster": {"worker": ["a:1", "b:2"]}, "task": {"type": "worker", "index": 1}}))
    monkeypatch.setenv("DTF_COORDINATOR", "c:9")
    monkeypatch.setenv("DTF_PROCESS_ID", "0")
    monkeypatch.setenv("DTF_PROCESS_COUNT", "3")
    topo = topology_from_env()
    assert topo == {"coordinator_address": "c:9", "process_id": 0,
                    "process_count": 3}


def test_worker_hosts_flag(monkeypatch):
    monkeypatch.delenv("TF_CONFIG", raising=False)
    cfg = parse_flags(["--worker_hosts", "w0:1234,w1:1234",
                       "--task_index", "1"])
    assert cfg.coordinator_address == "w0:1234"
    assert cfg.process_count == 2
    assert cfg.process_id == 1
