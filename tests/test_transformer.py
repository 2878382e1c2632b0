"""Transformer LM + sequence-parallel training tests.

Covers what no reference test could (vision-only upstream): causal
masking, ring-attention model parity against the single-device flash
path, and end-to-end seq-parallel training on the 8-device CPU mesh.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dtf_tpu.data.base as data_base
from dtf_tpu.cli import run
from dtf_tpu.config import Config
from dtf_tpu.models import build_model
from dtf_tpu.models.transformer import TransformerLM

TINY_LM = dataclasses.replace(data_base.LM, num_classes=64, seq_len=16,
                              num_train=64, num_eval=16)


@pytest.fixture(autouse=True)
def tiny_lm_spec(monkeypatch):
    monkeypatch.setitem(data_base._SPECS, "lm", TINY_LM)


def tiny_model(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("d_model", 32)
    kw.setdefault("num_heads", 2)
    kw.setdefault("d_ff", 64)
    kw.setdefault("max_seq_len", 16)
    return TransformerLM(**kw)


def test_forward_shape_and_dtype():
    model = tiny_model()
    tokens = jnp.zeros((2, 16), jnp.int32)
    variables = model.init(jax.random.key(0), tokens)
    logits = model.apply(variables, tokens)
    assert logits.shape == (2, 16, 64)
    assert logits.dtype == jnp.float32


def test_causality():
    """Changing a future token must not change past logits."""
    model = tiny_model()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, (1, 16)).astype(np.int32)
    variables = model.init(jax.random.key(0), jnp.asarray(tokens))
    base = model.apply(variables, jnp.asarray(tokens))
    t = 8
    perturbed = tokens.copy()
    perturbed[0, t + 1 :] = (perturbed[0, t + 1 :] + 1) % 64
    out = model.apply(variables, jnp.asarray(perturbed))
    np.testing.assert_allclose(np.asarray(base[0, : t + 1]),
                               np.asarray(out[0, : t + 1]), atol=1e-5)
    assert not np.allclose(np.asarray(base[0, t + 1 :]),
                           np.asarray(out[0, t + 1 :]))


@pytest.mark.parametrize("kw", [dict(remat=True),
                                dict(remat=True, remat_policy="dots"),
                                dict(remat_policy="dots")])
def test_remat_variants_match_baseline(kw):
    """remat and remat_policy change what is saved between forward and
    backward, never the math — but they DO change which values XLA
    recomputes vs reloads, and on jax 0.4.37/CPU the recomputed
    elementwise chains fuse differently, reordering f32 accumulations.
    Loss must still match exactly (the forward graph is identical);
    gradients are compared at an ulp-scale tolerance: observed drift is
    ≤ 3e-8 absolute (≈ a few ulps at the ~0.05 gradient magnitudes
    here, f32 eps = 1.19e-7), so atol 1e-7 + rtol 1e-6 admits
    accumulation-order noise and nothing else — a real math divergence
    (wrong policy residual, dropped term) is orders of magnitude
    larger."""
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 64, (2, 16)).astype(np.int32))

    def loss_fn(model):
        variables = model.init(jax.random.key(0), tokens)

        def loss(v):
            logits = model.apply(v, tokens)
            return jnp.mean((logits - 1.0) ** 2)

        return (jax.jit(loss)(variables),
                jax.jit(jax.grad(loss))(variables))

    base_loss, base_grads = loss_fn(tiny_model())
    got_loss, got_grads = loss_fn(tiny_model(**kw))
    np.testing.assert_array_equal(np.asarray(base_loss),
                                  np.asarray(got_loss))
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(base_grads),
            jax.tree_util.tree_leaves_with_path(got_grads)):
        assert pa == pb
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(pa))


def test_remat_policy_unknown_name_raises():
    model = tiny_model(remat_policy="everything")
    with pytest.raises(ValueError, match="remat_policy"):
        model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))


def test_remat_policy_cli(tiny_transformer_registry):
    """--remat_policy dots trains through the runner (implies remat)."""
    stats = run(base_cfg(distribution_strategy="off", train_steps=1,
                         remat_policy="dots"))
    assert np.isfinite(stats["loss"])


def test_remat_policy_rejected_for_resnet():
    with pytest.raises(ValueError, match="remat"):
        run(Config(model="resnet20", dataset="cifar10",
                   use_synthetic_data=True, train_steps=1, batch_size=4,
                   distribution_strategy="off", skip_eval=True,
                   skip_checkpoint=True, model_dir="",
                   remat_policy="dots"))


def test_ring_model_matches_single_device(eight_devices):
    """Same params, same tokens: the seq-sharded ring-attention model
    must produce the flash/blockwise model's logits."""
    from jax.sharding import PartitionSpec as P
    from dtf_tpu.runtime.mesh import DATA_AXIS, SEQ_AXIS, make_mesh

    mesh = make_mesh(eight_devices[:4], data=1, seq=4, model=1)
    ref_model = tiny_model()
    ring_model = tiny_model(seq_axis=SEQ_AXIS)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, 64, (2, 16)).astype(np.int32))
    variables = ref_model.init(jax.random.key(0), tokens)
    ref = ref_model.apply(variables, tokens)

    spec = P(DATA_AXIS, SEQ_AXIS)
    ring_fn = jax.jit(jax.shard_map(
        lambda v, t: ring_model.apply(v, t),
        mesh=mesh, in_specs=(P(), spec), out_specs=spec, check_vma=False))
    out = ring_fn(variables, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               atol=2e-4, rtol=2e-4)


def base_cfg(**kw):
    kw.setdefault("model", "transformer")
    kw.setdefault("dataset", "lm")
    kw.setdefault("use_synthetic_data", True)
    kw.setdefault("train_steps", 2)
    kw.setdefault("batch_size", 8)
    kw.setdefault("skip_eval", True)
    kw.setdefault("skip_checkpoint", True)
    kw.setdefault("log_steps", 1)
    kw.setdefault("model_dir", "")
    return Config(**kw)


@pytest.fixture()
def tiny_transformer_registry(monkeypatch):
    import functools
    from dtf_tpu.models import registry
    monkeypatch.setitem(
        registry._REGISTRY, "transformer",
        (functools.partial(TransformerLM, num_layers=2, d_model=32,
                           num_heads=2, d_ff=64, max_seq_len=16),
         64, 0.0))


def test_lm_train_smoke_single(tiny_transformer_registry):
    stats = run(base_cfg(distribution_strategy="off"))
    assert np.isfinite(stats["loss"])


def test_lm_seq_len_past_the_preset_position_table(
        tiny_transformer_registry):
    """--seq_len beyond the preset's position table (16 rows here, 2048
    in the registry) grows the table with it; before, the run died
    slicing 32 positions out of 16 — which is what `--seq_len 8192`
    did to transformer_tpu on the chip."""
    stats = run(base_cfg(distribution_strategy="off", seq_len=32))
    assert np.isfinite(stats["loss"])


def test_lm_train_data_parallel(tiny_transformer_registry):
    stats = run(base_cfg(distribution_strategy="mirrored", num_devices=4))
    assert np.isfinite(stats["loss"])


def test_lm_train_seq_parallel(tiny_transformer_registry):
    """2-way data x 4-way sequence: the full SP path through the CLI."""
    stats = run(base_cfg(seq_parallelism=4))
    assert np.isfinite(stats["loss"])


def test_seq_parallel_matches_data_parallel(tiny_transformer_registry):
    """The SP invariant: identical loss whether the sequence dimension
    is sharded or not (params replicated, same global batch, no BN)."""
    s1 = run(base_cfg(distribution_strategy="off", train_steps=2))
    s2 = run(base_cfg(seq_parallelism=4, train_steps=2))
    np.testing.assert_allclose(s1["loss"], s2["loss"], rtol=2e-3)


def test_lm_eval_path(tiny_transformer_registry):
    stats = run(base_cfg(skip_eval=False))
    assert np.isfinite(stats["eval_loss"])


def test_lm_cli_main(tiny_transformer_registry):
    from dtf_tpu.cli.lm_main import main
    stats = main(["--use_synthetic_data", "--train_steps", "1",
                  "--batch_size", "8", "--skip_checkpoint",
                  "--model_dir", "", "--dtype", "fp32"])
    assert np.isfinite(stats["loss"])


def test_build_model_registry_sizes():
    m, l2 = build_model("transformer_small", num_classes=128)
    assert m.vocab_size == 128 and m.num_layers == 4 and l2 == 0.0
