"""Model architecture tests: parameter counts, output shapes/dtypes,
and the L2-as-loss-term rule (reference resnet_model.py:37-43,
resnet_cifar_model.py:36)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtf_tpu.models import (
    ResNet50,
    TrivialModel,
    build_model,
    l2_weight_penalty,
    resnet20,
    resnet56,
)


def n_params(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def test_resnet50_param_count():
    """25,559,081 = standard ResNet-50 v1.5 with a 1001-way classifier
    (23,508,032 trunk + 2048×1001+1001 fc)."""
    m = ResNet50(num_classes=1001)
    v = jax.eval_shape(
        lambda: m.init(jax.random.key(0), jnp.zeros((1, 64, 64, 3)),
                       train=False))
    assert n_params(v["params"]) == 25_559_081


@pytest.mark.slow
def test_resnet50_space_to_depth_stem_exact():
    """The s2d stem (Conv1SpaceToDepth) is a pure reformulation of the
    reference 7×7/2 conv: same param tree, same logits."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (2, 32, 32, 3)).astype(np.float32))
    m_s2d = ResNet50(num_classes=11)
    m_ref = ResNet50(num_classes=11, stem_space_to_depth=False)
    v = m_s2d.init(jax.random.key(0), x, train=False)
    assert jax.tree_util.tree_structure(v) == jax.tree_util.tree_structure(
        m_ref.init(jax.random.key(0), x, train=False))
    np.testing.assert_allclose(
        np.asarray(m_s2d.apply(v, x, train=False)),
        np.asarray(m_ref.apply(v, x, train=False)), atol=5e-4)


@pytest.mark.slow
def test_resnet50_odd_input_falls_back_to_plain_conv():
    """Non-even spatial dims can't space-to-depth; the plain conv path
    keeps the model usable on any input size."""
    x = jnp.zeros((1, 33, 33, 3), jnp.float32)
    m = ResNet50(num_classes=5)
    v = m.init(jax.random.key(0), x, train=False)
    assert m.apply(v, x, train=False).shape == (1, 5)


def test_tagged_batchnorm_bit_exact_vs_flax():
    """TaggedBatchNorm (the checkpoint_name-tagged BN) must be
    bit-identical to nn.BatchNorm in train AND eval, including the
    running-stats update — it reuses flax's own stat/normalize
    internals, and this pins that equivalence."""
    import flax.linen as nn
    from dtf_tpu.models.resnet import TaggedBatchNorm

    x = jax.random.normal(jax.random.key(0), (4, 8, 8, 16), jnp.bfloat16)
    kw = dict(momentum=0.9, epsilon=1e-5, dtype=jnp.bfloat16,
              param_dtype=jnp.float32)
    ref = nn.BatchNorm(use_running_average=False, **kw)
    mine = TaggedBatchNorm(use_running_average=False, **kw)
    vr = ref.init(jax.random.key(1), x)
    vm = mine.init(jax.random.key(1), x)
    assert (jax.tree_util.tree_structure(vr)
            == jax.tree_util.tree_structure(vm))
    yr, mr = ref.apply(vr, x, mutable=["batch_stats"])
    ym, mm = mine.apply(vm, x, mutable=["batch_stats"])
    np.testing.assert_array_equal(np.asarray(yr, np.float32),
                                  np.asarray(ym, np.float32))
    for a, b in zip(jax.tree_util.tree_leaves(mr),
                    jax.tree_util.tree_leaves(mm)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ref_e = nn.BatchNorm(use_running_average=True, **kw)
    mine_e = TaggedBatchNorm(use_running_average=True, **kw)
    np.testing.assert_array_equal(
        np.asarray(ref_e.apply(vr, x), np.float32),
        np.asarray(mine_e.apply(vm, x), np.float32))


@pytest.mark.slow
def test_resnet50_remat_grad_exact():
    """--remat (selective conv_out/bn_stats policy) is bit-identical in
    outputs, gradients, and batch-stats updates — it only re-schedules
    the backward.  (Measured on-chip it is byte-neutral: XLA CSE
    restores the baseline program — docs/DESIGN.md byte-lever table.)"""
    xi = jax.random.normal(jax.random.key(2), (2, 32, 32, 3), jnp.float32)
    m0 = ResNet50(num_classes=10, dtype=jnp.bfloat16)
    m1 = ResNet50(num_classes=10, dtype=jnp.bfloat16, remat=True)
    v = m0.init(jax.random.key(3), xi, train=True)
    assert (jax.tree_util.tree_structure(v) == jax.tree_util.tree_structure(
        m1.init(jax.random.key(3), xi, train=True)))

    def loss(params, model):
        out, mut = model.apply(
            {"params": params, "batch_stats": v["batch_stats"]},
            xi, train=True, mutable=["batch_stats"])
        return jnp.sum(out.astype(jnp.float32) ** 2), mut

    g0, mut0 = jax.grad(lambda p: loss(p, m0), has_aux=True)(v["params"])
    g1, mut1 = jax.grad(lambda p: loss(p, m1), has_aux=True)(v["params"])
    for a, b in zip(jax.tree_util.tree_leaves((g0, mut0)),
                    jax.tree_util.tree_leaves((g1, mut1))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # eval path (remat wrapper skipped) still runs
    assert m1.apply(v, xi, train=False).shape == (2, 10)


def test_resnet56_param_count():
    m = resnet56()
    v = jax.eval_shape(
        lambda: m.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)),
                       train=False))
    assert n_params(v["params"]) == 856_058


def test_resnet_cifar_family_depths():
    """(6n+2) sizing: each BasicBlock holds 2 convs; 3 stages of n blocks
    + conv1 ⇒ 6n+1 convs (+ projection shortcuts) and depth 6n+2 layers."""
    for ctor, n in ((resnet20, 3), (resnet56, 9)):
        m = ctor()
        v = jax.eval_shape(
            lambda m=m: m.init(jax.random.key(0), jnp.zeros((1, 16, 16, 3)),
                               train=False))
        convs = [p for p in jax.tree_util.tree_leaves_with_path(v["params"])
                 if getattr(p[0][-1], "key", "") == "kernel"
                 and len(p[1].shape) == 4]
        # 1 stem + 6n body + 3 projection shortcuts
        assert len(convs) == 1 + 6 * n + 3


def test_cifar_forward_shapes_and_dtype():
    m = resnet20(dtype=jnp.bfloat16)
    x = jnp.zeros((2, 16, 16, 3), jnp.float32)
    v = m.init(jax.random.key(0), x, train=False)
    logits = m.apply(v, x, train=False)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32  # fp32 logits under mixed precision
    # params stay fp32
    assert all(p.dtype == jnp.float32
               for p in jax.tree_util.tree_leaves(v["params"]))


def test_batch_stats_update():
    m = resnet20()
    x = jax.random.normal(jax.random.key(1), (4, 16, 16, 3))
    v = m.init(jax.random.key(0), x, train=False)
    _, mutated = m.apply(v, x, train=True, mutable=["batch_stats"])
    old = jax.tree_util.tree_leaves(v["batch_stats"])
    new = jax.tree_util.tree_leaves(mutated["batch_stats"])
    assert any(not np.allclose(a, b) for a, b in zip(old, new))


def test_trivial_model():
    m = TrivialModel(num_classes=7)
    x = jnp.zeros((3, 8, 8, 3))
    v = m.init(jax.random.key(0), x, train=False)
    assert m.apply(v, x, train=False).shape == (3, 7)
    assert "batch_stats" not in v


def test_l2_penalty_filters():
    """Penalize conv/dense kernels + classifier bias; never BN scale/bias
    (Keras regularizer placement, resnet_cifar_model.py:66-79,250-251)."""
    params = {
        "conv1": {"kernel": jnp.ones((2, 2, 3, 4))},
        "bn_conv1": {"scale": jnp.ones((4,)), "bias": jnp.ones((4,))},
        "fc": {"kernel": jnp.ones((4, 10)), "bias": jnp.ones((10,))},
    }
    got = float(l2_weight_penalty(params, 2e-4))
    expected = 2e-4 * (2 * 2 * 3 * 4 + 4 * 10 + 10)
    np.testing.assert_allclose(got, expected, rtol=1e-6)


def test_l2_zero_weight_is_zero():
    assert float(l2_weight_penalty({"a": jnp.ones((3,))}, 0.0)) == 0.0


def test_registry():
    m, l2 = build_model("resnet56")
    assert l2 == 2e-4
    m, l2 = build_model("resnet50")
    assert l2 == 1e-4
    m, l2 = build_model("trivial")
    assert l2 == 0.0
    with pytest.raises(ValueError):
        build_model("resnet9000")


def test_registry_misnamed_parity_alias():
    """The reference's `resnet10` is actually ResNet-662 (SURVEY §2.1);
    we expose it honestly as resnet662."""
    m, _ = build_model("resnet662")
    assert m.num_blocks == 110
