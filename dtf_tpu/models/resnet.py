"""ResNet-50 v1.5 for 224×224 ImageNet, as a flax module.

Capability parity with reference resnet_model.py (resnet50, :224-389):
  - bottleneck blocks [1×1, 3×3(stride), 1×1]; the stride sits on the
    3×3 ("v1.5", reference conv_block:124-221)
  - stage layout 3/4/6/3, filters (64,64,256)→(512,512,2048)
  - conv1: 7×7 stride 2, explicit (3,3) zero-pad, no bias
  - BatchNorm momentum 0.9, eps 1e-5 (resnet_model.py:38-39)
  - he_normal conv init; final Dense init N(0, 0.01) (:377)
  - L2 weight decay 1e-4 applied as a loss term over conv/dense kernels
    AND the final dense bias (:37-43, :378-380) — see registry.l2_weight_penalty
  - logits cast to float32 before softmax under mixed precision (:383-385)

TPU-first choices: NHWC layout (MXU/XLA native), bf16 compute with fp32
params and fp32 BatchNorm, padding='SAME' where it is numerically
identical, logits returned (loss applies log-softmax — cheaper and
fused by XLA; the reference bakes softmax into the model).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

BATCH_NORM_DECAY = 0.9
BATCH_NORM_EPSILON = 1e-5

conv_init = nn.initializers.he_normal()
dense_init = nn.initializers.normal(stddev=0.01)

# Selective-remat policy for the bandwidth-bound ResNet step: save conv
# outputs and BN batch statistics as backward residuals; recompute the
# elementwise normalize/relu chains in the backward instead of storing
# their outputs.  The step is HBM-floored (docs/DESIGN.md roofline:
# 78.8 GB/step at 97.5% of peak with 65 ms of FLOP headroom), so
# trading free VPU recompute for residual reads/writes attacks the only
# binding constraint.  BN stats are saved so the backward never re-runs
# the mean/var reductions (those would re-read the conv output).
RESNET_REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    "conv_out", "bn_stats")


def _bn_stats(x, reduction_axes, dtype, axis_name):
    """Batch mean/variance, vendored op-for-op from flax's
    ``_compute_stats`` (the real-input, fast-variance path) so a flax
    upgrade can't rename a private helper out from under ResNet import
    (ADVICE r5): reductions promoted to ≥ f32, Var = E[x²] − E[x]² with
    the negative-roundoff clamp, and the distributed (sync-BN) form
    stacking [mean, mean-of-squares] into ONE ``lax.pmean``.  Parity is
    pinned by test_tagged_batchnorm_bit_exact_vs_flax."""
    if dtype is None:
        dtype = jnp.result_type(x)
    dtype = jnp.promote_types(dtype, jnp.float32)
    x = jnp.asarray(x, dtype)
    mu = x.mean(reduction_axes)
    mu2 = lax.square(x).mean(reduction_axes)
    if axis_name is not None:
        mu, mu2 = lax.pmean(jnp.stack([mu, mu2]), axis_name)
    var = jnp.maximum(0.0, mu2 - lax.square(mu))
    return mu, var


class TaggedBatchNorm(nn.Module):
    """nn.BatchNorm (feature-last), bit-identical by construction — the
    ~15 lines of stat/normalize math are vendored op-for-op from flax
    (see `_bn_stats`; the normalize below keeps flax's exact operation
    order: y = x − mean, mul = rsqrt(var + ε) · scale, y·mul + bias) —
    plus `checkpoint_name` tags on the batch mean/var so the
    selective-remat policy can keep the statistics as residuals while
    the normalize itself is recomputed.  Parameter/collection tree
    paths match nn.BatchNorm ('scale', 'bias'; batch_stats 'mean',
    'var')."""
    use_running_average: bool = False
    momentum: float = BATCH_NORM_DECAY
    epsilon: float = BATCH_NORM_EPSILON
    dtype: Any = None
    param_dtype: Any = jnp.float32
    axis_name: Any = None  # cross-replica (sync) BN

    @nn.compact
    def __call__(self, x):
        feature_shape = (x.shape[-1],)
        reduction_axes = tuple(range(x.ndim - 1))
        ra_mean = self.variable(
            "batch_stats", "mean",
            lambda s: jnp.zeros(s, jnp.float32), feature_shape)
        ra_var = self.variable(
            "batch_stats", "var",
            lambda s: jnp.ones(s, jnp.float32), feature_shape)
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            mean, var = _bn_stats(x, reduction_axes, self.dtype,
                                  self.axis_name)
            mean = checkpoint_name(mean, "bn_stats")
            var = checkpoint_name(var, "bn_stats")
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * var
        # normalize (flax `_normalize`, feature-last + scale&bias case)
        bshape = (1,) * (x.ndim - 1) + feature_shape
        y = x - mean.reshape(bshape)
        mul = lax.rsqrt(var.reshape(bshape) + self.epsilon)
        scale = self.param("scale", nn.initializers.ones_init(),
                           feature_shape, self.param_dtype)
        mul *= scale.reshape(bshape)
        y *= mul
        bias = self.param("bias", nn.initializers.zeros_init(),
                          feature_shape, self.param_dtype)
        y += bias.reshape(bshape)
        dtype = (jnp.result_type(x, scale, bias) if self.dtype is None
                 else self.dtype)
        return jnp.asarray(y, dtype)


class Conv1SpaceToDepth(nn.Module):
    """The stem 7×7/2 conv, computed as a 4×4/1 conv over 2×2
    space-to-depth blocks — numerically identical, ~4× better MXU
    utilization (12 input channels instead of 3; the standard TPU
    ResNet stem trick).  The parameter keeps the reference shape
    (7,7,3,64) and the `conv1/kernel` tree path, so checkpoints and
    the plain-conv path are interchangeable; the zero-pad + block
    reshape of the kernel is traced into the step (trivially small)."""
    features: int = 64
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        assert c == 3, (f"Conv1SpaceToDepth is the RGB stem; got "
                        f"{c}-channel input")
        kernel = self.param("kernel", conv_init, (7, 7, c, self.features),
                            jnp.float32)
        x = jnp.pad(x, ((0, 0), (3, 3), (3, 3), (0, 0)))
        # 2×2 space-to-depth: [B, (H+6)/2, (W+6)/2, 12]
        hb, wb = (h + 6) // 2, (w + 6) // 2
        x = x.reshape(b, hb, 2, wb, 2, c).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(b, hb, wb, 4 * c).astype(self.dtype)
        # kernel 7×7 → zero-pad to 8×8 → 4×4 blocks over 12 channels
        k = jnp.pad(kernel, ((0, 1), (0, 1), (0, 0), (0, 0)))
        k = k.reshape(4, 2, 4, 2, c, self.features)
        k = k.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c,
                                                  self.features)
        return lax.conv_general_dilated(
            x, k.astype(self.dtype), window_strides=(1, 1),
            padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))


class BottleneckBlock(nn.Module):
    """conv_block / identity_block of reference resnet_model.py:46-221."""
    filters: Sequence[int]
    strides: int = 1
    projection: bool = False
    dtype: Any = jnp.float32
    bn_axis: Any = None  # axis_name for cross-replica (sync) BN

    @nn.compact
    def __call__(self, x, train: bool = True):
        f1, f2, f3 = self.filters
        conv = partial(nn.Conv, use_bias=False, kernel_init=conv_init,
                       dtype=self.dtype, param_dtype=jnp.float32)
        # dtype=self.dtype keeps activations bf16 between convs (half the
        # HBM traffic of fp32 BN I/O); the mean/var math itself is still
        # fp32 (flax _compute_stats upcasts)
        bn = partial(TaggedBatchNorm, use_running_average=not train,
                     axis_name=self.bn_axis,
                     momentum=BATCH_NORM_DECAY, epsilon=BATCH_NORM_EPSILON,
                     dtype=self.dtype, param_dtype=jnp.float32)
        shortcut = x
        y = checkpoint_name(conv(f1, (1, 1), name="conv_a")(x), "conv_out")
        y = bn(name="bn_a")(y)
        y = nn.relu(y)
        y = conv(f2, (3, 3), strides=(self.strides, self.strides),
                 padding="SAME", name="conv_b")(y)
        y = checkpoint_name(y, "conv_out")
        y = bn(name="bn_b")(y)
        y = nn.relu(y)
        y = checkpoint_name(conv(f3, (1, 1), name="conv_c")(y), "conv_out")
        y = bn(name="bn_c")(y)
        if self.projection:
            shortcut = conv(f3, (1, 1), strides=(self.strides, self.strides),
                            name="conv_proj")(x)
            shortcut = checkpoint_name(shortcut, "conv_out")
            shortcut = bn(name="bn_proj")(shortcut)
        return nn.relu(y + shortcut.astype(y.dtype))


class ResNet50(nn.Module):
    """Returns float32 logits of shape [batch, num_classes]."""
    num_classes: int = 1001
    dtype: Any = jnp.float32
    bn_axis: Any = None  # axis_name for cross-replica (sync) BN
    # stem as a space-to-depth conv (exact reformulation, see
    # Conv1SpaceToDepth); False = the literal reference conv1
    stem_space_to_depth: bool = True
    # selective remat: save conv outputs + BN stats only, recompute the
    # elementwise normalize/relu chains in the backward (see
    # RESNET_REMAT_POLICY).  A bytes lever, not a memory one — the step
    # is HBM-bound.  Identical math either way.
    remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = x.astype(self.dtype)
        # conv1: explicit (3,3) pad + VALID 7×7/2 ≡ reference conv1_pad+conv1
        if self.stem_space_to_depth and x.shape[1] % 2 == 0 and \
                x.shape[2] % 2 == 0 and x.shape[3] == 3:
            x = Conv1SpaceToDepth(dtype=self.dtype, name="conv1")(x)
        else:
            x = nn.Conv(64, (7, 7), strides=(2, 2),
                        padding=[(3, 3), (3, 3)],
                        use_bias=False, kernel_init=conv_init,
                        dtype=self.dtype,
                        param_dtype=jnp.float32, name="conv1")(x)
        x = TaggedBatchNorm(use_running_average=not train,
                            axis_name=self.bn_axis,
                            momentum=BATCH_NORM_DECAY,
                            epsilon=BATCH_NORM_EPSILON,
                            dtype=self.dtype, param_dtype=jnp.float32,
                            name="bn_conv1")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")

        # remat only where it matters (train step); lifted nn.remat does
        # not change variable tree paths, so train/eval stay compatible
        block_cls = BottleneckBlock
        if self.remat and train:
            # prevent_cse=False: we are under jit (not pmap/scan), where
            # the CSE-barrier workaround is unnecessary — and its
            # optimization barriers would force XLA to materialize the
            # recomputed elementwise chains instead of fusing them into
            # the backward convolutions' operand reads
            block_cls = nn.remat(BottleneckBlock,
                                 policy=RESNET_REMAT_POLICY,
                                 prevent_cse=False,
                                 static_argnums=(2,))
        stages = (
            ((64, 64, 256), 3, 1),
            ((128, 128, 512), 4, 2),
            ((256, 256, 1024), 6, 2),
            ((512, 512, 2048), 3, 2),
        )
        for s, (filters, blocks, stride) in enumerate(stages, start=2):
            x = block_cls(filters, strides=stride, projection=True,
                          dtype=self.dtype, bn_axis=self.bn_axis,
                          name=f"stage{s}_block0")(x, train)
            for b in range(1, blocks):
                x = block_cls(filters, dtype=self.dtype,
                              bn_axis=self.bn_axis,
                              name=f"stage{s}_block{b}")(x, train)

        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, kernel_init=dense_init,
                     dtype=self.dtype, param_dtype=jnp.float32, name="fc")(x)
        # mixed-precision parity: logits in float32 (resnet_model.py:383-385)
        return x.astype(jnp.float32)
