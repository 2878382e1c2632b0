"""On-chip input normalization — the compiled-step half of the uint8 wire.

TPU-first placement of the reference's normalization ops: the reference
runs mean subtraction / per-image standardization inside its C++ graph
runtime (imagenet_preprocessing.py:397-430, cifar_preprocessing.py:98);
the TPU-native home for that math is the chip.  Pipelines ship uint8
HWC batches — 4x fewer host→device bytes than a float32 wire (an
ImageNet batch of 64 is 38 MB in float32) — and the dataset's
normalization runs in f32 as the FIRST op inside the jitted train/eval
step, where XLA fuses it into the consuming convolution's input.

Numerics: uint8→f32 conversion is exact, and these functions apply the
same f32 arithmetic the host pipelines apply, so on-chip normalization
of a uint8 batch matches host normalization of the same pixels (tests
pin this; reductions in per-image standardization may differ by float
association order, ~1e-6 relative).  The only wire-format delta is
ImageNet's post-resize round-half-up to uint8 (≤0.5/255 quantization of
bilinear samples — below JPEG decode noise).
"""

from __future__ import annotations

import jax.numpy as jnp


def cifar_standardize(images):
    """tf.image.per_image_standardization in-graph: per-image zero mean,
    unit stddev with the 1/sqrt(N) floor (cifar_preprocessing.py:98 —
    the host-side twin is data/cifar.py standardize)."""
    x = images.astype(jnp.float32)
    n_elems = float(x.shape[-1] * x.shape[-2] * x.shape[-3])
    mean = jnp.mean(x, axis=(-3, -2, -1), keepdims=True)
    std = jnp.std(x, axis=(-3, -2, -1), keepdims=True)
    adjusted = jnp.maximum(std, 1.0 / jnp.sqrt(jnp.float32(n_elems)))
    return (x - mean) / adjusted


def imagenet_mean_subtract(images):
    """Channel-mean subtraction without scaling
    (imagenet_preprocessing.py:397-430 — the host twin is
    data/imagenet.py CHANNEL_MEANS)."""
    from dtf_tpu.data.imagenet import CHANNEL_MEANS
    return images.astype(jnp.float32) - jnp.asarray(CHANNEL_MEANS)


def for_dataset(name: str):
    """The on-chip normalize fn a uint8-wire pipeline defers to."""
    fns = {"cifar10": cifar_standardize,
           "imagenet": imagenet_mean_subtract}
    if name not in fns:
        raise ValueError(f"no on-chip normalization for dataset {name!r}")
    return fns[name]


def for_config(cfg, spec):
    """The compiled-step normalization a config's input wire implies —
    the SINGLE source of that decision for every training path (SPMD
    runner and async PS).  None when batches arrive host-normalized:
    the float32 wire, or synthetic data (the same
    use_synthetic_data/data_dir predicate the input-fn builders branch
    on), or token-sequence datasets (no image normalization)."""
    if (cfg.input_wire != "uint8" or cfg.use_synthetic_data
            or not cfg.data_dir or spec.is_sequence):
        return None
    return for_dataset(spec.name)
