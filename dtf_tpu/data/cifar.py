"""CIFAR-10 binary input pipeline.

Parity with reference cifar_preprocessing.py:
  - fixed-length records: 1 label byte + 3072 image bytes CHW
    (:30-33), files data_batch_{1..5}.bin / test_batch.bin under
    `cifar-10-batches-bin` (:102-114)
  - train augmentation: pad to 40×40 (resize_with_crop_or_pad ≡
    zero-pad), random 32×32 crop, random horizontal flip (:84-96)
  - per_image_standardization: (x-mean)/max(stddev, 1/√N) (:98)
  - per-process shard-by-file (:147-152), full-dataset shuffle
    (process_record_dataset shuffle_buffer=NUM_IMAGES)

TPU-first shape: the dataset is 150 MB — it is loaded once into host
memory and batches are assembled with vectorized numpy (no per-record
op graph), which outruns the reference's generic record pipeline by
construction.
"""

from __future__ import annotations

import os
from typing import Iterator, Optional, Tuple

import numpy as np

from dtf_tpu.data.base import CIFAR10
from dtf_tpu.data.pipeline import shard_for_process

HEIGHT = WIDTH = 32
NUM_CHANNELS = 3
RECORD_BYTES = HEIGHT * WIDTH * NUM_CHANNELS + 1
NUM_DATA_FILES = 5


def get_filenames(is_training: bool, data_dir: str):
    """Reference get_filenames (:102-114), including the assert on the
    extracted directory layout."""
    if "cifar-10-batches-bin" not in data_dir:
        data_dir = os.path.join(data_dir, "cifar-10-batches-bin")
    if not os.path.isdir(data_dir):
        raise FileNotFoundError(
            f"CIFAR-10 binary directory not found: {data_dir}; download and "
            f"extract cifar-10-binary.tar.gz")
    if is_training:
        return [os.path.join(data_dir, f"data_batch_{i}.bin")
                for i in range(1, NUM_DATA_FILES + 1)]
    return [os.path.join(data_dir, "test_batch.bin")]


def write_binary_file(path: str, images: np.ndarray,
                      labels: np.ndarray) -> None:
    """Write records in the CIFAR binary wire format: 1 label byte +
    3072 CHW image bytes each (cifar_preprocessing.py:30-33).  The
    inverse of :func:`load_records`; used by tests to
    synthesize datasets the production reader consumes."""
    images = np.asarray(images, np.uint8)
    labels = np.asarray(labels)
    n = len(labels)
    recs = np.zeros((n, RECORD_BYTES), np.uint8)
    recs[:, 0] = labels
    recs[:, 1:] = images.transpose(0, 3, 1, 2).reshape(n, -1)
    with open(path, "wb") as f:
        f.write(recs.tobytes())


def load_records(filenames, dtype=np.float32) -> Tuple[np.ndarray, np.ndarray]:
    """Parses fixed-length records → (images HWC ``dtype``, labels
    int32).  CHW→HWC transpose per reference parse_record (:43-75).
    ``dtype=np.uint8`` keeps the raw pixels (the uint8-wire mode — 4x
    less host memory and memcpy per batch)."""
    blobs = []
    for fn in filenames:
        raw = np.fromfile(fn, dtype=np.uint8)
        if raw.size % RECORD_BYTES:
            raise IOError(f"{fn}: size {raw.size} not a multiple of "
                          f"{RECORD_BYTES}")
        blobs.append(raw.reshape(-1, RECORD_BYTES))
    records = np.concatenate(blobs)
    labels = records[:, 0].astype(np.int32)
    images = (records[:, 1:]
              .reshape(-1, NUM_CHANNELS, HEIGHT, WIDTH)
              .transpose(0, 2, 3, 1)
              .astype(dtype, copy=False))
    return images, labels


def augment_batch(images: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Vectorized pad-4 → random crop → random flip.  dtype-preserving:
    pad/crop/flip move pixels without arithmetic, so uint8 in → uint8
    out, bit-identical to augmenting the same pixels in float32."""
    n = images.shape[0]
    padded = np.zeros((n, HEIGHT + 8, WIDTH + 8, NUM_CHANNELS),
                      images.dtype)
    padded[:, 4:4 + HEIGHT, 4:4 + WIDTH] = images
    ys = rng.integers(0, 9, n)
    xs = rng.integers(0, 9, n)
    flips = rng.random(n) < 0.5
    out = np.empty_like(images)
    for i in range(n):  # gather per-image offsets (cheap vs. the copy)
        crop = padded[i, ys[i]:ys[i] + HEIGHT, xs[i]:xs[i] + WIDTH]
        out[i] = crop[:, ::-1] if flips[i] else crop
    return out


def standardize(images: np.ndarray) -> np.ndarray:
    """tf.image.per_image_standardization: per-image zero mean, unit
    stddev with the 1/√N floor."""
    n_elems = float(np.prod(images.shape[1:]))
    mean = images.mean(axis=(1, 2, 3), keepdims=True)
    std = images.std(axis=(1, 2, 3), keepdims=True)
    adjusted = np.maximum(std, 1.0 / np.sqrt(n_elems))
    return (images - mean) / adjusted


def cifar_input_fn(data_dir: str, is_training: bool, batch_size: int,
                   seed: int = 0, process_id: Optional[int] = None,
                   process_count: Optional[int] = None,
                   drop_remainder: bool = True,
                   wire: str = "float32", start_step: int = 0) -> Iterator:
    """Yields (images, labels) numpy batches; infinite for training.

    POSITION-DERIVED randomness (crash-exact resume): the shuffle order
    of epoch *e* and the augmentation draws of batch *(e, k)* are each
    seeded from ``(seed, process_id, e[, k])`` counters, never from a
    long-lived RNG stream.  Batch *n* of the training stream is
    therefore a pure function of (seed, process, n) — a run restored
    from a checkpoint at step *n* passes ``start_step=n`` and sees the
    EXACT batch sequence the uninterrupted run would have seen, without
    replaying (or skipping) a single example.

    ``wire``: host→device batch format.  ``"float32"`` standardizes on
    the host (per_image_standardization, the r1-r3 behavior);
    ``"uint8"`` ships raw augmented pixels — 4x fewer bytes over the
    wire — and defers standardization to the compiled step
    (data/normalize.py cifar_standardize).  The augmentation
    (pad-crop-flip) moves pixels without arithmetic, so both wires see
    bit-identical pixel values.

    Multi-process: each process loads its shard of the files
    (cifar_preprocessing.py:147-152 semantics). `batch_size` is the
    per-host batch (global / process_count), matching how the loop's
    shard_batch assembles the global array.

    Eval with ``drop_remainder=False`` (the default config): examples
    are stride-sharded across processes and the final partial batch is
    zero-padded with a mask — batches are ``(images, labels, mask)``
    3-tuples, every process yields the same batch count, and eval
    covers exactly the full 10k test set once (the reference's full-set
    eval).  ``drop_remainder=True`` keeps the 2-tuple
    every-host-reads-everything behavior (benchmark purity).
    """
    import jax
    process_id = jax.process_index() if process_id is None else process_id
    process_count = (jax.process_count() if process_count is None
                     else process_count)
    if wire not in ("float32", "uint8"):
        raise ValueError(f"wire must be 'float32' or 'uint8', got {wire!r}")
    u8 = wire == "uint8"

    files = get_filenames(is_training, data_dir)
    if is_training and process_count > 1:
        files = shard_for_process(files, process_id, process_count) or files
    # raw uint8 resident set (150 MB, not 600); the f32 wire casts at
    # yield time, which reproduces the old all-f32 numerics exactly
    # (pad/crop/flip are value-preserving)
    images, labels = load_records(files, dtype=np.uint8)
    if is_training and len(images) < batch_size:
        raise ValueError(
            f"process {process_id}'s file shard holds {len(images)} images, "
            f"fewer than the per-host batch {batch_size}; reduce batch_size "
            f"or process count")
    # nonnegative per-process base entropy for the counter-derived RNGs
    seed_base = (int(seed) + 7919 * int(process_id)) & 0xFFFFFFFF

    def finalize(batch: np.ndarray) -> np.ndarray:
        if u8:
            return batch
        return standardize(batch.astype(np.float32))

    def gen():
        if is_training:
            per_epoch = len(images) // batch_size
            step = int(start_step)
            cur_epoch, order = -1, None
            while True:
                epoch, k = divmod(step, per_epoch)
                if epoch != cur_epoch:
                    # full-dataset shuffle, derived from (seed, epoch)
                    # alone — any step of any epoch is reconstructable
                    cur_epoch = epoch
                    order = np.random.default_rng(
                        np.random.SeedSequence(
                            [seed_base, epoch])).permutation(len(images))
                idx = order[k * batch_size:(k + 1) * batch_size]
                brng = np.random.default_rng(
                    np.random.SeedSequence([seed_base, epoch, k, 1]))
                yield finalize(augment_batch(images[idx], brng)), labels[idx]
                step += 1
        elif drop_remainder:
            for i in range(0, len(images) - batch_size + 1, batch_size):
                yield (finalize(images[i:i + batch_size].copy()),
                       labels[i:i + batch_size])
        else:
            # exact full-coverage eval: each process takes the stride
            # slice [pid::pcount]; all processes compute the same batch
            # count from the (globally known) total, so the collective
            # eval steps stay aligned
            total = len(images)
            local_idx = np.arange(process_id, total, process_count)
            max_local = -(-total // process_count)
            nbatches = -(-max_local // batch_size)
            for b in range(nbatches):
                sel = local_idx[b * batch_size:(b + 1) * batch_size]
                imgs = np.zeros((batch_size, HEIGHT, WIDTH, NUM_CHANNELS),
                                np.uint8 if u8 else np.float32)
                lbls = np.zeros((batch_size,), np.int32)
                mask = np.zeros((batch_size,), np.float32)
                if len(sel):
                    imgs[:len(sel)] = finalize(images[sel].copy())
                    lbls[:len(sel)] = labels[sel]
                    mask[:len(sel)] = 1.0
                yield imgs, lbls, mask

    return gen()
