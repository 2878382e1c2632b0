"""Synthetic ImageNet-shaped JPEG shards for tests and smokes."""

from __future__ import annotations

import io
import os
from typing import Tuple

import numpy as np


def make_shards(root: str, num_shards: int, images_per_shard: int,
                height: Tuple[int, int] = (350, 420),
                width: Tuple[int, int] = (450, 550)) -> str:
    """Write ``num_shards`` TFRecord files of random-noise JPEGs
    (quality 90, labels 1..1000) under ``root`` in the production
    ``train-%05d-of-01024`` layout; returns ``root``.

    ``height`` / ``width`` are half-open ranges a source's size is drawn
    from: the defaults are ImageNet's typical ~500x375; a caller that
    only needs a stream (a determinism check) passes small ones so
    decode stays cheap.  Seeded: the same arguments give the same bytes.
    """
    from PIL import Image
    from dtf_tpu.data import records
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng(0)
    for shard in range(num_shards):
        recs = []
        for _ in range(images_per_shard):
            h, w = int(rng.integers(*height)), int(rng.integers(*width))
            arr = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
            buf = io.BytesIO()
            Image.fromarray(arr).save(buf, format="JPEG", quality=90)
            recs.append(records.build_example({
                "image/encoded": buf.getvalue(),
                "image/class/label": [int(rng.integers(1, 1001))],
            }))
        records.write_tfrecord_file(
            os.path.join(root, f"train-{shard:05d}-of-01024"), recs)
    return root
