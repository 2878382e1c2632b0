"""ImageNet TFRecord pipeline tests against generated shards with real
JPEG payloads (format of imagenet_preprocessing.py:156-223)."""

import io

import numpy as np
import pytest
from PIL import Image

from dtf_tpu.data import imagenet, records


def make_jpeg(rng, h=64, w=80):
    arr = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", quality=90)
    return buf.getvalue()


@pytest.fixture()
def imagenet_dir(tmp_path):
    rng = np.random.default_rng(0)
    for shard in range(2):
        recs = []
        for i in range(6):
            ex = records.build_example({
                "image/encoded": make_jpeg(rng),
                "image/class/label": [1 + (shard * 6 + i) % 1000],
                "image/object/bbox/ymin": [0.1],
                "image/object/bbox/xmin": [0.1],
                "image/object/bbox/ymax": [0.9],
                "image/object/bbox/xmax": [0.9],
            })
            recs.append(ex)
        records.write_tfrecord_file(
            str(tmp_path / f"train-{shard:05d}-of-01024"), recs)
        records.write_tfrecord_file(
            str(tmp_path / f"validation-{shard:05d}-of-00128"), recs)
    return str(tmp_path)


def test_get_filenames(imagenet_dir):
    assert len(imagenet.get_filenames(True, imagenet_dir)) == 2
    assert len(imagenet.get_filenames(False, imagenet_dir)) == 2
    with pytest.raises(FileNotFoundError):
        imagenet.get_filenames(True, "/nonexistent")


def test_parse_example_record(imagenet_dir):
    raw = next(records.read_tfrecord_file(
        imagenet.get_filenames(True, imagenet_dir)[0]))
    buf, label, bbox = imagenet.parse_example_record(raw)
    assert buf[:2] == b"\xff\xd8"  # JPEG SOI
    assert 0 <= label < 1000  # shifted to [0,1000) (:254-255)
    assert bbox.shape == (1, 4)


def test_decode_jpeg_rgb():
    rng = np.random.default_rng(1)
    img = imagenet.decode_jpeg(make_jpeg(rng, 32, 48))
    assert img.shape == (32, 48, 3)
    assert img.dtype == np.uint8


def test_sample_distorted_bbox_constraints():
    rng = np.random.default_rng(2)
    h, w = 200, 300
    bbox = np.array([[0.2, 0.2, 0.8, 0.8]], np.float32)
    for _ in range(20):
        y, x, ch, cw = imagenet.sample_distorted_bbox(rng, h, w, bbox)
        assert 0 <= y <= h - ch and 0 <= x <= w - cw
        if (ch, cw) != (h, w):  # not the fallback
            area = ch * cw / (h * w)
            aspect = cw / ch
            assert 0.04 <= area <= 1.01
            assert 0.70 <= aspect <= 1.40


def test_preprocess_eval_shape_and_mean():
    rng = np.random.default_rng(3)
    out = imagenet.preprocess_eval(make_jpeg(rng, 300, 400))
    assert out.shape == (224, 224, 3)
    # channel means subtracted: values roughly centered
    assert -130 <= out.mean() <= 130


def test_preprocess_train_shape():
    rng = np.random.default_rng(4)
    out = imagenet.preprocess_train(make_jpeg(rng, 100, 150), None, rng)
    assert out.shape == (224, 224, 3)
    assert out.dtype == np.float32


def test_input_fn_train(imagenet_dir):
    it = imagenet.imagenet_input_fn(imagenet_dir, True, 4, seed=0,
                                    num_threads=2, process_id=0,
                                    process_count=1)
    images, labels = next(it)
    assert images.shape == (4, 224, 224, 3)
    assert labels.dtype == np.int32
    assert 0 <= labels.min()
    images2, _ = next(it)
    assert not np.array_equal(images, images2)


def test_input_fn_eval_exhausts(imagenet_dir):
    it = imagenet.imagenet_input_fn(imagenet_dir, False, 4, num_threads=2,
                                    process_id=0, process_count=1)
    batches = list(it)
    assert len(batches) == 12 // 4


DECODE_WORKER = """
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
dir_, n = sys.argv[1], int(sys.argv[2])
from dtf_tpu.data.imagenet import imagenet_input_fn
it = imagenet_input_fn(dir_, True, 64, seed=int(sys.argv[3]),
                       process_id=0, process_count=1)
for _ in range(2):
    next(it)
t0 = time.perf_counter()
seen = 0
while seen < n:
    images, labels = next(it)
    seen += len(labels)
print("RATE=%.2f" % (seen / (time.perf_counter() - t0)))
it.close()
"""


@pytest.mark.slow
def test_two_process_decode_co_residency(tmp_path):
    """The multi-core feeding claim rests on serial_fraction ~ 0
    measured on a 1-core host; this puts cross-PROCESS
    evidence behind the extrapolation: two decode pipelines co-resident
    on the same host and the same shard files split the core's
    throughput ~fairly, with no cross-process serialization collapse —
    their SUM stays close to the solo rate.  (On an N-core host the
    same property is what makes N input processes scale; this is the
    strongest test a 1-core box can run.)"""
    import os
    import re
    import subprocess
    import sys as _sys

    from dtf_tpu.testing.shards import make_shards

    shards = tmp_path / "shards"
    shards.mkdir()
    make_shards(str(shards), num_shards=2, images_per_shard=200)
    script = tmp_path / "decode_worker.py"
    script.write_text(DECODE_WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo)

    def rate_of(proc):
        out, err = proc.communicate(timeout=300)
        m = re.search(r"RATE=([\d.]+)", out)
        assert m, f"no rate line:\n{out[-800:]}\n{err[-800:]}"
        return float(m.group(1))

    def spawn(seed):
        return subprocess.Popen(
            [_sys.executable, str(script), str(shards), "1280", str(seed)],
            cwd=repo, env=env, text=True, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE)

    solo = rate_of(spawn(0))
    p1, p2 = spawn(1), spawn(2)
    r1, r2 = rate_of(p1), rate_of(p2)
    # no serialization collapse: the pair's combined throughput holds
    # most of the solo rate (scheduling overhead only) ...
    assert r1 + r2 > 0.7 * solo, (solo, r1, r2)
    # ... and neither process is starved by the other
    assert min(r1, r2) > 0.2 * solo, (solo, r1, r2)
