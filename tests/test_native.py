"""C++ data runtime tests: native results must match the pure-Python
reference implementations bit-for-bit."""

import io

import numpy as np
import pytest
from PIL import Image

from dtf_tpu import native
from dtf_tpu.data import records

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="libdtf_native.so not built")


def test_crc32c_matches_python():
    for data in (b"", b"a", b"123456789", bytes(range(256)) * 7):
        assert native.crc32c(data) == records.crc32c(data)


def test_tfrecord_reader_matches_python(tmp_path):
    path = str(tmp_path / "x.tfrecord")
    payloads = [b"abc", b"", b"z" * 5000]
    records.write_tfrecord_file(path, payloads)
    assert list(native.read_tfrecord_file(path, verify_crc=True)) == payloads


def test_tfrecord_reader_detects_corruption(tmp_path):
    path = str(tmp_path / "bad.tfrecord")
    records.write_tfrecord_file(path, [b"hello world"])
    raw = bytearray(open(path, "rb").read())
    raw[14] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises(IOError):
        list(native.read_tfrecord_file(path, verify_crc=True))


def test_tfrecord_missing_file():
    with pytest.raises(IOError):
        list(native.read_tfrecord_file("/nonexistent.tfrecord"))


def _jpeg(arr):
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", quality=95)
    return buf.getvalue()


def test_jpeg_shape():
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(0)
    buf = _jpeg(rng.integers(0, 256, (37, 53, 3), dtype=np.uint8))
    assert jpeg.shape(buf) == (37, 53)


def test_jpeg_decode_matches_pil():
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(1)
    arr = rng.integers(0, 256, (64, 48, 3), dtype=np.uint8)
    buf = _jpeg(arr)
    ours = jpeg.decode(buf)
    pil = np.asarray(Image.open(io.BytesIO(buf)).convert("RGB"))
    assert ours.shape == pil.shape
    # same decoder library → identical output
    np.testing.assert_array_equal(ours, pil)


def test_jpeg_decode_crop_equals_full_decode_slice():
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(2)
    buf = _jpeg(rng.integers(0, 256, (100, 120, 3), dtype=np.uint8))
    full = jpeg.decode(buf)
    crop = jpeg.decode_crop(buf, 10, 20, 50, 60)
    np.testing.assert_array_equal(crop, full[10:60, 20:80])


def test_jpeg_invalid_data():
    from dtf_tpu.native import jpeg
    with pytest.raises(ValueError):
        jpeg.decode(b"not a jpeg at all")


def test_jpeg_crop_out_of_bounds():
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(3)
    buf = _jpeg(rng.integers(0, 256, (32, 32, 3), dtype=np.uint8))
    with pytest.raises(ValueError):
        jpeg.decode_crop(buf, 0, 0, 64, 64)


def test_jpeg_decode_batch_matches_single():
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(7)
    bufs, crops = [], []
    for i in range(6):
        h, w = 40 + i, 50 + i
        bufs.append(_jpeg(rng.integers(0, 256, (h, w, 3), dtype=np.uint8)))
        crops.append((i % 3, i % 2, 32, 32))
    batch = jpeg.decode_batch(bufs, crops, 32, 32, num_threads=3)
    assert batch.shape == (6, 32, 32, 3)
    for i, (buf, (y, x, ch, cw)) in enumerate(zip(bufs, crops)):
        single = jpeg.decode_crop(buf, y, x, ch, cw)
        np.testing.assert_array_equal(batch[i], single)


def test_jpeg_decode_batch_reports_failures():
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(8)
    good = _jpeg(rng.integers(0, 256, (40, 40, 3), dtype=np.uint8))
    with pytest.raises(ValueError):
        jpeg.decode_batch([good, b"not a jpeg"], [(0, 0, 32, 32)] * 2, 32, 32)


def _tf_bilinear(img, oh, ow):
    """Numpy reference of tf.image.resize v2 bilinear (half-pixel
    centers, no antialias) — the semantics the C++ resize implements."""
    sh, sw = img.shape[:2]
    fy = (np.arange(oh) + 0.5) * sh / oh - 0.5
    fx = (np.arange(ow) + 0.5) * sw / ow - 0.5
    y0 = np.floor(fy).astype(int)
    x0 = np.floor(fx).astype(int)
    wy, wx = fy - y0, fx - x0
    ya, yb = np.clip(y0, 0, sh - 1), np.clip(y0 + 1, 0, sh - 1)
    xa, xb = np.clip(x0, 0, sw - 1), np.clip(x0 + 1, 0, sw - 1)
    img = img.astype(np.float32)
    top = (img[ya][:, xa] * (1 - wx[None, :, None])
           + img[ya][:, xb] * wx[None, :, None])
    bot = (img[yb][:, xa] * (1 - wx[None, :, None])
           + img[yb][:, xb] * wx[None, :, None])
    return top * (1 - wy[:, None, None]) + bot * wy[:, None, None]


def test_decode_crop_resize_batch_matches_reference():
    """The fused train-augmentation op ≡ decode_crop → flip →
    tf-bilinear resize → mean subtract, per image."""
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(11)
    bufs, crops, flips = [], [], []
    for i in range(6):
        h, w = 50 + 9 * i, 70 + 5 * i
        bufs.append(_jpeg(rng.integers(0, 256, (h, w, 3), dtype=np.uint8)))
        crops.append((i, 2 * i, 30 + i, 40 + i))
        flips.append(i % 2)
    sub = np.array([123.68, 116.78, 103.94], np.float32)
    out, ok = jpeg.decode_crop_resize_batch(bufs, crops, flips, 24, 28,
                                            sub, num_threads=3)
    assert ok.all() and out.shape == (6, 24, 28, 3)
    for i in range(6):
        y, x, ch, cw = crops[i]
        dec = jpeg.decode_crop(bufs[i], y, x, ch, cw)
        if flips[i]:
            dec = dec[:, ::-1]
        want = _tf_bilinear(dec, 24, 28) - sub
        np.testing.assert_allclose(out[i], want, atol=2e-3)


def test_eval_batch_matches_reference():
    """Fused eval pass (window decode + one sampling) ≡ full decode →
    tf-bilinear aspect resize → central crop → mean subtract."""
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(21)
    sub = np.array([123.68, 116.78, 103.94], np.float32)
    bufs = []
    for h, w in [(300, 400), (400, 300), (256, 256), (260, 513)]:
        bufs.append(_jpeg(rng.integers(0, 256, (h, w, 3), dtype=np.uint8)))
    out, ok = jpeg.eval_batch(bufs, 256, 224, 224, sub, num_threads=2)
    assert ok.all() and out.shape == (4, 224, 224, 3)
    for i, buf in enumerate(bufs):
        img = jpeg.decode(buf)
        h, w = img.shape[:2]
        scale = 256 / min(h, w)
        nh, nw = int(round(h * scale)), int(round(w * scale))
        resized = _tf_bilinear(img, nh, nw)
        oy, ox = (nh - 224) // 2, (nw - 224) // 2
        want = resized[oy:oy + 224, ox:ox + 224] - sub
        # float32 association differs between the C++ single-pass and
        # the numpy reference; 0.02 on a 0..255 scale is rounding noise
        np.testing.assert_allclose(out[i], want, atol=2e-2)


def test_eval_batch_rejects_tiny_images():
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(22)
    buf = _jpeg(rng.integers(0, 256, (40, 40, 3), dtype=np.uint8))
    # shorter side scales to 256, but a crop larger than resize_min
    # cannot be served
    out, ok = jpeg.eval_batch([buf], 128, 224, 224,
                              np.zeros(3, np.float32))
    assert not ok[0]


def test_decode_crop_resize_batch_scaled_decode():
    """--input_scaled_decode: crops larger than the output decode at
    the smallest N/8 DCT-space scale keeping the scaled crop >= the
    output — numerically close to the full decode on real (smooth)
    content, and bit-identical when the crop is not larger than the
    output."""
    from dtf_tpu.native import jpeg
    # smooth content (JPEG's home turf): gradients + a low-freq wave
    yy, xx = np.mgrid[0:512, 0:640].astype(np.float32)
    img = np.stack([
        96 + 64 * np.sin(yy / 70) + 0.05 * xx,
        128 + 0.15 * yy,
        80 + 48 * np.cos(xx / 90),
    ], axis=-1).clip(0, 255).astype(np.uint8)
    buf = _jpeg(img)
    sub = np.zeros(3, np.float32)
    big = [(10, 20, 480, 600)]  # → N=4 (4/8 = half-res decode)
    for flip in (0, 1):
        plain, ok1 = jpeg.decode_crop_resize_batch(
            [buf], big, [flip], 224, 224, sub)
        scaled, ok2 = jpeg.decode_crop_resize_batch(
            [buf], big, [flip], 224, 224, sub, scaled_decode=True)
        assert ok1.all() and ok2.all()
        # the scaled path must actually engage (bit-identical output
        # would mean the flag is dead) ...
        assert np.any(scaled != plain)
        # ... while the filter-chain difference stays tightly bounded
        # on smooth content, tiny in the mean
        assert np.abs(scaled - plain).max() < 8.0
        assert np.abs(scaled - plain).mean() < 1.0
    # N=5..7 scales are a measured loss (no SIMD reduced IDCT) — a
    # 300px crop (would-be N=6) must take the plain path bit-for-bit
    small = [(0, 0, 300, 300)]
    a, _ = jpeg.decode_crop_resize_batch([buf], small, [0], 224, 224, sub)
    b, _ = jpeg.decode_crop_resize_batch([buf], small, [0], 224, 224, sub,
                                         scaled_decode=True)
    np.testing.assert_array_equal(a, b)


def test_decode_crop_resize_batch_scaled_decode_deep():
    """A very large crop picks a deep scale (here 2/8 = quarter-res)
    and still lands near the unscaled result."""
    from dtf_tpu.native import jpeg
    yy, xx = np.mgrid[0:1200, 0:1400].astype(np.float32)
    img = np.stack([
        100 + 0.08 * yy, 120 + 0.05 * xx, 90 + 40 * np.sin(yy / 200),
    ], axis=-1).clip(0, 255).astype(np.uint8)
    buf = _jpeg(img)
    sub = np.zeros(3, np.float32)
    crops = [(4, 8, 1180, 1380)]  # >= 4x 224 → d=4
    plain, ok1 = jpeg.decode_crop_resize_batch([buf], crops, [0], 224,
                                               224, sub)
    scaled, ok2 = jpeg.decode_crop_resize_batch([buf], crops, [0], 224,
                                                224, sub,
                                                scaled_decode=True)
    assert ok1.all() and ok2.all()
    assert np.any(scaled != plain)  # the deep scale must engage
    assert np.abs(scaled - plain).max() < 8.0
    assert np.abs(scaled - plain).mean() < 1.0


def test_decode_crop_resize_batch_flags_bad_images():
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(12)
    good = _jpeg(rng.integers(0, 256, (40, 40, 3), dtype=np.uint8))
    out, ok = jpeg.decode_crop_resize_batch(
        [good, b"not a jpeg"], [(0, 0, 32, 32)] * 2, [0, 0], 24, 24,
        np.zeros(3, np.float32))
    assert list(ok) == [True, False]
    assert np.isfinite(out[0]).all()


def _train_example(rng, h, w, label, bbox=None):
    from dtf_tpu.data import records
    arr = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
    feats = {"image/encoded": _jpeg(arr),
             "image/class/label": [int(label)]}
    if bbox is not None:
        ymin, xmin, ymax, xmax = bbox
        feats.update({
            "image/object/bbox/ymin": [float(ymin)],
            "image/object/bbox/xmin": [float(xmin)],
            "image/object/bbox/ymax": [float(ymax)],
            "image/object/bbox/xmax": [float(xmax)],
        })
    return records.build_example(feats)


def _has_train_batch():
    from dtf_tpu.native import load
    lib = load()
    return lib is not None and hasattr(lib, "dtf_train_example_batch")


@pytest.mark.skipif(not native.available() or not _has_train_batch(),
                    reason="dtf_train_example_batch not built")
def test_train_example_batch_end_to_end():
    """The fully-native train path (proto parse → sample → decode)
    produces images identical to the two-step path given the crops and
    flips it reports, correct shifted labels, and in-bounds crops."""
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(31)
    dims = [(int(rng.integers(80, 140)), int(rng.integers(90, 150)))
            for _ in range(8)]
    recs = [_train_example(rng, h, w, 1 + i) for i, (h, w) in
            enumerate(dims)]
    sub = np.array([123.68, 116.78, 103.94], np.float32)
    images, labels, crops, flips, st = jpeg.train_example_batch(
        recs, seed=7, out_h=64, out_w=64, sub=sub, num_threads=2)
    assert (st == 0).all()
    np.testing.assert_array_equal(labels, np.arange(8, dtype=np.int32))
    for i, (h, w) in enumerate(dims):
        y, x, ch, cw = crops[i]
        assert 0 <= y and 0 <= x and y + ch <= h and x + cw <= w
        assert ch > 0 and cw > 0
    # identical images from the two-step op with the same crops/flips
    from dtf_tpu.data import records as rec_mod
    bufs = [rec_mod.parse_example(r)["image/encoded"][0] for r in recs]
    ref, ok = jpeg.decode_crop_resize_batch(
        bufs, [tuple(c) for c in crops], list(flips), 64, 64, sub)
    assert ok.all()
    np.testing.assert_array_equal(images, ref)
    # determinism: same seed → same everything
    images2, labels2, crops2, flips2, st2 = jpeg.train_example_batch(
        recs, seed=7, out_h=64, out_w=64, sub=sub, num_threads=1)
    np.testing.assert_array_equal(images, images2)
    np.testing.assert_array_equal(crops, crops2)
    np.testing.assert_array_equal(flips, flips2)
    # different seed → different crops somewhere
    _, _, crops3, _, _ = jpeg.train_example_batch(
        recs, seed=8, out_h=64, out_w=64, sub=sub)
    assert (np.asarray(crops3) != np.asarray(crops)).any()


@pytest.mark.skipif(not native.available() or not _has_train_batch(),
                    reason="dtf_train_example_batch not built")
def test_train_example_batch_bbox_coverage():
    """Sampled crops respect min_object_covered=0.1 against the first
    bbox (the reference sampler's constraint)."""
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(32)
    h = w = 200
    bbox = (0.4, 0.4, 0.6, 0.6)
    recs = [_train_example(rng, h, w, 5, bbox=bbox) for _ in range(16)]
    sub = np.zeros(3, np.float32)
    _, _, crops, _, st = jpeg.train_example_batch(
        recs, seed=3, out_h=32, out_w=32, sub=sub)
    assert (st == 0).all()
    by0, bx0, by1, bx1 = [v * h for v in bbox]
    box_area = (by1 - by0) * (bx1 - bx0)
    for y, x, ch, cw in np.asarray(crops):
        if (y, x, ch, cw) == (0, 0, h, w):
            continue  # whole-image fallback is always legal
        inter_h = max(0.0, min(y + ch, by1) - max(y, by0))
        inter_w = max(0.0, min(x + cw, bx1) - max(x, bx0))
        assert inter_h * inter_w / box_area >= 0.1


@pytest.mark.skipif(not native.available() or not _has_train_batch(),
                    reason="dtf_train_example_batch not built")
def test_train_example_batch_flags_bad_records():
    """Garbage records report status 1 (parse) and good neighbors
    still process; a record with a corrupt JPEG reports its crop for
    the Python re-decode."""
    from dtf_tpu.native import jpeg
    rng = np.random.default_rng(33)
    good = _train_example(rng, 100, 120, 7)
    from dtf_tpu.data import records
    bad_jpeg = records.build_example({
        "image/encoded": b"\xff\xd8 not a jpeg",
        "image/class/label": [3]})
    images, labels, crops, flips, st = jpeg.train_example_batch(
        [good, b"not a proto", bad_jpeg], seed=1, out_h=32, out_w=32,
        sub=np.zeros(3, np.float32))
    assert st[0] == 0 and np.isfinite(images[0]).all()
    assert st[1] == 1
    assert st[2] == 1  # header unreadable → python whole path
    assert labels[0] == 6


def test_tfrecord_reader_rejects_absurd_length(tmp_path):
    """A corrupt length field must raise, not abort the process."""
    path = str(tmp_path / "huge.tfrecord")
    with open(path, "wb") as f:
        f.write((1 << 62).to_bytes(8, "little") + b"\x00" * 4)
    with pytest.raises(IOError):
        list(native.read_tfrecord_file(path, verify_crc=False))
