"""JPEG decode via the native library (libjpeg-turbo).

`decode` and `decode_crop` mirror tf.image.decode_jpeg /
decode_and_crop_jpeg (the fused op the reference leans on,
imagenet_preprocessing.py:363-368).  ctypes calls release the GIL, so
calling these from Python worker threads scales across cores.
"""

from __future__ import annotations

import ctypes

import numpy as np

from dtf_tpu.native import load


def _lib():
    lib = load()
    if lib is None:
        raise ImportError("libdtf_native.so not built; run "
                          "`make -C dtf_tpu/native`")
    return lib


def shape(buf: bytes):
    """(height, width) from the JPEG header only."""
    lib = _lib()
    h = ctypes.c_int()
    w = ctypes.c_int()
    if lib.dtf_jpeg_shape(buf, len(buf), ctypes.byref(h), ctypes.byref(w)):
        raise ValueError("invalid JPEG")
    return h.value, w.value


def decode_crop(buf: bytes, y: int, x: int, ch: int, cw: int) -> np.ndarray:
    """Fused decode-and-crop → RGB uint8 [ch, cw, 3]."""
    lib = _lib()
    out = np.empty((ch, cw, 3), np.uint8)
    rc = lib.dtf_jpeg_decode_crop(
        buf, len(buf), y, x, ch, cw,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if rc:
        raise ValueError(f"JPEG decode failed (rc={rc})")
    return out


def decode(buf: bytes) -> np.ndarray:
    """Full-image RGB uint8 decode."""
    h, w = shape(buf)
    return decode_crop(buf, 0, 0, h, w)


def decode_batch(bufs, crops, ch: int, cw: int,
                 num_threads: int = 4) -> np.ndarray:
    """Decode-and-crop ``len(bufs)`` JPEGs in parallel C++ threads.

    ``crops``: sequence of (y, x, h, w) per image, with h == ch and
    w == cw (one fixed output geometry per batch — the training path's
    shape anyway).  Returns uint8 [n, ch, cw, 3]; raises on any failed
    image.
    """
    lib = _lib()
    n = len(bufs)
    out = np.empty((n, ch, cw, 3), np.uint8)
    buf_ptrs = (ctypes.c_char_p * n)(*bufs)
    lens = (ctypes.c_int64 * n)(*[len(b) for b in bufs])
    crop_arr = (ctypes.c_int * (4 * n))(
        *[int(v) for c in crops for v in c])
    failures = lib.dtf_jpeg_decode_batch(
        buf_ptrs, lens, n, crop_arr, ch, cw,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), num_threads)
    if failures:
        raise ValueError(f"{failures}/{n} JPEGs failed to decode")
    return out


def _out_ptr(lib, out):
    """Output pointer matching the declared argtype: void* on u8-wire
    libraries, float* on older builds."""
    if hasattr(lib, "dtf_wire_u8"):
        return out.ctypes.data_as(ctypes.c_void_p)
    return out.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _u8_tail(lib, out_u8: bool):
    """Trailing out_u8 argument — only on libraries whose signature has
    it (callers already raised if out_u8 was requested without it)."""
    return (int(out_u8),) if hasattr(lib, "dtf_wire_u8") else ()


def wire_u8_supported() -> bool:
    """True when the built library supports the uint8 output wire
    (the trailing ``out_u8`` parameter on the fused batch ops).  A
    stale .so without the marker symbol degrades to the float32 wire."""
    lib = load()
    return lib is not None and hasattr(lib, "dtf_wire_u8")


def decode_crop_resize_batch(bufs, crops, flips, out_h: int, out_w: int,
                             sub, num_threads: int = 4,
                             scaled_decode: bool = False,
                             out_u8: bool = False):
    """The whole train-time augmentation for a batch in one C++ call:
    fused decode-and-crop (per-image variable windows) → horizontal
    flip → bilinear resize (half-pixel centers, tf.image.resize v2
    semantics) → channel-mean subtraction, across ``num_threads``
    GIL-free threads.

    The decode is libjpeg's exact JDCT_ISLOW: the C entry points keep a
    ``fast_dct`` argument in their ABI and are passed 0.

    ``scaled_decode``: crops >=2x the output are decoded at the
    smallest N/8 resolution (libjpeg-turbo DCT-space scaling, N<=4)
    that keeps the scaled crop >= the output — a 460px crop bound for
    224 decodes at half resolution.  Measured win is 10-30% on such
    crops (entropy decode, which scaling cannot skip, bounds it);
    N=5..7 scales measured slower than the full decode (no SIMD for
    the odd reduced IDCT sizes) and are never used.  Changes the
    downsampling filter chain, not the crop geometry; a throughput
    opt-in for large-image datasets, never a default.

    ``out_u8``: uint8 output wire — pixels round-half-up post-resize,
    NO mean subtraction (normalization moves into the compiled step on
    the accelerator; 4x fewer host→device bytes).  Requires a library
    with :func:`wire_u8_supported`.

    Returns (float32|uint8 [n, out_h, out_w, 3], ok mask bool [n]);
    failed images (rare decoder edge cases) have ok=False and undefined
    content — the caller re-decodes them however it likes.
    """
    lib = _lib()
    if out_u8 and not hasattr(lib, "dtf_wire_u8"):
        raise ImportError("libdtf_native.so predates the uint8 wire; "
                          "rebuild (make -C dtf_tpu/native)")
    n = len(bufs)
    out = np.empty((n, out_h, out_w, 3),
                   np.uint8 if out_u8 else np.float32)
    statuses = np.empty((n,), np.uint8)
    buf_ptrs = (ctypes.c_char_p * n)(*bufs)
    lens = (ctypes.c_int64 * n)(*[len(b) for b in bufs])
    crop_arr = (ctypes.c_int * (4 * n))(
        *[int(v) for c in crops for v in c])
    flip_arr = np.ascontiguousarray(np.asarray(flips, np.uint8))
    sub_arr = np.ascontiguousarray(np.asarray(sub, np.float32))
    lib.dtf_jpeg_decode_crop_resize_batch(
        buf_ptrs, lens, n, crop_arr,
        flip_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out_h, out_w,
        sub_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        _out_ptr(lib, out),
        statuses.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        num_threads, 0, int(scaled_decode), *_u8_tail(lib, out_u8))
    return out, statuses == 0


def train_example_batch(records, seed: int, out_h: int, out_w: int, sub,
                        num_threads: int = 4,
                        scaled_decode: bool = False,
                        out_u8: bool = False):
    """The whole train path for a batch of raw tf.train.Example
    records in one C++ call: proto parse (image/encoded, label, first
    bbox) → JPEG header → distorted-bbox sampling (reference
    constants; splitmix64 per-image streams seeded by ``seed``) →
    flip → fused decode-crop-resize-mean-subtract.  This is the
    formerly GIL-held per-record Python work (the input pipeline's
    measured Amdahl serial fraction), off the interpreter.

    ``out_u8``: uint8 output wire (see
    :func:`decode_crop_resize_batch`).

    Returns (images f32|u8 [n,oh,ow,3], labels i32 [n] (shifted to
    [0,1000)), crops i32 [n,4], flips u8 [n], statuses u8 [n]):
    status 0 ok; 1 parse/header failure (reprocess the record in
    Python); 2 decode failure (re-decode with the returned crop/flip
    so the augmentation stays identical).
    """
    lib = _lib()
    if not hasattr(lib, "dtf_train_example_batch"):
        raise ImportError("libdtf_native.so predates "
                          "dtf_train_example_batch; rebuild")
    if out_u8 and not hasattr(lib, "dtf_wire_u8"):
        raise ImportError("libdtf_native.so predates the uint8 wire; "
                          "rebuild (make -C dtf_tpu/native)")
    n = len(records)
    out = np.empty((n, out_h, out_w, 3),
                   np.uint8 if out_u8 else np.float32)
    labels = np.empty((n,), np.int32)
    crops = np.empty((n, 4), np.int32)
    flips = np.empty((n,), np.uint8)
    statuses = np.empty((n,), np.uint8)
    rec_ptrs = (ctypes.c_char_p * n)(*records)
    lens = (ctypes.c_int64 * n)(*[len(r) for r in records])
    sub_arr = np.ascontiguousarray(np.asarray(sub, np.float32))
    lib.dtf_train_example_batch(
        rec_ptrs, lens, n, ctypes.c_uint64(seed & (2**64 - 1)),
        out_h, out_w,
        sub_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        0, int(scaled_decode), num_threads,
        _out_ptr(lib, out),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        crops.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        flips.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        statuses.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        *_u8_tail(lib, out_u8))
    return out, labels, crops, flips, statuses


def eval_batch(bufs, resize_min: int, out_h: int, out_w: int, sub,
               num_threads: int = 4, out_u8: bool = False):
    """Fused eval preprocessing for a batch: aspect-preserving resize to
    shorter-side ``resize_min`` + central [out_h, out_w] crop +
    channel-mean subtraction in one sampling pass over a decode window
    (only the needed source rows/cols are decoded).  tf-bilinear
    numerics — the reference's eval path
    (imagenet_preprocessing.py:375-394,464-480).

    ``out_u8``: uint8 output wire (see
    :func:`decode_crop_resize_batch`).

    Returns (float32|uint8 [n, out_h, out_w, 3], ok mask bool [n]).
    """
    lib = _lib()
    if out_u8 and not hasattr(lib, "dtf_wire_u8"):
        raise ImportError("libdtf_native.so predates the uint8 wire; "
                          "rebuild (make -C dtf_tpu/native)")
    n = len(bufs)
    out = np.empty((n, out_h, out_w, 3),
                   np.uint8 if out_u8 else np.float32)
    statuses = np.empty((n,), np.uint8)
    buf_ptrs = (ctypes.c_char_p * n)(*bufs)
    lens = (ctypes.c_int64 * n)(*[len(b) for b in bufs])
    sub_arr = np.ascontiguousarray(np.asarray(sub, np.float32))
    lib.dtf_jpeg_eval_batch(
        buf_ptrs, lens, n, resize_min, out_h, out_w,
        sub_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        _out_ptr(lib, out),
        statuses.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        num_threads, 0, *_u8_tail(lib, out_u8))
    return out, statuses == 0
