"""ctypes bindings for the C++ data runtime (libdtf_native.so).

The library is a build artifact git does not carry, so the first
:func:`load` of a process builds it from ``dtf_native.cpp`` /
``ps_store.cpp`` with ``make -C dtf_tpu/native`` (a no-op when it is
up to date).  Where that build cannot run (no compiler, no
libjpeg-turbo) load() says so once and returns None: every consumer
then degrades to the pure-Python implementation, or, where it needs
the native code, fails naming the build command.  ctypes foreign calls
release the GIL, so Python worker threads get true decode parallelism.
"""

from __future__ import annotations

import ctypes
import fcntl
import logging
import os
import subprocess
from typing import Optional

log = logging.getLogger("dtf_tpu")

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libdtf_native.so")
_lib: Optional[ctypes.CDLL] = None
_build_tried = False


def _build() -> None:
    """``make -C dtf_tpu/native``, once per process.  A file lock
    serializes it across processes: input-service reader workers reach
    their first load() at the same moment."""
    global _build_tried
    if _build_tried:
        return
    _build_tried = True
    try:
        with open(os.path.join(_DIR, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            subprocess.run(["make", "-C", _DIR], check=True,
                           capture_output=True, timeout=300)
    except (OSError, subprocess.SubprocessError) as e:
        stderr = getattr(e, "stderr", None) or b""
        log.warning("native: `make -C %s` failed (%s: %s)%s", _DIR,
                    type(e).__name__, e,
                    "\n" + stderr.decode(errors="replace")[-400:]
                    if stderr else "")


def load() -> Optional[ctypes.CDLL]:
    """Returns the loaded library (built first if need be), or None
    when it cannot be built here."""
    global _lib
    if _lib is not None:
        return _lib
    _build()
    if not os.path.exists(_LIB_PATH):
        return None
    lib = ctypes.CDLL(_LIB_PATH)
    u8p = ctypes.POINTER(ctypes.c_uint8)

    # Input buffers are declared c_char_p so Python `bytes` pass
    # zero-copy (the C side is const and never writes).
    lib.dtf_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.dtf_crc32c.restype = ctypes.c_uint32

    lib.dtf_tfr_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.dtf_tfr_open.restype = ctypes.c_void_p
    lib.dtf_tfr_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(u8p)]
    lib.dtf_tfr_next.restype = ctypes.c_int64
    lib.dtf_tfr_close.argtypes = [ctypes.c_void_p]

    lib.dtf_jpeg_shape.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                   ctypes.POINTER(ctypes.c_int),
                                   ctypes.POINTER(ctypes.c_int)]
    lib.dtf_jpeg_shape.restype = ctypes.c_int
    lib.dtf_jpeg_decode_crop.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, u8p]
    lib.dtf_jpeg_decode_crop.restype = ctypes.c_int
    lib.dtf_jpeg_decode_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        ctypes.c_int, u8p, ctypes.c_int]
    lib.dtf_jpeg_decode_batch.restype = ctypes.c_int
    f32p = ctypes.POINTER(ctypes.c_float)
    # Libraries exporting dtf_wire_u8 take a void* output plus a
    # trailing out_u8 selector on the fused batch ops (the uint8
    # host→device wire); older builds keep the f32-only signatures.
    u8_wire = hasattr(lib, "dtf_wire_u8")
    outp = ctypes.c_void_p if u8_wire else f32p
    tail = [ctypes.c_int] if u8_wire else []
    lib.dtf_jpeg_decode_crop_resize_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.POINTER(ctypes.c_int), u8p, ctypes.c_int,
        ctypes.c_int, f32p, outp, u8p, ctypes.c_int, ctypes.c_int,
        ctypes.c_int] + tail
    lib.dtf_jpeg_decode_crop_resize_batch.restype = ctypes.c_int
    lib.dtf_jpeg_eval_batch.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int, f32p,
        outp, u8p, ctypes.c_int, ctypes.c_int] + tail
    lib.dtf_jpeg_eval_batch.restype = ctypes.c_int
    if hasattr(lib, "dtf_train_example_batch"):
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.dtf_train_example_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_uint64, ctypes.c_int, ctypes.c_int, f32p,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, outp, i32p, i32p,
            u8p, u8p] + tail
        lib.dtf_train_example_batch.restype = ctypes.c_int
    if hasattr(lib, "dtf_f32_to_bf16"):
        u16p = ctypes.POINTER(ctypes.c_uint16)
        lib.dtf_f32_to_bf16.argtypes = [f32p, u16p, ctypes.c_int64]
        lib.dtf_f32_to_bf16.restype = None
        lib.dtf_bf16_to_f32.argtypes = [u16p, f32p, ctypes.c_int64]
        lib.dtf_bf16_to_f32.restype = None
    _lib = lib
    return _lib


def available() -> bool:
    return load() is not None


def crc32c(data: bytes) -> int:
    lib = load()
    assert lib is not None
    return lib.dtf_crc32c(data, len(data))


def read_tfrecord_file(path: str, verify_crc: bool = False):
    """Native streaming TFRecord reader; same contract as
    records.read_tfrecord_file."""
    lib = load()
    assert lib is not None
    handle = lib.dtf_tfr_open(path.encode(), int(verify_crc))
    if not handle:
        raise IOError(f"{path}: cannot open")
    try:
        data_p = ctypes.POINTER(ctypes.c_uint8)()
        while True:
            n = lib.dtf_tfr_next(handle, ctypes.byref(data_p))
            if n == -1:
                return
            if n < 0:
                raise IOError(f"{path}: corrupt or truncated record")
            yield ctypes.string_at(data_p, n)
    finally:
        lib.dtf_tfr_close(handle)
