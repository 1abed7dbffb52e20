"""ctypes loader for the C++ host runtime (native/bigdl_tpu_native.cc) —
the TPU build's counterpart of the reference's BigDL-core JNI layer
(SURVEY §2.1): CRC32C, bf16 wire codec with compressed-domain add, and
the multithreaded image batcher.

The .so is built by ``make -C native`` (g++ is in the image), which
stamps the sha256 of the source into it.  The loader builds it on first
use when it is missing OR was built from other source (a stale .so left
in the tree is rebuilt, never loaded).  If the build fails (no
toolchain) a warning names the error and every entry point runs a numpy
implementation with identical semantics — the library is an
accelerator, never a hard dep; ``available()`` says which one runs.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

_SO_PATH = os.path.join(os.path.dirname(__file__), "libbigdl_tpu_native.so")
_SRC_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_SRC = os.path.join(_SRC_DIR, "bigdl_tpu_native.cc")


def _build() -> bool:
    try:
        subprocess.run(["make", "-B", "-C", _SRC_DIR], check=True,
                       capture_output=True, text=True, timeout=120)
        return os.path.exists(_SO_PATH)
    except (OSError, subprocess.SubprocessError) as e:
        log.warning("native runtime build failed, running the numpy "
                    "implementations: %s %s", e,
                    (getattr(e, "stderr", "") or "")[-500:])
        return False


def _matches_source() -> bool:
    """Whether the .so on disk carries the stamp of the source next to
    it.  An installed tree ships the library without the source; there
    is nothing to compare it with, and it is loaded as packaged."""
    if not os.path.exists(_SO_PATH):
        return False
    if not os.path.exists(_SRC):
        return True
    with open(_SRC, "rb") as f:
        tag = b"btpu-source-sha256:" + hashlib.sha256(
            f.read()).hexdigest().encode()
    with open(_SO_PATH, "rb") as f:
        return tag in f.read()


def _load() -> Optional[ctypes.CDLL]:
    if not _matches_source() and not _build():
        return None
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError as e:
        log.warning("could not load %s: %s", _SO_PATH, e)
        return None
    lib.btpu_crc32c.restype = ctypes.c_uint32
    lib.btpu_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                ctypes.c_uint32]
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    u16p = np.ctypeslib.ndpointer(np.uint16, flags="C_CONTIGUOUS")
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    lib.btpu_f32_to_bf16.argtypes = [f32p, u16p, ctypes.c_int64]
    lib.btpu_bf16_to_f32.argtypes = [u16p, f32p, ctypes.c_int64]
    lib.btpu_bf16_add.argtypes = [u16p, u16p, ctypes.c_int64]
    lib.btpu_batch_images_u8.argtypes = [
        u8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        f32p, f32p, f32p]
    lib.btpu_batch_images_f32.argtypes = [
        f32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        f32p, f32p, f32p]
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    lib.btpu_parse_records.restype = ctypes.c_int64
    lib.btpu_parse_records.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, i64p, i64p, ctypes.c_int64,
        ctypes.c_int]
    lib.btpu_num_threads.restype = ctypes.c_int
    return lib


_lib = None
_load_attempted = False


def _get_lib() -> Optional[ctypes.CDLL]:
    """Lazy load on first use — import of the package must not spawn a
    compiler subprocess or block on disk."""
    global _lib, _load_attempted
    if not _load_attempted:
        _load_attempted = True
        _lib = _load()
    return _lib


def available() -> bool:
    """reference MKL.isMKLLoaded analogue (tensor/Tensor.scala:689)."""
    return _get_lib() is not None


def num_threads() -> int:
    lib = _get_lib()
    return lib.btpu_num_threads() if lib else 1


# ---------------------------------------------------------------------------
# CRC32C
# ---------------------------------------------------------------------------

def crc32c(data: bytes, crc: int = 0) -> int:
    lib = _get_lib()
    if lib is not None:
        if not isinstance(data, (bytes, bytearray)):
            # ctypes c_char_p takes bytes only; memoryview callers (the
            # zero-copy record walk) pay one slice-local copy here
            data = bytes(data)
        return lib.btpu_crc32c(data, len(data), crc)
    from ..visualization.crc32c import crc32c as py_crc

    return py_crc(data, crc)


# ---------------------------------------------------------------------------
# bf16 wire codec (FP16CompressedTensor parity, reference
# parameters/FP16CompressedTensor.scala — fp32 truncated to its high two
# bytes IS the bf16 bit pattern; native TPU dtype, SURVEY §2.1)
# ---------------------------------------------------------------------------

def f32_to_bf16(src: np.ndarray) -> np.ndarray:
    src = np.ascontiguousarray(src, np.float32)
    out = np.empty(src.size, np.uint16)
    lib = _get_lib()
    if lib is not None:
        lib.btpu_f32_to_bf16(src.ravel(), out, src.size)
    else:
        bits = src.ravel().view(np.uint32).astype(np.uint64)
        rounding = 0x7FFF + ((bits >> 16) & 1)
        trunc = ((bits + rounding) >> 16).astype(np.uint32)
        nan = (bits & 0x7F800000 == 0x7F800000) & (bits & 0x007FFFFF != 0)
        out[:] = np.where(nan, (bits >> 16) | 0x0040,
                          trunc).astype(np.uint16)
    return out.reshape(src.shape)


def bf16_to_f32(src: np.ndarray) -> np.ndarray:
    src = np.ascontiguousarray(src, np.uint16)
    out = np.empty(src.size, np.float32)
    lib = _get_lib()
    if lib is not None:
        lib.btpu_bf16_to_f32(src.ravel(), out, src.size)
    else:
        out[:] = (src.ravel().astype(np.uint32) << 16).view(np.float32)
    return out.reshape(src.shape)


def bf16_add(dst: np.ndarray, src: np.ndarray) -> np.ndarray:
    """dst += src in the compressed domain (parAdd parity).  Mutates and
    returns ``dst``."""
    assert dst.dtype == np.uint16 and src.dtype == np.uint16
    assert dst.size == src.size
    lib = _get_lib()
    if lib is not None and dst.flags.c_contiguous:
        lib.btpu_bf16_add(dst, np.ascontiguousarray(src).ravel(), dst.size)
    else:
        s = bf16_to_f32(dst) + bf16_to_f32(src)
        dst[...] = f32_to_bf16(s)
    return dst


# ---------------------------------------------------------------------------
# record-file framing scan (ingest hot loop)
# ---------------------------------------------------------------------------

def parse_records(buf, verify: bool = True):
    """Scan a TFRecord-framed buffer → list of (offset, length) payload
    spans, CRC-verified natively.  ``buf`` may be bytes OR any readable
    buffer (memoryview over an mmap — the zero-copy ingest path).
    Returns None when the native library is unavailable (caller falls
    back to the python scanner); raises IOError on corruption."""
    lib = _get_lib()
    if lib is None:
        return None
    cap = max(1, len(buf) // 16)
    offsets = np.empty(cap, np.int64)
    lengths = np.empty(cap, np.int64)
    if isinstance(buf, bytes):
        ptr = buf
    else:
        arr = np.frombuffer(buf, np.uint8)
        ptr = ctypes.cast(arr.ctypes.data_as(ctypes.c_void_p),
                          ctypes.c_char_p)
    n = lib.btpu_parse_records(ptr, len(buf), offsets, lengths, cap,
                               1 if verify else 0)
    if n < 0:
        raise IOError(f"corrupt record at byte {-n - 1}")
    return list(zip(offsets[:n].tolist(), lengths[:n].tolist()))


# ---------------------------------------------------------------------------
# multithreaded batch assembly (MTLabeledBGRImgToBatch parity)
# ---------------------------------------------------------------------------

def batch_images(images: np.ndarray, mean, std) -> np.ndarray:
    """(N, H, W, C) uint8/float HWC images -> normalized (N, C, H, W)
    float32 batch, assembled across the native thread pool."""
    n, h, w, c = images.shape
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    assert mean.size == c and std.size == c
    out = np.empty(n * c * h * w, np.float32)
    lib = _get_lib()
    if lib is not None and images.dtype == np.uint8:
        lib.btpu_batch_images_u8(np.ascontiguousarray(images).reshape(-1),
                                 n, h, w, c, mean, std, out)
    elif lib is not None:
        lib.btpu_batch_images_f32(
            np.ascontiguousarray(images, np.float32).reshape(-1),
            n, h, w, c, mean, std, out)
    else:
        normed = (images.astype(np.float32) - mean) / std
        out[:] = np.transpose(normed, (0, 3, 1, 2)).ravel()
    return out.reshape(n, c, h, w)
