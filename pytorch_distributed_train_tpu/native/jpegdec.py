"""ctypes bindings for the native JPEG batch decoder (native/jpegdec.cpp).

The decode arm of the input pipeline's native fast path (SURVEY C17 /
§7.4 hard part #1): Python reads raw JPEG bytes out of the tar shard and
owns the augmentation policy (crop boxes from its rng); the C++ side does
header parse, IDCT-scaled decode, crop-box bilinear resize, flip, and the
fused uint8→float32 normalize across a std::thread pool — no GIL.

``available()`` gates use: the build needs jpeglib.h + libjpeg; callers
fall back to the PIL per-item path when it's missing.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

_LIB = None
_TRIED = False


def _lib():
    global _LIB, _TRIED
    if _LIB is None and not _TRIED:
        _TRIED = True
        try:
            from pytorch_distributed_train_tpu.native import build_library

            lib = ctypes.CDLL(build_library("jpegdec", extra_libs=("-ljpeg",)))
            u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
            f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
            i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
            lib.jpegdec_dims.argtypes = [
                u8p, i64p, i64p, ctypes.c_int, i32p, ctypes.c_int]
            lib.jpegdec_dims.restype = ctypes.c_int
            lib.jpegdec_decode_batch.argtypes = [
                u8p, i64p, i64p, ctypes.c_int, f32p, u8p, ctypes.c_int,
                f32p, f32p, f32p, ctypes.c_int]
            lib.jpegdec_decode_batch.restype = ctypes.c_int
            _LIB = lib
        except (RuntimeError, OSError) as e:
            # Said once per process: a fallback nobody hears about reads
            # as a slow input pipeline later.
            import sys

            print(f"[native] jpegdec unavailable — using the PIL path: "
                  f"{str(e).splitlines()[0] if str(e) else type(e).__name__}",
                  file=sys.stderr, flush=True)
            _LIB = None
    return _LIB


def available() -> bool:
    return _lib() is not None


def default_threads() -> int:
    # PDTT_NATIVE_THREADS: per-process C++ thread budget — set by the
    # shared-memory decode pool (data/workers.py) so N worker processes
    # x the solo default can't oversubscribe the host.
    env = os.environ.get("PDTT_NATIVE_THREADS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return max(1, min(8, (os.cpu_count() or 1) // 2))


def _as_blob(blobs: list[bytes]):
    """Concatenate per-image byte strings → (blob, offsets, sizes)."""
    sizes = np.asarray([len(b) for b in blobs], np.int64)
    offs = np.zeros(len(blobs), np.int64)
    np.cumsum(sizes[:-1], out=offs[1:]) if len(blobs) > 1 else None
    blob = np.frombuffer(b"".join(blobs), np.uint8)
    return np.ascontiguousarray(blob), offs, sizes


def dims(blobs: list[bytes], nthreads: int = 0) -> np.ndarray:
    """(B, 2) int32 [width, height] per JPEG; [0, 0] on a corrupt header."""
    lib = _lib()
    assert lib is not None, "jpegdec library unavailable"
    blob, offs, sizes = _as_blob(blobs)
    out = np.zeros((len(blobs), 2), np.int32)
    lib.jpegdec_dims(blob, offs, sizes, len(blobs), out.reshape(-1),
                     nthreads or default_threads())
    return out


def decode_batch(blobs: list[bytes], boxes: np.ndarray, flips: np.ndarray,
                 size: int, mean: np.ndarray, std: np.ndarray,
                 nthreads: int = 0) -> tuple[np.ndarray, int]:
    """Decode + crop-resize + normalize a batch of JPEGs.

    boxes: (B, 4) float32 (x0, y0, w, h) in original pixel coords;
    flips: (B,) bool. Returns ((B, size, size, 3) float32, n_failures) —
    failed images are zeroed, matching the C side's poison-tolerance.
    """
    lib = _lib()
    assert lib is not None, "jpegdec library unavailable"
    blob, offs, sizes = _as_blob(blobs)
    boxes = np.ascontiguousarray(boxes, np.float32)
    flips_u8 = np.ascontiguousarray(flips, np.uint8)
    out = np.empty((len(blobs), size, size, 3), np.float32)
    fails = lib.jpegdec_decode_batch(
        blob, offs, sizes, len(blobs), boxes.reshape(-1), flips_u8, size,
        np.ascontiguousarray(mean, np.float32),
        np.ascontiguousarray(std, np.float32),
        out.reshape(-1), nthreads or default_threads())
    return out, int(fails)
