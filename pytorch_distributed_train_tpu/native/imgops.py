"""ctypes bindings for the native image-augment kernels (native/imgops.cpp).

Replaces the Python per-image crop/flip loop and uint8→float32 math in the
input pipeline (SURVEY C17 / §7.4 hard part #1 — host-side throughput).
``available()`` gates use: callers fall back to the numpy path when the
toolchain is missing, so the pipeline never hard-depends on the build.
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

            lib = ctypes.CDLL(build_library("imgops"))
            u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
            f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
            i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
            lib.imgops_augment_batch.argtypes = [
                u8p, f32p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, ctypes.c_int, i32p, i32p, u8p, f32p, f32p,
                ctypes.c_int]
            lib.imgops_normalize_batch.argtypes = [
                u8p, f32p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, f32p, f32p, ctypes.c_int]
            _LIB = lib
        except (RuntimeError, OSError) as e:
            # Said once per process: a fallback nobody hears about reads
            # as a slow input pipeline later.
            import sys

            print(f"[native] imgops unavailable — using the numpy path: "
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


def augment_batch(images: np.ndarray, pad: int, ys: np.ndarray, xs: np.ndarray,
                  flips: np.ndarray, mean: np.ndarray, std: np.ndarray,
                  nthreads: int = 0) -> np.ndarray:
    """Fused reflect-pad random crop + hflip + normalize.

    images: (B,H,W,C) uint8; ys/xs: (B,) offsets in [0, 2*pad];
    flips: (B,) bool. Returns (B,H,W,C) float32 = (x/255 - mean)/std.
    """
    B, H, W, C = images.shape
    out = np.empty((B, H, W, C), np.float32)
    _lib().imgops_augment_batch(
        np.ascontiguousarray(images), out, B, H, W, C, pad,
        np.ascontiguousarray(ys, np.int32),
        np.ascontiguousarray(xs, np.int32),
        np.ascontiguousarray(flips, np.uint8),
        np.ascontiguousarray(mean, np.float32),
        np.ascontiguousarray(std, np.float32),
        nthreads or default_threads(),
    )
    return out


def normalize_batch(images: np.ndarray, mean: np.ndarray, std: np.ndarray,
                    nthreads: int = 0) -> np.ndarray:
    """(B,H,W,C) uint8 → normalized float32."""
    B, H, W, C = images.shape
    out = np.empty((B, H, W, C), np.float32)
    _lib().imgops_normalize_batch(
        np.ascontiguousarray(images), out, B, H, W, C,
        np.ascontiguousarray(mean, np.float32),
        np.ascontiguousarray(std, np.float32),
        nthreads or default_threads(),
    )
    return out
