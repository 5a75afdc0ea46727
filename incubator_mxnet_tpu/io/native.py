"""ctypes bridge to the native C++ image-record pipeline
(src/io/recordio_pipeline.cc — the ImageRecordIOParser2 equivalent).

The shared library is compiled on first use (g++ is part of the
toolchain; libjpeg is the system decoder) and cached next to the source.
`NativeImageRecordReader` hands out (data, label) float32 numpy batches;
ImageRecordIter wraps it with the prefetch thread + device_put."""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as _np

__all__ = ["available", "NativeImageRecordReader", "build_library"]

_LOCK = threading.Lock()
_LIB = None
_TRIED = False

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src", "io",
    "recordio_pipeline.cc")
_SO = os.path.join(os.path.dirname(_SRC), "libmxtpu_io.so")


def build_library(force=False, src=None, out=None):
    """Compile the pipeline .so (idempotent; also the ONE compile
    recipe setup.py's wheel build calls — keep flags here).  No
    -march=native: the mtime check trusts a library built on another
    host (a copied tree, a wheel), so the build must run on any CPU."""
    src = src or _SRC
    out = out or _SO
    if os.path.exists(out) and not force and \
            os.path.getmtime(out) >= os.path.getmtime(src):
        return out
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread",
           src, "-ljpeg", "-o", out]
    subprocess.run(cmd, check=True, capture_output=True)
    return out


_PACKAGED_SO = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "libmxtpu_io.so")


def _load():
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        # wheel installs ship the prebuilt library as package data
        # (setup.py); in a dev checkout the mtime-checked compile from
        # src/io wins so C++ edits always take effect, and a stale or
        # wrong-arch packaged copy falls back to compiling
        candidates = []
        if os.path.exists(_PACKAGED_SO) and (
                not os.path.exists(_SRC) or
                os.path.getmtime(_PACKAGED_SO) >=
                os.path.getmtime(_SRC)):
            candidates.append(lambda: _PACKAGED_SO)
        if os.path.exists(_SRC):
            candidates.append(build_library)
        lib = None
        for get_so in candidates:
            try:
                lib = ctypes.CDLL(get_so())
                break
            except (OSError, subprocess.CalledProcessError):
                continue
        if lib is None:
            return None
        lib.mxio_create.restype = ctypes.c_void_p
        lib.mxio_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_uint64, ctypes.c_int]
        lib.mxio_num_records.restype = ctypes.c_int64
        lib.mxio_num_records.argtypes = [ctypes.c_void_p]
        lib.mxio_next.restype = ctypes.c_int
        lib.mxio_next.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_float),
                                  ctypes.POINTER(ctypes.c_float)]
        lib.mxio_next_u8.restype = ctypes.c_int
        lib.mxio_next_u8.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_uint8),
                                     ctypes.POINTER(ctypes.c_float)]
        lib.mxio_reset.argtypes = [ctypes.c_void_p]
        lib.mxio_destroy.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return _LIB


def available():
    return _load() is not None


class NativeImageRecordReader:
    """Batch iterator over a .rec file, decoded/augmented in C++ threads.

    Yields (data, label) float32 arrays; data layout NCHW (default) or
    NHWC, already mean/std-normalized."""

    def __init__(self, rec_path, batch_size, data_shape, resize=0,
                 rand_crop=False, rand_mirror=False, shuffle=False,
                 label_width=1, layout="NCHW", mean=None, std=None,
                 seed=0, num_threads=None, dtype="float32"):
        lib = _load()
        if lib is None:
            raise RuntimeError("native io library unavailable")
        self._lib = lib
        if dtype not in ("float32", "uint8"):
            raise ValueError("dtype must be float32 or uint8")
        # uint8: raw augmented pixels, NO mean/std (normalize on the
        # accelerator) — 4x fewer host->device bytes
        self._u8 = dtype == "uint8"
        if self._u8 and (mean or std):
            raise ValueError("uint8 output skips normalization; "
                             "apply mean/std on device")
        if len(data_shape) != 3 or data_shape[0] != 3:
            raise ValueError("data_shape must be (3, H, W)")
        _, h, w = data_shape
        self._batch = batch_size
        self._h, self._w = h, w
        self._label_width = label_width
        self._nchw = layout == "NCHW"
        mean_arr = (ctypes.c_float * 3)(*(mean or (0.0, 0.0, 0.0)))
        std_arr = (ctypes.c_float * 3)(*(std or (1.0, 1.0, 1.0)))
        nthreads = num_threads or min(os.cpu_count() or 8, 16)
        self._h_ptr = lib.mxio_create(
            rec_path.encode(), batch_size, h, w, resize,
            int(rand_crop), int(rand_mirror), int(shuffle),
            label_width, int(self._nchw), mean_arr, std_arr,
            seed, nthreads)
        if not self._h_ptr:
            raise IOError("cannot open record file %r" % rec_path)

    @property
    def num_records(self):
        return self._lib.mxio_num_records(self._h_ptr)

    def reset(self):
        self._lib.mxio_reset(self._h_ptr)

    def next_batch(self):
        """Returns (data, label) with the actual sample count, or None at
        epoch end. Fresh buffers per batch — safe to hand to device_put."""
        from .. import fault
        fault.maybe_slow("io.slow")
        fault.maybe_raise("io.read", exc_type=fault.InjectedIOError)
        shape = ((self._batch, 3, self._h, self._w) if self._nchw
                 else (self._batch, self._h, self._w, 3))
        label = _np.empty((self._batch, self._label_width), _np.float32)
        if self._u8:
            data = _np.empty(shape, _np.uint8)
            n = self._lib.mxio_next_u8(
                self._h_ptr,
                data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                label.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        else:
            data = _np.empty(shape, _np.float32)
            n = self._lib.mxio_next(
                self._h_ptr,
                data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                label.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        if n == 0:
            return None
        if n < self._batch:
            data = data[:n]
            label = label[:n]
        return data, label

    def __iter__(self):
        while True:
            b = self.next_batch()
            if b is None:
                return
            yield b

    def close(self):
        """Release the native reader handle (idempotent)."""
        if getattr(self, "_h_ptr", None):
            self._lib.mxio_destroy(self._h_ptr)
            self._h_ptr = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
