"""ctypes binding + lazy build for the native C++ shard reader.

Builds ``data/native/shard_reader.cc`` once per checkout (g++ -O3 -shared)
into a cache directory and exposes ``NativeShard`` — an mmap-backed .npy
token shard with single-pass x/y batch assembly.  ``available()`` gates
callers; everything falls back to the numpy path when the toolchain or the
binding is missing.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

from mamba_distributed_tpu.utils.platform import CACHE_ROOT

_SRC = os.path.join(os.path.dirname(__file__), "native", "shard_reader.cc")
_lib = None
_tried = False


def _build_and_load():
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    # one fixed git-ignored directory in the checkout: a shared temp dir
    # would reuse whatever newer .so another checkout left there
    cache_dir = os.environ.get(
        "MAMBA_TPU_NATIVE_CACHE", os.path.join(CACHE_ROOT, "native")
    )
    os.makedirs(cache_dir, exist_ok=True)
    so_path = os.path.join(cache_dir, "shard_reader.so")
    try:
        if not os.path.exists(so_path) or os.path.getmtime(
            so_path
        ) < os.path.getmtime(_SRC):
            # compile to a per-process temp file and rename into place so
            # concurrent builders never dlopen a half-written .so
            tmp_path = f"{so_path}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-o", tmp_path, _SRC],
                check=True, capture_output=True, text=True,
            )
            os.replace(tmp_path, so_path)
        lib = ctypes.CDLL(so_path)
        lib.shard_open.restype = ctypes.c_void_p
        lib.shard_open.argtypes = [ctypes.c_char_p]
        lib.shard_close.argtypes = [ctypes.c_void_p]
        lib.shard_len.restype = ctypes.c_int64
        lib.shard_len.argtypes = [ctypes.c_void_p]
        lib.shard_fill_batch.restype = ctypes.c_int
        lib.shard_fill_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ]
        _lib = lib
    except Exception as e:
        import warnings

        detail = getattr(e, "stderr", "") or str(e)
        warnings.warn(f"native shard reader unavailable: {detail}")
        _lib = None
    return _lib


def available() -> bool:
    return _build_and_load() is not None


class NativeShard:
    """mmap-backed token shard; x/y assembly happens in C++."""

    def __init__(self, path: str):
        lib = _build_and_load()
        if lib is None:
            raise RuntimeError("native shard reader unavailable")
        self._lib = lib
        self._handle = lib.shard_open(path.encode())
        if not self._handle:
            raise OSError(f"cannot open npy shard: {path}")
        self.path = path

    def __len__(self) -> int:
        return int(self._lib.shard_len(self._handle))

    def fill_batch(self, pos: int, B: int, T: int):
        """tokens[pos : pos+B*T(+1)] -> x, y of shape (B, T) int32."""
        x = np.empty(B * T, np.int32)
        y = np.empty(B * T, np.int32)
        rc = self._lib.shard_fill_batch(
            self._handle, pos, B * T,
            x.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            y.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        if rc != 0:
            raise IndexError(
                f"batch window [{pos}, {pos + B * T + 1}) out of range "
                f"for shard of {len(self)} tokens"
            )
        return x.reshape(B, T), y.reshape(B, T)

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.shard_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
