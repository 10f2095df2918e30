"""ctypes binding + lazy build for the native BPE merge loop.

Same pattern as data/native.py (the shard reader): build
``data/native/bpe_merge.cc`` once per checkout into a cache dir, gate on
``available()``, fall back to the pure-Python merge when the toolchain
is missing or ``MDT_NATIVE_BPE=0``.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

from mamba_distributed_tpu.utils.platform import CACHE_ROOT

_SRC = os.path.join(os.path.dirname(__file__), "native", "bpe_merge.cc")
_lib = None
_tried = False


def _build_and_load():
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("MDT_NATIVE_BPE") == "0":
        return None
    # one fixed git-ignored directory in the checkout: a shared temp dir
    # would reuse whatever newer .so another checkout left there
    cache_dir = os.environ.get(
        "MAMBA_TPU_NATIVE_CACHE", os.path.join(CACHE_ROOT, "native")
    )
    os.makedirs(cache_dir, exist_ok=True)
    so_path = os.path.join(cache_dir, "bpe_merge.so")
    try:
        if not os.path.exists(so_path) or os.path.getmtime(
            so_path
        ) < os.path.getmtime(_SRC):
            tmp_path = f"{so_path}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-o", tmp_path, _SRC],
                check=True, capture_output=True, text=True,
            )
            os.replace(tmp_path, so_path)
        lib = ctypes.CDLL(so_path)
        lib.bpe_table_new.restype = ctypes.c_void_p
        lib.bpe_table_new.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
        ]
        lib.bpe_table_free.argtypes = [ctypes.c_void_p]
        lib.bpe_apply.restype = ctypes.c_int32
        lib.bpe_apply.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
        ]
        lib.bpe_apply_spans.restype = ctypes.c_int32
        lib.bpe_apply_spans.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
        ]
        _lib = lib
    except Exception as e:
        import warnings

        detail = getattr(e, "stderr", "") or str(e)
        warnings.warn(f"native BPE unavailable: {detail}")
        _lib = None
    return _lib


def available() -> bool:
    return _build_and_load() is not None


class NativeBpeTable:
    """Owns a C-side (a, b) -> (rank, merged) table."""

    def __init__(self, triples: list[tuple[int, int, int]]):
        lib = _build_and_load()
        if lib is None:
            raise RuntimeError("native BPE unavailable")
        self._lib = lib
        n = len(triples)
        Arr = ctypes.c_int32 * n
        a = Arr(*(t[0] for t in triples))
        b = Arr(*(t[1] for t in triples))
        c = Arr(*(t[2] for t in triples))
        self._handle = lib.bpe_table_new(a, b, c, n)

    def apply(self, ids: list[int]) -> list[int]:
        n = len(ids)
        buf = (ctypes.c_int32 * n)(*ids)
        out_n = self._lib.bpe_apply(self._handle, buf, n)
        return buf[:out_n]

    def apply_spans(self, flat: list[int], offsets: list[int]):
        """Merge many concatenated spans in ONE native call.

        flat = span0 + span1 + ...; offsets has len(spans)+1 entries.
        Returns (per-span merged lengths, compacted merged ids).
        """
        n_spans = len(offsets) - 1
        buf = (ctypes.c_int32 * len(flat))(*flat)
        offs = (ctypes.c_int32 * len(offsets))(*offsets)
        lens = (ctypes.c_int32 * n_spans)()
        total = self._lib.bpe_apply_spans(self._handle, buf, offs, n_spans, lens)
        return lens[:n_spans], buf[:total]

    def __del__(self):
        lib = getattr(self, "_lib", None)
        handle = getattr(self, "_handle", None)
        if lib is not None and handle:
            lib.bpe_table_free(handle)
