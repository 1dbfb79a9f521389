"""ctypes bridge to the native safetensors reader (native/loader/).

``open_native_safetensors(dir)`` returns the same name->lazy-loader dict
shape as the pure-Python path in weights.py, backed by libstload.so's
mmap + madvise + parallel-copy reads. Falls back to None when the shared
library is absent or unloadable (the Python ``safetensors`` package then
handles loading) — the native path is an accelerator, not a requirement.

Set LLMK_NATIVE_LOADER=0 to force the Python path.
"""

from __future__ import annotations

import ctypes
import os
import pathlib
from typing import Callable, Optional

import numpy as np

_DTYPES = {
    "F64": np.float64, "F32": np.float32, "F16": np.float16,
    "I64": np.int64, "I32": np.int32, "I16": np.int16, "I8": np.int8,
    "U8": np.uint8, "BOOL": np.bool_,
    "U64": np.uint64, "U32": np.uint32, "U16": np.uint16,
}


class UnsupportedDTypeError(ValueError):
    """A safetensors dtype this bridge cannot map to numpy; callers fall
    back to the Python safetensors reader for that tensor (round-2 review
    finding: an FP8 checkpoint previously crashed with a raw KeyError)."""


def _np_dtype(dtype_s: str) -> Optional[np.dtype]:
    if dtype_s in _DTYPES:
        return np.dtype(_DTYPES[dtype_s])
    import ml_dtypes  # ships with jax

    ext = {
        "BF16": ml_dtypes.bfloat16,
        # compressed-tensors FP8 checkpoints (the reference's default
        # gemma-3-27b-it-FP8-Dynamic, reference values.yaml:3)
        "F8_E4M3": ml_dtypes.float8_e4m3fn,
        "F8_E5M2": ml_dtypes.float8_e5m2,
    }
    if dtype_s in ext:
        return np.dtype(ext[dtype_s])
    return None


_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _find_lib() -> Optional[str]:
    """LLMK_NATIVE_LOADER_PATH (the image's prebuilt library), else the
    checkout's native/loader/libstload.so — BUILT from the tracked source
    first (``make`` is a no-op when it is fresh): a git-ignored binary
    found lying in the tree says nothing about the source beside it, and
    a failed build is an error, not a reason to read another way. None
    only outside a checkout (an installed package without native/)."""
    override = os.environ.get("LLMK_NATIVE_LOADER_PATH")
    if override:
        return override if os.path.exists(override) else None
    src = pathlib.Path(__file__).resolve().parents[2] / "native" / "loader"
    if not (src / "Makefile").exists():
        return None
    import subprocess

    r = subprocess.run(["make", "-C", str(src)], capture_output=True,
                       text=True)
    if r.returncode != 0:
        raise RuntimeError(
            f"building native/loader failed:\n{r.stderr[-2000:]}")
    return str(src / "libstload.so")


def _load_lib() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    if os.environ.get("LLMK_NATIVE_LOADER", "1") == "0":
        return None
    path = _find_lib()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    lib.stl_open.restype = ctypes.c_void_p
    lib.stl_open.argtypes = [ctypes.c_char_p]
    lib.stl_error.restype = ctypes.c_char_p
    lib.stl_count.restype = ctypes.c_int64
    lib.stl_count.argtypes = [ctypes.c_void_p]
    lib.stl_name.restype = ctypes.c_char_p
    lib.stl_name.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.stl_info.restype = ctypes.c_int64
    lib.stl_info.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                             ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
                             ctypes.POINTER(ctypes.c_int64)]
    lib.stl_read.restype = ctypes.c_int
    lib.stl_read.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                             ctypes.c_void_p, ctypes.c_int64]
    lib.stl_close.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


class _NativeShards:
    """Owns the native handle; loaders close over it (kept alive by refs)."""

    def __init__(self, lib: ctypes.CDLL, handle: int):
        self._lib = lib
        self._handle = handle

    def __del__(self):
        try:
            self._lib.stl_close(self._handle)
        except Exception:
            pass

    def names(self) -> list[str]:
        n = self._lib.stl_count(self._handle)
        return [self._lib.stl_name(self._handle, i).decode()
                for i in range(n)]

    def read(self, name: str) -> np.ndarray:
        dtype_buf = ctypes.create_string_buffer(16)
        shape = (ctypes.c_int64 * 8)()
        nbytes = ctypes.c_int64()
        ndim = self._lib.stl_info(self._handle, name.encode(), dtype_buf,
                                  shape, ctypes.byref(nbytes))
        if ndim < 0:
            raise KeyError(self._lib.stl_error().decode())
        dtype_s = dtype_buf.value.decode()
        np_dtype = _np_dtype(dtype_s)
        if np_dtype is None:
            raise UnsupportedDTypeError(
                f"tensor {name!r} has safetensors dtype {dtype_s!r} with no "
                f"numpy mapping")
        shp = tuple(shape[i] for i in range(ndim))
        out = np.empty(shp, np_dtype)
        assert out.nbytes == nbytes.value, (name, out.nbytes, nbytes.value)
        rc = self._lib.stl_read(
            self._handle, name.encode(),
            out.ctypes.data_as(ctypes.c_void_p), out.nbytes,
        )
        if rc != 0:
            raise IOError(self._lib.stl_error().decode())
        return out


def open_native_safetensors(
    model_dir: str,
) -> Optional[dict[str, Callable[[], np.ndarray]]]:
    """name -> lazy loader dict via libstload, or None (use Python path)."""
    lib = _load_lib()
    if lib is None:
        return None
    handle = lib.stl_open(str(model_dir).encode())
    if not handle:
        return None  # e.g. no shards found; Python path raises the error
    shards = _NativeShards(lib, handle)
    return {
        name: (lambda s=shards, n=name: s.read(n))
        for name in shards.names()
    }
