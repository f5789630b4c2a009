"""Lazy g++ build + ctypes bindings for the native runtime.

The reference ships csrc/ as setuptools CUDAExtensions (setup.py:96-589) and
falls back to Python when the modules are absent; here the build is a single
``g++ -O3 -shared`` invocation, cached beside the source under a name that
carries a hash of the source (a copied tree keeps no mtimes, and a library
built from other source must never be trusted), with the same fallback
stance.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "apex_runtime.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"_apex_runtime.{digest}.so")


def _build() -> Optional[ctypes.CDLL]:
    global _build_failed
    try:
        lib_path = _lib_path()
        try:
            if os.path.exists(lib_path):
                return ctypes.CDLL(lib_path)
        except OSError:
            pass  # corrupt/wrong-arch cache: fall through to rebuild
        # build under a private name, then rename: a concurrent process
        # never loads a half-written library
        tmp = f"{lib_path}.{os.getpid()}.tmp"
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
             _SRC, "-o", tmp],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, lib_path)
        return ctypes.CDLL(lib_path)
    except Exception:  # noqa: BLE001 - any failure selects the numpy fallback
        _build_failed = True
        return None


def _get() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is None and not _build_failed:
            lib = _build()
            if lib is not None:
                lib.apex_flatten.argtypes = [
                    ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
                    ctypes.c_int, ctypes.c_void_p, ctypes.c_int]
                lib.apex_unflatten.argtypes = [
                    ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
                    ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int]
                lib.tl_create.restype = ctypes.c_void_p
                lib.tl_create.argtypes = [
                    ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                    ctypes.c_int64, ctypes.c_int, ctypes.c_int]
                lib.tl_next.restype = ctypes.c_int
                lib.tl_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
                lib.tl_destroy.argtypes = [ctypes.c_void_p]
            _lib = lib
    return _lib


def available() -> bool:
    return _get() is not None


def flatten(arrays: Sequence[np.ndarray], threads: int = 4) -> np.ndarray:
    """Pack arrays into one contiguous uint8 buffer
    (apex_C.flatten, csrc/flatten_unflatten.cpp:15)."""
    arrays = [np.ascontiguousarray(a) for a in arrays]
    total = sum(a.nbytes for a in arrays)
    out = np.empty((total,), np.uint8)
    lib = _get()
    if lib is None or not arrays:
        off = 0
        for a in arrays:
            out[off : off + a.nbytes] = a.view(np.uint8).reshape(-1)
            off += a.nbytes
        return out
    n = len(arrays)
    srcs = (ctypes.c_void_p * n)(*[a.ctypes.data for a in arrays])
    sizes = (ctypes.c_int64 * n)(*[a.nbytes for a in arrays])
    lib.apex_flatten(srcs, sizes, n, out.ctypes.data_as(ctypes.c_void_p), threads)
    return out


def unflatten(flat: np.ndarray, like: Sequence[np.ndarray], threads: int = 4) -> List[np.ndarray]:
    """Split a flat buffer back into arrays shaped/typed like ``like``
    (apex_C.unflatten, csrc/flatten_unflatten.cpp:16)."""
    flat = np.ascontiguousarray(flat).view(np.uint8).reshape(-1)
    total = sum(a.nbytes for a in like)
    if flat.nbytes != total:
        raise ValueError(f"flat buffer {flat.nbytes}B != templates {total}B")
    outs = [np.empty(a.shape, a.dtype) for a in like]
    lib = _get()
    if lib is None or not outs:
        off = 0
        for o in outs:
            o.view(np.uint8).reshape(-1)[:] = flat[off : off + o.nbytes]
            off += o.nbytes
        return outs
    n = len(outs)
    dsts = (ctypes.c_void_p * n)(*[o.ctypes.data for o in outs])
    sizes = (ctypes.c_int64 * n)(*[o.nbytes for o in outs])
    lib.apex_unflatten(flat.ctypes.data_as(ctypes.c_void_p), dsts, sizes, n, threads)
    return outs


class TokenLoader:
    """Stream fixed-size batches from binary files on a native worker thread.

    ``batch_shape``/``dtype`` define one batch; files are concatenated in
    order (and re-looped with ``loop=True``), so a corpus sharded into
    ``.bin`` files streams as one token sequence — the Megatron pretraining
    data idiom. Falls back to a Python reader when the native lib is absent.
    """

    def __init__(self, paths: Sequence[str], batch_shape: Sequence[int],
                 dtype=np.int32, n_buffers: int = 4, loop: bool = False):
        self.paths = [os.fspath(p) for p in paths]
        if not self.paths:
            raise ValueError("no input files")
        for p in self.paths:  # both backends: fail fast, not in a worker
            if not os.path.exists(p):
                raise FileNotFoundError(p)
        self.batch_shape = tuple(batch_shape)
        self.dtype = np.dtype(dtype)
        self.batch_bytes = int(np.prod(self.batch_shape)) * self.dtype.itemsize
        if self.batch_bytes <= 0:
            raise ValueError(f"empty batch shape {self.batch_shape}")
        self.loop = loop
        self._lib = _get()
        self._n_buffers = n_buffers
        self._handles: set = set()

    def _create_handle(self):
        arr = (ctypes.c_char_p * len(self.paths))(*[p.encode() for p in self.paths])
        return self._lib.tl_create(
            arr, len(self.paths), self.batch_bytes, self._n_buffers, int(self.loop))

    def __iter__(self):
        """Each iteration restarts the stream, with either backend."""
        if self._lib is not None:
            return self._native_iter()
        return self._python_iter()

    def _native_iter(self):
        # each iterator owns its stream: concurrent iterators are independent
        handle = self._create_handle()
        self._handles.add(handle)
        out = np.empty(self.batch_shape, self.dtype)
        try:
            while True:
                ok = self._lib.tl_next(handle, out.ctypes.data_as(ctypes.c_void_p))
                if not ok:
                    return
                yield out.copy()
        finally:
            if handle in self._handles:
                self._handles.discard(handle)
                self._lib.tl_destroy(handle)

    def _python_iter(self):
        carry = b""
        while True:
            produced = 0  # fruitless-pass guard, mirrors the native backend
            for p in self.paths:
                with open(p, "rb") as f:
                    while chunk := f.read(1 << 16):
                        produced += len(chunk)
                        carry += chunk
                        while len(carry) >= self.batch_bytes:
                            buf, carry = carry[: self.batch_bytes], carry[self.batch_bytes :]
                            yield np.frombuffer(buf, self.dtype).reshape(self.batch_shape).copy()
            if not self.loop or produced == 0:
                return

    def close(self):
        """Stop all live native streams."""
        while self._handles:
            self._lib.tl_destroy(self._handles.pop())

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass
