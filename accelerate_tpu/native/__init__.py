"""Native host-runtime bindings (ctypes over a small C++17 library).

The reference's host-side performance comes from vendored native code —
torch's C++ DataLoader worker pool / collate and native serialization
(reference: src/accelerate/data_loader.py:643-693 drives torch loaders whose
row loops are ATen C++).  accelerate_tpu's equivalent lives in
``src/fastloader.cc``: fused batch assembly (gather/stack/pad-stack) and
chunked parallel checkpoint IO.

Binding strategy (no pybind11 in the image): a plain ``extern "C"`` ABI
loaded with ctypes.  The .so is built on demand with g++ the first time it
is needed, cached next to the source, and keyed by a hash of the source
(recorded beside it) + ABI probe, so what loads is always built from the
``fastloader.cc`` that is there.  Everything here degrades gracefully:

* ``ACCELERATE_TPU_NO_NATIVE=1`` disables the library entirely;
* missing g++ / failed compile / load error → ``available()`` is False and
  callers fall back to their numpy paths (the wrappers below raise if called
  while unavailable — call sites must guard with ``available()``).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src", "fastloader.cc")
_SO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src", "_fastloader.so")
_SO_HASH = _SO + ".sha256"  # hash of the source the library was built from
_ABI_VERSION = 1

_lock = threading.Lock()
_lib = None
_load_failed: str | None = None


def _threads_default() -> int:
    n = os.environ.get("ACCELERATE_TPU_NATIVE_THREADS")
    if n is not None:
        return max(1, int(n))
    return max(1, os.cpu_count() or 1)


_MIN_BYTES_PER_THREAD = 1 << 20


def _cap_threads(threads: int | None, total_bytes: int) -> int:
    """Never spawn a thread for <1 MiB of work — std::thread create+join costs
    more than a small memcpy, so tiny batches stay single-threaded."""
    t = threads or _threads_default()
    return max(1, min(t, total_bytes // _MIN_BYTES_PER_THREAD or 1))


def _source_hash() -> str:
    import hashlib

    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _build() -> str | None:
    """Compile the .so unless the one on disk was built from exactly this
    source; returns an error string on failure.

    Staleness is keyed on a hash of ``fastloader.cc`` recorded beside the
    library, not on mtimes: the .so is git-ignored and travels with copies
    of the tree (a chip machine, a CI checkout), where copied mtimes say
    nothing about which source it was built from.
    """
    try:
        want = _source_hash()
        try:
            with open(_SO_HASH, encoding="utf-8") as f:
                have = f.read().strip()
        except OSError:
            have = None
        if have == want and os.path.exists(_SO):
            return None
        # per-process tmp name: concurrent first-use builds (pytest-xdist,
        # data workers) must not interleave linker output on a shared path
        tmp = f"{_SO}.{os.getpid()}.tmp"
        cmd = [
            "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
            _SRC, "-o", tmp,
        ]
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            return f"g++ failed: {proc.stderr[-500:]}"
        os.replace(tmp, _SO)
        # the hash lands after the library: a crash between the two leaves
        # a stale hash, which only costs a rebuild
        tmp_hash = f"{_SO_HASH}.{os.getpid()}.tmp"
        with open(tmp_hash, "w", encoding="utf-8") as f:
            f.write(want + "\n")
        os.replace(tmp_hash, _SO_HASH)
        return None
    except (OSError, subprocess.SubprocessError) as e:  # g++ missing, RO fs, ...
        return f"build error: {e}"


def _load():
    global _lib, _load_failed
    if _lib is not None or _load_failed is not None:
        return
    with _lock:
        if _lib is not None or _load_failed is not None:
            return
        if os.environ.get("ACCELERATE_TPU_NO_NATIVE") == "1":
            _load_failed = "disabled via ACCELERATE_TPU_NO_NATIVE"
            return
        err = _build()
        if err is not None:
            _load_failed = err
            return
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            _load_failed = f"dlopen failed: {e}"
            return
        try:
            if lib.at_abi_version() != _ABI_VERSION:
                _load_failed = "stale ABI; delete src/_fastloader.so"
                return
        except AttributeError:
            _load_failed = "ABI probe symbol missing"
            return
        c = ctypes
        lib.at_gather_rows.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p,
                                       c.c_int64, c.c_int64, c.c_int]
        lib.at_stack_rows.argtypes = [c.c_void_p, c.c_void_p, c.c_int64,
                                      c.c_int64, c.c_int]
        lib.at_pad_stack.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p,
                                     c.c_int64, c.c_int64, c.c_int64,
                                     c.c_void_p, c.c_int]
        lib.at_write_file.argtypes = [c.c_char_p, c.c_void_p, c.c_int64, c.c_int]
        lib.at_write_file.restype = c.c_int
        lib.at_write_region.argtypes = [c.c_char_p, c.c_void_p, c.c_int64,
                                        c.c_int64, c.c_int]
        lib.at_write_region.restype = c.c_int
        lib.at_read_file.argtypes = [c.c_char_p, c.c_void_p, c.c_int64,
                                     c.c_int64, c.c_int]
        lib.at_read_file.restype = c.c_int
        _lib = lib


def available() -> bool:
    """True when the native library is built and loaded (or buildable)."""
    _load()
    return _lib is not None


def load_error() -> str | None:
    """Why the native library is unavailable (None when it is available)."""
    _load()
    return _load_failed


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


def gather_rows(src: np.ndarray, indices: np.ndarray,
                out: np.ndarray | None = None, threads: int | None = None) -> np.ndarray:
    """out[i] = src[indices[i]] for a C-contiguous 2-D+ src (rows on axis 0).

    The DataLoader-worker inner loop (``[dataset[i] for i in batch]`` +
    collate) fused into one native call; src is typically a np.memmap token
    array so nothing but the gathered rows is ever touched.
    """
    _load()
    if _lib is None:
        raise RuntimeError(f"native library unavailable: {_load_failed}")
    if not src.flags.c_contiguous:
        raise ValueError("src must be C-contiguous")
    idx = np.ascontiguousarray(indices, dtype=np.int64)
    if idx.ndim != 1:
        raise ValueError("indices must be 1-D")
    n = idx.shape[0]
    if n and (idx.min() < 0 or idx.max() >= src.shape[0]):
        raise IndexError("gather index out of range")
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    if out is None:
        out = np.empty((n,) + src.shape[1:], dtype=src.dtype)
    else:
        if not out.flags.c_contiguous or out.shape != (n,) + src.shape[1:] or out.dtype != src.dtype:
            raise ValueError("out has wrong shape/dtype/layout")
    _lib.at_gather_rows(_ptr(src), _ptr(idx), _ptr(out), n, row_bytes,
                        _cap_threads(threads, n * row_bytes))
    return out


def stack_rows(samples: list[np.ndarray], out: np.ndarray | None = None,
               threads: int | None = None) -> np.ndarray:
    """np.stack(samples) with the per-sample Python loop in native code."""
    _load()
    if _lib is None:
        raise RuntimeError(f"native library unavailable: {_load_failed}")
    n = len(samples)
    if n == 0:
        raise ValueError("empty sample list")
    first = samples[0]
    row_bytes = first.dtype.itemsize * first.size
    ptrs = (ctypes.c_void_p * n)()
    for i, s in enumerate(samples):
        if s.shape != first.shape or s.dtype != first.dtype or not s.flags.c_contiguous:
            raise ValueError("samples must be homogeneous C-contiguous arrays")
        ptrs[i] = s.ctypes.data
    if out is None:
        out = np.empty((n,) + first.shape, dtype=first.dtype)
    elif (not out.flags.c_contiguous or out.shape != (n,) + first.shape
          or out.dtype != first.dtype):
        raise ValueError("out has wrong shape/dtype/layout")
    _lib.at_stack_rows(ptrs, _ptr(out), n, row_bytes,
                       _cap_threads(threads, n * row_bytes))
    return out


def pad_stack(samples: list[np.ndarray], max_len: int | None = None,
              pad_value=0, threads: int | None = None) -> np.ndarray:
    """Stack ragged 1-D rows into [n, max_len], right-padded with pad_value."""
    _load()
    if _lib is None:
        raise RuntimeError(f"native library unavailable: {_load_failed}")
    n = len(samples)
    if n == 0:
        raise ValueError("empty sample list")
    dtype = samples[0].dtype
    lens = np.empty(n, dtype=np.int64)
    ptrs = (ctypes.c_void_p * n)()
    for i, s in enumerate(samples):
        if s.ndim != 1 or s.dtype != dtype or not s.flags.c_contiguous:
            raise ValueError("samples must be C-contiguous 1-D arrays of one dtype")
        lens[i] = s.shape[0]
        ptrs[i] = s.ctypes.data
    ml = int(lens.max()) if max_len is None else int(max_len)
    if lens.max() > ml:
        raise ValueError(f"sample longer than max_len={ml}")
    out = np.empty((n, ml), dtype=dtype)
    pad = np.asarray(pad_value, dtype=dtype)
    _lib.at_pad_stack(ptrs, _ptr(lens), _ptr(out), n, ml, dtype.itemsize,
                      _ptr(pad), _cap_threads(threads, out.nbytes))
    return out


def write_file(path: str, data: np.ndarray | bytes | memoryview,
               threads: int | None = None) -> None:
    """Write a contiguous buffer to path with chunked parallel pwrite."""
    _load()
    if _lib is None:
        raise RuntimeError(f"native library unavailable: {_load_failed}")
    if isinstance(data, np.ndarray):
        if not data.flags.c_contiguous:
            data = np.ascontiguousarray(data)
        buf, nbytes = _ptr(data), data.nbytes
        rc = _lib.at_write_file(path.encode(), buf, nbytes, _cap_threads(threads, nbytes))
    else:
        raw = bytes(data)
        rc = _lib.at_write_file(path.encode(), raw, len(raw), _cap_threads(threads, len(raw)))
    if rc != 0:
        raise OSError(-rc, os.strerror(-rc), path)


def write_region(path: str, data: np.ndarray, offset: int,
                 threads: int | None = None) -> None:
    """Parallel pwrite of a contiguous array at offset into an existing file."""
    _load()
    if _lib is None:
        raise RuntimeError(f"native library unavailable: {_load_failed}")
    if not data.flags.c_contiguous:
        data = np.ascontiguousarray(data)
    rc = _lib.at_write_region(path.encode(), _ptr(data), data.nbytes, offset,
                              _cap_threads(threads, data.nbytes))
    if rc != 0:
        raise OSError(-rc, os.strerror(-rc), path)


def read_into(path: str, out: np.ndarray, offset: int = 0,
              threads: int | None = None) -> np.ndarray:
    """Fill a preallocated contiguous array from path[offset:offset+nbytes]."""
    _load()
    if _lib is None:
        raise RuntimeError(f"native library unavailable: {_load_failed}")
    if not out.flags.c_contiguous:
        raise ValueError("out must be C-contiguous")
    rc = _lib.at_read_file(path.encode(), _ptr(out), out.nbytes, offset,
                           _cap_threads(threads, out.nbytes))
    if rc != 0:
        raise OSError(-rc, os.strerror(-rc), path)
    return out
