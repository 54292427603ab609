"""Native (C++) runtime components, bound via ctypes.

The compute path is JAX/XLA/Pallas; the host-side runtime around it —
batch gather for the data feed — is C++ (midgpt_tpu/native/gather.cpp),
built on first use with g++ (no pybind11 required) on the machine that
runs it. Where no toolchain exists the numpy path serves the same
windows; :func:`gather_backend` says which one is in use and why.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
import typing as tp

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "gather.cpp")
# portable code generation: the tree (binary included) is copied between
# machines, so the build may not assume the CPU it happens to run on
_FLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17", "-pthread")

_lock = threading.Lock()
_lib: tp.Optional[ctypes.CDLL] = None
_tried = False
_why_numpy: tp.Optional[str] = None


def _lib_path() -> str:
    """The binary is named after the source and flags it was built from:
    a library left behind by other source is a different file, never
    loaded, instead of one trusted by its modification time."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read() + " ".join(_FLAGS).encode())
    return os.path.join(_HERE, f"libdatagather-{digest.hexdigest()[:16]}.so")


def _build(lib_path: str) -> tp.Optional[str]:
    """Compile gather.cpp to ``lib_path``; the failure, or None."""
    # build to a process-unique temp path and rename into place: publication
    # is atomic, so concurrent builders can't hand a half-written .so to a
    # loader, and a rebuild never truncates a file another process has
    # already dlopen'd
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", *_FLAGS, _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
        return None
    except (OSError, subprocess.SubprocessError) as e:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        detail = getattr(e, "stderr", b"") or b""
        return f"{type(e).__name__}: {e} {detail.decode(errors='replace')[-400:]}"


def load_library() -> tp.Optional[ctypes.CDLL]:
    """The compiled gather library, building it on first call; None if it
    cannot be built or loaded here (said once on stderr; callers then
    take the numpy path)."""
    global _lib, _tried, _why_numpy
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        lib_path = _lib_path()
        if not os.path.exists(lib_path):
            _why_numpy = _build(lib_path)
        if _why_numpy is None:
            try:
                lib = ctypes.CDLL(lib_path)
            except OSError as e:
                _why_numpy = f"OSError: {e}"
        if _why_numpy is not None:
            print(
                f"midgpt_tpu.native: gather falls back to numpy "
                f"({_why_numpy.strip()})",
                file=sys.stderr,
            )
            return None
        lib.dg_gather.restype = ctypes.c_int
        lib.dg_gather.argtypes = [
            ctypes.c_void_p,  # tokens
            ctypes.c_int64,  # n_tokens
            ctypes.c_void_p,  # offsets
            ctypes.c_int64,  # n_seqs
            ctypes.c_int64,  # block_size
            ctypes.c_void_p,  # x_out
            ctypes.c_void_p,  # y_out
            ctypes.c_int,  # n_threads
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    return load_library() is not None


def gather_backend() -> str:
    """``"native"``, or ``"numpy (<why the library is not there>)"``."""
    if load_library() is not None:
        return "native"
    return f"numpy ({(_why_numpy or '').strip()})"


def gather_windows(
    tokens: np.ndarray,  # 1-D uint16
    offsets: np.ndarray,  # 1-D int
    block_size: int,
    n_threads: tp.Optional[int] = None,
) -> tp.Tuple[np.ndarray, np.ndarray]:
    """(x, y) int32 [n_seqs, block_size] windows; y shifted by one.

    Native multi-threaded gather when the library is available, else the
    numpy path (same recipe as the reference's get_batch, train.py:61-62).
    """
    assert tokens.dtype == np.uint16 and tokens.ndim == 1
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n_seqs = len(offsets)
    lib = load_library()
    if lib is not None and tokens.flags["C_CONTIGUOUS"]:
        x = np.empty((n_seqs, block_size), dtype=np.int32)
        y = np.empty((n_seqs, block_size), dtype=np.int32)
        if n_threads is None:
            n_threads = min(os.cpu_count() or 1, 16)
        rc = lib.dg_gather(
            tokens.ctypes.data, len(tokens),
            offsets.ctypes.data, n_seqs, block_size,
            x.ctypes.data, y.ctypes.data, n_threads,
        )
        if rc == 0:
            return x, y
        raise IndexError("gather window out of range")
    if np.any(offsets < 0) or np.any(offsets + block_size + 1 > len(tokens)):
        raise IndexError("gather window out of range")
    idx = offsets[:, None] + np.arange(block_size + 1)[None, :]
    windows = np.take(tokens, idx, axis=0).astype(np.int32)
    return windows[:, :-1], windows[:, 1:]
