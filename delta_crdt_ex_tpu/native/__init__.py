"""Native host-runtime extensions (C++, built on demand, optional).

The TPU compute path is JAX/XLA; the host runtime around it uses native
code where per-element Python overhead matters. Components degrade
gracefully: if no compiler is available the pure-Python paths are used —
and they compute byte-identical results, so mixed native/non-native
clusters stay consistent.

Current components:
- ``fasthash``: batch BLAKE2b key/value hashing (see ``fasthash.cpp``).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "fasthash.cpp")

_lock = threading.Lock()
_lib = None
_tried = False


def _so_path() -> str:
    """The library built from this exact source: keyed on the source's
    hash, so a library left from other sources (or copied in with the
    tree) is never loaded."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"libfasthash-{digest}.so")


def _build(so: str) -> bool:
    tmp = f"{so}.{os.getpid()}.tmp"  # concurrent builders never share it
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", _SRC, "-o", tmp],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def _load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            lib = ctypes.CDLL(so)
            lib.hash64_batch.argtypes = [
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_uint64,
                ctypes.c_void_p,
            ]
            lib.hash32_batch.argtypes = lib.hash64_batch.argtypes
            _lib = lib
        except OSError:
            _lib = None
        return _lib


def available() -> bool:
    return _load() is not None


def _pack(blobs: list[bytes]) -> tuple[np.ndarray, np.ndarray]:
    offsets = np.zeros(len(blobs) + 1, np.uint64)
    total = 0
    for i, b in enumerate(blobs):
        total += len(b)
        offsets[i + 1] = total
    packed = np.empty(total, np.uint8)
    pos = 0
    for b in blobs:
        packed[pos : pos + len(b)] = np.frombuffer(b, np.uint8)
        pos += len(b)
    return packed, offsets


def hash64_batch(blobs: list[bytes]) -> np.ndarray | None:
    """uint64 key ids for canonical encodings; None if native unavailable."""
    lib = _load()
    if lib is None or not blobs:
        return None
    packed, offsets = _pack(blobs)
    out = np.empty(len(blobs), np.uint64)
    lib.hash64_batch(
        packed.ctypes.data, offsets.ctypes.data, len(blobs), out.ctypes.data
    )
    return out


def hash32_batch(blobs: list[bytes]) -> np.ndarray | None:
    lib = _load()
    if lib is None or not blobs:
        return None
    packed, offsets = _pack(blobs)
    out = np.empty(len(blobs), np.uint32)
    lib.hash32_batch(
        packed.ctypes.data, offsets.ctypes.data, len(blobs), out.ctypes.data
    )
    return out
