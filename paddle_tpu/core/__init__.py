"""Native runtime core: TCPStore, shared-memory queue.

Reference parity: the reference's native host runtime —
paddle/phi/core/distributed/store/tcp_store (rendezvous), DataLoader shm
transport [— verify]. (Host spans are Python's one recorder,
``observability/tracing.py``: the C++ host tracer went with PR 25.)
Compute stays with XLA; these are the host-side native subsystems a TPU
framework still genuinely needs in C++.

The shared library is a build product, never committed: it is compiled
from ``native/ptcore.cc`` with g++ on first use (this image has no
pybind11; bindings are ctypes over a C ABI) and rebuilt when the sha256
of the source differs from the one recorded beside the binary — mtimes
mean nothing on a copied checkout. Pure-Python fallbacks keep every
feature working when no compiler is available.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "native")
_SRC = os.path.join(_NATIVE_DIR, "ptcore.cc")
_LIB = os.path.join(_NATIVE_DIR, "libptcore.so")
_STAMP = _LIB + ".src_sha256"     # hash of the ptcore.cc that built _LIB

_lib = None
_lib_lock = threading.Lock()
_build_error = None


def _src_hash() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _stale(src_hash: str) -> bool:
    try:
        with open(_STAMP) as f:
            return f.read().strip() != src_hash
    except OSError:
        return True


def _build(src_hash: str):
    # per-pid temp names: concurrent first-use builds (launch with several
    # local workers) must not interleave writes into one temp file
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    stamp_tmp = f"{_STAMP}.{os.getpid()}.tmp"
    cmd = ["g++", "-std=c++17", "-O2", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
        os.replace(tmp, _LIB)   # atomic: losers just overwrite with same
        with open(stamp_tmp, "w") as f:
            f.write(src_hash + "\n")
        os.replace(stamp_tmp, _STAMP)   # stamp lands only after the lib
    finally:
        for t in (tmp, stamp_tmp):
            if os.path.exists(t):
                os.unlink(t)


def load_native():
    """Load (building if needed) libptcore; returns None if unavailable."""
    global _lib, _build_error
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            return None
        try:
            src_hash = _src_hash()
            if not os.path.exists(_LIB) or _stale(src_hash):
                _build(src_hash)
            lib = ctypes.CDLL(_LIB)
        except (OSError, subprocess.SubprocessError) as e:
            _build_error = e
            return None
        lib.pt_store_server_start.argtypes = [ctypes.c_int]
        lib.pt_store_server_start.restype = ctypes.c_void_p
        lib.pt_store_server_port.argtypes = [ctypes.c_void_p]
        lib.pt_store_server_stop.argtypes = [ctypes.c_void_p]
        lib.pt_store_client_connect.argtypes = [ctypes.c_char_p,
                                                ctypes.c_int, ctypes.c_int]
        lib.pt_store_client_connect.restype = ctypes.c_void_p
        lib.pt_store_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_char_p, ctypes.c_int]
        lib.pt_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_void_p, ctypes.c_int]
        lib.pt_store_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int64]
        lib.pt_store_add.restype = ctypes.c_int64
        lib.pt_store_wait.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pt_store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pt_store_check.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pt_store_client_close.argtypes = [ctypes.c_void_p]
        lib.pt_shmq_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.pt_shmq_create.restype = ctypes.c_void_p
        lib.pt_shmq_open.argtypes = [ctypes.c_char_p]
        lib.pt_shmq_open.restype = ctypes.c_void_p
        lib.pt_shmq_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_uint64, ctypes.c_int]
        lib.pt_shmq_pop.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_uint64, ctypes.c_int]
        lib.pt_shmq_pop.restype = ctypes.c_int64
        lib.pt_shmq_size.argtypes = [ctypes.c_void_p]
        lib.pt_shmq_size.restype = ctypes.c_uint64
        lib.pt_shmq_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return load_native() is not None


from .native_api import TCPStore, ShmQueue, MasterDaemon  # noqa: E402

__all__ = ["load_native", "native_available", "TCPStore", "ShmQueue",
           "MasterDaemon"]
