"""Native (C++) IO runtime: shared-memory ring transport for DataLoader
workers (see shm_ring.cc for the design and reference mapping).

The library is compiled on first use with the system toolchain and cached
under the build directory, keyed by a hash of the source it was built from
(a binary left there by another checkout is never picked up); everything
degrades gracefully to the multiprocessing.Queue transport when a
toolchain is unavailable.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional

_LIB = None
_LIB_LOCK = threading.Lock()
_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "shm_ring.cc")


def _build_dir() -> str:
    d = os.path.join(tempfile.gettempdir(), "paddle_tpu_native")
    os.makedirs(d, exist_ok=True)
    return d


def load_library() -> Optional[ctypes.CDLL]:
    """Compile (once) and load the ring library; None if no toolchain."""
    global _LIB
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        out = os.path.join(_build_dir(), f"libshm_ring-{digest}.so")
        if not os.path.exists(out):
            tmp = f"{out}.{os.getpid()}.tmp"
            res = subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-o",
                 tmp, _SRC, "-lpthread", "-lrt"],
                capture_output=True, text=True)
            if res.returncode != 0:
                return None
            os.replace(tmp, out)
        lib = ctypes.CDLL(out)
        lib.shm_ring_create.restype = ctypes.c_void_p
        lib.shm_ring_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.shm_ring_attach.restype = ctypes.c_void_p
        lib.shm_ring_attach.argtypes = [ctypes.c_char_p]
        lib.shm_ring_push.restype = ctypes.c_int
        lib.shm_ring_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_uint64, ctypes.c_int]
        lib.shm_ring_peek_size.restype = ctypes.c_int64
        lib.shm_ring_peek_size.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.shm_ring_pop.restype = ctypes.c_int64
        lib.shm_ring_pop.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                     ctypes.c_uint64, ctypes.c_int]
        lib.shm_ring_close_producer.argtypes = [ctypes.c_void_p]
        lib.shm_ring_detach.argtypes = [ctypes.c_void_p]
        lib.shm_ring_unlink.argtypes = [ctypes.c_char_p]
        _LIB = lib
        return _LIB


def available() -> bool:
    return load_library() is not None


class ShmRing:
    """Python handle over one SPSC shared-memory ring."""

    def __init__(self, name: str, capacity: int = 0, create: bool = False):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native shm_ring unavailable (no toolchain)")
        self._lib = lib
        self.name = name.encode()
        if create:
            self._ptr = lib.shm_ring_create(self.name, capacity)
        else:
            self._ptr = lib.shm_ring_attach(self.name)
        if not self._ptr:
            raise OSError(f"shm_ring {'create' if create else 'attach'} "
                          f"failed for {name}")
        self._creator = create

    def push(self, data: bytes, timeout_ms: int = -1):
        rc = self._lib.shm_ring_push(self._ptr, data, len(data), timeout_ms)
        if rc == -2:
            raise ValueError(
                f"record of {len(data)} bytes exceeds ring capacity")
        if rc == -3:
            raise BrokenPipeError("ring closed")
        if rc != 0:
            raise TimeoutError("shm_ring push timed out")

    def pop(self, timeout_ms: int = -1) -> Optional[bytes]:
        """One record, or None when the producer closed and drained."""
        size = self._lib.shm_ring_peek_size(self._ptr, timeout_ms)
        if size == -3:
            return None
        if size < 0:
            raise TimeoutError("shm_ring pop timed out")
        buf = ctypes.create_string_buffer(int(size))
        got = self._lib.shm_ring_pop(self._ptr, buf, int(size), timeout_ms)
        if got == -3:
            return None
        if got < 0:
            raise TimeoutError("shm_ring pop timed out")
        return buf.raw[:got]

    def close_producer(self):
        self._lib.shm_ring_close_producer(self._ptr)

    def close(self):
        if self._ptr:
            self._lib.shm_ring_detach(self._ptr)
            self._ptr = None
        if self._creator:
            self._lib.shm_ring_unlink(self.name)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
