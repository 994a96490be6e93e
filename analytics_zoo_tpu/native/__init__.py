"""Native (C++) runtime: bounded MPMC byte queue + batch assembly.

Reference parity (SURVEY.md §2.10): the reference's host data plane was
native (BlockManager/plasma/Redis/PMEM behind JNI).  Here the equivalent —
the queueing/synchronization under data prefetch and serving batching — is
C++ (zoo_native.cpp), compiled on first use with g++ and loaded via
ctypes.  No binary is kept in git: the library is built from the source
beside this file and from nothing else.  A pure-Python fallback
(queue.Queue) keeps every feature working on a machine without a
compiler; ``NativeQueue.is_native`` reports which is active.
"""

from __future__ import annotations

import atexit
import ctypes
import hashlib
import logging
import os
import queue as pyqueue
import subprocess
import threading
import weakref
from typing import Optional, Tuple

logger = logging.getLogger("analytics_zoo_tpu")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "zoo_native.cpp")
_lib = None
_lib_lock = threading.Lock()


def _so_path() -> str:
    """The library's path, named by the SOURCE's content hash: an edited
    zoo_native.cpp gets a new name and so a rebuild, with no reliance on
    file times (meaningless in a copied tree)."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_HERE, f"libzoonative-{digest}.so")


def _build() -> Optional[str]:
    so = _so_path()
    if os.path.exists(so):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"  # concurrent first uses don't collide
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return so
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning("native build failed (%s); using Python fallback "
                       "queue", e)
        return None


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first use (None if
    unavailable — callers must fall back)."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib or None
        so = _build()
        if so is None:
            _lib = False
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            logger.warning("native library %s failed to load (%s); using "
                           "Python fallback queue", so, e)
            _lib = False
            return None
        lib.zn_queue_create.restype = ctypes.c_void_p
        lib.zn_queue_create.argtypes = [ctypes.c_size_t, ctypes.c_size_t]
        lib.zn_queue_destroy.argtypes = [ctypes.c_void_p]
        lib.zn_queue_close.argtypes = [ctypes.c_void_p]
        lib.zn_queue_push.restype = ctypes.c_int
        lib.zn_queue_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_size_t, ctypes.c_uint64,
                                      ctypes.c_int]
        lib.zn_queue_pop.restype = ctypes.c_longlong
        lib.zn_queue_pop.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_size_t,
                                     ctypes.POINTER(ctypes.c_uint64),
                                     ctypes.c_int]
        lib.zn_queue_next_size.restype = ctypes.c_size_t
        lib.zn_queue_next_size.argtypes = [ctypes.c_void_p]
        lib.zn_queue_len.restype = ctypes.c_size_t
        lib.zn_queue_len.argtypes = [ctypes.c_void_p]
        lib.zn_queue_pushed.restype = ctypes.c_uint64
        lib.zn_queue_pushed.argtypes = [ctypes.c_void_p]
        lib.zn_queue_popped.restype = ctypes.c_uint64
        lib.zn_queue_popped.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


# Every live queue, closed from an atexit hook: worker threads blocked in
# push/pop must wake and exit while the interpreter is still fully alive —
# a daemon thread returning from the (GIL-released) native call during
# interpreter teardown is a "Fatal Python error" crash.
_live_queues: "weakref.WeakSet[NativeQueue]" = weakref.WeakSet()


@atexit.register
def _close_all_queues() -> None:
    for q in list(_live_queues):
        try:
            q.close()
        except Exception:  # noqa: BLE001 — best-effort shutdown
            pass


class NativeQueue:
    """Bounded MPMC byte queue; C++-backed when the native lib builds."""

    def __init__(self, max_items: int = 0, max_bytes: int = 0):
        lib = get_lib()
        self._lib = lib
        if lib is not None:
            self._q = lib.zn_queue_create(max_items, max_bytes)
            self.is_native = True
        else:
            self._pyq = pyqueue.Queue(maxsize=max_items or 0)
            self.is_native = False
        self._closed = False
        _live_queues.add(self)

    # -- ops ------------------------------------------------------------------

    def push(self, payload: bytes, tag: int = 0,
             timeout: Optional[float] = None) -> bool:
        """False on timeout; raises if the queue is closed."""
        if self.is_native:
            rc = self._lib.zn_queue_push(
                self._q, payload, len(payload), tag,
                -1 if timeout is None else int(timeout * 1000))
            if rc == -2:
                raise RuntimeError("queue closed")
            return rc == 0
        # poll in short slices so close() can wake a blocked producer (the
        # C++ path gets this from the condvar broadcast in zn_queue_close)
        import time as _time
        deadline = None if timeout is None else _time.monotonic() + timeout
        while True:
            if self._closed:
                raise RuntimeError("queue closed")
            slice_t = 0.05
            if deadline is not None:
                left = deadline - _time.monotonic()
                if left <= 0:
                    return False
                slice_t = min(slice_t, left)
            try:
                self._pyq.put((payload, tag), timeout=slice_t)
                return True
            except pyqueue.Full:
                continue

    def pop(self, timeout: Optional[float] = None
            ) -> Optional[Tuple[bytes, int]]:
        """(payload, tag) or None on timeout; raises when closed+drained."""
        if self.is_native:
            tag = ctypes.c_uint64(0)
            size = self._lib.zn_queue_next_size(self._q)
            buf = ctypes.create_string_buffer(max(size, 1 << 16))
            while True:
                rc = self._lib.zn_queue_pop(
                    self._q, buf, len(buf), ctypes.byref(tag),
                    -1 if timeout is None else int(timeout * 1000))
                if rc == -3:        # distinct from a popped empty payload
                    return None
                if rc == -2:
                    raise RuntimeError("queue closed")
                if rc < 0:          # buffer too small: retry with exact size
                    buf = ctypes.create_string_buffer(-rc)
                    continue
                return buf.raw[:rc], tag.value
        try:
            item = self._pyq.get(timeout=timeout)
        except pyqueue.Empty:
            if self._closed:
                raise RuntimeError("queue closed") from None
            return None
        if item is None:
            raise RuntimeError("queue closed")
        return item

    def close(self) -> None:
        self._closed = True
        if self.is_native:
            self._lib.zn_queue_close(self._q)
        else:
            try:
                self._pyq.put_nowait(None)
            except pyqueue.Full:
                pass

    def __len__(self) -> int:
        if self.is_native:
            return int(self._lib.zn_queue_len(self._q))
        return self._pyq.qsize()

    def stats(self) -> Tuple[int, int]:
        if self.is_native:
            return (int(self._lib.zn_queue_pushed(self._q)),
                    int(self._lib.zn_queue_popped(self._q)))
        return (-1, -1)

    def __del__(self):
        try:
            if getattr(self, "is_native", False):
                self._lib.zn_queue_destroy(self._q)
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass
