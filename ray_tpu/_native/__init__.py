"""ctypes bindings for the native runtime components (librtpu_native.so).

The native layer implements the pieces that stay native in the reference —
the object-store arena allocator (plasma_allocator.cc / dlmalloc.cc) and
the mutable-object channel atomics (experimental_mutable_object_manager.h)
— behind a C ABI. No pybind11 in the image, so binding is plain ctypes.

The library is not in the repository: it is built from ``arena.cc`` and
``channel.cc`` on first use (one ``make`` shelling out to g++, kept next to
the sources). If that fails, ``available()`` is False and the pure-Python
data plane takes over (per-object shm segments; RPC-based channels), which
is a different data plane: the zero-copy block decode needs the arena. So
the failure is logged once, with the compiler's output, and a caller that
must not run on the fallback checks ``available()`` (``chip_smoke.py``).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

from ray_tpu.utils.logging import get_logger

logger = get_logger("native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "librtpu_native.so")

_lib: Optional[ctypes.CDLL] = None
_build_lock = threading.Lock()
_build_failed = False


def _try_build(force: bool = False) -> bool:
    srcs = [os.path.join(_DIR, f) for f in ("arena.cc", "channel.cc")]
    if not force and os.path.exists(_SO) and all(
        os.path.getmtime(_SO) >= os.path.getmtime(s) for s in srcs
    ):
        return True
    try:
        out = subprocess.run(
            ["make", "-C", _DIR] + (["-B"] if force else []),
            capture_output=True, text=True, timeout=120,
        )
    except (OSError, subprocess.TimeoutExpired) as e:  # no make / g++ hung
        logger.warning("librtpu_native.so not built (%s: %s); the object "
                       "store runs on per-object shm segments",
                       type(e).__name__, e)
        return False
    if out.returncode != 0 or not os.path.exists(_SO):
        logger.warning("librtpu_native.so not built (make exited %d); the "
                       "object store runs on per-object shm segments\n%s",
                       out.returncode, out.stderr[-2000:])
        return False
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    with _build_lock:
        if _lib is not None:
            return _lib
        if not _try_build():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            # a stale/foreign-arch .so (e.g. copied checkout): rebuild from
            # source once before giving up on the native backend
            if not _try_build(force=True):
                _build_failed = True
                return None
            try:
                lib = ctypes.CDLL(_SO)
            except OSError:
                _build_failed = True
                return None
        c = ctypes
        # arena
        lib.rtpu_arena_create.argtypes = [c.c_char_p, c.c_uint64]
        lib.rtpu_arena_create.restype = c.c_int64
        lib.rtpu_arena_attach.argtypes = [c.c_char_p]
        lib.rtpu_arena_attach.restype = c.c_int64
        lib.rtpu_arena_base.argtypes = [c.c_int64]
        lib.rtpu_arena_base.restype = c.c_void_p
        lib.rtpu_arena_capacity.argtypes = [c.c_int64]
        lib.rtpu_arena_capacity.restype = c.c_uint64
        lib.rtpu_arena_alloc.argtypes = [c.c_int64, c.c_char_p, c.c_uint64]
        lib.rtpu_arena_alloc.restype = c.c_int64
        lib.rtpu_arena_free.argtypes = [c.c_int64, c.c_uint64]
        lib.rtpu_arena_free.restype = c.c_int
        lib.rtpu_arena_validate.argtypes = [c.c_int64, c.c_char_p, c.c_uint64,
                                            c.c_uint64]
        lib.rtpu_arena_validate.restype = c.c_int
        lib.rtpu_arena_used.argtypes = [c.c_int64]
        lib.rtpu_arena_used.restype = c.c_uint64
        lib.rtpu_arena_num_free_blocks.argtypes = [c.c_int64]
        lib.rtpu_arena_num_free_blocks.restype = c.c_uint64
        lib.rtpu_arena_largest_free.argtypes = [c.c_int64]
        lib.rtpu_arena_largest_free.restype = c.c_uint64
        lib.rtpu_arena_close.argtypes = [c.c_int64]
        lib.rtpu_arena_close.restype = None
        lib.rtpu_arena_unlink.argtypes = [c.c_char_p]
        lib.rtpu_arena_unlink.restype = c.c_int
        # channel
        lib.rtpu_chan_header_size.argtypes = []
        lib.rtpu_chan_header_size.restype = c.c_uint64
        lib.rtpu_chan_init.argtypes = [c.c_void_p]
        lib.rtpu_chan_init.restype = None
        lib.rtpu_chan_write_acquire.argtypes = [c.c_void_p, c.c_int, c.c_uint64]
        lib.rtpu_chan_write_acquire.restype = c.c_int64
        lib.rtpu_chan_write_release.argtypes = [c.c_void_p, c.c_uint64]
        lib.rtpu_chan_write_release.restype = None
        lib.rtpu_chan_read_acquire.argtypes = [c.c_void_p, c.c_uint64,
                                               c.POINTER(c.c_uint64), c.c_uint64]
        lib.rtpu_chan_read_acquire.restype = c.c_int64
        lib.rtpu_chan_read_validate.argtypes = [c.c_void_p, c.c_uint64]
        lib.rtpu_chan_read_validate.restype = c.c_int
        lib.rtpu_chan_read_ack.argtypes = [c.c_void_p, c.c_int, c.c_uint64]
        lib.rtpu_chan_read_ack.restype = None
        lib.rtpu_chan_close.argtypes = [c.c_void_p]
        lib.rtpu_chan_close.restype = None
        lib.rtpu_chan_is_closed.argtypes = [c.c_void_p]
        lib.rtpu_chan_is_closed.restype = c.c_int
        lib.rtpu_chan_version.argtypes = [c.c_void_p]
        lib.rtpu_chan_version.restype = c.c_uint64
        _lib = lib
        return _lib


def available() -> bool:
    """True if the native library is (or can be) loaded."""
    return _load() is not None


def lib() -> ctypes.CDLL:
    l = _load()
    if l is None:
        raise RuntimeError(
            "librtpu_native.so unavailable (no g++/make?); use the "
            "pure-Python fallbacks"
        )
    return l


class Arena:
    """Owner-side (allocating) or attached (read/write) view of one arena."""

    def __init__(self, path: str, capacity: Optional[int] = None,
                 create: bool = False):
        self._lib = lib()
        self.path = path
        if create:
            assert capacity is not None
            self._h = self._lib.rtpu_arena_create(path.encode(), capacity)
        else:
            self._h = self._lib.rtpu_arena_attach(path.encode())
        if self._h < 0:
            raise OSError(f"arena {'create' if create else 'attach'} failed: {path}")
        self.owner = create
        self.capacity = self._lib.rtpu_arena_capacity(self._h)
        base = self._lib.rtpu_arena_base(self._h)
        # one zero-copy view over the whole arena; object views are slices
        self._buf = (ctypes.c_char * self.capacity).from_address(base)
        self.view: memoryview = memoryview(self._buf).cast("B")

    # ---- owner ops --------------------------------------------------------
    def alloc(self, oid24: bytes, size: int) -> int:
        """Returns the payload offset, or -1 if no block fits."""
        return self._lib.rtpu_arena_alloc(self._h, oid24, size)

    def free(self, offset: int) -> bool:
        return self._lib.rtpu_arena_free(self._h, offset) == 0

    def used(self) -> int:
        return self._lib.rtpu_arena_used(self._h)

    def largest_free(self) -> int:
        return self._lib.rtpu_arena_largest_free(self._h)

    def num_free_blocks(self) -> int:
        return self._lib.rtpu_arena_num_free_blocks(self._h)

    # ---- shared ops -------------------------------------------------------
    def validate(self, oid24: bytes, offset: int, size: int) -> bool:
        return self._lib.rtpu_arena_validate(self._h, oid24, offset, size) == 1

    def slice(self, offset: int, size: int) -> memoryview:
        return self.view[offset : offset + size]

    def close(self) -> None:
        if self._h >= 0:
            try:
                self.view.release()
            except BufferError:
                # live views still alias the mapping: munmap would turn their
                # next access into SIGSEGV. Leak the mapping instead (the OS
                # reclaims at process exit) — mirror of ShmSegment.close.
                self._h = -1
                return
            self._lib.rtpu_arena_close(self._h)
            self._h = -1

    def unlink(self) -> None:
        self._lib.rtpu_arena_unlink(self.path.encode())
