"""DMA-friendly host slabs (src/pinned.cc).

The arena pool allocates its per-batch host buffers out of these slabs
when pinned mode is on: page-aligned, pre-faulted, and best-effort
``mlock``\\ ed so the accelerator runtime's DMA engine never stalls on a
page fault or an evicted page mid-transfer.

Three tiers, degrading gracefully:

``native``
    The compiled probe: ``mmap(MAP_POPULATE)`` + ``mlock``.
``mmap``
    Toolchain missing — anonymous :mod:`mmap` mappings (page-aligned by
    construction) with ``mlock`` attempted through libc.
``None`` (:func:`allocate` returns ``None``)
    Neither tier works (or ``PETASTORM_TPU_NO_NATIVE`` plus no mmap);
    callers fall back to plain ``np.empty`` — the arena pool stays
    fully functional, just unpinned.
"""

import ctypes
import logging
import mmap as mmap_mod
import os
import weakref

import numpy as np

from petastorm_tpu.native.build import NativeBuildError, build_and_load

logger = logging.getLogger(__name__)

_lib = None
_load_failed = False


def _load():
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    if os.environ.get('PETASTORM_TPU_NO_NATIVE'):
        _load_failed = True
        return None
    try:
        lib = build_and_load('pst_pinned', ['pinned.cc'])
    except NativeBuildError as exc:
        logger.warning('native pinned allocator unavailable, '
                       'falling back to mmap: %s', exc)
        _load_failed = True
        return None
    lib.pst_pinned_alloc.restype = ctypes.c_int
    lib.pst_pinned_alloc.argtypes = [ctypes.c_size_t, ctypes.c_int,
                                     ctypes.POINTER(ctypes.c_void_p)]
    lib.pst_pinned_free.restype = None
    lib.pst_pinned_free.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                    ctypes.c_int]
    _lib = lib
    return _lib


def available():
    """True when the compiled allocator is usable (mmap fallback not
    counted — callers that care about the tier read ``PinnedSlab.mode``)."""
    return _load() is not None


class PinnedSlab(object):
    """One page-aligned host allocation; freed on :meth:`free` or GC.

    ``array`` is a ``np.uint8`` view of the whole slab; ``locked`` says
    whether ``mlock`` actually succeeded (page-aligned-only slabs are
    still useful — alignment and pre-faulting are most of the win).
    """

    def __init__(self, array, nbytes, locked, mode, release):
        self.array = array
        self.nbytes = nbytes
        self.locked = locked
        self.mode = mode
        self._finalizer = weakref.finalize(self, release)

    def free(self):
        self._finalizer()


def _allocate_native(nbytes, lock):
    lib = _load()
    if lib is None:
        return None
    ptr = ctypes.c_void_p()
    rc = lib.pst_pinned_alloc(nbytes, 1 if lock else 0, ctypes.byref(ptr))
    if rc < 0 or not ptr.value:
        return None
    buf = (ctypes.c_ubyte * nbytes).from_address(ptr.value)
    arr = np.frombuffer(buf, dtype=np.uint8)
    addr, locked = ptr.value, bool(rc)

    def release(lib=lib, addr=addr, nbytes=nbytes, locked=locked):
        lib.pst_pinned_free(addr, nbytes, 1 if locked else 0)

    return PinnedSlab(arr, nbytes, locked, 'native', release)


def _mlock_via_libc(addr, nbytes):
    try:
        libc = ctypes.CDLL(None, use_errno=True)
        return libc.mlock(ctypes.c_void_p(addr), ctypes.c_size_t(nbytes)) == 0
    except Exception:  # noqa: BLE001 - no libc / no mlock: stay unlocked
        return False


def _allocate_mmap(nbytes, lock):
    try:
        m = mmap_mod.mmap(-1, nbytes)
    except (OSError, ValueError, OverflowError):
        return None
    arr = np.frombuffer(m, dtype=np.uint8)
    locked = bool(lock) and _mlock_via_libc(arr.ctypes.data, nbytes)

    def release(m=m):
        try:
            m.close()
        except BufferError:  # a view still exported: the GC will get it
            pass

    return PinnedSlab(arr, nbytes, locked, 'mmap', release)


def allocate(nbytes, lock=True):
    """A :class:`PinnedSlab` of ``nbytes`` (page-aligned, best-effort
    mlocked) or ``None`` when no tier can serve it."""
    nbytes = int(nbytes)
    if nbytes <= 0:
        return None
    slab = _allocate_native(nbytes, lock)
    if slab is None:
        slab = _allocate_mmap(nbytes, lock)
    return slab
