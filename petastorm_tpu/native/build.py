"""On-demand compilation of the C++ sources in ``petastorm_tpu/native/src``.

A tiny build system instead of a packaging-time ``build_ext``: sources are
compiled lazily on first use with ``g++`` into a content-hash-keyed shared
object under ``~/.cache/petastorm_tpu/native`` (override with
``PETASTORM_TPU_NATIVE_CACHE``), so editing a .cc file triggers exactly one
rebuild and concurrent processes race safely (atomic rename + lock file).
"""

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading
import time

logger = logging.getLogger(__name__)

_SRC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'src')
_LOCK = threading.Lock()
_LOADED = {}
_REPORT = {}


def native_cache_dir():
    cache = os.environ.get('PETASTORM_TPU_NATIVE_CACHE')
    if not cache:
        cache = os.path.join(os.path.expanduser('~'), '.cache', 'petastorm_tpu', 'native')
    os.makedirs(cache, exist_ok=True)
    return cache


def source_path(filename):
    return os.path.join(_SRC_DIR, filename)


def _build_key(sources, compile_flags, link_flags):
    h = hashlib.sha256()
    for src in sources:
        with open(src, 'rb') as f:
            h.update(f.read())
        h.update(b'\0')
    h.update(' '.join(compile_flags + link_flags).encode())
    return h.hexdigest()[:16]


def build_and_load(name, sources, compile_flags=None, link_flags=None):
    """Compile ``sources`` (paths under src/) into lib<name>-<hash>.so and dlopen it.

    Returns a ``ctypes.CDLL``. Raises ``NativeBuildError`` when the toolchain
    or a dependency is missing; callers catch it and fall back to Python paths.
    """
    compile_flags = list(compile_flags or [])
    link_flags = list(link_flags or [])
    srcs = [s if os.path.isabs(s) else source_path(s) for s in sources]

    with _LOCK:
        cached = _LOADED.get(name)
        if cached is not None:
            return cached

        key = _build_key(srcs, compile_flags, link_flags)
        out_path = os.path.join(native_cache_dir(), 'lib{}-{}.so'.format(name, key))
        t0 = time.perf_counter()
        if not os.path.exists(out_path):
            # Cross-process lock: N spawned workers hitting a cold cache
            # should compile once, not N times.
            import fcntl
            with open(out_path + '.lock', 'w') as lock_file:  # pstlint: disable=lock-order-blocking(one-time lazy build path: serializing every in-process caller behind the flock'd compile IS the contract — N threads hitting a cold cache must produce one .so, then the _LOADED memo makes this branch unreachable)
                fcntl.flock(lock_file, fcntl.LOCK_EX)
                if not os.path.exists(out_path):
                    _compile(srcs, out_path, compile_flags, link_flags)
        lib = ctypes.CDLL(out_path)
        _LOADED[name] = lib
        _REPORT[name] = {'source_hash': key, 'path': out_path,
                         'build_or_wait_s': round(time.perf_counter() - t0, 3)}
        return lib


def build_report():
    """``{library: {'source_hash', 'path', 'build_or_wait_s'}}`` for every
    library this process loaded: the content hash its ``.so`` is keyed by
    and the seconds this process spent compiling it or waiting for another
    process that was (0 when the cache already held it)."""
    with _LOCK:
        return {name: dict(rec) for name, rec in _REPORT.items()}


class NativeBuildError(RuntimeError):
    pass


def _compile(srcs, out_path, compile_flags, link_flags):
    fd, tmp = tempfile.mkstemp(suffix='.so', dir=os.path.dirname(out_path))
    os.close(fd)
    cmd = (['g++', '-O3', '-std=c++17', '-fPIC', '-shared', '-pthread']
           + compile_flags + srcs + ['-o', tmp] + link_flags)
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as exc:
        os.unlink(tmp)
        raise NativeBuildError('failed to run g++: {}'.format(exc))
    if proc.returncode != 0:
        os.unlink(tmp)
        raise NativeBuildError(
            'native build failed ({}):\n{}'.format(' '.join(cmd), proc.stderr[-4000:]))
    os.replace(tmp, out_path)  # atomic: concurrent builders converge on the same key
    logger.info('built native library %s', out_path)
