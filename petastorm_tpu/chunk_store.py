"""Mmap-backed decoded-chunk store: the NVMe cache tier.

A jpeg pipeline is decode-bound cold and memcpy-bound warm (no chip record
bears on the rates), and the other cache tiers leave a hole:
``DeviceDatasetCache`` needs the dataset in HBM, ``MemoryCache`` needs it
in RAM *per process* (no sharing across a process pool), and
``LocalDiskCache`` historically stored **encoded** bytes behind pickle, so
every epoch re-paid decode plus a deserialize copy (the reference
petastorm's ``local_disk_cache.py`` has the same shape). tf.data's
snapshot/cache and NVIDIA DALI's decoded-cache design (PAPERS.md) both
show that persisting *post-decode* tensors in their final memory layout is
the tier that actually removes the CPU from steady-state epochs.

:class:`DecodedChunkStore` is that tier, TPU-host-native:

* **Epoch 0 (fill)**: decoded column blocks coming off the
  ``TensorWorker`` path are handed to a background writer thread
  (write-behind — the decode hot path never blocks on NVMe) which
  serializes them into one file per (dataset fingerprint, row-group,
  schema hash) key: a small JSON header with per-field dtype/shape/offset
  records plus a CRC32 per field, then the raw field buffers, 64-byte
  aligned, written to a temp file and **atomically renamed** into place
  under an ``flock``'d lock file — concurrent writers from a process pool
  produce exactly one entry and a reader can never observe a torn chunk.
* **Epoch >= 1 (serve)**: the entry is ``mmap``'d (validated once per
  process per entry) and the store hands out numpy views straight over the
  mapping. The views travel the existing ``reader.last_chunk_private=False``
  shared-block protocol, so the staging engine's block fast path copies
  once, mmap -> arena, with no decode, no pickle, and no per-process
  duplication: every pool worker and every training process shares the
  same page-cache pages. A dataset bigger than RAM but smaller than NVMe
  trains at memcpy speed served by the page cache.
* **Robustness**: a corrupt or truncated entry (bad magic, short file,
  CRC mismatch — or the ``store-read-corrupt`` fault site) is quarantined
  (renamed to ``*.corrupt``) and transparently refilled by re-decode; a
  re-decode failure flows into the PR-1 ``error_budget`` quarantine
  machinery instead of crashing the epoch.
* **Autotune hookup**: :meth:`set_writer_throttled` pauses the write-behind
  writer; the autotuner arms it while the pipeline itself is the
  bottleneck (see :func:`petastorm_tpu.autotune.writer_throttle_listener`)
  so epoch-0 spill never steals decode throughput. Dropped writes are
  self-healing — the chunk misses again next epoch and re-enqueues.

The on-disk layout (:func:`pack_tensor_chunk`) is shared with
``LocalDiskCache``'s ndarray-dict fast path so both tiers speak one
format::

    magic 'PSTC' | u16 version | u32 header_len | u64 data_start
    header JSON {fields: [{name, dtype, shape, offset, nbytes, crc32}]}
    ...padding to 64-byte alignment...
    field payloads (each 64-byte aligned, offsets relative to data_start)

Activation: ``cache_type='chunk-store'`` on the reader factories (location
from ``cache_location`` or the ``PETASTORM_TPU_CHUNK_STORE`` environment
variable), or set the env var alone — ``make_tensor_reader`` with the
default ``cache_type`` then adopts the store without a code change.

Offline pre-fill: ``python -m petastorm_tpu.tools.transcode`` walks a
dataset through the tensor decode path once and publishes every chunk via
this module's flock'd single-writer protocol, so a production job's
epoch 0 already serves from the store (``decode_s`` = 0) — the
``pre-transcoded`` row of the decode-paths table (docs/tpu_guide.rst).
"""

import hashlib
import json
import logging
import mmap
import os
import queue
import shutil
import struct
import tempfile
import threading
import time
import zlib
from collections import OrderedDict

import numpy as np

from petastorm_tpu.cache import CacheBase
from petastorm_tpu.errors import CorruptChunkError

logger = logging.getLogger(__name__)

ENV_VAR = 'PETASTORM_TPU_CHUNK_STORE'

#: Temp-dir prefix for stores created without an explicit directory; the
#: conftest ``chunkstore`` guard deletes leaked matches.
TEMP_DIR_PREFIX = 'pst-chunk-store-'

_MAGIC = b'PSTC'
_VERSION = 1
_PREAMBLE = struct.Struct('<4sHIQ')   # magic, version, header_len, data_start
_ALIGN = 64                           # per-field payload alignment
_ENTRY_SUFFIX = '.chunk'

#: Age past which an orphaned ``*.tmp``/``*.lock`` file cannot belong to a
#: live write (a write holds its temp file for seconds): swept at store
#: init so killed workers (chaos/respawn paths) don't leak chunk-sized
#: invisible-to-eviction files forever.
_STALE_SCRATCH_S = 600

_STOP = object()


def _file_fingerprint(path):
    """size+mtime of the row-group's parquet file — the content component
    of the store key. An epoch-persistent store outlives sessions, so a
    dataset *regenerated in place* (same URL, same file names) must miss
    and refill, never serve stale decoded tensors; size+mtime_ns changes
    on any rewrite. Remote stores (no local stat) get a constant — for
    them only URL/field drift invalidates (documented limitation)."""
    try:
        st = os.stat(path)
        return '{}-{}'.format(st.st_size, st.st_mtime_ns)
    except (OSError, ValueError):
        return 'nofp'


def tensor_chunk_key(dataset_path_hash, piece_path, row_group, schema):
    """The cache key of one decoded row-group chunk: (dataset fingerprint,
    row-group id, parquet-file content fingerprint, schema hash). Shared
    between ``TensorWorker`` (store lookup ahead of decode) and ``Reader``
    (ventilation-order readahead) so the two sides can never drift apart.
    Chunks are cached *pre-transform*, so a TransformSpec does not enter
    the key — the same store serves any transform over the same decoded
    fields."""
    schema_digest = hashlib.md5(
        ','.join(sorted(schema.fields)).encode()).hexdigest()[:8]
    return 'tensor:{}:{}:{}:{}:{}'.format(
        dataset_path_hash, piece_path, row_group,
        _file_fingerprint(str(piece_path)), schema_digest)


def _align(offset):
    return (offset + _ALIGN - 1) // _ALIGN * _ALIGN


def conforms_tensor_chunk(value):
    """True when ``value`` is storable in the raw-buffer layout: a
    non-empty dict of numpy arrays with plain buffer-protocol dtypes.
    Object columns (decoded string scalars) cannot be mmapped back, and
    structured/void dtypes don't survive the ``dtype.str`` round trip
    (field names would silently drop) — both fall back to pickle in
    ``LocalDiskCache`` / pass through uncached here."""
    if not isinstance(value, dict) or not value:
        return False
    for v in value.values():
        if not isinstance(v, np.ndarray) or v.dtype.kind in ('O', 'V'):
            return False
    return True


def _field_records(cols):
    """Per-field header records + the contiguous buffers to write, with
    payload offsets relative to the data section."""
    records, buffers = [], []
    offset = 0
    for name in sorted(cols):
        arr = np.ascontiguousarray(cols[name])
        if arr.dtype.kind in ('M', 'm'):
            # The buffer protocol refuses datetime64/timedelta64 exports,
            # but their bytes are plain int64 ticks — view them as raw
            # bytes for the write; the header dtype string ('<M8[ns]')
            # restores the real dtype on read (np.frombuffer accepts it).
            mv = memoryview(arr.view(np.uint8)).cast('B')
        else:
            mv = memoryview(arr).cast('B')
        offset = _align(offset)
        records.append({'name': name,
                        'dtype': arr.dtype.str,
                        'shape': list(arr.shape),
                        'offset': offset,
                        'nbytes': arr.nbytes,
                        'crc32': zlib.crc32(mv) & 0xFFFFFFFF})
        buffers.append(mv)
        offset += arr.nbytes
    return records, buffers


def write_tensor_chunk(f, cols):
    """Serialize ``{name: ndarray}`` into open binary file ``f`` in the
    store layout. Returns the total bytes written."""
    records, buffers = _field_records(cols)
    header = json.dumps({'fields': records}).encode('utf-8')
    data_start = _align(_PREAMBLE.size + len(header))
    f.write(_PREAMBLE.pack(_MAGIC, _VERSION, len(header), data_start))
    f.write(header)
    pos = _PREAMBLE.size + len(header)
    for record, mv in zip(records, buffers):
        target = data_start + record['offset']
        if target > pos:
            f.write(b'\0' * (target - pos))
            pos = target
        f.write(mv)
        pos += record['nbytes']
    return pos


def pack_tensor_chunk(cols):
    """:func:`write_tensor_chunk` into bytes (the ``LocalDiskCache``
    ndarray-dict serialization path)."""
    import io
    sink = io.BytesIO()
    write_tensor_chunk(sink, cols)
    return sink.getvalue()


def is_tensor_chunk(blob):
    """True when ``blob`` (bytes-like) starts with the store layout magic."""
    return bytes(blob[:4]) == _MAGIC


def read_tensor_chunk(buf, validate=True, source='<buffer>'):
    """Parse the store layout over ``buf`` (bytes or mmap) into a dict of
    numpy views — zero-copy; the arrays alias ``buf``. Raises
    :class:`~petastorm_tpu.errors.CorruptChunkError` on any structural or
    checksum mismatch (truncation, bit rot, torn write of a non-atomic
    copy)."""
    size = len(buf)
    if size < _PREAMBLE.size:
        raise CorruptChunkError('{}: short preamble ({} bytes)'.format(source, size))
    magic, version, header_len, data_start = _PREAMBLE.unpack_from(buf, 0)
    if magic != _MAGIC:
        raise CorruptChunkError('{}: bad magic {!r}'.format(source, magic))
    if version != _VERSION:
        raise CorruptChunkError('{}: unsupported version {}'.format(source, version))
    if _PREAMBLE.size + header_len > size or data_start > size:
        raise CorruptChunkError('{}: truncated header'.format(source))
    try:
        header = json.loads(bytes(buf[_PREAMBLE.size:_PREAMBLE.size + header_len])
                            .decode('utf-8'))
        fields = header['fields']
    except (ValueError, KeyError, UnicodeDecodeError) as e:
        raise CorruptChunkError('{}: unparsable header: {}'.format(source, e))
    cols = {}
    for record in fields:
        # The CRCs cover payloads only; a bit-flip in the header itself can
        # keep the JSON parseable while mangling dtype/shape/offset — every
        # header-derived value must validate into CorruptChunkError, never
        # escape as TypeError/ValueError (that would crash the epoch the
        # quarantine machinery exists to save).
        try:
            name = record['name']
            dtype = np.dtype(str(record['dtype']))
            shape = tuple(int(d) for d in record['shape'])
            nbytes = int(record['nbytes'])
            start = data_start + int(record['offset'])
            crc = int(record['crc32'])
        except (TypeError, ValueError, KeyError) as e:
            raise CorruptChunkError('{}: bad field record: {}'.format(source, e))
        if dtype.hasobject or dtype.itemsize == 0:
            # An unluckily-mangled dtype string can still parse (e.g. '|O',
            # 'V0'); frombuffer would raise ValueError/ZeroDivisionError.
            raise CorruptChunkError('{}: field {!r} has non-buffer dtype {}'
                                    .format(source, name, dtype))
        expected = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        if expected != nbytes or nbytes < 0 or min(shape, default=0) < 0:
            raise CorruptChunkError(
                '{}: field {!r} shape {} x {} does not cover {} bytes'
                .format(source, name, shape, dtype, nbytes))
        if start < 0 or start + nbytes > size:
            raise CorruptChunkError('{}: field {!r} extends past EOF'
                                    .format(source, name))
        view = memoryview(buf)[start:start + nbytes]
        if validate and (zlib.crc32(view) & 0xFFFFFFFF) != crc:
            raise CorruptChunkError('{}: field {!r} checksum mismatch'
                                    .format(source, name))
        try:
            arr = np.frombuffer(buf, dtype=dtype,
                                count=nbytes // dtype.itemsize, offset=start)
            cols[name] = arr.reshape(shape)
        except (ValueError, TypeError) as e:
            # Belt and braces: whatever numpy refuses is corruption here.
            raise CorruptChunkError('{}: field {!r} unmappable: {}'
                                    .format(source, name, e))
    return cols


class _OpenEntry(object):
    """One validated, mmapped store entry (kept open in a per-process LRU).

    The mmap is never explicitly closed: views of it may be anywhere in
    the pipeline (staged batches, arena holds), and ``mmap.close`` with
    exported buffers raises. Dropping the entry from the LRU lets the
    mapping die with its last view."""

    __slots__ = ('mm', 'views', 'nbytes')

    def __init__(self, mm, views, nbytes):
        self.mm = mm
        self.views = views
        self.nbytes = nbytes

    @classmethod
    def open(cls, path, validate=True):
        with open(path, 'rb') as f:
            if os.fstat(f.fileno()).st_size == 0:
                raise CorruptChunkError('{}: empty entry'.format(path))
            # ACCESS_COPY (MAP_PRIVATE copy-on-write), not ACCESS_READ: the
            # read path is identical — zero-copy views over shared page
            # cache — but the views stay WRITEABLE, which keeps downstream
            # zero-copy paths (DLPack export refuses read-only buffers and
            # the loader would silently fall back to a per-batch memcpy).
            # A protocol-violating in-process write diverges onto a private
            # page instead of corrupting the store every other process
            # shares — strictly safer than MemoryCache, where the same bug
            # corrupts every later epoch.
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_COPY)
        views = read_tensor_chunk(mm, validate=validate, source=path)
        return cls(mm, views, len(mm))

    def willneed(self):
        """Hint the kernel to fault this entry's pages in ahead of the
        collate copy (no-op where madvise is unavailable)."""
        if hasattr(self.mm, 'madvise'):
            try:
                self.mm.madvise(mmap.MADV_WILLNEED)
            except (OSError, ValueError):  # pragma: no cover - advisory only
                pass


class DecodedChunkStore(CacheBase):
    """Epoch-persistent, cross-process decoded-chunk cache on local NVMe.

    Plugs into the worker-side ``cache.get(key, fill_fn)`` protocol of the
    tensor path (values are ``{field: ndarray}`` column blocks). Misses
    run ``fill_fn`` (read + decode) and hand the result to a background
    write-behind thread; hits return zero-copy numpy views over the
    mmapped (copy-on-write) entry. Unlike :class:`~petastorm_tpu.cache.MemoryCache` the
    store is shared **across a process pool**: each worker process opens
    the same files, so the dataset is decoded once per host, not once per
    process, and warm reads all hit the same page-cache pages.

    :param path: store directory (created if missing). ``None`` reads the
        ``PETASTORM_TPU_CHUNK_STORE`` environment variable.
    :param size_limit: approximate total entry bytes; oldest-mtime entries
        are evicted after a write pushes past it. ``None`` = unlimited.
    :param writer_queue_depth: pending write-behind chunks; an overflowing
        queue DROPS the write (``stats()['write_skipped']``) rather than
        ever blocking the decode path — the chunk re-enqueues on its next
        epoch's miss.
    :param throttle_delay_s: writer pause granularity while throttled.
    :param validate: ``'open'`` (default) checks every field's CRC32 once
        per process when an entry is first mmapped; ``'off'`` trusts the
        bytes (experiments only).
    :param cleanup: remove the whole store directory on :meth:`cleanup`.
    """

    #: Diagnostics gate (``Reader.diagnostics()['chunk_store']``).
    is_chunk_store = True
    #: Provenance serving-tier label (``petastorm_tpu.lineage``): a chunk
    #: served from this store is an NVMe mmap hit, not a fresh decode.
    lineage_tier = 'chunk-store'

    def __init__(self, path=None, size_limit=None, writer_queue_depth=16,
                 throttle_delay_s=0.05, validate='open', cleanup=False,
                 max_open_entries=1024, **_):
        if path is None:
            path = os.environ.get(ENV_VAR) or None
        if not path:
            raise ValueError(
                "DecodedChunkStore needs a directory: pass cache_location or "
                "set the {} environment variable".format(ENV_VAR))
        self._config = {'path': path, 'size_limit': size_limit,
                        'writer_queue_depth': writer_queue_depth,
                        'throttle_delay_s': throttle_delay_s,
                        'validate': validate, 'cleanup': cleanup,
                        'max_open_entries': max_open_entries}
        self._init_from_config()

    def _init_from_config(self):
        cfg = self._config
        self._path = cfg['path']
        self._size_limit = cfg['size_limit']
        self._queue_depth = max(1, int(cfg['writer_queue_depth']))
        self._throttle_delay_s = float(cfg['throttle_delay_s'])
        self._validate = cfg['validate'] != 'off'
        self._do_cleanup = bool(cfg['cleanup'])
        self._max_open = max(1, int(cfg['max_open_entries']))
        os.makedirs(self._path, exist_ok=True)
        self._sweep_stale_scratch()
        self._lock = threading.RLock()
        self._entries = OrderedDict()      # digest -> _OpenEntry (LRU)
        # Entries validated once per process: a store larger than the open-
        # entry LRU (the tier's flagship case) must not re-CRC a full
        # entry on every post-eviction reopen — entries are immutable
        # (atomic-rename published), so one payload pass per process is
        # enough. A quarantine drops the digest again.
        self._validated = set()
        self._writeq = None                # lazily started with the thread
        self._writeq_bytes = 0             # decoded bytes pinned by the queue
        self._writer = None
        self._stopping = False
        self._throttled = False
        self._spill_paused = False         # memory governor's advisory hook
        self._dir_bytes = None   # running size estimate; None = needs a scan
        # Registry mirror (petastorm_tpu.metrics): the same counters as
        # scrapable instruments — one registry.collect() then covers the
        # NVMe tier next to staging/autotune/watchdog without a reader
        # handle. Worker PROCESSES count in their own registries (the
        # entry files are still shared); thread pools cover the pipeline.
        from petastorm_tpu import metrics as metrics_mod
        self._m = {name: metrics_mod.counter(
            'pst_chunk_store_{}_total'.format(name),
            'Decoded-chunk store {} count'.format(name.replace('_', ' ')))
            for name in ('hits', 'misses', 'fills', 'writes',
                         'write_skipped', 'corrupt', 'bytes_written',
                         'bytes_mapped', 'readaheads', 'unstorable')}
        # counters (read via stats(); guarded by _lock)
        self.hits = 0
        self.misses = 0
        self.fills = 0          # fill_fn calls that produced a chunk
                                # (misses minus empty row-groups)
        self.writes = 0
        self.write_skipped = 0
        self.write_races = 0    # another process won the flock first
        self.corrupt = 0
        self.bytes_written = 0
        self.bytes_mapped = 0
        self.readaheads = 0
        self.unstorable = 0

    def _sweep_stale_scratch(self):
        """Unlink ``*.tmp``/``*.lock`` files older than ``_STALE_SCRATCH_S``:
        a worker killed between ``mkstemp`` and the atomic rename leaves a
        chunk-sized temp file no rename will ever claim (and size-cap
        eviction only reclaims published entries)."""
        now = time.time()
        try:
            names = os.listdir(self._path)
        except OSError:  # pragma: no cover - directory racing a cleanup
            return
        for name in names:
            if not name.endswith(('.tmp', '.lock')):
                continue
            full = os.path.join(self._path, name)
            try:
                if now - os.stat(full).st_mtime > _STALE_SCRATCH_S:
                    os.unlink(full)
            except OSError:  # pragma: no cover - already gone
                continue

    # -- pickling (process pools ship the cache inside worker args) -------

    def __getstate__(self):
        return {'config': dict(self._config)}

    def __setstate__(self, state):
        self._config = state['config']
        self._init_from_config()

    # -- key/paths ---------------------------------------------------------

    @staticmethod
    def _digest(key):
        return hashlib.md5(str(key).encode('utf-8')).hexdigest()

    def _entry_path(self, key):
        return os.path.join(self._path, self._digest(key) + _ENTRY_SUFFIX)

    # -- read path ---------------------------------------------------------

    def _quarantine(self, path, error):
        """A corrupt/truncated entry must never be served OR retried
        forever: move it aside (post-mortem debuggable) and let the caller
        refill by re-decode."""
        logger.warning('chunk store entry quarantined: %s', error)
        try:
            os.replace(path, path + '.corrupt')
        except OSError:
            try:
                os.unlink(path)
            except OSError:  # pragma: no cover - already gone
                pass
        from petastorm_tpu.trace import get_global_tracer
        get_global_tracer().instant('chunk_store_quarantine', cat='fault')

    def _open_entry(self, key):
        """The validated entry for ``key``, opening+checking it on first
        touch, or ``None`` (absent or quarantined-just-now)."""
        digest = self._digest(key)
        with self._lock:
            entry = self._entries.get(digest)
            if entry is not None:
                self._entries.move_to_end(digest)
                return entry
        path = os.path.join(self._path, digest + _ENTRY_SUFFIX)
        if not os.path.exists(path):
            return None
        # Open + CRC-validate OUTSIDE the store lock: first-touch validation
        # is a full NVMe read of the entry, and holding the lock across it
        # would serialize every concurrent worker hit (and the ventilator's
        # readahead) behind one disk scan. Two threads racing the same
        # entry just validate twice; the insert below keeps one winner.
        with self._lock:
            validate = self._validate and digest not in self._validated
        try:
            from petastorm_tpu.faults import get_injector
            if get_injector().should_fire('store-read-corrupt', key=str(key)):
                raise CorruptChunkError(
                    '{}: injected fault store-read-corrupt (key={!r})'
                    .format(path, key))
            entry = _OpenEntry.open(path, validate=validate)
        except CorruptChunkError as e:
            with self._lock:
                self.corrupt += 1
                self._m['corrupt'].inc()
                self._validated.discard(digest)
            self._quarantine(path, e)
            return None
        except OSError as e:
            logger.warning('chunk store entry %s unreadable: %s', path, e)
            return None
        with self._lock:
            winner = self._entries.get(digest)
            if winner is not None:      # lost an open race: serve the winner
                self._entries.move_to_end(digest)
                return winner
            self._entries[digest] = entry
            self._validated.add(digest)
            self.bytes_mapped += entry.nbytes
            self._m['bytes_mapped'].inc(entry.nbytes)
            while len(self._entries) > self._max_open:
                # Dropped, not closed: live views keep the mapping alive.
                self._entries.popitem(last=False)
            return entry

    def readahead(self, key):
        """Fault-in hint for a row-group the ventilator just scheduled:
        ``madvise(WILLNEED)`` over the entry's extents so the pages are
        resident by the time a worker's hit copies them toward an arena.
        Deliberately does NOT parse or CRC-validate the entry — this runs
        on the single ventilator feed thread, and forcing first-touch
        validation there would serialize behind one thread what the N
        workers otherwise validate in parallel; a not-yet-open entry is
        just mmapped, hinted, and dropped (the pages stay in the cache).
        Returns True when an entry was hinted."""
        digest = self._digest(key)
        with self._lock:
            entry = self._entries.get(digest)
        if entry is not None:
            entry.willneed()
        else:
            path = os.path.join(self._path, digest + _ENTRY_SUFFIX)
            try:
                with open(path, 'rb') as f:
                    if os.fstat(f.fileno()).st_size == 0:
                        return False
                    mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
            except (OSError, ValueError):
                return False
            if hasattr(mm, 'madvise'):
                try:
                    mm.madvise(mmap.MADV_WILLNEED)
                except (OSError, ValueError):  # pragma: no cover - advisory
                    pass
            mm.close()   # nothing exported; the page-cache warmth remains
        with self._lock:
            self.readaheads += 1
            self._m['readaheads'].inc()
        return True

    # -- CacheBase protocol ------------------------------------------------

    def get(self, key, fill_cache_func):
        entry = self._open_entry(key)
        if entry is not None:
            with self._lock:
                self.hits += 1
                hits = self.hits
                self._m['hits'].inc()
            from petastorm_tpu.trace import get_global_tracer
            get_global_tracer().counter('chunk_store_hits', hits, 'chunk-store')
            # A fresh shallow dict per hit: callers slice/pop their copy
            # (resume skip, transform field filtering) without aliasing
            # another worker's view dict. The arrays themselves are the
            # shared read-only mmap views — the last_chunk_private=False
            # protocol guarantees downstream only ever copies FROM them.
            return dict(entry.views)
        with self._lock:
            self.misses += 1
            self._m['misses'].inc()
        value = fill_cache_func()
        if value is None:
            return None
        with self._lock:
            self.fills += 1   # actual decoded chunks (None = empty row-group)
            self._m['fills'].inc()
        if conforms_tensor_chunk(value):
            self._enqueue_write(key, value)
        else:
            with self._lock:
                self.unstorable += 1
                self._m['unstorable'].inc()
        return value

    def has(self, key):
        """True when ``key`` is already persisted (no mmap is opened —
        an existence probe, not a read)."""
        return os.path.exists(self._entry_path(key))

    def put(self, key, cols):
        """Synchronous fill: persist ``{field: ndarray}`` under ``key``
        NOW (fsync + atomic rename), bypassing the write-behind queue.
        The warm-join protocol uses this — a joining replica pre-filling
        from a peer needs durability it can assert, not best-effort
        spill that may have been shed under pressure. Returns True when
        the entry is on disk (already present counts), False when the
        value does not conform to the dense-chunk layout."""
        if not conforms_tensor_chunk(cols):
            with self._lock:
                self.unstorable += 1
                self._m['unstorable'].inc()
            return False
        self._write_entry(key, cols)
        return True

    # -- write-behind ------------------------------------------------------

    def _enqueue_write(self, key, cols):
        with self._lock:
            if self._stopping:
                return
            if self._spill_paused:
                # Advisory rung: refuse new spill work instead of pinning
                # decoded bytes in the queue — counted, never silent.
                self.write_skipped += 1
                self._m['write_skipped'].inc()
                return
            if self._writer is None:
                self._writeq = queue.Queue(maxsize=self._queue_depth)
                self._writer = threading.Thread(
                    target=self._writer_loop, daemon=True,
                    name='pst-chunk-store-writer')
                self._writer.start()
            nbytes = sum(int(getattr(arr, 'nbytes', 0)) for arr in cols.values())
            try:
                self._writeq.put_nowait((key, cols, nbytes))
                self._writeq_bytes += nbytes
            except queue.Full:
                # NEVER block decode on NVMe: drop, self-heals next epoch.
                self.write_skipped += 1
                self._m['write_skipped'].inc()

    def set_spill_paused(self, paused):
        """Memory-governor advisory hook: while True, new spill work is
        REFUSED at enqueue (counted as ``write_skipped``, self-healing on
        the chunk's next-epoch miss) and the already-queued backlog keeps
        draining to NVMe. Refusing-at-enqueue rather than holding the
        writer matters: a held writer would PIN a full queue of decoded
        chunks for the whole advisory episode — the relief rung would
        itself sustain the pressure (and could latch the ladder at
        advisory forever on a tight budget). Released the moment the
        ladder leaves the advisory band."""
        self._spill_paused = bool(paused)

    @property
    def spill_paused(self):
        return self._spill_paused

    def set_writer_throttled(self, throttled):
        """Autotune hookup: while True the write-behind writer is PACED —
        one entry per ``throttle_delay_s`` — so epoch-0 spill cedes CPU and
        NVMe bandwidth to a pipeline that is already the bottleneck without
        ever starving the fill. A hard pause would deadlock the tier's
        whole point on decode-bound workloads: the fill epochs ARE the
        reader-starved epochs, and a writer that stops during them never
        populates the store at all (everything drops as write_skipped)."""
        self._throttled = bool(throttled)

    @property
    def writer_throttled(self):
        return self._throttled

    def _writer_loop(self):
        while True:
            item = self._writeq.get()
            try:
                if item is _STOP:
                    return
                # Paced, not paused (see set_writer_throttled): yield for at
                # most throttle_delay_s per entry, waking early on
                # unthrottle/stop so flush() and close() stay prompt.
                waited = 0.0
                while (self._throttled and not self._stopping
                       and waited < self._throttle_delay_s):
                    time.sleep(0.005)
                    waited += 0.005
                key, cols, nbytes = item
                try:
                    self._write_entry(key, cols)
                except Exception:  # noqa: BLE001 - spill must never kill the pipe
                    logger.exception('chunk store write-behind failed for %r', key)
                with self._lock:
                    self._writeq_bytes = max(0, self._writeq_bytes - nbytes)
            finally:
                self._writeq.task_done()

    def _write_entry(self, key, cols):
        import fcntl
        path = self._entry_path(key)
        if os.path.exists(path):
            return
        # flock'd lock file: of N pool processes decoding the same
        # row-group (epoch-boundary duplicate dispatch), exactly one pays
        # the serialize+write; the others skip on the existence re-check.
        lock_path = path + '.lock'
        with open(lock_path, 'a') as lock_file:
            fcntl.flock(lock_file, fcntl.LOCK_EX)
            try:
                if os.path.exists(path):
                    with self._lock:
                        self.write_races += 1
                    return
                fd, tmp = tempfile.mkstemp(dir=self._path, suffix='.tmp')
                try:
                    with os.fdopen(fd, 'wb') as f:
                        nbytes = write_tensor_chunk(f, cols)
                        f.flush()
                        os.fsync(f.fileno())
                    # Atomic publish: a concurrent reader sees either no
                    # entry or the complete one — never a torn chunk.
                    os.replace(tmp, path)
                except BaseException:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                    raise
                # Published: the lock file has served its purpose. A racer
                # already blocked on it locks the orphaned inode, re-checks
                # existence, and skips; the pathological interleaving
                # (quarantine between) at worst double-writes through the
                # same atomic-rename path — still never a torn read.
                try:
                    os.unlink(lock_path)
                except OSError:  # pragma: no cover - already gone
                    pass
            finally:
                fcntl.flock(lock_file, fcntl.LOCK_UN)
        with self._lock:
            self.writes += 1
            self.bytes_written += nbytes
            writes = self.writes
            self._m['writes'].inc()
            self._m['bytes_written'].inc(nbytes)
        from petastorm_tpu.trace import get_global_tracer
        get_global_tracer().counter('chunk_store_writes', writes, 'chunk-store')
        self._maybe_evict(nbytes)

    def _maybe_evict(self, new_bytes=0):
        """Size-cap enforcement, amortized: a running byte estimate grows
        with each write and the full directory scan (O(entries) stats)
        only runs when the estimate crosses the limit — not per write.
        Quarantined ``*.corrupt`` files count toward (and age out of) the
        budget like live entries; the estimate resyncs from every scan."""
        if self._size_limit is None:
            return
        with self._lock:
            if self._dir_bytes is not None:
                self._dir_bytes += new_bytes
                if self._dir_bytes <= self._size_limit:
                    return
        entries, total = [], 0
        for name in os.listdir(self._path):
            if not name.endswith((_ENTRY_SUFFIX, '.corrupt')):
                continue
            full = os.path.join(self._path, name)
            try:
                st = os.stat(full)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, full))
            total += st.st_size
        if total > self._size_limit:
            entries.sort()  # oldest first
            for _, size, full in entries:
                try:
                    os.unlink(full)
                except OSError:
                    continue
                total -= size
                if total <= self._size_limit:
                    break
        with self._lock:
            self._dir_bytes = total

    # -- memory-governor accounting (membudget.py) -------------------------

    def governed_nbytes(self):
        """Bytes this store currently pins in host memory: decoded chunks
        parked in the write-behind queue plus the resident open-entry
        mmaps (ACCESS_COPY mappings occupy page cache / private pages for
        every byte a hit has touched — the upper bound is the mapped
        size, which is what a budget must assume)."""
        with self._lock:
            mapped = sum(entry.nbytes for entry in self._entries.values())
            return self._writeq_bytes + mapped

    def close_lru_mmaps(self, keep_frac=0.5):
        """Drop the least-recently-used open entries until at most
        ``keep_frac`` of them remain (the governor's *degrade* hook). The
        mappings are dropped, not closed — live views keep their pages
        alive until the consumer releases them (the same rule the
        ``max_open_entries`` LRU follows) — so this is safe at any time;
        a dropped entry just re-mmaps (without re-CRC: the per-process
        validated set survives) on its next hit. Returns the mapped bytes
        released from the accounting."""
        freed = 0
        with self._lock:
            keep = int(len(self._entries) * float(keep_frac))
            while len(self._entries) > keep:
                _, entry = self._entries.popitem(last=False)
                freed += entry.nbytes
        return freed

    def flush(self, timeout_s=30.0):
        """Block until the write-behind queue drains (tests / epoch-end
        barriers). Returns False on timeout — e.g. a throttled writer."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            q = self._writeq
            if q is None or q.unfinished_tasks == 0:
                return True
            time.sleep(0.005)
        return False

    # -- lifecycle / stats -------------------------------------------------

    def stats(self):
        """Hit/miss/write-behind counters for ``stats['chunk_store']`` /
        ``Reader.diagnostics()['chunk_store']``. With a thread pool these
        cover the whole pipeline; with process pools each worker process
        counts its own (the files are still shared)."""
        with self._lock:
            q = self._writeq
            return {'path': self._path,
                    'hits': self.hits,
                    'misses': self.misses,
                    'fills': self.fills,
                    'writes': self.writes,
                    'write_skipped': self.write_skipped,
                    'write_races': self.write_races,
                    'corrupt_quarantined': self.corrupt,
                    'bytes_written': self.bytes_written,
                    'bytes_mapped': self.bytes_mapped,
                    'readaheads': self.readaheads,
                    'unstorable': self.unstorable,
                    'pending_writes': (q.unfinished_tasks if q is not None else 0),
                    'pending_write_bytes': self._writeq_bytes,
                    'writer_throttled': self._throttled,
                    'spill_paused': self._spill_paused,
                    'open_entries': len(self._entries)}

    def close(self):
        """Stop the write-behind thread (pending writes drain first)."""
        with self._lock:
            self._stopping = True
            writer, q = self._writer, self._writeq
            self._writer = None
        joined = True
        if writer is not None and writer.is_alive():
            q.put(_STOP)
            writer.join(timeout=10)
            joined = not writer.is_alive()
        if joined:
            # Re-arm only once the old writer is provably gone: resetting
            # under a timed-out join would revive a (possibly throttled)
            # zombie writer spinning against a store being deleted.
            with self._lock:
                self._stopping = False
        else:  # pragma: no cover - requires a wedged NVMe write
            logger.warning('chunk store writer still alive after close(); '
                           'the store stays write-disabled')

    def cleanup(self):
        self.close()
        if self._do_cleanup:
            shutil.rmtree(self._path, ignore_errors=True)
