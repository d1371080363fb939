"""Shared machinery for row-group workers (dict & arrow flavors).

Hosts the per-worker Parquet file-handle LRU cache, the native C++ row-group
fast path, and the shuffle-row-drop-partition slice computation so the two
worker implementations cannot drift apart.
"""

import logging
import os
from collections import OrderedDict
from urllib.parse import urlparse

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from petastorm_tpu.workers import WorkerBase

logger = logging.getLogger(__name__)

_PARQUET_FILE_CACHE_SIZE = 32


class RowGroupWorkerBase(WorkerBase):
    """Worker base with a lazily-connected store and an LRU of open files."""

    #: Whether 'auto' native-parquet mode picks the C++ reader for this worker
    #: class. Columnar workers (tensor/arrow) win from its zero-copy export;
    #: the per-row dict worker converts to Python rows anyway and measures
    #: faster on pyarrow, whose column decode parallelizes internally
    #: (round-3 profile: ~5-10% on the hello_world per-row path).
    _prefer_native_parquet = True

    def __init__(self, worker_id, publish_func, args):
        super().__init__(worker_id, publish_func, args)
        self._store = None
        self._file_cache = OrderedDict()
        self._native_parquet = None      # resolved lazily at first read
        self._native_required = False
        self._leaf_index_cache = {}
        #: What the ``reader.read`` span is tagged with and where it adds
        #: its seconds: the worker sets both to the chunk it is producing
        #: (its key, its ``timings`` dict).
        self._trace_id = None
        self._read_total = None

    def initialize(self):
        self._store = self.args['store_factory']()

    def _publish_hole(self, pst_det):
        """Deterministic mode: a ventilated item that produced no chunk
        (empty after predicate / drop-partition slicing) still publishes a
        placeholder carrying its ``pst_det`` tag, so the consumer-side
        resequencer's expected-seq frontier advances past it instead of
        waiting forever. No-op outside deterministic mode. Arrow workers
        override (their transport serializes tables, not dicts)."""
        if pst_det is not None:
            from petastorm_tpu.determinism import hole_marker
            self.publish_func(hole_marker(pst_det))

    # --- row-group reads ----------------------------------------------

    def _native_parquet_enabled(self):
        """Native C++ row-group decode (SURVEY §2.9): used for local stores
        when the library builds; ``PETASTORM_TPU_NATIVE_PARQUET=0`` disables,
        ``=1`` requires — build failure, a remote store, or a native read
        error then raise instead of silently measuring the pyarrow path."""
        if self._native_parquet is None:
            setting = os.environ.get('PETASTORM_TPU_NATIVE_PARQUET', 'auto')
            self._native_required = setting == '1'
            if setting == '0' or (setting == 'auto'
                                  and not self._prefer_native_parquet):
                self._native_parquet = False
            else:
                from petastorm_tpu.native import parquet as native_pq
                local = urlparse(self._store.url).scheme == 'file'
                available = native_pq.is_available()
                if self._native_required:
                    if not available:
                        raise RuntimeError('PETASTORM_TPU_NATIVE_PARQUET=1 but '
                                           'the native parquet reader failed to build')
                    if not local:
                        raise RuntimeError('PETASTORM_TPU_NATIVE_PARQUET=1 but the '
                                           'store is not local ({}); the C++ reader '
                                           'opens filesystem paths'.format(self._store.url))
                self._native_parquet = bool(available and local)
        return self._native_parquet

    def _leaf_indices(self, path, columns):
        # Keyed by (path, columns): files written by different writers may
        # order the same columns differently.
        key = (path, tuple(columns))
        indices = self._leaf_index_cache.get(key, -1)
        if indices == -1:
            from petastorm_tpu.native import parquet as native_pq
            indices = native_pq.leaf_indices_for_fields(
                self._parquet_file(path).schema, columns)
            self._leaf_index_cache[key] = indices  # None => nested; fall back
        return indices

    def _read_row_group(self, piece, columns):
        """One row-group as a ``pa.Table``, restricted to ``columns``.

        Native path: decode runs wholly in C++ with the GIL released and the
        buffers import zero-copy (Arrow C Data Interface). Falls back to
        pyarrow for remote stores, nested columns, or build failure.
        """
        from petastorm_tpu.trace import get_global_tracer
        with get_global_tracer().span('reader.read', 'reader',
                                      id=self._trace_id,
                                      total=self._read_total):
            return self._read_row_group_traced(piece, columns)

    def _read_row_group_traced(self, piece, columns):
        from petastorm_tpu.faults import maybe_inject, rowgroup_fault_key
        fault_key = rowgroup_fault_key(piece.path, piece.row_group)
        maybe_inject('fs-read-delay', key=fault_key)
        maybe_inject('fs-read-error', key=fault_key)
        if self._native_parquet_enabled():
            indices = self._leaf_indices(piece.path, columns)
            if indices is not None:
                from petastorm_tpu.native import parquet as native_pq
                try:
                    batch = self._native_file(piece.path).read_row_group(
                        piece.row_group, columns=indices)
                    table = pa.Table.from_batches([batch])
                    # Column order follows leaf order; restore the request's.
                    return table.select(columns)
                except native_pq.NativeParquetError as e:
                    if self._native_required:
                        raise
                    logger.warning('native row-group read failed (%s); '
                                   'falling back to pyarrow', e)
                    self._native_parquet = False
        pf = self._parquet_file(piece.path)
        return pf.read_row_group(piece.row_group, columns=columns)

    def _native_file(self, path):
        """Handle-cached native reader, LRU'd alongside the pyarrow handles."""
        from petastorm_tpu.native import parquet as native_pq

        key = ('native', path)
        nf = self._file_cache.get(key)
        if nf is not None:
            self._file_cache.move_to_end(key)
            return nf
        if len(self._file_cache) >= _PARQUET_FILE_CACHE_SIZE:
            _, old = self._file_cache.popitem(last=False)
            try:
                old.close()
            except Exception:  # noqa: BLE001
                pass
        nf = native_pq.NativeParquetFile(path)
        self._file_cache[key] = nf
        return nf

    def _parquet_file(self, path):
        pf = self._file_cache.get(path)
        if pf is not None:
            self._file_cache.move_to_end(path)
            return pf
        if len(self._file_cache) >= _PARQUET_FILE_CACHE_SIZE:
            _, old = self._file_cache.popitem(last=False)  # least recently used
            try:
                old.close()
            except Exception:  # noqa: BLE001
                pass
        if urlparse(self._store.url).scheme == 'file':
            # Local store: hand pyarrow the OS path so reads run on its
            # native (memory-mapped) IO instead of round-tripping every
            # buffer through a Python fsspec file object.
            pf = pq.ParquetFile(path, memory_map=True)
        else:
            pf = pq.ParquetFile(self._store.open_file(path))
        self._file_cache[path] = pf
        return pf

    def shutdown(self):
        for pf in self._file_cache.values():
            try:
                pf.close()
            except Exception:  # noqa: BLE001
                pass
        self._file_cache = OrderedDict()


def compute_row_slice(num_rows, shuffle_row_drop_partition, ngram=None):
    """(start, stop) row bounds for one drop-partition of a row-group.

    Parity: reference ``py_dict_reader_worker.py:254-274`` — for ngram the
    kept slice is tail-extended so windows spanning the boundary survive.
    Returns None when the whole range is kept.
    """
    if shuffle_row_drop_partition is None:
        return None
    this_partition, num_partitions = shuffle_row_drop_partition
    if num_partitions <= 1:
        return None
    bounds = [int(round(i * num_rows / num_partitions)) for i in range(num_partitions + 1)]
    start, stop = bounds[this_partition], bounds[this_partition + 1]
    if ngram is not None:
        stop = min(num_rows, stop + ngram.length - 1)
    return start, stop


def chunk_row_permutation(seed, dataset_hash, piece_path, row_group,
                          shuffle_row_drop_partition, n_rows):
    """Stable row permutation for one chunk (``shuffle_rows_in_chunk``).

    Keyed by the row-group's identity, NOT by epoch or arrival order — the
    same chunk permutes identically in every epoch and every session, which
    is what keeps checkpoint-resume row skips exact. The permutation is
    computed by argsorting a splitmix64 hash of each row index (NOT a numpy
    Generator stream, whose bit-exactness across numpy versions is not
    guaranteed — a resume under a different numpy must reproduce it).
    """
    import hashlib
    drop_idx = shuffle_row_drop_partition[0] if shuffle_row_drop_partition else 0
    digest = hashlib.md5('{}:{}:{}:{}:{}'.format(
        seed, dataset_hash, piece_path, row_group, drop_idx).encode()).digest()
    base = np.uint64(int.from_bytes(digest[:8], 'little'))
    z = np.arange(n_rows, dtype=np.uint64) + base
    # splitmix64 finalizer: well-mixed, pure uint64 arithmetic (wraps mod
    # 2^64 in numpy), identical on every platform/version.
    z = (z + np.uint64(0x9E3779B97F4A7C15))
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    return np.argsort(z, kind='stable')
