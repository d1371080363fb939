"""Decoded-columnar row-group worker: the TPU hot path.

The reference offers two mutually exclusive read modes: per-row decoded
(``py_dict_reader_worker.py`` — codecs run, but every sample crosses the
pool as a Python dict) and columnar raw (``arrow_reader_worker.py:39-79`` —
zero-copy-ish, but codec cells stay encoded). Neither can feed an
accelerator decoded tensors without per-row Python costs. This worker is
the missing third mode: it decodes every codec column *inside the worker*
straight into one contiguous ``[N, ...field.shape]`` numpy block per field
(images via the native C++ batch decoder with the GIL released,
``native/src/image_codec.cc``), and publishes a small dict of big arrays —
O(fields) Python objects per row-group instead of O(rows).

Downstream, ``jax_loader.iter_numpy_batches`` slices these blocks into
fixed-size batches with one memcpy per batch and stages them with
``jax.device_put`` / ``make_array_from_process_local_data`` — decoded
tensors cross zero per-row Python boundaries end to end.

Requires every non-scalar field to have a fully static shape (XLA needs
static shapes anyway); ``make_tensor_reader`` validates this up front.
"""

import logging

import numpy as np
import pyarrow as pa

from petastorm_tpu.checkpoint import DeferredRowAccounting, chunk_key
from petastorm_tpu.codecs import (CompressedImageCodec, CompressedNdarrayCodec,
                                  NdarrayCodec, ScalarCodec, _fast_npy_decode,
                                  _native_image)
from petastorm_tpu.determinism import ResequencedReads, is_hole
from petastorm_tpu.errors import DecodeFieldError
from petastorm_tpu.workers.rowgroup_worker_base import (RowGroupWorkerBase,
                                                        chunk_row_permutation,
                                                        compute_row_slice)

logger = logging.getLogger(__name__)


def validate_tensor_schema(schema):
    """Raise unless every field can decode into a fixed-shape dense block."""
    for name, field in schema.fields.items():
        codec = field.resolved_codec()
        if isinstance(codec, ScalarCodec) or (codec is None and field.shape == ()):
            continue
        if field.shape and any(dim is None for dim in field.shape):
            raise ValueError(
                'make_tensor_reader requires static shapes, but field {!r} has '
                'shape {} (None = variable dim). Re-materialize with a fixed '
                'shape, or use make_reader with a shape policy in the '
                'JaxLoader.'.format(name, field.shape))
        if codec is None and field.shape:
            raise ValueError(
                'make_tensor_reader requires a codec on tensor field {!r} '
                '(plain Parquet stores: use make_batch_reader)'.format(name))


class TensorWorker(RowGroupWorkerBase):
    """Same args dict as PyDictWorker/ArrowWorker (see PyDictWorker docstring).

    Publishes ``{'__pst_tensor_chunk__': 1, 'key': str, 'cols': {name: np
    block}, 'timings': {...}}`` per row-group. The per-stage timings feed
    ``loader.stats['worker_stage_timings']``.
    """

    #: Reader-mode tag for batch provenance contexts (lineage.py replay
    #: picks its decode path by this).
    lineage_mode = 'tensor'

    def process(self, piece_index, worker_predicate=None,
                shuffle_row_drop_partition=None, pst_det=None):
        from petastorm_tpu import metrics
        from petastorm_tpu.faults import maybe_inject, rowgroup_fault_key
        from petastorm_tpu.trace import get_global_tracer

        piece = self.args['row_groups'][piece_index]
        schema = self.args['schema']
        maybe_inject('decode-corrupt',
                     key=rowgroup_fault_key(piece.path, piece.row_group))
        # One span a stage, each adding its seconds to the chunk's
        # ``timings`` (they cross process pools on the payload): the miss's
        # read and decode nest inside ``reader.cache_get``, whose SELF time
        # is ``cache_s``: the cache's own bookkeeping, neither counted twice.
        key = self._trace_id = chunk_key(piece_index,
                                         shuffle_row_drop_partition)
        timings = {}
        self._read_total = (timings, 'read_s')
        tracer = get_global_tracer()
        decoded_fresh = []    # load() ran => served from decode, not a cache

        def load():
            decoded_fresh.append(True)
            table = self._load_table(piece, worker_predicate)
            if table is None or table.num_rows == 0:
                return None
            # Recorded by the process-local global tracer (a sidecar
            # spiller inside pool workers, see trace.install_worker_tracer),
            # which is what makes worker-subprocess decode visible on a
            # merged timeline.
            with tracer.span('decode.decode', 'decode', id=key,
                             total=(timings, 'decode_s'),
                             hist=metrics.histogram(
                                 'pst_decode_seconds',
                                 'Row-group decode latency inside workers')):
                cols = decode_table_to_blocks(
                    table, schema, self.args.get('decode_threads'),
                    fault_key=rowgroup_fault_key(piece.path, piece.row_group),
                    raw_fields=self.args.get('raw_image_fields') or ())
            return cols

        from petastorm_tpu.cache import NullCache
        # The predicate path bypasses the cache entirely, so its chunks are
        # always private — no defensive copy needed before transforms.
        cached = (worker_predicate is None
                  and not isinstance(self.args['cache'], NullCache))
        # Block-handoff ownership marker: ``private=False`` blocks are (or
        # may be) shared by reference with the RAM cache and MUST only ever
        # be copied FROM downstream — the loader's recycled-arena collate
        # path would corrupt every later epoch if it took ownership of (or
        # padded/recycled in place) a cached block. Transform and in-chunk-
        # shuffle below both copy, flipping the chunk back to private.
        private = not cached
        if worker_predicate is None:
            # Shared key builder (chunk_store.tensor_chunk_key): the NVMe
            # store lookup happens here, AHEAD of decode — cache.get only
            # runs load() (read + decode) on a store miss, and the reader's
            # ventilation-order readahead computes the identical key.
            from petastorm_tpu.chunk_store import tensor_chunk_key
            cache_key = tensor_chunk_key(self.args['dataset_path_hash'],
                                         piece.path, piece.row_group, schema)
            with tracer.span('reader.cache_get', 'reader', id=key,
                             self_total=(timings, 'cache_s')) as span:
                cols = self.args['cache'].get(cache_key, load)
                span.cause = 'miss' if decoded_fresh else 'hit'
        else:
            cols = load()
        if cols is None:
            return self._publish_hole(pst_det)
        n_rows = len(next(iter(cols.values())))

        row_slice = compute_row_slice(n_rows, shuffle_row_drop_partition)
        if row_slice is not None:
            start, stop = row_slice
            if stop <= start:
                return self._publish_hole(pst_det)
            cols = {k: v[start:stop] for k, v in cols.items()}
            n_rows = stop - start

        transform_spec = self.args.get('transform_spec')
        if transform_spec is not None and transform_spec.func is not None:
            # Tensor-mode transforms operate on the dict of column blocks
            # (numpy in, numpy out) — the vectorized analog of the reference's
            # pandas TransformSpec (``arrow_reader_worker.py:163-178``).
            # Cached blocks are shared by reference across epochs; in-place
            # user transforms (a common idiom) must see private copies or
            # epoch 2's cache hit would serve already-transformed data.
            if cached:
                cols = {k: np.array(v, copy=True) for k, v in cols.items()}
                private = True
            out = transform_spec.func(dict(cols))
            for name in transform_spec.removed_fields:
                out.pop(name, None)
            keep = self.args['transformed_schema'].fields
            cols = {k: np.asarray(v) for k, v in out.items() if k in keep}
            if not cols:
                return self._publish_hole(pst_det)
            n_rows = len(next(iter(cols.values())))

        if n_rows and self.args.get('shuffle_rows_in_chunk'):
            # Deterministic per-(seed, row-group, drop-partition) permutation:
            # fixed across epochs and across sessions, so mid-epoch resume
            # skips target the same (permuted) leading rows. Fancy indexing
            # copies, so cached blocks are never mutated.
            perm = chunk_row_permutation(
                self.args.get('shuffle_seed'), self.args['dataset_path_hash'],
                piece.path, piece.row_group, shuffle_row_drop_partition, n_rows)
            cols = {k: v[perm] for k, v in cols.items()}
            private = True

        if n_rows:
            from petastorm_tpu.lineage import chunk_lineage
            # Serving tier: a fresh decode when load() actually ran (incl.
            # every predicate read, which bypasses the cache), else the
            # cache's own tier label (memory / chunk-store / disk).
            tier = ('decode' if decoded_fresh or worker_predicate is not None
                    else getattr(self.args['cache'], 'lineage_tier', 'cache'))
            lineage = chunk_lineage(
                piece, piece_index, shuffle_row_drop_partition, n_rows,
                tier, permuted=bool(n_rows
                                    and self.args.get('shuffle_rows_in_chunk')),
                filtered=worker_predicate is not None,
                worker_id=self.worker_id)
            payload = {'__pst_tensor_chunk__': 1,
                       'key': key,
                       'cols': cols,
                       'private': private,
                       'lineage': lineage,
                       'timings': timings}
            if pst_det is not None:
                payload['det'] = pst_det
            with tracer.span('reader.publish', 'reader', id=key):
                self.publish_func(payload)
        else:
            self._publish_hole(pst_det)

    # --- loading ------------------------------------------------------

    def _load_table(self, piece, worker_predicate):
        schema = self.args['schema']
        field_names = list(schema.fields)
        partition_names = set(self.args['partition_names'])
        physical = [n for n in field_names if n not in partition_names]

        if worker_predicate is not None:
            table = self._load_with_predicate(piece, physical, field_names,
                                              worker_predicate)
            if table is None:
                return None
        else:
            table = self._read_row_group(piece, physical)
        for name, value in piece.partition_values.items():
            if name in field_names and name not in table.column_names:
                table = table.append_column(name, pa.array([value] * table.num_rows))
        return table

    def _load_with_predicate(self, piece, physical, field_names, predicate):
        """Two-phase predicate read on *decoded* values.

        Unlike the Arrow worker (which evaluates predicates on raw cells),
        tensor-mode predicates see what ``make_reader`` predicates see:
        decoded scalars. Tensor fields in predicates are rejected by
        ``make_tensor_reader``.
        """
        predicate_fields = sorted(predicate.get_fields())
        full_schema = self.args['full_schema']
        unknown = set(predicate_fields) - set(full_schema.fields)
        if unknown:
            raise ValueError('Predicate uses unknown fields: {}'.format(sorted(unknown)))
        partition_names = set(self.args['partition_names'])
        pred_physical = [n for n in predicate_fields if n not in partition_names]
        pred_table = (self._read_row_group(piece, pred_physical) if pred_physical
                      else None)
        n = pred_table.num_rows if pred_table is not None else None
        pred_cols = {}
        if pred_table is not None:
            pred_schema = full_schema.create_schema_view(
                [f for f in predicate_fields if f in full_schema.fields and f in pred_physical])
            pred_cols = decode_table_to_blocks(pred_table, pred_schema,
                                               self.args.get('decode_threads'))
        for name in predicate_fields:
            if name in piece.partition_values:
                if n is None:
                    raise ValueError('Predicate on partition values only should '
                                     'have been pruned before ventilation')
                pred_cols[name] = np.asarray([piece.partition_values[name]] * n)
        mask = np.asarray([predicate.do_include({f: pred_cols[f][i] for f in predicate_fields})
                           for i in range(n)], dtype=bool)
        if not mask.any():
            return None
        table = self._read_row_group(piece, physical)
        return table.take(pa.array(np.flatnonzero(mask)))


class TensorResultsQueueReader(DeferredRowAccounting, ResequencedReads):
    """Consumer side: one decoded chunk -> namedtuple of numpy blocks.

    Checkpoint accounting is chunk-level by default, row-granular after
    ``enable_deferred_rows`` (see ``checkpoint.DeferredRowAccounting``).
    In deterministic mode chunk pops route through the reader's
    resequencer (``ResequencedReads``) so delivery order equals
    ventilation order.
    """

    def __init__(self):
        self._timings = {'read_s': 0.0, 'decode_s': 0.0, 'cache_s': 0.0,
                         'chunks': 0}
        self._last_private = False
        self._last_lineage = None
        self._last_det = None
        #: Optional health.Heartbeat (wired by ``Reader.attach_health``):
        #: beaten per decoded chunk crossing the pool->consumer handoff,
        #: so the watchdog sees TensorWorker output flow directly.
        self.heartbeat = None

    @property
    def batched_output(self):
        return True

    @property
    def stage_timings(self):
        return dict(self._timings)

    @property
    def last_chunk_private(self):
        """Ownership of the chunk most recently returned by ``read_next``:
        True when its blocks are NOT shared with a cache, so a downstream
        collate stage may take ownership of (donate/recycle) them. Read
        synchronously right after the reader yields — the flag refers to
        that sample. Resume-skip slicing keeps the flag: a view of a
        private block is still unshared."""
        return self._last_private

    @property
    def last_chunk_lineage(self):
        """Provenance segment of the chunk most recently returned by
        ``read_next`` (``petastorm_tpu.lineage``): published-chunk
        coordinates with ``row_start`` advanced past any resume skip.
        ``None`` for payloads without lineage metadata."""
        return self._last_lineage

    def read_next(self, pool, schema, ngram):
        if ngram is not None:
            raise NotImplementedError('NGram is not supported with tensor readers')
        while True:
            chunk = self._pull(pool)
            if self.heartbeat is not None:
                self.heartbeat.beat('handoff')
            if is_hole(chunk):
                # Deterministic-mode placeholder: its only job (advancing
                # the resequencer frontier) is already done.
                continue
            cols, key = chunk['cols'], chunk['key']
            det = chunk.get('det')
            self._last_private = bool(chunk.get('private'))
            lineage = chunk.get('lineage')
            t = chunk.get('timings') or {}
            for k in ('read_s', 'decode_s', 'cache_s'):
                if k in t:
                    self._timings[k] += t[k]
            self._timings['chunks'] += 1
            n_rows = len(next(iter(cols.values())))
            if self._tracker is not None:
                skip = self._tracker.on_chunk(key, n_rows, det=det)
                if skip:
                    cols = {k: v[skip:] for k, v in cols.items()}
                    n_rows -= skip
                    if lineage is not None:
                        # Resume re-delivery: the prior session consumed the
                        # chunk's leading rows — the delivered span starts
                        # past them (chunk_rows stays the published length,
                        # which is what replay's permutation recompute needs).
                        lineage = dict(lineage)
                        lineage['row_start'] = lineage.get('row_start', 0) + skip
                if n_rows <= 0:
                    continue
                self._record_chunk(key, n_rows)
            self._last_lineage = lineage
            self._last_det = det
            break
        names = [n for n in schema.fields if n in cols]
        return schema.make_namedtuple(**{n: cols[n] for n in names})

    @property
    def last_chunk_det(self):
        """Deterministic-mode tag (``seq``/``epoch``/``pos``) of the chunk
        most recently returned, or None outside deterministic mode."""
        return self._last_det


# --------------------------------------------------------------------------
# columnar decode
# --------------------------------------------------------------------------

def decode_table_to_blocks(table, schema, decode_threads=None,
                           fault_key=None, raw_fields=()):
    """Arrow table -> dict of contiguous per-field numpy blocks, decoded.

    ``raw_fields`` names image-codec columns shipped *encoded* (the
    on-device decode path): those come out as object-dtype columns of the
    raw bytes instead of decoded pixel blocks — the loader's staging step
    owns their decode (``JaxLoader`` docstring, ``on_device_augment``).
    """
    cols = {}
    for name in schema.fields:
        if name not in table.column_names:
            continue
        field = schema.fields[name]
        column = table.column(name).combine_chunks()
        if column.null_count:
            raise DecodeFieldError(
                'Field {!r} contains nulls; the tensor path requires dense '
                'columns (fill them with a TransformSpec or use make_reader)'
                .format(name))
        codec = field.resolved_codec()
        try:
            if isinstance(codec, CompressedImageCodec):
                if name in raw_fields:
                    cols[name] = _raw_image_column(column)
                else:
                    cols[name] = _decode_image_column(
                        column, field, decode_threads, fault_key=fault_key)
            elif isinstance(codec, (NdarrayCodec, CompressedNdarrayCodec)):
                cols[name] = _decode_ndarray_column(column, field, codec)
            else:  # scalars (incl. partition-value columns)
                cols[name] = _scalar_column_to_numpy(column, field)
        except DecodeFieldError:
            raise
        except Exception as e:
            raise DecodeFieldError('Unable to decode field {!r}: {}'.format(name, e)) from e
    return cols


def _binary_column_view(column):
    """(base_address + offsets, lengths) pointer math over a BinaryArray —
    no per-cell ``bytes`` objects."""
    buffers = column.buffers()
    # [validity, offsets, data]; offset dtype depends on binary vs large_binary
    off_dtype = np.int64 if pa.types.is_large_binary(column.type) else np.int32
    offsets = np.frombuffer(buffers[1], dtype=off_dtype,
                            count=len(column) + column.offset + 1)
    offsets = offsets[column.offset:column.offset + len(column) + 1].astype(np.int64)
    base = buffers[2].address
    return base + offsets[:-1], np.diff(offsets)


def _decode_image_column(column, field, decode_threads, fault_key=None):
    """One contiguous ``[N, ...field.shape]`` block per column via the
    shared batched core (:func:`petastorm_tpu.codecs.decode_image_batch_into`):
    pointer math over the Arrow value buffer feeds one native call for the
    whole row-group; scalar/fallback paths produce byte-identical blocks."""
    from petastorm_tpu.codecs import decode_image_batch_into
    n = len(column)
    dtype = np.dtype(field.numpy_dtype)
    out = np.empty((n,) + tuple(field.shape), dtype=dtype)
    ptrs = lens = None
    if _native_image() is not None and dtype == np.uint8:
        ptrs, lens = _binary_column_view(column)
    decode_image_batch_into(field, out, lambda i: column[i].as_py(),
                            ptrs=ptrs, lens=lens,
                            decode_threads=decode_threads,
                            fault_key=fault_key)
    return out


def _raw_image_column(column):
    """Encoded bytes as an object-dtype column (the raw-image handoff for
    on-device decode): O(1)-per-cell reference copies, no pixel work."""
    n = len(column)
    out = np.empty(n, dtype=object)
    for i, cell in enumerate(column):
        out[i] = cell.as_py()
    return out


def _decode_ndarray_column(column, field, codec):
    n = len(column)
    out = np.empty((n,) + tuple(field.shape), dtype=field.numpy_dtype)
    if isinstance(codec, NdarrayCodec):
        for i, cell in enumerate(column):
            arr = _fast_npy_decode(cell.as_py())
            if arr is None:
                arr = codec.decode(field, cell.as_py())
            out[i] = arr
    else:
        for i, cell in enumerate(column):
            out[i] = codec.decode(field, cell.as_py())
    return out


def _scalar_column_to_numpy(column, field):
    np_dtype = np.dtype(field.numpy_dtype)
    if np_dtype.kind in ('O', 'S', 'U'):
        return np.asarray(column.to_pylist(), dtype=object)
    if np_dtype.kind == 'M':
        return column.to_numpy(zero_copy_only=False).astype('datetime64[ns]')
    arr = column.to_numpy(zero_copy_only=False)
    if arr.dtype != np_dtype:
        arr = arr.astype(np_dtype)
    # Blocks may be sliced + concatenated downstream; ensure ownership so the
    # chunk's Arrow table can be dropped.
    return np.ascontiguousarray(arr)
