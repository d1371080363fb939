"""Columnar row-group worker: keeps data as Arrow tables end to end.

Parity: reference ``petastorm/arrow_reader_worker.py`` — same per-row-group
flow as the dict worker but columnar: pandas-vectorized predicate (``:212``),
pandas-based TransformSpec (``:163-178``), unrequested partition columns
dropped (``:249-255``); the queue reader converts Arrow columns to numpy and
vstacks fixed-length list columns (``:39-79``); ``batched_output=True``
(``:36-37``); no ngram support (``:97-98``).

This is the TPU hot path: batched columnar decode feeds
``jax_loader`` with whole-row-group numpy blocks for zero-copy
``device_put`` staging.
"""

import hashlib

import numpy as np
import pyarrow as pa

from petastorm_tpu.checkpoint import DeferredRowAccounting, chunk_key
from petastorm_tpu.determinism import ResequencedReads
from petastorm_tpu.workers.rowgroup_worker_base import (RowGroupWorkerBase,
                                                        chunk_row_permutation,
                                                        compute_row_slice)


class ArrowWorker(RowGroupWorkerBase):
    """Same args dict as PyDictWorker (see its docstring)."""

    #: Reader-mode tag for batch provenance contexts (lineage.py).
    lineage_mode = 'arrow'

    def process(self, piece_index, worker_predicate=None,
                shuffle_row_drop_partition=None, pst_det=None):
        from petastorm_tpu.faults import maybe_inject, rowgroup_fault_key

        from petastorm_tpu.trace import get_global_tracer

        piece = self.args['row_groups'][piece_index]
        maybe_inject('decode-corrupt',
                     key=rowgroup_fault_key(piece.path, piece.row_group))
        # Arrow mode ships raw cells, so its 'decode.decode' span covers the
        # columnar table prep ('reader.read' nests inside it) — the same
        # three-span vocabulary as the dict/tensor workers on a merged
        # timeline even though codecs don't run here.
        with get_global_tracer().span('decode.decode', 'decode'):
            table, read_fresh = self._load_table_cached(piece, worker_predicate)
        if table is None or table.num_rows == 0:
            return self._publish_hole(pst_det)

        row_slice = compute_row_slice(table.num_rows, shuffle_row_drop_partition)
        if row_slice is not None:
            start, stop = row_slice
            table = table.slice(start, stop - start)
            if table.num_rows == 0:
                return self._publish_hole(pst_det)

        transform_spec = self.args.get('transform_spec')
        if transform_spec is not None and transform_spec.func is not None:
            table = self._apply_transform(table, transform_spec)

        if table.num_rows and self.args.get('shuffle_rows_in_chunk'):
            # Same session-stable permutation as the tensor path
            # (chunk_row_permutation): decorrelates storage order within
            # the chunk, keeps resume row-skips exact.
            perm = chunk_row_permutation(
                self.args.get('shuffle_seed'), self.args['dataset_path_hash'],
                piece.path, piece.row_group, shuffle_row_drop_partition,
                table.num_rows)
            table = table.take(pa.array(perm))

        if table.num_rows:
            import json as json_mod

            from petastorm_tpu.lineage import chunk_lineage
            # Ventilation key + provenance segment ride in the schema
            # metadata (survives the Arrow IPC serializer) for checkpoint/
            # resume tracking and the batch provenance ledger. Arrow mode
            # ships raw cells, so a cache hit serves the same bytes a read
            # would — the tier distinguishes disk-cache hits from reads.
            md = dict(table.schema.metadata or {})
            md[b'pst.key'] = chunk_key(piece_index, shuffle_row_drop_partition).encode()
            tier = ('decode' if read_fresh
                    else getattr(self.args['cache'], 'lineage_tier', 'cache'))
            lineage = chunk_lineage(
                piece, piece_index, shuffle_row_drop_partition,
                table.num_rows, tier,
                permuted=bool(self.args.get('shuffle_rows_in_chunk')),
                filtered=worker_predicate is not None,
                worker_id=self.worker_id)
            md[b'pst.lineage'] = json_mod.dumps(lineage).encode()
            if pst_det is not None:
                md[b'pst.det'] = json_mod.dumps(pst_det).encode()
            with get_global_tracer().span('reader.publish', 'reader'):
                self.publish_func(table.replace_schema_metadata(md))
        else:
            self._publish_hole(pst_det)

    def _publish_hole(self, pst_det):
        """Arrow transports serialize tables (never dicts): the sequence-
        hole placeholder is a zero-row, zero-column table whose schema
        metadata carries the ``pst.det`` tag — it survives the IPC
        serializer and the consumer recognizes ``num_rows == 0``."""
        if pst_det is None:
            return
        import json as json_mod
        empty = pa.table({}).replace_schema_metadata(
            {b'pst.det': json_mod.dumps(pst_det).encode()})
        self.publish_func(empty)

    def _apply_transform(self, table, transform_spec):
        """Pandas-based batch transform (parity: ``arrow_reader_worker.py:163-178``)."""
        df = table.to_pandas()
        out = transform_spec.func(df)
        for name in transform_spec.removed_fields:
            if name in out.columns:
                out = out.drop(columns=[name])
        transformed_schema = self.args['transformed_schema']
        keep = [n for n in transformed_schema.fields if n in out.columns]
        return pa.Table.from_pandas(out[keep], preserve_index=False)

    # --- loading ------------------------------------------------------

    def _load_table_cached(self, piece, worker_predicate):
        """``(table, read_fresh)`` — the flag says whether this call paid a
        store read (lineage tier 'decode') or was served by the cache."""
        schema = self.args['schema']
        field_names = list(schema.fields)
        partition_names = set(self.args['partition_names'])
        physical = [n for n in field_names if n not in partition_names]

        if worker_predicate is not None:
            return (self._load_with_predicate(piece, physical, field_names,
                                              worker_predicate), True)

        cache_key = '{}:{}:{}:{}'.format(
            self.args['dataset_path_hash'], piece.path, piece.row_group,
            hashlib.md5(','.join(field_names).encode()).hexdigest()[:8])
        fresh = []

        def load():
            fresh.append(True)
            table = self._read_row_group(piece, physical)
            return self._append_partition_columns(table, piece, field_names)

        return self.args['cache'].get(cache_key, load), bool(fresh)

    def _append_partition_columns(self, table, piece, field_names):
        for name, value in piece.partition_values.items():
            if name in field_names and name not in table.column_names:
                table = table.append_column(
                    name, pa.array([value] * table.num_rows))
        return table

    def _load_with_predicate(self, piece, physical, field_names, predicate):
        """Vectorized two-phase predicate read (parity: ``arrow_reader_worker.py:180-247``)."""
        predicate_fields = sorted(predicate.get_fields())
        full_schema = self.args['full_schema']
        unknown = set(predicate_fields) - set(full_schema.fields)
        if unknown:
            raise ValueError('Predicate uses unknown fields: {}'.format(sorted(unknown)))
        partition_names = set(self.args['partition_names'])
        pred_physical = [n for n in predicate_fields if n not in partition_names]
        pred_table = self._read_row_group(piece, pred_physical)
        pred_table = self._append_partition_columns(pred_table, piece, predicate_fields)
        pred_df = pred_table.to_pandas()
        mask = pred_df.apply(
            lambda r: predicate.do_include({f: r[f] for f in predicate_fields}), axis=1).values \
            if len(pred_df) else np.zeros(0, dtype=bool)
        if not mask.any():
            return None
        other = [n for n in physical if n not in predicate_fields]
        if other:
            other_table = self._read_row_group(piece, other)
            for col in other_table.column_names:
                pred_table = pred_table.append_column(col, other_table.column(col))
        table = self._append_partition_columns(pred_table, piece, field_names)
        keep = [n for n in field_names if n in table.column_names]
        indices = np.flatnonzero(mask)
        return table.select(keep).take(pa.array(indices))


class ArrowResultsQueueReader(DeferredRowAccounting, ResequencedReads):
    """Consumer-side: one Arrow table -> namedtuple of numpy arrays (a batch).

    Parity: reference ``arrow_reader_worker.py:39-79``. Checkpoint
    accounting is chunk-level by default, row-granular after
    ``enable_deferred_rows`` (see ``checkpoint.DeferredRowAccounting``).
    In deterministic mode chunk pops route through the reader's
    resequencer (``ResequencedReads``).
    """

    _last_lineage = None
    _last_det = None

    @property
    def batched_output(self):
        return True

    @property
    def last_chunk_lineage(self):
        """Provenance segment of the most recent chunk (see
        ``TensorResultsQueueReader.last_chunk_lineage``)."""
        return self._last_lineage

    @property
    def last_chunk_det(self):
        """Deterministic-mode tag of the most recent chunk, or None."""
        return self._last_det

    def read_next(self, pool, schema, ngram):
        import json as json_mod
        if ngram is not None:
            raise NotImplementedError('NGram is not supported with batch (Arrow) readers '
                                      '(parity: arrow_reader_worker.py:97-98)')
        while True:
            table = self._pull(pool)
            if table.num_rows == 0:
                # Deterministic-mode sequence-hole placeholder (a worker
                # never publishes a genuinely empty chunk).
                continue
            md = table.schema.metadata or {}
            key = md.get(b'pst.key')
            key = key.decode() if key is not None else None
            lineage = md.get(b'pst.lineage')
            if lineage is not None:
                try:
                    lineage = json_mod.loads(lineage.decode())
                except ValueError:
                    lineage = None
            det = md.get(b'pst.det')
            if det is not None:
                try:
                    det = json_mod.loads(det.decode())
                except ValueError:
                    det = None
            if self._tracker is not None and key is not None:
                skip = self._tracker.on_chunk(key, table.num_rows, det=det)
                if skip:
                    table = table.slice(skip)
                    if lineage is not None:
                        lineage['row_start'] = lineage.get('row_start', 0) + skip
                if table.num_rows == 0:
                    continue
                self._record_chunk(key, table.num_rows)
            self._last_lineage = lineage
            self._last_det = det
            break
        columns = {}
        for name in schema.fields:
            if name not in table.column_names:
                continue
            column = table.column(name)
            columns[name] = _arrow_column_to_numpy(column, schema.fields[name])
        return schema.make_namedtuple(**columns)


def _arrow_column_to_numpy(column, field):
    """Arrow column -> numpy; fixed-length list columns vstack into 2-D arrays.

    Parity: reference ``arrow_reader_worker.py:53-79``.
    """
    if pa.types.is_list(column.type) or pa.types.is_large_list(column.type):
        values = column.to_pylist()
        shapes = {np.shape(v) for v in values if v is not None}
        if len(shapes) == 1 and None not in values:
            return np.vstack([np.asarray(v, dtype=field.numpy_dtype) for v in values]) \
                if len(values) else np.zeros((0,), dtype=field.numpy_dtype)
        out = np.empty(len(values), dtype=object)
        for i, v in enumerate(values):
            out[i] = None if v is None else np.asarray(v, dtype=field.numpy_dtype)
        return out
    np_dtype = field.numpy_dtype
    if np_dtype.kind in ('O', 'S', 'U'):
        return column.to_pandas().values
    try:
        # Zero-copy for single-chunk null-free primitives: the numpy array
        # is a read-only view over the Arrow buffer the C++ decode produced
        # (SURVEY §2.9's "Arrow-compatible columnar buffers" leg).
        return column.to_numpy(zero_copy_only=False)
    except (pa.ArrowInvalid, NotImplementedError):
        return column.to_pandas().values
