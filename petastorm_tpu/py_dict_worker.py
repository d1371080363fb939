"""Per-row row-group worker: Parquet read -> codec decode -> transform -> rows.

Parity: reference ``petastorm/py_dict_reader_worker.py`` — one row-group per
``process()`` call, cached loads (``:160``), two-phase predicate read
(predicate columns first, early exit, then the rest — ``:188-252``), row-drop
partitioning with ngram tail extension (``:254-274``), per-row TransformSpec
(``:38-52``), ngram window formation (``:165-166``), and the paired results
queue reader that buffers a chunk and pops single rows (``:64-97``).
"""

import hashlib

from petastorm_tpu.checkpoint import chunk_key
from petastorm_tpu.determinism import ResequencedReads, is_hole
from petastorm_tpu.unischema import decode_rows
from petastorm_tpu.workers.rowgroup_worker_base import (RowGroupWorkerBase,
                                                        compute_row_slice)


class PyDictWorker(RowGroupWorkerBase):
    """Worker args (dict):
      store_factory: picklable zero-arg -> ParquetStore
      schema: Unischema view of fields to read+decode
      full_schema: stored dataset Unischema
      ngram: NGram or None
      row_groups: list[RowGroupPiece]
      cache: CacheBase
      transform_spec: TransformSpec or None
      transformed_schema: post-transform Unischema (for output filtering)
      partition_names: list of hive partition column names
      dataset_path_hash: stable dataset identity for cache keys
    """

    _prefer_native_parquet = False  # pyarrow is faster for the to-rows path

    #: Reader-mode tag for batch provenance contexts (lineage.py).
    lineage_mode = 'py_dict'

    def process(self, piece_index, worker_predicate=None,
                shuffle_row_drop_partition=None, pst_det=None):
        from petastorm_tpu.faults import maybe_inject, rowgroup_fault_key

        piece = self.args['row_groups'][piece_index]
        schema = self.args['schema']
        ngram = self.args['ngram']
        maybe_inject('decode-corrupt',
                     key=rowgroup_fault_key(piece.path, piece.row_group))

        decoded_fresh = []
        if worker_predicate is not None:
            rows = self._load_rows_with_predicate(piece, worker_predicate)
            decoded_fresh.append(True)
        else:
            rows = self._load_rows_cached(piece, decoded_fresh)

        row_slice = compute_row_slice(len(rows), shuffle_row_drop_partition, ngram)
        if row_slice is not None:
            rows = rows[row_slice[0]:row_slice[1]]

        transform_spec = self.args.get('transform_spec')
        if transform_spec is not None and transform_spec.func is not None and ngram is None:
            rows = [self._apply_transform(row, transform_spec) for row in rows]

        if ngram is not None:
            rows = ngram.form_ngram(rows, schema)
            if transform_spec is not None and transform_spec.func is not None:
                rows = [{offset: self._apply_transform(r, transform_spec)
                         for offset, r in window.items()} for window in rows]

        if rows:
            # Envelope tags the chunk with its ventilation key so the consumer
            # can track per-row-group consumption for checkpoint/resume
            # (petastorm_tpu.checkpoint), plus its provenance segment for the
            # batch lineage ledger (petastorm_tpu.lineage). NGram windows
            # re-index rows (a window is not a storage row), so their
            # lineage is omitted — batch records over ngrams are inexact.
            from petastorm_tpu.lineage import chunk_lineage
            from petastorm_tpu.trace import get_global_tracer
            lineage = None
            if ngram is None:
                tier = ('decode' if decoded_fresh
                        else getattr(self.args['cache'], 'lineage_tier',
                                     'cache'))
                lineage = chunk_lineage(
                    piece, piece_index, shuffle_row_drop_partition, len(rows),
                    tier, filtered=worker_predicate is not None,
                    worker_id=self.worker_id)
            payload = {'__pst_chunk__': 1,
                       'key': chunk_key(piece_index, shuffle_row_drop_partition),
                       'lineage': lineage,
                       'rows': rows}
            if pst_det is not None:
                payload['det'] = pst_det
            with get_global_tracer().span('reader.publish', 'reader'):
                self.publish_func(payload)
        else:
            self._publish_hole(pst_det)

    def _apply_transform(self, row, transform_spec):
        out = transform_spec.func(row)
        for name in transform_spec.removed_fields:
            out.pop(name, None)
        return out

    # --- loading ------------------------------------------------------

    def _columns_to_read(self, field_names):
        partition_names = set(self.args['partition_names'])
        return [n for n in field_names if n not in partition_names]

    def _read_columns(self, piece, column_names):
        physical = self._columns_to_read(column_names)
        table = self._read_row_group(piece, physical)
        encoded_rows = table.to_pylist()
        for row in encoded_rows:
            for name, value in piece.partition_values.items():
                if name in column_names:
                    row[name] = value
        return encoded_rows

    def _load_rows_cached(self, piece, decoded_fresh=None):
        schema = self.args['schema']
        if self.args['ngram'] is not None:
            field_names = sorted(self.args['ngram'].get_field_names_at_all_timesteps())
        else:
            field_names = list(schema.fields)
        cache_key = '{}:{}:{}:{}'.format(
            self.args['dataset_path_hash'], piece.path, piece.row_group,
            hashlib.md5(','.join(field_names).encode()).hexdigest()[:8])

        def load():
            from petastorm_tpu.faults import rowgroup_fault_key
            from petastorm_tpu.trace import get_global_tracer
            if decoded_fresh is not None:
                decoded_fresh.append(True)
            encoded_rows = self._read_columns(piece, field_names)
            decode_schema = (self.args['full_schema'].create_schema_view(
                [n for n in field_names if n in self.args['full_schema'].fields])
                if self.args['ngram'] is not None else schema)
            with get_global_tracer().span('decode.decode', 'decode'):
                return decode_rows(encoded_rows, decode_schema,
                                   num_threads=self.args.get('decode_threads'),
                                   fault_key=rowgroup_fault_key(
                                       piece.path, piece.row_group))

        return self.args['cache'].get(cache_key, load)

    def _load_rows_with_predicate(self, piece, predicate):
        """Two-phase read: predicate columns -> early exit -> remaining columns.

        Parity: reference ``py_dict_reader_worker.py:188-252``.
        """
        schema = self.args['schema']
        full_schema = self.args['full_schema']
        predicate_fields = set(predicate.get_fields())
        unknown = predicate_fields - set(full_schema.fields)
        if unknown:
            raise ValueError('Predicate uses unknown fields: {}'.format(sorted(unknown)))
        other_fields = [n for n in schema.fields if n not in predicate_fields]

        predicate_schema = full_schema.create_schema_view(sorted(predicate_fields))
        encoded_pred_rows = self._read_columns(piece, sorted(predicate_fields))
        decoded_pred_rows = decode_rows(encoded_pred_rows, predicate_schema,
                                        num_threads=self.args.get('decode_threads'))
        mask = [predicate.do_include(row) for row in decoded_pred_rows]
        if not any(mask):
            return []

        if other_fields:
            other_schema = schema.create_schema_view(other_fields)
            encoded_other = self._read_columns(piece, other_fields)
            surviving = [(pred_row, other_row) for include, pred_row, other_row
                         in zip(mask, decoded_pred_rows, encoded_other) if include]
            decoded_other = decode_rows([other for _, other in surviving], other_schema,
                                        num_threads=self.args.get('decode_threads'))
            result = []
            for (pred_row, _), decoded in zip(surviving, decoded_other):
                decoded.update({k: v for k, v in pred_row.items() if k in schema.fields})
                result.append(decoded)
            return result
        return [{k: v for k, v in row.items() if k in schema.fields}
                for row, include in zip(decoded_pred_rows, mask) if include]

class PyDictResultsQueueReader(ResequencedReads):
    """Consumer-side: buffers a published chunk, pops single rows.

    Parity: reference ``py_dict_reader_worker.py:64-97``. In deterministic
    mode chunk pops route through the reader's resequencer
    (``ResequencedReads``) so delivery order equals ventilation order.
    """

    def __init__(self):
        from collections import deque
        self._buffer = deque()
        self._tracker = None
        self._last_lineage = None
        self._last_det = None

    def set_tracker(self, tracker):
        self._tracker = tracker

    @property
    def batched_output(self):
        return False

    @property
    def last_chunk_lineage(self):
        """Provenance segment of the single row most recently returned:
        the producing chunk's segment narrowed to that row
        (``row_start`` = the row's index within the published chunk;
        consecutive rows coalesce downstream). ``None`` for untagged or
        ngram payloads."""
        return self._last_lineage

    @property
    def last_chunk_det(self):
        """Deterministic-mode tag of the chunk the most recently returned
        row came from, or None outside deterministic mode."""
        return self._last_det

    def read_next(self, pool, schema, ngram):
        while not self._buffer:
            chunk = self._pull(pool)
            if is_hole(chunk):
                continue
            if isinstance(chunk, dict) and chunk.get('__pst_chunk__'):
                key, rows = chunk['key'], chunk['rows']
                lineage = chunk.get('lineage')
                det = chunk.get('det')
            else:  # untagged payload (e.g. a custom worker)
                key, rows, lineage, det = None, chunk, None, None
            skip = 0
            if self._tracker is not None and key is not None:
                skip = self._tracker.on_chunk(key, len(rows), det=det)
            self._buffer.extend(
                (key, row, lineage, skip + i, det)
                for i, row in enumerate(rows[skip:]))
        key, row, lineage, row_index, det = self._buffer.popleft()
        if lineage is not None:
            self._last_lineage = dict(lineage, row_start=row_index)
        else:
            self._last_lineage = None
        self._last_det = det
        if self._tracker is not None and key is not None:
            self._tracker.rows_yielded(key, 1)
        if ngram is not None:
            return {offset: ngram.get_schema_at_timestep(schema, offset).make_namedtuple(**fields)
                    for offset, fields in row.items()}
        return schema.make_namedtuple(**row)
