"""JAX loader: the TPU-native framework adapter (the point of the project).

The reference feeds TF via ``tf_utils.py`` and torch via ``pytorch.py``
(SURVEY.md §2.6). This module is their TPU equivalent, designed per
SURVEY.md §7.6:

  * fixed-size batch re-chunking of row-group output (the reference's
    ``BatchingTableQueue`` idea, ``pyarrow_helpers/batching_table_queue.py``),
  * optional seeded row-level shuffling (``RandomShufflingBuffer``),
  * dtype sanitization to TPU-supported dtypes (cf. ``pytorch.py:36-66`` /
    ``tf_utils.py:58-97``),
  * ragged-field shape policies (pad/crop) because XLA needs static shapes —
    a decision the reference never had to make (SURVEY.md §7 "Hard parts"),
  * device staging onto a ``Mesh``-sharded layout (each pod host
    contributes its disjoint reader shard — ``make_pod_reader`` maps
    ``cur_shard`` to ``jax.process_index()``): per-device sharded
    assembly by default — zero-copy batch-dim sub-slices going out as
    one batched per-device transfer a field, inline or from a per-device
    stream by the shard's bytes — with
    ``jax.make_array_from_process_local_data`` as the one-shot fallback
    for shardings that split non-batch dims; plain ``device_put``
    single-chip,
  * a pipelined staging engine (``staging.py``): batch assembly into
    recycled host arenas overlapped with a bounded window of in-flight
    ``device_put``s, so collate of batch N+1 hides under the transfer of
    batch N and host->HBM transfer of batch N+1 hides under XLA step N.
"""

import contextlib
import logging
import queue
import threading
import time
import warnings
from collections import Counter, deque

import numpy as np

from petastorm_tpu import trace as trace_mod
from petastorm_tpu.utils import cached_namedtuple

logger = logging.getLogger(__name__)

_END = object()


def _never_ready():
    """Fallback readiness probe for array types without ``is_ready`` —
    the engine then waits via the blocking ``ready_fn`` instead."""
    return False


# --------------------------------------------------------------------------
# shape policies
# --------------------------------------------------------------------------

class ShapePolicy(object):
    """How to give a ragged field a static shape."""

    def apply(self, array):
        raise NotImplementedError


class PadTo(ShapePolicy):
    """Pad (and clip) every sample to ``target_shape`` with ``fill_value``."""

    def __init__(self, target_shape, fill_value=0):
        self.target_shape = tuple(target_shape)
        self.fill_value = fill_value

    def apply(self, array):
        array = np.asarray(array)
        if array.shape == self.target_shape:
            return array
        out = np.full(self.target_shape, self.fill_value, dtype=array.dtype)
        slices = tuple(slice(0, min(a, t)) for a, t in zip(array.shape, self.target_shape))
        out[slices] = array[slices]
        return out


class CropTo(ShapePolicy):
    """Center-crop every sample to ``target_shape`` (must fit)."""

    def __init__(self, target_shape):
        self.target_shape = tuple(target_shape)

    def apply(self, array):
        array = np.asarray(array)
        if array.shape == self.target_shape:
            return array
        starts = [(a - t) // 2 for a, t in zip(array.shape, self.target_shape)]
        if any(s < 0 for s in starts):
            raise ValueError('CropTo{}: sample shape {} too small'.format(
                self.target_shape, array.shape))
        slices = tuple(slice(s, s + t) for s, t in zip(starts, self.target_shape))
        return array[slices]


# --------------------------------------------------------------------------
# dtype sanitization
# --------------------------------------------------------------------------

def _sanitize_dtype(np_dtype, x64=False):
    """Map a numpy dtype to its TPU-friendly dtype (or None if unsupported).

    Parity role: reference ``pytorch.py:36-66`` / ``tf_utils.py:58-97``.
    """
    np_dtype = np.dtype(np_dtype)
    if np_dtype.kind in ('O', 'U', 'S'):
        return None
    if np_dtype.kind == 'M':
        # datetime64 -> ns-epoch int64. Without x64 the values cannot be
        # represented (int32 would wrap) — treat as unsupported rather than
        # silently corrupt.
        return np.dtype('int64') if x64 else None
    if not x64:
        if np_dtype == np.float64:
            return np.dtype('float32')
        if np_dtype == np.int64:
            return np.dtype('int32')
        if np_dtype == np.uint64:
            return np.dtype('uint32')
    return np_dtype


def _sanitize_array(array, x64=False):
    array = np.asarray(array)
    target = _sanitize_dtype(array.dtype, x64)
    if target is None:
        return None
    if array.dtype.kind == 'M':
        array = array.astype('datetime64[ns]').astype(np.int64)
    return np.ascontiguousarray(array.astype(target, copy=False))


# --------------------------------------------------------------------------
# host-side batch assembly (no jax dependency — independently testable)
# --------------------------------------------------------------------------

#: Optional on-device image decode op (``register_device_decode``): when a
#: backend exposes a real JPEG->tensor op inside XLA, registering it here
#: makes the loader ship raw bytes all the way to the device. No such op
#: exists on stock CPU/TPU jax — the staging step then host-decodes via
#: the native batched codec (the documented fallback), which still moves
#: decode OFF the worker pool and NEXT to the transfer.
_DEVICE_DECODE_HOOK = None


def register_device_decode(fn):
    """Register ``fn(encoded_column, shape, dtype) -> device array`` as the
    on-device image decode op (``encoded_column`` is an object ndarray of
    JPEG/PNG bytes; the result must be a ``[N, *shape]`` device array).
    Pass ``None`` to clear. Returns the previously registered hook."""
    global _DEVICE_DECODE_HOOK
    previous, _DEVICE_DECODE_HOOK = _DEVICE_DECODE_HOOK, fn
    return previous


def _build_shuffling_buffer(capacity, min_after_dequeue, seed):
    """The one shuffling-buffer construction shared by ``JaxLoader`` and
    standalone ``iter_numpy_batches`` callers — same decorrelation floor
    default (4/5 of capacity) and add-overshoot headroom either way."""
    from petastorm_tpu.shuffling_buffer import RandomShufflingBuffer
    if min_after_dequeue is None:
        min_after_dequeue = capacity * 4 // 5
    return RandomShufflingBuffer(capacity, min_after_dequeue, seed=seed,
                                 extra_capacity=100000)


def iter_numpy_batches(reader, batch_size, shape_policies=None,
                       shuffling_queue_capacity=0, min_after_dequeue=None,
                       seed=None, last_batch='drop', x64=False,
                       strict_fields=False, batch_buffers=None, views_ok=True,
                       lineage=None, shuffler=None, commit_rows=None,
                       raw_fields=None):
    """Yield dicts of numpy arrays with exact leading dim ``batch_size``.

    Works over both row readers (``make_reader``) and batch readers
    (``make_batch_reader``); re-chunks row-group-sized output into fixed
    batches. ``last_batch``: 'drop' | 'pad' (repeat-pad the final partial
    batch) | 'partial' (yield it short). ``strict_fields=True`` raises
    instead of warn-and-drop when a selected field cannot batch (e.g. a
    nullable-declared field that is never actually null) — pass
    ``schema_fields`` excluding it, or a TransformSpec redeclaring it
    non-nullable, to proceed.

    ``batch_buffers`` (the staging engine's arena hookup): a callable
    ``spec -> dict of arrays or None`` (``spec``: {name: (shape, dtype)})
    providing preallocated output buffers; batches are then collated into
    those buffers in place (``np.copyto``/``out=``) instead of allocating
    with ``np.stack``/``np.concatenate``, and the provider pairs each
    yielded batch with its backing arena (``ArenaPool.claim_pending``).
    ``views_ok=False`` additionally forces batches that would be zero-copy
    chunk views into the buffers — transfer backends that don't alias host
    memory prefer stable recycled buffers over views.

    ``lineage`` (a :class:`petastorm_tpu.lineage.LineageCollector`): batch
    provenance capture — each arriving chunk's segment metadata is pushed
    and each emitted batch pops the FIFO spans composing it (exact without
    a shuffling buffer; a shuffling buffer flags records inexact).

    ``shuffler``: a pre-built (possibly checkpoint-restored)
    :class:`~petastorm_tpu.shuffling_buffer.RandomShufflingBuffer` to use
    instead of constructing one from ``shuffling_queue_capacity`` — the
    JaxLoader owns its buffer this way so ``state_dict()`` can snapshot
    buffered-but-undelivered rows.
    """
    if last_batch not in ('drop', 'pad', 'partial'):
        raise ValueError("last_batch must be drop|pad|partial, got {!r}".format(last_batch))
    shape_policies = dict(shape_policies or {})
    raw_fields = tuple(raw_fields
                       if raw_fields is not None
                       else getattr(reader, 'raw_image_fields', ()) or ())

    field_names = None
    dropped = set()
    columns = {}
    count = 0

    if shuffler is None and shuffling_queue_capacity \
            and shuffling_queue_capacity > 0:
        shuffler = _build_shuffling_buffer(shuffling_queue_capacity,
                                           min_after_dequeue, seed)
    if shuffler is not None and lineage is not None:
        # Row-level shuffling breaks the FIFO chunk->batch mapping:
        # records still name the contributing chunks, but row spans
        # are no longer exact (replay refuses such records).
        lineage.mark_inexact()

    def _is_tensor_like(probe, name):
        """True if a sample value can become a TPU tensor (possibly via policy)."""
        if probe is None:
            # Field with None values cannot batch; dropped with a warning.
            # (A later None in a kept field raises a clear error in
            # _stack_column.) Fill nullables via TransformSpec to keep them.
            return False
        arr = np.asarray(probe)
        if arr.dtype.kind not in ('O', 'U', 'S'):
            return True
        # Object values may still be numeric ndarrays (ragged) — keep when a
        # shape policy exists, or when the payload itself is numeric.
        if isinstance(probe, np.ndarray) and probe.dtype.kind not in ('O', 'U', 'S'):
            return True
        return name in shape_policies

    schema = getattr(reader, 'transformed_schema', None)

    def _declared_nullable(name):
        # Row readers carry a deliberate Unischema: its nullable flag is
        # authoritative (batch readers infer schemas where arrow marks nearly
        # everything nullable, so probing is used there instead). A
        # TransformSpec that fills nulls can redeclare the field with
        # nullable=False via edit_fields to keep it.
        return (not reader.batched_output and schema is not None
                and name in schema.fields and schema.fields[name].nullable)

    def select_fields(sample):
        nonlocal field_names
        names = []
        for name in sample._fields:
            value = getattr(sample, name)
            if reader.batched_output:
                column = np.asarray(value)
                probe = column[0] if (column.dtype.kind == 'O' and len(column)) else column
            else:
                probe = value
            if not _declared_nullable(name) and _is_tensor_like(probe, name):
                names.append(name)
            else:
                dropped.add(name)
        if dropped:
            if strict_fields:
                raise ValueError(
                    'jax loader cannot batch fields: {} (nullable-declared or '
                    'non-tensor). With strict_fields=True this is an error; '
                    'narrow schema_fields, fill nulls via a TransformSpec that '
                    'redeclares the field nullable=False, or pass '
                    'strict_fields=False to drop them with a warning.'.format(
                        sorted(dropped)))
            warnings.warn('jax loader dropping non-tensor fields: {} '
                          '(select fields explicitly or add a TransformSpec '
                          'to keep them)'.format(sorted(dropped)))
        field_names = names
        if shuffler is not None:
            # Ride the checkpoint: the buffered row tuples are ordered by
            # this selection, and a resumed reader may yield zero samples
            # to re-learn it from (see the drain below).
            shuffler.field_names = list(names)

    def to_rows(sample):
        """Batched sample -> per-row tuples (reference pytorch.py:166-175)."""
        cols = [getattr(sample, n) for n in field_names]
        return list(zip(*cols))

    def add_sample_columns(sample):
        nonlocal count
        for name in field_names:
            value = getattr(sample, name)
            columns.setdefault(name, []).append(value)
        count += 1

    batch_spec = None     # learned from the first emitted batch (arena hookup)
    arenas_effective = True   # until a whole batch proves un-stackable

    def emit_batches(final=False):
        nonlocal columns, count, batch_spec, arenas_effective
        while count >= batch_size:
            out_bufs = (batch_buffers(batch_spec)
                        if batch_buffers is not None and batch_spec
                        and arenas_effective else None)
            batch = {}
            for name in field_names:
                buf = out_bufs.get(name) if out_bufs is not None else None
                batch[name] = _stack_column(columns[name][:batch_size], name,
                                            shape_policies, x64, out=buf)
                columns[name] = columns[name][batch_size:]
            count -= batch_size
            if batch_spec is None:
                batch_spec = {name: (arr.shape, arr.dtype)
                              for name, arr in batch.items()}
            elif out_bufs is not None:
                # Row dtypes that always need a sanitize conversion (e.g.
                # int64 rows into an int32 spec) can never stack into the
                # arena: if no field used its buffer, claiming an arena per
                # batch is pure overhead — stop asking for them.
                arenas_effective = any(batch[name] is out_bufs[name]
                                       for name in field_names)
            if lineage is not None:
                lineage.on_batch(batch_size, batch=batch)
            yield batch
        if final and count:
            if last_batch == 'drop':
                columns = {}
                count = 0
            elif last_batch in ('pad', 'partial'):
                batch = {}
                source_rows = count
                for name in field_names:
                    col = columns[name]
                    if last_batch == 'pad':
                        col = col + [col[-1]] * (batch_size - len(col))
                    batch[name] = _stack_column(col, name, shape_policies, x64)
                columns = {}
                count = 0
                if lineage is not None:
                    lineage.on_batch(source_rows, batch=batch,
                                     padded=(batch_size - source_rows
                                             if last_batch == 'pad' else 0))
                yield batch

    if getattr(reader, 'batched_output', False) and shuffler is None:
        # Block fast path: batched readers (tensor/arrow) without row-level
        # shuffling never transpose to per-row tuples — column blocks are
        # sliced/concatenated directly, one memcpy per batch at most (zero
        # when a batch lies inside one chunk). This is the decoded-columnar
        # hot path (VERDICT r2 #1); the reference's closest analog is the
        # unused BatchingTableQueue re-chunker
        # (``pyarrow_helpers/batching_table_queue.py:20-79``).
        yield from _iter_block_batches(reader, batch_size, shape_policies,
                                       last_batch, x64, strict_fields,
                                       batch_buffers=batch_buffers,
                                       views_ok=views_ok, lineage=lineage,
                                       raw_fields=raw_fields)
        return

    if raw_fields:
        raise ValueError(
            'raw image fields {} require the block fast path: a row-level '
            'shuffling buffer (shuffling_queue_capacity) re-rows encoded '
            'byte columns the staging-step decode cannot follow — shuffle '
            'with shuffle_row_groups/shuffle_rows_in_chunk instead'.format(
                sorted(raw_fields)))

    for sample in reader:
        if field_names is None:
            select_fields(sample)
        if reader.batched_output:
            rows = to_rows(sample)
        else:
            rows = [tuple(getattr(sample, n) for n in field_names)]
        if lineage is not None:
            lineage.on_chunk(getattr(reader, 'last_chunk_lineage', None),
                             len(rows))
        if shuffler is not None:
            if commit_rows is not None:
                # Loader-supplied atomic commit: buffer insert + checkpoint
                # attribution under one lock (see JaxLoader._commit_rows).
                commit_rows(rows)
            else:
                shuffler.add_many(rows)
            while shuffler.can_retrieve():
                row = shuffler.retrieve()
                for name, value in zip(field_names, row):
                    columns.setdefault(name, []).append(value)
                count += 1
                if count >= batch_size:
                    yield from emit_batches()
        else:
            for row in rows:
                for name, value in zip(field_names, row):
                    columns.setdefault(name, []).append(value)
                count += 1
            yield from emit_batches()

    if shuffler is not None:
        shuffler.finish()
        if field_names is None and shuffler.can_retrieve():
            # The reader yielded nothing — every remaining row was already
            # buffered at checkpoint time, so the selection was never
            # learned from a sample. The snapshot carried it.
            field_names = getattr(shuffler, 'field_names', None)
            if field_names is None:
                raise ValueError(
                    'restored shuffling buffer holds rows but the resumed '
                    'reader yielded no samples and the snapshot predates '
                    'field-name capture — the rows cannot be attributed '
                    'to fields (re-checkpoint with this version)')
        while shuffler.can_retrieve():
            row = shuffler.retrieve()
            for name, value in zip(field_names, row):
                columns.setdefault(name, []).append(value)
            count += 1
        yield from emit_batches(final=True)
    else:
        yield from emit_batches(final=True)


def _iter_block_batches(reader, batch_size, shape_policies, last_batch, x64,
                        strict_fields, batch_buffers=None, views_ok=True,
                        lineage=None, raw_fields=()):
    """Fixed-size batches assembled from column blocks (no per-row Python).

    Chunks (one per row-group) are sanitized once on arrival; batches are
    built from leading-dim slices — a contiguous view when one chunk covers
    the batch (``views_ok``), else collated into a recycled arena slice
    (``batch_buffers``) or, without an arena provider, one
    ``np.concatenate``-equivalent memcpy into a fresh buffer.

    Ownership: each chunk carries the reader's block-handoff marker
    (``last_chunk_private`` — see ``TensorWorker``). Shared (cache-
    resident) blocks are only ever *copied from*; a whole private chunk
    that exactly covers a batch may instead be handed out directly (its
    buffer is unshared, so downstream may keep or alias it freely without
    ever corrupting the cache).

    ``raw_fields`` names encoded-bytes columns (the on-device decode
    handoff, ``make_tensor_reader(raw_image_fields=...)``): object-dtype
    columns of raw JPEG/PNG bytes that flow through batching as O(1)
    reference slices — never sanitized, never arena-collated (an arena is
    a pixel buffer; these are pointers) — and leave this iterator still
    encoded for the loader's staging step to decode.
    """
    shape_policies = dict(shape_policies or {})
    raw_fields = frozenset(raw_fields or ())
    overlap = raw_fields & set(shape_policies)
    if overlap:
        raise ValueError(
            'shape policies on raw image fields {} are impossible: the '
            'column holds encoded bytes until the staging-step decode'
            .format(sorted(overlap)))
    field_names = None
    dropped = []
    chunks = []   # list of [dict name -> sanitized array, private_bool]
    have = 0

    def densify(name, arr):
        """Object (ragged) columns become dense via per-row policy+stack;
        a policy on an already-dense column still applies per row (same
        semantics as the per-row ``_stack_column`` path)."""
        arr = np.asarray(arr)
        policy = shape_policies.get(name)
        if arr.dtype.kind != 'O':
            if policy is None:
                return arr
            return np.stack([policy.apply(v) for v in arr])
        values = [policy.apply(v) for v in arr] if policy is not None else list(arr)
        if any(v is None for v in values):
            raise ValueError(
                'Field {!r} contains None (nullable) values; fill or drop them '
                'with a TransformSpec before batching for TPU'.format(name))
        try:
            return np.stack([np.asarray(v) for v in values])
        except ValueError as e:
            raise ValueError(
                'Field {!r} has ragged shapes and no shape policy; pass '
                "shape_policies={{'{}': PadTo(...)}} or CropTo(...): {}".format(
                    name, name, e)) from e

    def select(sample):
        names = []
        for name in sample._fields:
            if name in raw_fields:
                names.append(name)
                continue
            column = np.asarray(getattr(sample, name))
            probe = column[0] if (column.dtype.kind == 'O' and len(column)) else column
            arr = np.asarray(probe)
            ok = arr.dtype.kind not in ('O', 'U', 'S') or name in shape_policies
            if ok:
                names.append(name)
            else:
                dropped.append(name)
        if dropped:
            if strict_fields:
                raise ValueError(
                    'jax loader cannot batch fields: {} (non-tensor). Narrow '
                    'schema_fields or pass strict_fields=False to drop them '
                    'with a warning.'.format(sorted(dropped)))
            warnings.warn('jax loader dropping non-tensor fields: {}'.format(
                sorted(dropped)))
        if not names:
            raise ValueError('No batchable fields left (all dropped: {})'.format(
                sorted(dropped)))
        return names

    def out_buffers(n, head):
        """A destination for ``n`` collated rows: an arena from the
        provider when available (recycled, zero allocations), else fresh.
        Raw (encoded-bytes) columns never ride arenas — their cells are
        object references, not pixels — and always get a fresh tiny
        object array."""
        spec = {name: ((n,) + head[name].shape[1:], head[name].dtype)
                for name in field_names if name not in raw_fields}
        out = (batch_buffers(spec)
               if batch_buffers is not None and spec else None)
        if out is None:
            out = {name: np.empty(shape, dtype)
                   for name, (shape, dtype) in spec.items()}
        for name in raw_fields:
            if name in field_names:
                out[name] = np.empty(n, dtype=object)
        return out

    def take(n):
        """Pop ``n`` leading rows across chunks -> dict of arrays.

        Zero-copy single-chunk fast paths first (a leading-dim view when
        ``views_ok``; whole-chunk handout when the chunk is private);
        otherwise collate into ``out_buffers`` slice by slice via
        ``np.copyto`` — shared chunks are only ever read.
        """
        nonlocal have
        head, head_private = chunks[0]
        rows = len(head[field_names[0]])
        if rows == n and (views_ok or head_private):
            chunks.pop(0)
            have -= n
            return head
        if rows > n and views_ok:
            chunks[0][0] = {name: head[name][n:] for name in field_names}
            have -= n
            return {name: head[name][:n] for name in field_names}
        out = out_buffers(n, head)
        pos, need = 0, n
        while need > 0:
            head, _ = chunks[0]
            rows = len(head[field_names[0]])
            k = min(rows, need)
            for name in field_names:
                np.copyto(out[name][pos:pos + k], head[name][:k])
            if k == rows:
                chunks.pop(0)
            else:
                chunks[0][0] = {name: head[name][k:] for name in field_names}
            pos += k
            need -= k
        have -= n
        return out

    for sample in reader:
        if field_names is None:
            field_names = select(sample)
        private = bool(getattr(reader, 'last_chunk_private', False))
        chunk = {}
        all_copied = True
        for name in field_names:
            source = np.asarray(getattr(sample, name))
            if name in raw_fields:
                # Encoded bytes pass through untouched (decoded at the
                # staging step); slicing an object column copies refs,
                # so treat it like any shared block.
                chunk[name] = source
                all_copied = False
                continue
            arr = _sanitize_array(densify(name, source), x64)
            if arr is None:
                raise ValueError('Field {!r} dtype is not TPU-compatible'.format(name))
            chunk[name] = arr
            all_copied = all_copied and arr is not source
        # densify/sanitize copies (dtype conversion, ragged stack) make the
        # blocks private even when the reader's came out of a cache.
        if not all_copied and not private:
            # Cache-shared views may be chunk-store mmaps: hint the kernel
            # to fault their extents in now, while earlier batches collate,
            # instead of paying major faults inside the copy loop below.
            from petastorm_tpu.staging import willneed_arrays
            willneed_arrays(chunk.values())
        chunks.append([chunk, private or all_copied])
        chunk_rows = len(chunk[field_names[0]]) if field_names else 0
        have += chunk_rows
        if lineage is not None:
            lineage.on_chunk(getattr(reader, 'last_chunk_lineage', None),
                             chunk_rows)
        while have >= batch_size:
            batch = take(batch_size)
            if lineage is not None:
                lineage.on_batch(batch_size, batch=batch)
            yield batch

    if have and field_names:
        if last_batch == 'partial':
            source_rows = have
            batch = take(have)
            if lineage is not None:
                lineage.on_batch(source_rows, batch=batch)
            yield batch
        elif last_batch == 'pad':
            # Repeat-pad the tail into a full-size buffer. Never in place:
            # the tail chunk may be a cache-shared block, which is strictly
            # copy-from (see the ownership marker above).
            out = out_buffers(batch_size, chunks[0][0])
            pos = 0
            while chunks:
                head, _ = chunks.pop(0)
                k = len(head[field_names[0]])
                for name in field_names:
                    np.copyto(out[name][pos:pos + k], head[name])
                pos += k
            for name in field_names:
                out[name][pos:] = out[name][pos - 1]
            source_rows, have = have, 0
            if lineage is not None:
                lineage.on_batch(source_rows, batch=out,
                                 padded=batch_size - source_rows)
            yield out


def _stack_column(values, name, shape_policies, x64, out=None):
    if any(v is None for v in values):
        raise ValueError(
            'Field {!r} contains None (nullable) values; fill or drop them with a '
            'TransformSpec before batching for TPU'.format(name))
    policy = shape_policies.get(name)
    if policy is not None:
        values = [policy.apply(v) for v in values]
    if out is not None:
        # Arena fast path: when the rows already match the sanitized target
        # dtype/shape, stack straight into the recycled buffer — no
        # allocation, and the later sanitize pass is a no-op by
        # construction. Any mismatch (e.g. int64 rows headed for an int32
        # buffer) falls through to the allocating path below (reusing the
        # converted rows).
        rows = [np.asarray(v) for v in values]
        if (len(rows) == out.shape[0]
                and all(r.dtype == out.dtype and r.shape == out.shape[1:]
                        for r in rows)):
            np.stack(rows, out=out)
            return out
        values = rows
    try:
        stacked = np.stack([np.asarray(v) for v in values])
    except ValueError as e:
        raise ValueError(
            'Field {!r} has ragged shapes and no shape policy; pass '
            "shape_policies={{'{}': PadTo(...)}} or CropTo(...): {}".format(
                name, name, e)) from e
    sanitized = _sanitize_array(stacked, x64)
    if sanitized is None:
        raise ValueError('Field {!r} dtype {} is not TPU-compatible'.format(
            name, stacked.dtype))
    return sanitized


# --------------------------------------------------------------------------
# device staging + prefetch
# --------------------------------------------------------------------------

class _BatchedShardWave(object):
    """One field's whole per-device wave: what :meth:`JaxLoader.
    _batched_assemble` puts as one C++ batched transfer over every shard
    view, inline or as a SINGLE stream item. On a stream, DMA-scale
    fields get the cheap dispatch of the inline tier AND land against the
    per-device in-flight windows (fence pipelining) instead of blocking
    the dispatch thread; the put records the true per-device byte/shard
    breakdown itself (``record_inline_wave``), so the submitting stream
    keeps the window's books only and claims none of the wave's bytes as
    its own."""

    __slots__ = ('sharding', 'plan', 'streams', 'views', 'from_arena',
                 'nbytes')

    def __init__(self, sharding, plan, streams, views, from_arena):
        self.sharding = sharding
        self.plan = plan
        self.streams = streams
        self.views = views
        self.from_arena = from_arena
        self.nbytes = sum(v.nbytes for v in views)


class JaxLoader(object):
    """Iterates mesh-sharded ``jax.Array`` batches off a Reader.

    Each field of a batch is staged by one of six tiers, chosen from what
    the loader can observe and counted in ``stats['stage_tiers']`` (the
    ``dispatch.stage`` span's ``cause`` names the batch's):
    ``device-decoded`` (the decode hook already returned a device array);
    with a ``mesh``/``sharding`` whose devices this process addresses,
    ``inline-batched`` or ``streamed-batched`` by the shard's bytes against
    ``device_stream_min_bytes`` where the sharding partitions just the
    leading batch dim (each device's shard is then a zero-copy contiguous
    sub-slice of the host batch:
    :func:`petastorm_tpu.parallel.mesh.device_shard_plan`, computed once
    per schema), else ``one-shot``
    (``jax.make_array_from_process_local_data``, e.g. a sequence-sharded
    dim); without either, ``dlpack`` on the CPU backend and ``plain``
    (``jax.device_put``) elsewhere.

    :param reader: a ``make_reader``/``make_batch_reader`` Reader (each pod
        host should construct it with ``cur_shard=jax.process_index()``).
    :param batch_size: **global** batch size when ``mesh``/``sharding`` is
        given (each host contributes ``batch_size / process_count`` rows);
        plain host batch size otherwise.
    :param mesh: ``jax.sharding.Mesh`` — batches are sharded over its 'data'
        axis (override via ``sharding``).
    :param sharding: explicit ``NamedSharding`` (or dict field->sharding).
    :param prefetch: device batches staged ahead (double-buffering default 2).
        ``prefetch > 0`` runs the pipelined staging engine — an assemble
        thread collating into recycled host arenas plus a dispatch thread
        keeping ``inflight`` transfers in the air (see ``staging.py``).
        ``0`` disables the staging threads entirely: host batches are
        assembled ahead by the reader's worker pool as usual, but the
        ``device_put`` happens inline in the consumer thread.
    :param shape_policies: dict field -> ShapePolicy for ragged fields.
    :param last_batch: 'drop' (pod-safe default) | 'pad' | 'partial'.
    :param strict_fields: raise (instead of warn-and-drop) when a selected
        field cannot batch — e.g. declared nullable but never actually null.
    :param tracer: a ``trace.Tracer`` to record the loader's collate,
        dispatch and consumer spans into (a chrome://tracing timeline).
        ``None`` (default) means ``trace.get_global_tracer()``: the
        process-wide bounded ring, on unless
        ``trace.set_global_tracer(trace.NullTracer())`` switched it off.
    :param echo: data echoing (Choi et al., "Faster Neural Network Training
        with Data Echoing"): deliver each staged batch ``echo`` times. When
        the pipeline is input-bound (``input_stall_frac`` high) echoed
        repeats trade statistical efficiency for step throughput — the chip
        trains instead of idling. Epoch/checkpoint accounting counts source
        rows once; ``stats['batches']`` counts echoed deliveries.
    :param arena_depth: host-batch arenas in the staging engine's pool
        (``prefetch > 0`` only). Batches are collated into these recycled
        preallocated buffers instead of allocating every batch; an arena
        returns to the pool once its transfer completed and (on zero-copy
        backends) the consumer dropped its arrays. Default sizes the pool
        to ``max(2, prefetch) + inflight + 2``; an exhausted pool briefly
        backpressures the assembler, then grows (visible as
        ``stats['arena_alloc']``) rather than deadlocking a consumer that
        holds many batches (e.g. ``superbatches(k)``).
    :param inflight: staged batches whose transfers may be in flight
        before the dispatch stage blocks on the oldest — the window that
        lets collate of batch N+1 overlap the transfer of batch N
        (``stats['overlap_frac']``).
    :param device_inflight: per-device in-flight transfer window of the
        per-device dispatch streams (``staging.DeviceStager``,
        ``pst-device-put-*`` threads; each stream blocks on its own
        oldest transfer past this) — the autotuner's ``device_inflight``
        knob; dispatch-bound ticks widen it before the batch-level
        ``inflight`` window.
    :param device_stream_min_bytes: per-shard size at which a planned
        field's wave is issued from a per-device *stream thread*
        (``streamed-batched``: the transfer lands against the per-device
        in-flight window instead of blocking dispatch, which pays when
        each transfer is DMA-scale). Smaller shards are issued inline on
        the dispatch thread (``inline-batched``). Both are ONE batched
        per-device transfer a field (``pxla.batched_device_put`` over the
        precomputed zero-copy shard views) and produce the identical
        per-device-sharded global array. Default 8MB; ``0`` sends every
        planned field through the streams.
    :param pinned_arenas: allocate the host staging arenas as
        DMA-friendly pinned slabs (page-aligned, pre-faulted,
        best-effort ``mlock`` — see ``native/pinned.py``); falls back
        to plain buffers when no pinned tier is available. ``None``
        defers to ``PETASTORM_TPU_PINNED_ARENAS=1``; the autotuner's
        ``arena_pinned`` knob and the memory governor's advisory rung
        can flip it at runtime.
    :param watchdog: enable the pipeline health supervisor
        (``petastorm_tpu.health``): every stage beats a heartbeat and a
        watchdog thread classifies stalls (reader-starved / assemble-stuck
        / dispatch-hung / consumer-not-draining / arena-pool-wedged /
        remote-server-dead), records a diagnosis (thread stacks, beat
        table, stage counters) into ``stats['watchdog']``, runs soft
        recovery, and escalates a persistent stall to a
        :class:`~petastorm_tpu.errors.PipelineStallError` raised from
        ``__next__`` instead of an anonymous hang. ``None`` defers to the
        ``PETASTORM_TPU_WATCHDOG`` environment variable (off when unset).
    :param stall_timeout_s: per-stage stall deadlines for the watchdog —
        a number (applies to every stage) or a dict mapping stage name
        (``'assemble'``, ``'dispatch'``, ``'consumer'``, ``'remote-recv'``,
        ``'worker-pool'``, ...) or ``'default'`` to seconds. Default 60s.
    :param autotune: enable the adaptive pipeline autotuner
        (``petastorm_tpu.autotune``): a control thread classifies the
        dominant bottleneck each tick from the wait counters above and
        retunes prefetch depth, the in-flight transfer window, arena
        depth, the reader's live worker count, and the ventilation
        watermark within bounded ranges. ``True`` for defaults, an
        :class:`~petastorm_tpu.autotune.AutotuneConfig` for custom clamps
        and pacing; ``None`` defers to ``PETASTORM_TPU_AUTOTUNE``. The
        decision log and knob trajectory ride ``stats['autotune']``.
    :param lineage: batch provenance ledger (``petastorm_tpu.lineage``):
        every delivered batch gets a record — monotonic batch id, the
        ordered (parquet file, row-group, row-range) spans composing it,
        producing worker + serving tier per span, shuffle state, and a
        per-field CRC32 content digest — kept in a ring (dumped by the
        stall flight recorder) and spilled to a crash-tolerant JSONL
        ledger replayable with ``python -m petastorm_tpu.tools.replay``.
        ``True`` arms it (ledger dir from ``PETASTORM_TPU_LINEAGE_DIR``
        or a fresh temp dir); a string is the ledger directory; a
        :class:`~petastorm_tpu.lineage.LineageTracker` is adopted as-is;
        ``None`` defers to the environment variable; ``False`` disables.
        The record of the latest batch is ``last_batch_provenance``;
        counters ride ``stats['lineage']``.
    :param on_device_augment: the decode/augment-at-staging path. A
        callable ``batch_dict -> batch_dict`` is jit-compiled and applied
        to every staged device batch INSIDE the XLA step (augmentation
        composes with ``ops.train_augment``/``imagenet_train_augment``);
        ``True`` arms the staging-step decode without an augment. Pairs
        with ``make_tensor_reader(raw_image_fields=...)``: workers then
        ship raw JPEG/PNG bytes and the staging step runs JPEG->tensor —
        through a registered on-device decode op
        (:func:`register_device_decode`) when the backend has one, else
        the host batched decoder right next to the transfer (the
        fallback) — cutting the worker pool's decode CPU out of the
        steady state. With a plain (decoded) reader the augment still
        applies; the decode step is a no-op.
    """

    def __init__(self, reader, batch_size, mesh=None, sharding=None,
                 batch_axis='data', prefetch=2, shape_policies=None,
                 shuffling_queue_capacity=0, min_after_dequeue=None, seed=None,
                 last_batch='drop', strict_fields=False, echo=1, tracer=None,
                 arena_depth=None, inflight=2,
                 watchdog=None, stall_timeout_s=None, autotune=None,
                 lineage=None, resume_state=None, on_device_augment=None,
                 device_inflight=2, device_stream_min_bytes=None,
                 pinned_arenas=None):
        import jax

        # Fail a typo'd memory budget before any staging thread starts or
        # governor registration happens (mirrors Reader.__init__).
        from petastorm_tpu import membudget as membudget_mod
        membudget_mod.validate_env_budget()

        self._tracer = trace_mod.resolve(tracer)
        trace_mod.watch_jax_compiles()

        self._reader = reader
        self._mesh = mesh
        self._sharding = sharding
        self._batch_axis = batch_axis
        self._jax = jax
        x64 = bool(jax.config.jax_enable_x64)

        # On-device decode/augment (see the on_device_augment param): raw
        # image fields the reader ships encoded, decoded at the staging
        # step; an optional jitted augment applied to every staged batch.
        self._raw_specs = {}
        raw_fields = tuple(getattr(reader, 'raw_image_fields', ()) or ())
        if raw_fields:
            if shuffling_queue_capacity:
                raise ValueError(
                    'raw image fields {} require the block fast path; a '
                    'row-level shuffling buffer cannot carry encoded byte '
                    'columns — shuffle with shuffle_row_groups/'
                    'shuffle_rows_in_chunk instead'.format(sorted(raw_fields)))
            for name in raw_fields:
                self._raw_specs[name] = reader.schema.fields[name]
            # Staging-decode thread sizing: when raw fields cover EVERY
            # image field the worker pool decodes nothing and the staging
            # thread may spend the whole process budget; a partial
            # selection leaves workers decoding the rest, so the staging
            # thread takes a fair share like any other decoder.
            from petastorm_tpu.codecs import CompressedImageCodec
            image_fields = {n for n, f in reader.schema.fields.items()
                            if isinstance(f.resolved_codec(),
                                          CompressedImageCodec)}
            self._staging_owns_budget = set(raw_fields) >= image_fields
        self._augment_fn = None
        if callable(on_device_augment):
            self._augment_fn = jax.jit(on_device_augment)

        if mesh is not None or sharding is not None:
            n_proc = jax.process_count()
            if batch_size % n_proc:
                raise ValueError('global batch_size {} not divisible by process_count {}'
                                 .format(batch_size, n_proc))
            local_batch = batch_size // n_proc
        else:
            local_batch = batch_size
        self._global_batch = batch_size
        self._local_batch = local_batch

        if last_batch == 'partial' and (mesh is not None or sharding is not None):
            raise ValueError("last_batch='partial' breaks fixed global shapes on a mesh; "
                             "use 'drop' or 'pad'")

        # Without a row-level shuffle, rows are consumed in exact delivery
        # order, so checkpoint accounting can be deferred to actual batch
        # delivery (rows sitting in the prefetch queue at checkpoint time are
        # NOT counted consumed and re-deliver on resume).
        self._row_granular_ckpt = False
        self._defer_rows_consumed = False   # superbatches() group accounting
        self._pending_fresh_rows = 0        # fresh rows fetched but not yet
                                            # attributed (deferred mode)
        if not shuffling_queue_capacity and hasattr(reader, 'enable_row_granular_checkpoint'):
            self._row_granular_ckpt = reader.enable_row_granular_checkpoint()

        # The loader OWNS its shuffling buffer (rather than letting
        # iter_numpy_batches build one): state_dict() then snapshots
        # buffered-but-undelivered rows + the RNG state, so a checkpoint
        # with a row-level shuffle engaged no longer forces a drain —
        # restore them via JaxLoader(resume_state=the same dict handed to
        # the reader factory).
        self._shuffler = None
        self._ckpt_lock = threading.Lock()
        self._buffer_entry_ckpt = False
        if shuffling_queue_capacity and shuffling_queue_capacity > 0:
            self._shuffler = _build_shuffling_buffer(
                shuffling_queue_capacity, min_after_dequeue, seed)
            if isinstance(resume_state, dict) \
                    and resume_state.get('shuffling_buffer'):
                self._shuffler.restore(resume_state['shuffling_buffer'])
            # Rows drawn into staged-but-undelivered batches must ride the
            # snapshot too (they are in neither the buffer nor the
            # trainer's hands at checkpoint time); mark_delivered below
            # releases them batch-by-batch as batches actually arrive.
            self._shuffler.track_pending()
            # Buffer-entry attribution: defer the reader's checkpoint
            # cursor and advance it only when a chunk's rows actually land
            # in the buffer — _commit_rows does both under _ckpt_lock, and
            # state_dict() snapshots cursor + buffer under the same lock.
            # Without this, rows moving reader->buffer between the two
            # snapshots would be counted by neither (lost) or both
            # (duplicated) on resume.
            if hasattr(reader, 'enable_row_granular_checkpoint'):
                self._buffer_entry_ckpt = \
                    reader.enable_row_granular_checkpoint()
        elif isinstance(resume_state, dict) \
                and (resume_state.get('shuffling_buffer') or {}).get('rows'):
            # The snapshot's rows were already counted consumed by the
            # reader cursor at checkpoint time; with no buffer to restore
            # them into they would silently never be delivered.
            raise ValueError(
                'resume_state carries a shuffling-buffer snapshot of {} '
                'row(s) but the loader was rebuilt without '
                'shuffling_queue_capacity; those rows would be lost — '
                'resume with the same shuffling_queue_capacity the '
                'checkpoint was taken under'.format(
                    len(resume_state['shuffling_buffer']['rows'])))

        if echo < 1:
            raise ValueError('echo must be >= 1, got {}'.format(echo))
        self._echo = int(echo)
        self._echo_left = 0
        self._echo_item = None
        self._consumer_staging = prefetch == 0
        # Seconds by stage, each fed by the one span that clocks it:
        # wait_s by consumer.wait (the fetch in __next__), stage_decode_s by
        # the raw columns' dispatch.decode, and inline_reader_s and stage_s
        # by collate.batch and dispatch.stage where the consumer runs the
        # whole pipeline itself (prefetch=0: no engine clocks them) and
        # its blocked time alone cannot say WHICH stage is slow.
        self._totals = {'wait_s': 0.0, 'stage_s': 0.0, 'stage_decode_s': 0.0,
                        'inline_reader_s': 0.0}
        self._inline_seq = 0
        # `prefetch` bounds staged-but-undelivered batches (device memory).
        # The consumer's batched pop moves queued batches into its local
        # buffer, so the bound is enforced over BOTH: the queue's live
        # maxsize is always target - len(_ready) (floor 1) — a drained
        # slot does NOT become capacity the dispatch thread may refill,
        # or the ceiling would double.
        self._prefetch_target = max(1, prefetch)
        self._queue = queue.Queue(maxsize=self._prefetch_target)
        # Consumer-local drain buffer: __next__ moves every already-staged
        # batch here under one queue-mutex acquisition instead of paying a
        # lock round trip per batch. Consumer thread only.
        self._ready = deque()
        self._stop = threading.Event()
        self._exhausted = False
        # Pipeline health supervisor (petastorm_tpu.health), armed through
        # the shared control-plane lifecycle: heartbeats on every stage +
        # a watchdog that classifies stalls, runs soft recovery, and
        # escalates to PipelineStallError instead of hanging. Deferred
        # start (start_health below) — staging stages register later.
        from petastorm_tpu.fleet import control_plane as control_plane_mod
        self._supervisor = control_plane_mod.PipelineSupervisor()
        self._hb_consumer = None
        self._stall_error = None

        def attach_stages(registry):
            self._hb_consumer = registry.register('consumer')
            registry.register_probe(
                'consumer', lambda: {'queue_depth': (self._queue.qsize()
                                                     + len(self._ready)),
                                     'queue_capacity': self._prefetch_target,
                                     'exhausted': self._exhausted})
            attach = getattr(reader, 'attach_health', None)
            if attach is not None:
                attach(registry)
            # Memory-pressure classification (health.classify_stall): the
            # governor's ladder state rides every diagnosis, and a stall
            # while degradation is active classifies as memory-pressure
            # (soft) instead of blaming a deliberately-shrunk stage.
            from petastorm_tpu import membudget as membudget_mod
            registry.register_probe(
                'memory', membudget_mod.get_governor().probe)

        self._health = self._supervisor.arm_health(
            watchdog, stall_timeout_s, self._deliver_stall,
            tracer=self._tracer, attach_fn=attach_stages, start=False)
        # Batch provenance (petastorm_tpu.lineage): ring + ledger of what
        # exactly composed every delivered batch. Collector hooks ride the
        # host-batch iterators; records are minted at delivery in __next__.
        from petastorm_tpu import lineage as lineage_mod
        self._lineage = None
        self._lineage_owned = False
        self._last_provenance = None
        if isinstance(lineage, lineage_mod.LineageTracker):
            # Adopted as-is: lifecycle stays with the caller (stop()
            # flushes but must not close — the caller may ledger several
            # loaders through one tracker).
            self._lineage = lineage
        elif lineage_mod.lineage_enabled(lineage):
            ctx_fn = getattr(reader, 'lineage_context', None)
            ctx = ctx_fn() if ctx_fn is not None else {'mode': None}
            ctx['x64'] = x64
            ctx['batch_size'] = local_batch
            ctx['last_batch'] = last_batch
            ctx['shape_policies'] = sorted(shape_policies) \
                if shape_policies else None
            ctx['shuffling_queue_capacity'] = int(shuffling_queue_capacity or 0)
            self._lineage = lineage_mod.LineageTracker(
                ctx,
                ledger_dir=lineage_mod.resolve_ledger_dir(
                    lineage if isinstance(lineage, str) else None),
                state_fn=getattr(reader, 'lineage_state', None))
            self._lineage_owned = True
        self._namedtuple_cache = {}
        # Metrics-registry instruments (petastorm_tpu.metrics): the
        # machine-scrapable mirror of the `stats` dict. Cached here — one
        # registry lookup at construction, one small lock per batch.
        from petastorm_tpu import metrics as metrics_mod
        self._m_batches = metrics_mod.counter(
            'pst_loader_batches_total', 'Device batches delivered to the '
            'training loop (echoed re-deliveries included)')
        self._m_batch_wait = metrics_mod.histogram(
            'pst_batch_wait_seconds', 'Consumer-side blocked time per '
            'fetch (the input-stall signal; includes the end-of-stream '
            'fetch)')
        self._m_staged_bytes = metrics_mod.counter(
            'pst_staged_bytes_total', 'Host bytes handed to device staging')
        # input-stall accounting (BASELINE.json targets <5% input stall)
        self._batches_delivered = 0
        self._first_get_t = None
        # staging accounting (VERDICT r1 #4: measure copy/transfer cost).
        # Written by the staging thread, reset by the consumer — lock both.
        self._stats_lock = threading.Lock()
        self._staged_bytes = 0
        # Fields staged per transfer tier (stats['stage_tiers']): which of
        # _stage's branches carried the dispatch. Staging thread only.
        self._stage_tiers = Counter()
        # Latest staged batch's bytes (membudget prefetch-queue pool =
        # depth x this). Initialized BEFORE the staging engine starts:
        # a stage thread may record a size before __init__ finishes, and
        # a later zeroing would blank the accounting at spin-up.
        self._last_batch_nbytes = 0
        self._dlpack_staging = jax.default_backend() == 'cpu'

        # Zero-copy backends (CPU) hand out device arrays that ALIAS host
        # memory; recycling/accounting decisions below key off this once.
        from petastorm_tpu.staging import staging_aliases_host
        self._staging_aliasing = (self._dlpack_staging
                                  or staging_aliases_host(jax))

        # Per-device sharded staging: batch-dim shards are zero-copy
        # contiguous sub-slices of the host batch, and each field goes out
        # as one batched transfer, inline or from one of the per-device
        # streams. Shard layouts are planned once per (field, shape) in
        # _device_shard_plan; ineligible fields keep the one-shot path
        # per field.
        self._stager = None
        self._stager_devices = ()
        self._shard_plans = {}
        # Device-resident dataset tier (device_cache.DeviceDatasetCache
        # attaches itself here so loader stats surface the HBM tier).
        self._device_cache = None
        self._device_stream_min_bytes = (
            8 << 20 if device_stream_min_bytes is None
            else max(0, int(device_stream_min_bytes)))
        # Inline assembly tier: one C++ batched per-device transfer per
        # field (jax's own make_array_from_callback substrate) fed the
        # precomputed zero-copy shard views directly — no per-batch index
        # wrangling, no per-shard Python dispatch. An internal API of the
        # installed jax: if it moves, this import fails and says so.
        from jax._src.interpreters import pxla
        self._batched_put = pxla.batched_device_put
        if mesh is not None or sharding is not None:
            devices = self._collect_stager_devices()
            if devices:
                from petastorm_tpu.staging import DeviceStager, OverlapMeter
                self._stager_devices = devices
                # Stream threads start LAZILY on the first streamed wave
                # (DeviceStager.start via put_shards): a constructor
                # failure below must not leak parked pst-device-put
                # threads with no reachable stop path, and the inline
                # tier never needs them running.
                # The stager gets its OWN OverlapMeter: the loader tracks
                # 'host' around _stage on it, the stager tracks one
                # logical 'h2d' lane over its in-flight windows, and
                # their co-activity IS the streamed-path h2d_overlap_frac.
                self._stager = DeviceStager(
                    stream_keys=[str(getattr(d, 'id', i))
                                 for i, d in enumerate(devices)],
                    put_fn=self._batched_assemble,
                    inflight=device_inflight,
                    ready_fn=jax.block_until_ready,
                    stop_event=self._stop,
                    tracer=self._tracer,
                    meter=OverlapMeter())

        # Pipelined staging engine (prefetch > 0): an assemble stage that
        # collates batches into recycled host arenas and a dispatch stage
        # holding a bounded window of in-flight puts, so collate of batch
        # N+1 overlaps the transfer of batch N (see ``staging.py``).
        # ``prefetch == 0`` keeps the inline consumer-staging path: plain
        # allocation, no arenas, no extra threads.
        self._thread = None       # kept for back-compat introspection
        self._engine = None
        self._arena_pool = None
        self._metered_reader = None
        arena_buffers = None
        views_ok = True
        host_reader = reader
        if not self._consumer_staging:
            from petastorm_tpu.staging import (ArenaPool, MeteredReader,
                                               OverlapMeter, StagingEngine)
            # Zero-copy backends (CPU) hand out device arrays that ALIAS
            # host memory: staged chunk views stay the fastest path
            # (views_ok), and arena recycling must additionally wait for
            # the consumer to drop its arrays (holds_mode). Copying
            # backends (real TPU h2d) prefer every batch in a stable
            # recycled arena — transfers re-use warmed buffers and the
            # arena is free the moment the put completes.
            aliasing = self._staging_aliasing
            views_ok = aliasing
            inflight = max(1, int(inflight))
            if arena_depth is None:
                arena_depth = max(2, prefetch) + inflight + 2
            # Blocked time — reader pulls and arena backpressure — reports
            # as PAUSED assemble time so the overlap metric covers collate
            # work only (an input- or arena-bound run must not read as
            # perfect pipelining).
            meter = OverlapMeter()
            hb_assemble = (self._health.registry.register('assemble')
                           if self._health is not None else None)
            host_reader = MeteredReader(reader, meter, heartbeat=hb_assemble,
                                        tracer=self._tracer)
            self._metered_reader = host_reader
            self._arena_pool = ArenaPool(arena_depth, stop_event=self._stop,
                                         tracer=self._tracer, meter=meter,
                                         heartbeat=hb_assemble,
                                         pinned=pinned_arenas)
            arena_buffers = self._arena_pool.get_buffers
            if self._health is not None:
                self._health.registry.register_probe('arena-pool',
                                                     self._arena_pool.stats)

        self._host_iter = iter_numpy_batches(
            host_reader, local_batch, shape_policies=shape_policies,
            shuffling_queue_capacity=shuffling_queue_capacity,
            min_after_dequeue=min_after_dequeue, seed=seed,
            last_batch=last_batch, x64=x64, strict_fields=strict_fields,
            batch_buffers=arena_buffers, views_ok=views_ok,
            lineage=(self._lineage.collector
                     if self._lineage is not None else None),
            shuffler=self._shuffler,
            commit_rows=(self._commit_rows if self._shuffler is not None
                         else None))

        # Start the engine LAST: it touches the state above immediately.
        if not self._consumer_staging:
            def ready_fn(staged):
                jax.block_until_ready(list(staged.values()))

            def is_ready_fn(staged):
                return all(getattr(v, 'is_ready', _never_ready)()
                           for v in staged.values())

            self._engine = StagingEngine(
                host_iter=self._host_iter, stage_fn=self._stage,
                out_queue=self._queue, stop_event=self._stop,
                end_sentinel=_END, pool=self._arena_pool, inflight=inflight,
                ready_fn=ready_fn, is_ready_fn=is_ready_fn,
                holds_mode=aliasing, tracer=self._tracer,
                meter=meter,
                # The device-sharded stage reuses the arena's memoized
                # per-device sub-slice views (zero re-layout per batch).
                stage_with_arena=True,
                health=self._health.registry
                if self._health is not None else None,
                # Provenance accounting is FIFO-paired with delivered
                # batches: a batch the engine assembles but drops at stop
                # time must retract its pending record too.
                on_drop=(self._lineage.drop_newest
                         if self._lineage is not None else None)).start()
        # The watchdog starts only once every stage had the chance to
        # register, so its first classification sees the full beat table.
        self._supervisor.start_health()

        # Host memory governor (petastorm_tpu.membudget): the loader's
        # byte-holding pools register for unified accounting — the arena
        # pool (which also covers the staging in-flight window: staged
        # batches are arena-backed), the prefetch queue (staged batches x
        # the latest batch's bytes), and the shuffling buffer. Arming is
        # env-driven (PETASTORM_TPU_HOST_MEM_BUDGET) and refcounted;
        # breaches are delivered into the consumer queue exactly like a
        # watchdog hard stall — the trainer raises HostMemoryExceededError
        # with a flight dump instead of eating a kernel SIGKILL.
        from petastorm_tpu import membudget as membudget_mod
        governor = membudget_mod.get_governor()
        self._mem_handles = []
        if self._arena_pool is not None:
            pool = self._arena_pool
            self._arena_pinned_before_advisory = False

            def arena_advisory(active):
                # mlocked slabs are exactly the pages the kernel cannot
                # reclaim under pressure — the advisory rung unpins new
                # arena allocations (live slabs recycle out naturally)
                # and the release restores the configured mode.
                if active:
                    self._arena_pinned_before_advisory = pool.pinned
                    pool.set_pinned(False)
                elif self._arena_pinned_before_advisory:
                    pool.set_pinned(True)

            self._mem_handles.append(governor.register_pool(
                'arena-pool', lambda: pool.nbytes,
                advisory_fn=arena_advisory))
        def prefetch_queue_nbytes():
            # Arena-backed staging (the prefetch>0 engine path): every
            # queued batch's HOST bytes are already accounted by the
            # arena pool (zero-copy backends alias the arena; copying
            # backends queue device arrays that hold no host memory) —
            # reporting them here too would double-count the same bytes
            # and walk the ladder on phantom pressure. Only batches that
            # bypassed the arena pool are this pool's to count.
            if self._arena_pool is not None:
                return 0
            return ((self._queue.qsize() + len(self._ready))
                    * self._last_batch_nbytes)

        self._mem_handles.append(governor.register_pool(
            'prefetch-queue', prefetch_queue_nbytes))
        if self._stager is not None:
            stager = self._stager

            def device_window_nbytes():
                # Per-device in-flight windows are accountable bytes —
                # but only once: on aliasing backends the windowed shards
                # point into arena buffers the arena pool already counts
                # (donated, no host-side copy to double-account), and on
                # copying backends the window holds device memory, not
                # host bytes. Only windows over non-arena host batches
                # (zero-copy chunk views, consumer staging) are this
                # pool's to report.
                if not self._staging_aliasing \
                        or self._arena_pool is not None:
                    return 0
                return stager.window_nbytes

            self._mem_handles.append(governor.register_pool(
                'device-put-window', device_window_nbytes))
        if self._shuffler is not None:
            shuffler = self._shuffler
            degrade = None
            if getattr(reader, 'deterministic', None) is False:
                # Halving the buffer changes the draw sequence — only
                # readers that EXPLICITLY report non-deterministic register
                # the hook. Fail closed on readers without the property
                # (RemoteReader may be carrying a deterministic stream):
                # the deterministic contract outranks memory relief, and
                # the other rungs still apply.
                degrade = shuffler.shrink_capacity
            self._mem_handles.append(governor.register_pool(
                'shuffling-buffer', lambda: shuffler.nbytes,
                degrade_fn=degrade))
        self._mem_breach_sink = governor.add_breach_sink(self._deliver_stall)
        self._mem_armed = membudget_mod.maybe_arm_from_env()

        # Adaptive autotuning (petastorm_tpu.autotune): one controller for
        # the whole pipeline — the loader's knobs (prefetch depth, in-flight
        # transfer window, arena depth) merged with the reader tier's
        # (worker-pool size, ventilation watermark), which the reader hands
        # over via adopt_autotune (stopping any controller of its own).
        from petastorm_tpu import autotune as autotune_mod
        self._reader_telemetry = None

        def build_knobs(cfg):
            knobs = {}
            if not self._consumer_staging:
                knobs['prefetch'] = autotune_mod.Knob(
                    'prefetch', lambda: self._prefetch_target,
                    self.set_prefetch, lo=cfg.min_prefetch,
                    hi=cfg.max_prefetch)
                knobs['inflight'] = autotune_mod.Knob(
                    'inflight', lambda: self._engine.inflight_window,
                    self._engine.set_inflight, lo=cfg.min_inflight,
                    hi=cfg.max_inflight)
                knobs['arena_depth'] = autotune_mod.Knob(
                    'arena_depth', lambda: self._arena_pool.depth,
                    self._arena_pool.set_depth, lo=cfg.min_arena_depth,
                    hi=cfg.max_arena_depth)
                # DMA-friendly host slabs: a dispatch-bound pipeline grows
                # into pinned mode (faster transfers from page-aligned /
                # mlocked buffers); the memory-shrink ladder steps it back
                # off first — mlocked pages are unreclaimable.
                arena_pool = self._arena_pool
                knobs['arena_pinned'] = autotune_mod.Knob(
                    'arena_pinned', lambda: int(arena_pool.pinned),
                    lambda v: arena_pool.set_pinned(bool(v)), lo=0, hi=1)
            if self._stager is not None:
                # Per-device window: the dispatch-bound classification
                # steps this BEFORE the global inflight window (see
                # autotune._GROW_ACTIONS) — widening every device's
                # stream attacks the transfer backlog where it forms.
                stager = self._stager
                knobs['device_inflight'] = autotune_mod.Knob(
                    'device_inflight', lambda: stager.inflight_window,
                    stager.set_inflight, lo=cfg.min_device_inflight,
                    hi=cfg.max_device_inflight)
                # Growing the inline/batched threshold routes MORE fields
                # through the single C++ batched transfer per wave — the
                # cheapest dispatch path when the pipeline is
                # dispatch-bound.
                knobs['device_stream_min_mb'] = autotune_mod.Knob(
                    'device_stream_min_mb',
                    lambda: self._device_stream_min_bytes >> 20,
                    self.set_device_stream_min_mb,
                    lo=cfg.min_device_stream_mb,
                    hi=cfg.max_device_stream_mb)
            adopt = getattr(reader, 'adopt_autotune', None)
            if adopt is not None:
                reader_knobs, self._reader_telemetry = adopt(cfg)
                knobs.update(reader_knobs)
            return knobs

        watchdog_active = None
        if self._health is not None:
            watchdog_obj = self._health.watchdog
            watchdog_active = lambda: watchdog_obj.episode_active  # noqa: E731
        listeners = []
        store = getattr(reader, 'chunk_store', None)
        if store is not None:
            # Epoch-0 spill throttling (the reader's own controller is
            # stopped by adopt_autotune inside build_knobs): pause the
            # NVMe write-behind whenever the pipeline itself is the
            # classified bottleneck.
            listeners.append(autotune_mod.writer_throttle_listener(store))
        self._autotuner = self._supervisor.arm_autotune(
            autotune, build_knobs, self._autotune_telemetry,
            autotune_mod.classify_loader,
            watchdog_active_fn=watchdog_active,
            # Advisory rung of the memory ladder: the tuner stops
            # growing and steps every knob down instead.
            memory_state_fn=governor.pressure_level,
            tracer=self._tracer, listeners=listeners)

    # -- autotune hookups --------------------------------------------------

    def set_device_stream_min_mb(self, mb):
        """Retarget the inline-batched-put threshold at runtime (autotune
        hookup). Fields whose per-shard bytes fall below the threshold go
        out as one C++ batched transfer; at or above it they stream
        through the per-device windows as one batched wave item."""
        self._device_stream_min_bytes = max(0, int(mb)) << 20

    def set_prefetch(self, n):
        """Retarget the staged-batch bound at runtime (autotune hookup).
        Growing wakes a dispatch thread blocked on the bounded put;
        shrinking takes effect as the consumer drains below the new cap
        (no staged batch is dropped). The live queue capacity is the
        target minus the consumer's drain buffer (see ``__init__``)."""
        n = max(1, int(n))
        staging_queue = self._queue
        with staging_queue.mutex:
            self._prefetch_target = n
            staging_queue.maxsize = max(1, n - len(self._ready))
            staging_queue.not_full.notify_all()

    def _autotune_telemetry(self):
        """Cumulative per-stage wait counters + queue gauges — the inputs
        of :func:`petastorm_tpu.autotune.classify_loader`. Cheap enough
        for a sub-second tick: attribute reads plus two small locks."""
        out = {'batches': self._batches_delivered,
               'wait_s': self._totals['wait_s'],
               'queue_depth': self._queue.qsize() + len(self._ready),
               'queue_capacity': self._prefetch_target}
        if self._consumer_staging:
            # Inline staging: the consumer's blocked time IS the pipeline
            # running, so the stage split above supplies the per-stage
            # signals — without them every slow tick would classify as
            # input-bound and ratchet the worker pool to its clamp even
            # when the device dispatch is the bottleneck.
            out['reader_wait_s'] = self._totals['inline_reader_s']
            out['ready_wait_s'] = self._totals['stage_s']
        if self._metered_reader is not None:
            out['reader_wait_s'] = self._metered_reader.reader_wait_s
        if self._arena_pool is not None:
            out['arena_wait_s'] = self._arena_pool.wait_seconds
        if self._engine is not None:
            out['ready_wait_s'] = self._engine.ready_wait_seconds
        if self._stager is not None:
            # Per-device window fences are dispatch-bound signal exactly
            # like the engine's batch-level fence — fold them together so
            # the classifier sees transfer backpressure wherever it forms.
            out['ready_wait_s'] = (out.get('ready_wait_s', 0.0)
                                   + self._stager.ready_wait_seconds)
        if self._reader_telemetry is not None:
            reader_tel = self._reader_telemetry()
            # The reader tier reports its own delivery counter under
            # 'batches' (its rate signal when tuned standalone); here the
            # throughput guard must judge actions by DELIVERED loader
            # batches, not upstream chunk pulls — keep ours.
            reader_tel.pop('batches', None)
            out.update(reader_tel)
        return out

    # -- staging thread --------------------------------------------------

    def _field_sharding(self, name):
        if self._sharding is not None:
            if isinstance(self._sharding, dict):
                return self._sharding[name]
            return self._sharding
        from petastorm_tpu.parallel.mesh import batch_sharding
        return batch_sharding(self._mesh, self._batch_axis)

    # -- per-device sharded staging ---------------------------------------

    def _collect_stager_devices(self):
        """Addressable devices of the loader's mesh/sharding(s), sorted by
        id — one :class:`~petastorm_tpu.staging.DeviceStager` stream each."""
        jax = self._jax
        devices = set()
        if self._mesh is not None:
            process = jax.process_index()
            devices.update(d for d in self._mesh.devices.flat
                           if d.process_index == process)
        shardings = []
        if isinstance(self._sharding, dict):
            shardings.extend(self._sharding.values())
        elif self._sharding is not None:
            shardings.append(self._sharding)
        for sharding in shardings:
            devices.update(sharding.addressable_devices)
        return tuple(sorted(devices, key=lambda d: getattr(d, 'id', 0)))

    def _device_shard_plan(self, name, sharding, shape):
        """``(plan, stream_indices)`` for a batch-dim-sharded
        field, or ``None`` (ineligible: keep the one-shot path). Memoized
        per (field, host shape) — shard boundaries are computed from the
        ``NamedSharding`` exactly once per schema, and the arena pool
        learns the layout so arenas can hand out memoized per-device
        sub-slice views (zero re-layout at dispatch time)."""
        key = (name, tuple(shape))
        cached = self._shard_plans.get(key)
        if cached is not None:
            return cached if cached is not False else None
        from petastorm_tpu.parallel.mesh import device_shard_plan
        plan = device_shard_plan(sharding, shape)
        if plan is None or not set(plan.devices) <= set(self._stager_devices):
            self._shard_plans[key] = False
            return None
        index_of = {d: i for i, d in enumerate(self._stager_devices)}
        entry = (plan, tuple(index_of[d] for d in plan.devices))
        self._shard_plans[key] = entry
        if self._arena_pool is not None:
            self._arena_pool.learn_shard_layout({name: plan.bounds})
        return entry

    def _shard_arrays(self, name, array, arena, plan):
        """``(views, from_arena)`` for one field: the arena's memoized
        contiguous sub-slices when the batch collated into an arena
        buffer (``from_arena=True`` — recycling is transfer-and-GC-gated,
        so handing them over copy-free is safe), else fresh leading-dim
        views of whatever array arrived (e.g. a staging-step-decoded
        block, whose lifetime is NOT arena-gated). Both are zero-copy."""
        if arena is not None:
            buf = arena.buffers.get(name)
            if buf is not None and buf.shape == array.shape \
                    and np.may_share_memory(array, buf):
                try:
                    # The pool-learned layout (learn_shard_layout, written
                    # when the plan was computed) — per-arena memoized.
                    return arena.shard_views(name), True
                except KeyError:
                    return arena.shard_views(name, plan.bounds), True
        return tuple(array[start:stop]
                     for start, stop in plan.bounds), False

    def _stage_pending_shards(self, pending, out, arena):
        """Dispatch every planned field's per-device shards as the field's
        global ``jax.Array``. Two tiers by the shard's bytes, same result:

        * **inline-batched** (small shards): ONE batched per-device
          transfer per field on the dispatch thread — the precomputed
          zero-copy shard views go straight into
          ``pxla.batched_device_put``, so dispatch pays no per-batch
          layout work and no per-shard Python round-trips (the one-shot
          ``make_array_from_process_local_data`` re-wrangles indices
          every call);
        * **streamed-batched** (DMA-scale shards): the same single C++
          batched transfer, but issued FROM a stream thread as one
          :class:`_BatchedShardWave` item so it lands against the
          per-device in-flight windows (fence pipelining) instead of
          blocking the dispatch thread for the whole transfer.
        """
        waves = []
        for name, sharding, plan, streams, array in pending:
            views, from_arena = self._shard_arrays(name, array, arena, plan)
            wave = _BatchedShardWave(sharding, plan, streams, views,
                                     from_arena)
            shard_nbytes = views[0].nbytes if views else 0
            if shard_nbytes < self._device_stream_min_bytes:
                self._stage_tiers['inline-batched'] += 1
                out[name] = self._batched_assemble(wave)
            else:
                self._stage_tiers['streamed-batched'] += 1
                waves.append((name, wave))
        if not waves:
            return
        # Round-robin the submitting stream over the wave's own devices so
        # concurrent fields issue from different threads (the batched put
        # covers every device either way).
        staged = self._stager.put_shards(
            [(wave.streams[i % len(wave.streams)], wave)
             for i, (_name, wave) in enumerate(waves)])
        for (name, _wave), array in zip(waves, staged):
            out[name] = array

    def _batched_assemble(self, wave):
        """The global per-device-sharded array in one C++ batched transfer
        over the precomputed shard views — called on the dispatch thread
        (inline tier) or, as the DeviceStager's ``put_fn``, from a stream
        thread (streamed-batched tier: the stream loop keeps the window's
        books and this records the wave's true per-device breakdown).
        ``from_arena`` feeds the donation accounting (arena sub-slices
        handed over with no loader-side copy; the batched API itself never
        donates)."""
        t0 = time.perf_counter()
        staged = self._batched_put(
            self._jax.core.ShapedArray(wave.plan.global_shape,
                                       wave.views[0].dtype),
            wave.sharding, list(wave.views), list(wave.plan.devices))
        self._stager.record_inline_wave(
            wave.streams, [v.nbytes for v in wave.views],
            time.perf_counter() - t0, wave.from_arena)
        return staged

    def _decode_raw_columns(self, host_batch):
        """Staging-step JPEG->tensor for raw (encoded-bytes) columns: the
        registered on-device decode op when the backend has one (falling
        back on any failure), else ONE host batched-native call per
        column — spending the WHOLE process decode-thread budget when the
        raw selection covers every image field (the workers then decode
        nothing), else a fair share alongside the still-decoding
        workers."""
        from petastorm_tpu import decode_budget
        from petastorm_tpu.codecs import decode_image_batch_into
        budget = decode_budget.get_budget()
        decode_threads = (budget.total if self._staging_owns_budget
                          else budget.share())
        out = dict(host_batch)
        for name, field in self._raw_specs.items():
            column = out.get(name)
            if column is None or getattr(column, 'dtype', None) != np.dtype(object):
                continue   # already dense (e.g. a custom pipeline decoded it)
            hook = _DEVICE_DECODE_HOOK
            if hook is not None:
                try:
                    out[name] = hook(column, tuple(field.shape),
                                     np.dtype(field.numpy_dtype))
                    continue
                except Exception:  # noqa: BLE001 - fall back to host decode
                    logger.warning(
                        'on-device decode hook failed for field %r; host-'
                        'decoding this batch', name, exc_info=True)
            block = np.empty((len(column),) + tuple(field.shape),
                             dtype=field.numpy_dtype)
            decode_image_batch_into(
                field, block, lambda i, _c=column: _c[i],
                decode_threads=decode_threads)
            out[name] = block
        return out

    def _stage(self, host_batch, arena, span):
        """Issue the batch's device puts, inside the caller's
        ``dispatch.stage`` span (the staging engine's, or inline staging's
        own): ``span.id`` is the batch's number, and its cause is set to
        the tiers that carried the fields."""
        from petastorm_tpu.faults import maybe_inject
        from petastorm_tpu.staging import StagedBatch
        maybe_inject('device-put-delay')
        jax = self._jax
        if self._raw_specs:
            with self._tracer.span(
                    'dispatch.decode', 'dispatch', id=span.id,
                    total=(self._totals, 'stage_decode_s')):
                host_batch = self._decode_raw_columns(host_batch)
        out = {}
        pending = []   # per-device sharded fields, dispatched as one wave
        nbytes = 0
        # The stager's OverlapMeter: staging batch N+1 counts as 'host'
        # work; its co-activity with the stager's in-flight 'h2d' windows
        # (transfers of batch N still unfenced) is the streamed-path
        # h2d_overlap_frac.
        host_span = (self._stager.meter.track('host')
                     if self._stager is not None
                     and self._stager.meter is not None
                     else contextlib.nullcontext())
        tiers_before = dict(self._stage_tiers)
        with host_span:
            for name, array in host_batch.items():
                nbytes += array.nbytes
                if hasattr(array, 'is_ready'):
                    # A device-decode hook already produced a committed
                    # jax array: any re-staging path (process-local-data
                    # assembly, dlpack import) would at best round-trip it
                    # through the host.
                    self._stage_tiers['device-decoded'] += 1
                    out[name] = array
                    continue
                if self._mesh is not None or self._sharding is not None:
                    sharding = self._field_sharding(name)
                    planned = (self._device_shard_plan(name, sharding,
                                                       array.shape)
                               if self._stager is not None else None)
                    if planned is not None:
                        # Per-device sharded path: zero-copy shard views,
                        # dispatched as one wave below.
                        plan, streams = planned
                        pending.append((name, sharding, plan, streams,
                                        array))
                    else:
                        self._stage_tiers['one-shot'] += 1
                        out[name] = jax.make_array_from_process_local_data(
                            sharding, array)
                elif self._dlpack_staging:
                    self._stage_tiers['dlpack'] += 1
                    # CPU backend: import the host buffer zero-copy via
                    # DLPack. Aliasing is safe because recycling is
                    # deferred until the staged arrays are dropped: arena-
                    # backed batches get GC holds (StagingEngine holds_mode
                    # — an arena is never refilled while any staged array
                    # of it is alive), and non-arena batches (chunk views,
                    # consumer staging) are never written again at all. TPU
                    # backends need the real h2d transfer and take the
                    # device_put branch.
                    try:
                        out[name] = jax.dlpack.from_dlpack(array)
                    except BufferError:
                        # This buffer is unexportable (e.g. read-only):
                        # fall back for THIS array only — one such batch
                        # must not disable zero-copy for the whole run.
                        out[name] = jax.device_put(array)
                    except (TypeError, RuntimeError):
                        self._dlpack_staging = False
                        out[name] = jax.device_put(array)
                else:
                    self._stage_tiers['plain'] += 1
                    out[name] = jax.device_put(array)
            if pending:
                self._stage_pending_shards(pending, out, arena)
            if self._augment_fn is not None:
                # Inside the XLA step: the jitted augment consumes the
                # just-staged device arrays asynchronously — its compute
                # overlaps the consumer's step exactly like the transfer.
                out = dict(self._augment_fn(out))
        span.cause = sorted(tier for tier, n in self._stage_tiers.items()
                            if n != tiers_before.get(tier, 0))
        with self._stats_lock:
            self._staged_bytes += nbytes
        # Prefetch-queue byte accounting (membudget): depth x the latest
        # batch's bytes. Int rebind is atomic; staging thread only.
        self._last_batch_nbytes = nbytes
        self._m_staged_bytes.inc(nbytes)
        return StagedBatch(out, span.id)

    def _next_host_batch(self):
        """Inline staging's collate (prefetch=0): the consumer's thread."""
        with self._tracer.span('collate.batch', 'collate',
                               id=self._inline_seq,
                               total=(self._totals, 'inline_reader_s')):
            return next(self._host_iter)

    # The staging threads themselves live in ``staging.StagingEngine``
    # (assemble + dispatch); their stop-aware queue discipline — never
    # block indefinitely on a consumer that may already be gone — is
    # inherited from the single-loop stager this engine replaced (a leaked
    # stager holds reader/file objects whose teardown races its final
    # reads; observed as a pyarrow segfault under load).

    # -- consumer --------------------------------------------------------

    def _deliver_stall(self, error):
        """Hard-stall sink (watchdog thread): make the consumer raise the
        diagnosed :class:`PipelineStallError` instead of blocking forever.
        The error rides the staging queue (the consumer is typically parked
        in an untimed ``get()``); a full queue — the consumer-not-draining
        shape — has one stale batch evicted to make room."""
        self._stall_error = error
        for _ in range(2):
            try:
                self._queue.put_nowait(error)
                return
            except queue.Full:
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    pass
        logger.error('could not deliver PipelineStallError into the staging '
                     'queue; it will surface on the next __next__ call')

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        if self._stall_error is not None:
            # Consumer-staging mode (or a failed queue delivery): the
            # watchdog's hard diagnosis still surfaces here.
            self._exhausted = True
            error, self._stall_error = self._stall_error, None
            raise error
        if self._hb_consumer is not None:
            self._hb_consumer.beat('queue-wait')
        if self._first_get_t is None:
            self._first_get_t = time.perf_counter()
        # The consumer's blocked time for this fetch: the input-stall
        # signal (wait_s, pst_batch_wait_seconds), end-of-stream included.
        with self._tracer.span('consumer.wait', 'consumer',
                               hist=self._m_batch_wait,
                               total=(self._totals, 'wait_s')):
            item, fresh = self._fetch()
        if item is _END:
            self._exhausted = True
            if self._hb_consumer is not None:
                self._hb_consumer.beat('idle')   # exhausted, not stalled
            raise StopIteration
        if isinstance(item, Exception):
            self._exhausted = True
            raise item
        names = tuple(sorted(item))
        nt = cached_namedtuple(self._namedtuple_cache, 'JaxBatch', names)
        self._batches_delivered += 1
        self._m_batches.inc()
        # Which batch the training loop took and when its puts had been
        # issued: taken less staged is its time in the prefetch queue, the
        # loader's lead over the step.
        self._tracer.instant('consumer.deliver', 'consumer',
                             {'staged_ns': getattr(item, 'staged_ns', None)},
                             id=getattr(item, 'seq', None))
        if self._lineage is not None and fresh:
            # Mint this batch's provenance record (FIFO against the host-
            # batch iterator's collector pushes — the staging engine
            # preserves delivery order). Echoed re-deliveries reuse the
            # source batch's record.
            self._last_provenance = self._lineage.deliver()
        if self._hb_consumer is not None:
            # 'delivered' + stale = the training loop took this batch and
            # never came back (consumer-not-draining, never escalated).
            self._hb_consumer.beat('delivered')
        # A delivered batch IS recovery: a hard stall diagnosed while this
        # call was in flight (inline staging sleeping through its own
        # escalation) must not kill the pipeline that has since come back.
        # (Staged-path hard stalls ride the queue and still terminate.)
        self._stall_error = None
        if self._row_granular_ckpt and fresh:
            # A padded final batch over-reports by the pad amount; the
            # attribution FIFO simply drains empty, which is correct (the
            # padded copies duplicate rows already attributed). Echoed
            # re-deliveries are not fresh source rows and are never counted.
            if self._defer_rows_consumed:
                # superbatches(): attribution happens when the full group is
                # yielded, and only for the fresh rows actually in it.
                self._pending_fresh_rows += self._local_batch
            else:
                self._reader.rows_consumed(self._local_batch)
        elif self._shuffler is not None and fresh:
            # This batch's draws reached the trainer: release them from
            # the buffer's pending FIFO so only genuinely undelivered
            # draws fold into a checkpoint snapshot. (A padded/short
            # final batch over-reports; mark_delivered drains empty.)
            self._shuffler.mark_delivered(self._local_batch)
        return nt(**{k: item[k] for k in names})

    def _fetch(self):
        """``(item, fresh)``: the next staged batch (or ``_END``, or an
        exception to raise), and whether its source rows are delivered for
        the first time (an echo is not)."""
        fresh = True
        if self._echo_left > 0:
            self._echo_left -= 1
            item = self._echo_item
            fresh = False   # source rows already counted on first delivery
        else:
            if self._consumer_staging:
                # Inline staging (prefetch=0): the consumer thread IS the
                # pipeline, so its heartbeat states must distinguish a
                # starved reader from a hung device_put here too — without
                # the brackets a wedged inline transfer would read as
                # 'queue-wait' (an innocent state) and never classify.
                try:
                    if self._hb_consumer is not None:
                        self._hb_consumer.beat('reader-wait')
                    host_batch = self._next_host_batch()
                    if self._hb_consumer is not None:
                        self._hb_consumer.beat('device_put')
                    with self._tracer.span(
                            'dispatch.stage', 'dispatch', id=self._inline_seq,
                            total=(self._totals, 'stage_s')) as span:
                        item = self._stage(host_batch, None, span)
                    self._inline_seq += 1
                except StopIteration:
                    item = _END
                except Exception as e:  # noqa: BLE001 - match staged path
                    item = e
            elif self._ready:
                # Batched pop: a previous fetch drained the staging queue
                # into this consumer-local buffer. Consuming one gives a
                # capacity slot back to the dispatch thread (the drain
                # below converted queue slots into buffer debt, not into
                # refillable capacity).
                item = self._ready.popleft()
                staging_queue = self._queue
                with staging_queue.mutex:
                    staging_queue.maxsize = max(
                        1, self._prefetch_target - len(self._ready))
                    staging_queue.not_full.notify()
            else:
                item = self._queue.get()
                # Batched pop: move every staged batch into the local
                # buffer under ONE mutex acquisition (vs one Queue.get
                # lock round trip per batch). The queue's live
                # maxsize shrinks by the same count (no notify): drained
                # slots must NOT become capacity the dispatch thread
                # refills, or staged-but-undelivered device batches would
                # reach ~2x the documented `prefetch` bound.
                staging_queue = self._queue
                with staging_queue.mutex:
                    while staging_queue.queue:
                        self._ready.append(staging_queue.queue.popleft())
                    staging_queue.maxsize = max(
                        1, self._prefetch_target - len(self._ready))
            if self._echo > 1 and isinstance(item, dict):
                self._echo_item = item
                self._echo_left = self._echo - 1
        return item, fresh

    def superbatches(self, k):
        """Yield ``k``-batch on-device concatenations (for scan training).

        Pairs with ``models.train.make_scan_train_step(microbatches=k)``:
        transfers stay at the per-batch size (large single h2d events can be
        pathological on some interconnects) while the training loop pays one
        Python dispatch per ``k`` optimizer steps. The final incomplete
        group (fewer than ``k`` batches at end of data) is dropped — sizes
        stay static for XLA. Checkpoint row accounting happens per *yielded
        group*, so a dropped partial group's rows are NOT counted consumed
        and re-deliver on resume (exactly-once holds here too).
        """
        if k <= 1:
            yield from self
            return
        import jax.numpy as jnp
        concat = self._jax.jit(lambda *xs: jnp.concatenate(xs))
        it = iter(self)

        def fetch():
            # Deferral is scoped to this call alone, so interleaved direct
            # loader iteration (or an abandoned generator) keeps normal
            # immediate accounting.
            self._defer_rows_consumed = True
            try:
                return next(it)
            finally:
                self._defer_rows_consumed = False

        while True:
            parts = []
            try:
                for _ in range(k):
                    parts.append(fetch())
            except StopIteration:
                # Partial tail group: dropped, and its fresh rows stay
                # unattributed — they re-deliver on resume.
                return
            if self._row_granular_ckpt and self._pending_fresh_rows:
                self._reader.rows_consumed(self._pending_fresh_rows)
                self._pending_fresh_rows = 0
            yield parts[0]._replace(
                **{f: concat(*[getattr(p, f) for p in parts])
                   for f in parts[0]._fields})

    def reset_stats(self):
        """Zero the stall counters — call after warmup so ``stats`` reflects
        the steady-state window, not reader-pool spin-up."""
        self._batches_delivered = 0
        trace_mod.reset_totals(self._totals)
        self._first_get_t = None
        with self._stats_lock:
            self._staged_bytes = 0
        if self._engine is not None:
            self._engine.reset_stats()
        if self._stager is not None:
            self._stager.reset_stats()
        if self._arena_pool is not None:
            self._arena_pool.reset_stats()
        if self._metered_reader is not None:
            # Unlocked against the assembler's += (a concurrent pull could
            # resurrect one pre-reset sample) — stats noise, not state.
            self._metered_reader.reader_wait_s = 0.0

    @property
    def stats(self):
        """Input-pipeline health: delivered batches, seconds spent blocked
        waiting for the staging thread, and the stall fraction (blocked time /
        wall time since the first fetch). A training loop with
        ``input_stall_frac`` above ~0.05 is input-bound (BASELINE.json's
        <5% target) — raise ``workers_count``/``prefetch`` or speed up decode.

        ``reader_diagnostics`` carries the reader's robustness state through
        to the training loop: ``worker_respawns`` (dead pool workers that
        were respawned) and ``quarantined_rowgroups`` (poison row-groups
        skipped under ``error_budget`` — see ``docs/failure_model.rst``).
        """
        elapsed = (time.perf_counter() - self._first_get_t
                   if self._first_get_t is not None else 0.0)
        with self._stats_lock:
            staged_bytes = self._staged_bytes
        wait_s = self._totals['wait_s']
        # dispatch.stage is the engine's span, or inline staging's own; the
        # raw columns' decode inside it is reported apart.
        stage_s = (self._engine.stats()['dispatch_s']
                   if self._engine is not None else self._totals['stage_s'])
        out = {'batches': self._batches_delivered,
               'wait_s': round(wait_s, 4),
               'input_stall_frac': round(wait_s / elapsed, 4) if elapsed else 0.0,
               'stage_dispatch_s': round(
                   stage_s - self._totals['stage_decode_s'], 4),
               'staged_bytes': staged_bytes,
               # Fields staged per transfer tier since construction: which
               # branch of _stage carried the dispatch.
               'stage_tiers': dict(self._stage_tiers),
               'reader_diagnostics': self._reader.diagnostics}
        if self._raw_specs:
            # Staging-step decode seconds of the on-device path (host
            # fallback; 0 when a device decode op carried the batches).
            out['stage_decode_s'] = round(self._totals['stage_decode_s'], 4)
        if self._engine is not None:
            # Pipeline shape of the staging engine: per-stage busy seconds,
            # how much of the smaller stage ran concurrently with the other
            # (overlap_frac — the software-pipelining win), and time spent
            # fenced on the oldest in-flight transfer (ready_wait_s).
            out.update(self._engine.stats())
        if self._stager is not None:
            # Per-device dispatch health: stream count (n_devices — the
            # real data-parallel fan-out, not a dryrun), per-device put
            # seconds/bytes (what a per-device h2d GB/s is read from),
            # shards donated (zero-copy handoffs), and per-stream window
            # fences.
            stager_stats = self._stager.stats()
            stager_stats['device_put_leaked_threads'] = \
                stager_stats.pop('leaked_threads')
            out.update(stager_stats)
        if self._metered_reader is not None:
            # Seconds the assembler spent blocked pulling from the reader —
            # the reader-starved signal (pairs with arena_wait_s /
            # ready_wait_s to name the bottleneck stage).
            out['reader_wait_s'] = round(self._metered_reader.reader_wait_s, 4)
        if self._arena_pool is not None:
            # Arena recycling health: after warmup ``arena_alloc`` should
            # stay flat (near-zero new allocations) with ``arena_reuse``
            # climbing; ``arena_wait_s`` is assembler backpressure.
            out.update(self._arena_pool.stats())
        store = getattr(self._reader, 'chunk_store', None)
        if store is not None:
            # NVMe decoded-chunk tier health: hits/misses/fills say whether
            # epoch-N decode is actually dead; write-behind counters
            # (writes, skipped, throttled) cover the epoch-0 spill.
            out['chunk_store'] = store.stats()
        worker_timings = getattr(self._reader, 'stage_timings', None)
        if worker_timings:
            out['worker_stage_timings'] = {
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in worker_timings.items()}
        if self._health is not None:
            # Stall supervision: detections/recoveries/hard escalations and
            # the latest diagnosis (classification, stage, beat table,
            # probes — the stack dump stays on the error object).
            out['watchdog'] = self._health.stats()
        if self._autotuner is not None:
            # Feedback control: current knob values, the full decision log
            # (grow/shrink/revert/pause with bottleneck classifications),
            # and the knob trajectory over time.
            out['autotune'] = self._autotuner.stats()
        if self._lineage is not None:
            # Provenance ledger health: records minted vs dropped, the
            # write-behind lag, and where the ledger landed on disk.
            out['lineage'] = self._lineage.stats()
        if self._device_cache is not None:
            # HBM-resident dataset tier (device_cache.DeviceDatasetCache
            # attached itself): cached bytes/superbatches, hit/eviction
            # counts, and whether the governor paused or stopped the fill.
            out['device_cache'] = self._device_cache.stats()
        from petastorm_tpu import membudget as membudget_mod
        governor = membudget_mod.get_governor()
        if governor.armed:
            # Memory governor: budget, ladder position + peaks, per-pool
            # bytes, degrade-action counts.
            out['mem'] = governor.stats()
        return out

    @property
    def last_batch_provenance(self):
        """The provenance record of the most recently delivered batch
        (``None`` when ``lineage`` is unarmed): batch id, source spans,
        serving tiers, shuffle state, content digest. See
        ``petastorm_tpu.lineage``."""
        return self._last_provenance

    @property
    def lineage_tracker(self):
        """The loader's :class:`~petastorm_tpu.lineage.LineageTracker`
        (``None`` when unarmed) — ring access for a replay self-check."""
        return self._lineage

    def state_dict(self):
        """Mid-epoch resume state (see ``Reader.state_dict``).

        Capture at a batch boundary and rebuild via
        ``make_reader(..., resume_state=state)`` + a new JaxLoader. Resume
        never replays a delivered batch. Row accounting depends on the
        pipeline shape:

        * **Batched reader, no shuffling buffer** (the TPU default): the
          loader enables row-granular accounting — rows still sitting in the
          prefetch queue at checkpoint time are NOT counted consumed and
          re-deliver on resume. Exactly-once AND no loss, any epoch count.
        * **Shuffling buffer engaged**: rows buffered in it count as
          consumed, but the buffer itself rides the state
          (``state['shuffling_buffer']``: rows + RNG state — binary-safe
          through ``JobCheckpointer``, which pickles non-JSON loader
          states): rebuild the loader with ``resume_state=`` the same dict
          and the buffered rows re-deliver with the draw sequence intact.
          Rows inside a partially-assembled batch (fewer than
          ``batch_size``) still follow chunk-level semantics.
        * **Per-row readers without a buffer**: rows buffered downstream
          count as consumed; with ``num_epochs=None`` they come around on
          a later epoch.
        """
        if self._shuffler is not None \
                and hasattr(self._shuffler, 'state_dict'):
            # Atomic against _commit_rows: without the lock, rows moving
            # reader->buffer between the two snapshots would appear in
            # both (re-delivered twice on resume) or neither (lost).
            with self._ckpt_lock:
                state = dict(self._reader.state_dict())
                state['shuffling_buffer'] = self._shuffler.state_dict()
            return state
        return self._reader.state_dict()

    def _commit_rows(self, rows):
        """Move one chunk's rows into the shuffling buffer and advance the
        reader's checkpoint cursor as one atomic step (the assemble
        thread's side of the ``state_dict`` lock)."""
        with self._ckpt_lock:
            self._shuffler.add_many(rows)
            if self._buffer_entry_ckpt:
                self._reader.rows_consumed(len(rows))

    def stop(self):
        from petastorm_tpu import membudget as membudget_mod
        governor = membudget_mod.get_governor()
        for handle in self._mem_handles:
            handle.close()
        governor.remove_breach_sink(self._mem_breach_sink)
        if self._mem_armed:
            self._mem_armed = False
            governor.release()
        # Tuner first (a tuner firing mid-teardown would retune stages
        # that are being joined), then the watchdog (which would misread
        # the deliberately silent stages as a stall) — the order the
        # shared supervisor owns.
        # _health/_autotuner stay referenced: stats() remains readable
        # post-stop (post-mortems read stats['watchdog'] after teardown).
        self._supervisor.stop()
        self._stop.set()
        self._exhausted = True
        # Drain so the staging threads' bounded puts can exit.
        self._ready.clear()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        if self._engine is not None:
            self._engine.stop()
        if self._stager is not None:
            # After the engine: the dispatch thread must stop submitting
            # waves before the per-device streams join.
            self._stager.stop()
        if self._thread is not None:
            self._thread.join(timeout=10)
        if self._lineage is not None:
            if self._lineage_owned:
                # Drain + close the ledger write-behind (don't leave a
                # daemon writer spilling into a directory the caller may
                # be deleting).
                self._lineage.close()
            else:
                # Adopted tracker: the caller owns its lifecycle (it may
                # ledger another loader next) — just drain what this
                # loader produced.
                self._lineage.flush()
        self._reader.stop()
        self._reader.join()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.stop()
        return False


def make_jax_loader(reader, batch_size, **kwargs):
    """Factory mirroring the reference adapter entry points
    (``tf_utils.tf_tensors`` / ``pytorch.DataLoader``)."""
    return JaxLoader(reader, batch_size, **kwargs)
