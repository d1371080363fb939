"""Batch provenance ledger + deterministic single-batch replay.

PR 6 gave the pipeline timelines and gauges, but when a training job hits
a NaN at step 41,237 neither can answer the only question that matters:
*which exact rows, decoded by which worker, served from which cache tier,
produced that batch?* The reproducible-pipelines literature (PAPERS.md,
arXiv 2604.21275) argues the input pipeline must make every batch
reconstructible to debug and resume at scale; tf.data (2101.12127) shows
per-element provenance is what turns a data pipeline from a black box
into an auditable system. This module is that layer for petastorm_tpu:

Provenance records
    Every batch that leaves :class:`~petastorm_tpu.jax_loader.JaxLoader`
    gets a compact JSON-safe record: a monotonic ``batch_id``, the
    ordered list of **segments** — ``(parquet file, row-group,
    drop-partition, row-index range)`` spans, each tagged with the
    producing worker (pid/slot) and the serving tier (``decode`` /
    ``chunk-store`` / ``memory`` / ``disk`` / ``remote``) — plus the
    reader's dataset fingerprint, schema hash, shuffle seed and epoch
    order digest, transform-spec version, and an optional per-field
    CRC32 content digest of the staged host batch. Segment metadata is
    attached by the workers at publish time (tensor / arrow / py_dict
    handoff), flows through the results queue (and across the wire for
    :class:`~petastorm_tpu.data_service.RemoteReader`), and is folded
    into batch records by a FIFO :class:`LineageCollector` inside the
    loader's batch assembly.

Ledger
    Records spill to a bounded, crash-tolerant JSONL ledger
    (:class:`LineageLedger`): one header line carrying the reader
    context, then one line per batch, written line-buffered by a
    write-behind thread whose bounded queue DROPS on overflow — batch
    delivery never blocks on disk (``pst_lineage_dropped_total`` counts
    the loss; the ``pst_lineage_ledger_lag`` gauge is the queue depth).
    A SIGKILLed trainer leaves at most one torn trailing line, which
    :func:`read_ledger_file` skips — the same sidecar discipline as the
    PR-6 trace spill. Arm via ``PETASTORM_TPU_LINEAGE_DIR`` or the
    loader's ``lineage=`` knob.

Flight ring
    The last N records live in an in-memory ring; live trackers register
    in a process-wide registry so the stall flight recorder
    (``flight_recorder.py``) can dump ``lineage.json`` next to
    ``trace.json`` on watchdog escalation — the post-mortem then names
    the exact rows in flight when the pipeline died.

Replay
    :func:`replay_record` re-opens the dataset and deterministically
    re-materializes one recorded batch — re-reading exactly the recorded
    row-group spans, re-applying drop-partition slices and the
    session-stable in-chunk permutation, sanitizing dtypes the way the
    loader did — and (in assert mode) verifies the result against the
    record's content digest bit for bit. The
    ``python -m petastorm_tpu.tools.replay`` CLI wraps it.

Determinism contract: replay is exact for pipelines whose per-batch row
composition is itself deterministic given the record — any pool type,
any ``shuffle_row_groups``/``seed``, mid-epoch, process pools included
(the record pins what the shuffle chose). A row-level shuffling buffer
(``shuffling_queue_capacity``), worker predicates, NGrams, or shape
policies make records ``exact: false`` and replay refuses them.
"""

import json
import logging
import os
import queue
import tempfile
import threading
import time
import uuid
import weakref
import zlib
from collections import deque

import numpy as np

logger = logging.getLogger(__name__)

#: Directory that arms ledger spill for every LineageTracker built while
#: it is set (mirrors PETASTORM_TPU_TRACE_DIR / _FLIGHT_RECORDER).
ENV_VAR = 'PETASTORM_TPU_LINEAGE_DIR'

#: Temp-dir prefix for ledgers created without an explicit directory
#: (``lineage=True`` with no env var); the conftest ``lineage`` guard
#: sweeps leaked matches.
TEMP_DIR_PREFIX = 'pst-lineage-'

_HEADER_KEY = '__pst_lineage_ledger__'
LEDGER_GLOB = 'ledger-*.jsonl'

RECORD_VERSION = 1

#: Serving-tier vocabulary (docs + tests assert against these).
TIER_DECODE = 'decode'
TIER_CHUNK_STORE = 'chunk-store'
TIER_MEMORY = 'memory'
TIER_DISK = 'disk'
TIER_REMOTE = 'remote'


def lineage_enabled(explicit=None):
    """Resolve the ``lineage=`` knob against the environment default:
    ``explicit`` wins when not None (a path string or True arms, False
    disarms); otherwise ``PETASTORM_TPU_LINEAGE_DIR`` decides."""
    if explicit is not None:
        return bool(explicit)
    return bool(os.environ.get(ENV_VAR, '').strip())


def resolve_ledger_dir(explicit=None):
    """The ledger directory for an armed tracker: an explicit path wins,
    then the env var, then a fresh ``pst-lineage-*`` temp dir."""
    if isinstance(explicit, str) and explicit:
        return explicit
    env = os.environ.get(ENV_VAR, '').strip()
    if env:
        return env
    return tempfile.mkdtemp(prefix=TEMP_DIR_PREFIX)


def chunk_lineage(piece, piece_index, shuffle_row_drop_partition, n_rows,
                  tier, permuted=False, filtered=False, worker_id=None):
    """The segment metadata a worker attaches to one published chunk.

    Coordinates are *published-chunk-local*: ``row_start`` is the offset
    of the first delivered row within the chunk as published (consumer-
    side resume skips advance it), ``chunk_rows`` is the published
    length — what :func:`replay_record` needs to recompute the in-chunk
    permutation and the drop-partition slice.
    """
    drop = None
    if shuffle_row_drop_partition is not None \
            and shuffle_row_drop_partition[1] > 1:
        drop = [int(shuffle_row_drop_partition[0]),
                int(shuffle_row_drop_partition[1])]
    return {'path': str(piece.path),
            'row_group': int(piece.row_group),
            'piece_index': int(piece_index),
            'drop': drop,
            'chunk_rows': int(n_rows),
            'row_start': 0,
            'worker_pid': os.getpid(),
            'worker_id': worker_id,
            'tier': tier,
            'permuted': bool(permuted),
            'filtered': bool(filtered)}


def _digest_array(arr):
    """CRC32 of an array's bytes (C-order) — fast (~GB/s) and enough to
    prove bit-identity between a live batch and its replay. Object
    columns of bytes (raw image fields on the on-device decode path)
    digest their CONTENTS in order — hashing the object pointers would
    make every run's digest unique."""
    arr = np.asarray(arr)
    if arr.dtype.kind == 'O':
        crc = 0
        for cell in arr.ravel():
            if isinstance(cell, (bytes, bytearray, memoryview)):
                crc = zlib.crc32(cell, crc)
            else:
                crc = zlib.crc32(np.ascontiguousarray(cell), crc)
        return crc & 0xFFFFFFFF
    arr = np.ascontiguousarray(arr)
    return zlib.crc32(arr.view(np.uint8) if arr.dtype.kind in ('M', 'm')
                      else arr) & 0xFFFFFFFF


class LineageCollector(object):
    """FIFO row accounting from delivered chunks to emitted batches.

    The loader's batch assembly consumes reader chunks strictly in
    delivery order (the block fast path slices them FIFO; the per-row
    path without a shuffling buffer appends rows FIFO), so mapping a
    batch back to its source spans is a matter of draining the same FIFO
    here: :meth:`on_chunk` pushes each arriving chunk's segment (with
    its row count), :meth:`on_batch` pops spans covering the batch.

    A row-level shuffling buffer breaks the FIFO property;
    :meth:`mark_inexact` flags every subsequent record ``exact: false``
    (segments then name the contributing chunks, not exact row spans).

    Thread model: all methods are called from the single thread driving
    the host-batch iterator (the staging engine's assemble thread, or
    the consumer under ``prefetch=0``); the pending queue handed to the
    tracker is lock-protected there.
    """

    def __init__(self, tracker, digest=True):
        self._tracker = tracker
        self._digest = digest
        self._fifo = deque()      # [segment dict, consumed offset, remaining]
        self._inexact = False

    def mark_inexact(self):
        self._inexact = True

    def on_chunk(self, segment, n_rows):
        """One reader chunk (or row) arrived. ``segment`` may be None
        (a reader that doesn't attach lineage) — accounting stays exact
        per-row but the record is flagged inexact."""
        if n_rows <= 0:
            return
        if segment is None:
            self._inexact = True
            segment = {'unknown': True, 'row_start': 0,
                       'chunk_rows': int(n_rows)}
        if self._fifo:
            tail = self._fifo[-1]
            if self._coalesces(tail, segment):
                tail[2] += n_rows
                tail[0]['chunk_rows'] = max(
                    tail[0].get('chunk_rows', 0),
                    segment.get('row_start', 0) + n_rows)
                return
        self._fifo.append([dict(segment), 0, int(n_rows)])

    @staticmethod
    def _coalesces(tail, segment):
        """Per-row readers deliver one row at a time; consecutive rows of
        the same chunk merge into one span instead of one segment each."""
        prev = tail[0]
        if prev.get('unknown') or segment.get('unknown'):
            return bool(prev.get('unknown')) and bool(segment.get('unknown'))
        if (prev.get('path') != segment.get('path')
                or prev.get('row_group') != segment.get('row_group')
                or prev.get('drop') != segment.get('drop')):
            return False
        # Contiguity: the new row must extend the uncovered tail exactly.
        return (prev.get('row_start', 0) + tail[1] + tail[2]
                == segment.get('row_start', 0))

    def on_batch(self, n_rows, batch=None, padded=0):
        """A batch of ``n_rows`` source rows (+ ``padded`` repeat-pad
        rows) is being emitted: pop its spans and hand the tracker a
        pending entry (paired FIFO with delivered batches)."""
        segments = []
        need = int(n_rows)
        while need > 0 and self._fifo:
            entry = self._fifo[0]
            segment, offset, remaining = entry
            take = min(need, remaining)
            span = dict(segment)
            base = span.pop('row_start', 0) + offset
            span['row_start'] = base
            span['row_stop'] = base + take
            segments.append(span)
            entry[1] += take
            entry[2] -= take
            if entry[2] == 0:
                self._fifo.popleft()
            need -= take
        exact = not self._inexact and need == 0 \
            and not any(s.get('unknown') or s.get('filtered')
                        for s in segments)
        digest = None
        if self._digest and batch is not None:
            try:
                digest = {name: _digest_array(arr)
                          for name, arr in batch.items()}
            except Exception:  # noqa: BLE001 - advisory, never block a batch
                logger.debug('lineage digest failed', exc_info=True)
        self._tracker._push_pending({
            'rows': int(n_rows) + int(padded),
            'source_rows': int(n_rows),
            'padded': int(padded),
            'segments': segments,
            'exact': exact,
            'fields': sorted(batch) if batch is not None else None,
            'digest': digest})


# Process-wide registry of live trackers: the flight recorder dumps every
# live ring on stall escalation without construction-order coupling.
_live_trackers = weakref.WeakSet()
_live_lock = threading.Lock()


def live_rings():
    """``[{'ctx': ..., 'records': [...], 'in_flight': [...]}]`` for every
    live tracker — what the flight recorder writes to ``lineage.json``.
    ``records`` are delivered batches (newest last); ``in_flight`` are
    batches assembled but never delivered — on a stalled-at-start
    pipeline they are the only provenance there is, and they name the
    exact rows the pipeline died holding."""
    with _live_lock:
        trackers = list(_live_trackers)
    return [{'ctx': t.ctx, 'records': t.ring(),
             'in_flight': t.pending_snapshot()} for t in trackers]


class LineageTracker(object):
    """Owns one pipeline's provenance stream: collector -> pending queue
    -> per-delivery records -> ring + ledger.

    :param ctx: the reader's JSON-safe lineage context
        (:meth:`~petastorm_tpu.reader.Reader.lineage_context`), stored
        once in the ledger header and alongside the ring.
    :param ledger_dir: directory for the JSONL ledger; ``None`` disables
        spill (ring + stats only).
    :param ring_size: records retained for the flight recorder.
    :param digest: compute per-field CRC32 content digests (one fast pass
        per batch; what makes replay's assert mode bit-exact).
    :param state_fn: optional ``() -> dict`` sampled per record (the
        reader's live shuffle state: epoch + order digest).
    :param max_records: ledger line bound — past it records keep landing
        in the ring but the file stops growing (counted as dropped).
    :param queue_size: write-behind queue bound (overflow drops).
    """

    def __init__(self, ctx, ledger_dir=None, ring_size=128, digest=True,
                 state_fn=None, max_records=1000000, queue_size=1024):
        from petastorm_tpu import metrics
        self.ctx = dict(ctx or {})
        self._state_fn = state_fn
        # Sanitizer hookup: lock-order-recorded when PETASTORM_TPU_SANITIZE
        # is armed (name matches pstlint's static graph node).
        from petastorm_tpu.analysis import sanitize
        self._lock = sanitize.tracked_lock(
            'petastorm_tpu.lineage:LineageTracker._lock')
        self._pending = deque()
        self._ring = deque(maxlen=ring_size)
        self._next_batch_id = 0
        self.records = 0
        self.dropped = 0
        self.pressure_dropped = 0   # records shed by the memory governor
        self._pressure_shed = False
        self.collector = LineageCollector(self, digest=digest)
        self._m_records = metrics.counter(
            'pst_lineage_records_total',
            'Batch provenance records committed (ring + ledger)')
        self._m_dropped = metrics.counter(
            'pst_lineage_dropped_total',
            'Provenance records lost (writer queue overflow, ledger line '
            'bound, or batches dropped before delivery)')
        self._ledger = None
        if ledger_dir is not None:
            self._ledger = LineageLedger(ledger_dir, self.ctx,
                                         max_records=max_records,
                                         queue_size=queue_size)
        # Memory-governor accounting (membudget.py): the write-behind
        # queue's records are the only unbounded-ish bytes here (ring and
        # pending are small and bounded); under *degrade* the governor
        # sheds records — counted in pressure_dropped + the dropped
        # metric, never silently.
        from petastorm_tpu import membudget
        self._mem_handle = membudget.register_pool(
            'lineage-queue',
            self.queued_nbytes,
            degrade_fn=lambda: self.set_pressure_shedding(True),
            degrade_release_fn=lambda: self.set_pressure_shedding(False))
        with _live_lock:
            _live_trackers.add(self)

    # -- assemble side (collector calls) -----------------------------------

    def _push_pending(self, entry):
        with self._lock:
            self._pending.append(entry)

    def drop_newest(self):
        """The staging engine dropped the most recently assembled batch
        without delivering it (stop-time race): discard its pending entry
        so the FIFO pairing with delivered batches stays exact."""
        with self._lock:
            if self._pending:
                self._pending.pop()
                self.dropped += 1
        self._m_dropped.inc()

    # -- consumer side -----------------------------------------------------

    def deliver(self):
        """A fresh batch reached the consumer: mint its record (FIFO
        against the assemble side), append to ring + ledger, return it.
        Returns None when no pending entry exists (a reader without
        lineage attached)."""
        with self._lock:
            if not self._pending:
                return None
            entry = self._pending.popleft()
            batch_id = self._next_batch_id
            self._next_batch_id += 1
        record = {'v': RECORD_VERSION,
                  'batch_id': batch_id,
                  'wall_time': time.time(),
                  'pid': os.getpid()}
        record.update(entry)
        if self._state_fn is not None:
            try:
                record['shuffle'] = self._state_fn()
            except Exception:  # noqa: BLE001 - advisory state probe
                logger.debug('lineage state probe failed', exc_info=True)
        with self._lock:
            self._ring.append(record)
            self.records += 1
        self._m_records.inc()
        if self._ledger is not None:
            if self._pressure_shed:
                # Governor degrade rung: the spill is shed — counted, not
                # silent (the ring above still holds the record).
                with self._lock:
                    self.dropped += 1
                    self.pressure_dropped += 1
                self._m_dropped.inc()
            elif not self._ledger.append(record):
                with self._lock:
                    self.dropped += 1
                self._m_dropped.inc()
        return record

    def set_pressure_shedding(self, shed):
        """Memory-governor degrade hook: while True, delivered batches
        still mint ring records (bounded, the post-mortem surface) but the
        ledger spill is SHED — each skipped record counts in
        ``pressure_dropped``/``dropped`` and the dropped metric, never
        silently. Returns True when the flag actually flipped (the
        governor counts transitions, not the per-tick re-asserts)."""
        shed = bool(shed)
        with self._lock:
            changed = shed != self._pressure_shed
            self._pressure_shed = shed
        if changed:
            logger.warning('lineage ledger spill %s under memory pressure',
                           'shed' if shed else 'restored')
        return changed

    def queued_nbytes(self):
        """Estimated bytes parked in the ledger's write-behind queue — the
        memory governor's ``lineage-queue`` accounting hook."""
        if self._ledger is None:
            return 0
        return self._ledger.queued_nbytes()

    def ring(self):
        with self._lock:
            return list(self._ring)

    def pending_snapshot(self):
        """Batches assembled but not yet delivered (no batch_id yet) —
        the in-flight rows a stall post-mortem wants."""
        with self._lock:
            return [dict(e) for e in self._pending]

    @property
    def ledger_path(self):
        return self._ledger.path if self._ledger is not None else None

    def stats(self):
        with self._lock:
            out = {'records': self.records,
                   'dropped': self.dropped,
                   'pressure_dropped': self.pressure_dropped,
                   'pending': len(self._pending),
                   'ring': len(self._ring)}
        if self._ledger is not None:
            # Accepted-then-discarded (write failure) joins accept-time
            # drops: 'dropped' is every record that will never replay.
            out['dropped'] += self._ledger.dropped
            out['ledger_path'] = self._ledger.path
            out['ledger_lag'] = self._ledger.lag
        return out

    def flush(self, timeout_s=5.0):
        if self._ledger is not None:
            return self._ledger.flush(timeout_s)
        return True

    def close(self):
        with _live_lock:
            _live_trackers.discard(self)
        self._mem_handle.close()
        if self._ledger is not None:
            self._ledger.close()


class LineageLedger(object):
    """Bounded, crash-tolerant JSONL spill of provenance records.

    One file per tracker (``ledger-<pid>-<uid>.jsonl``): a header line
    with the reader context, then one line per record, written
    line-buffered by a daemon write-behind thread (named
    ``pst-lineage-writer``) so batch delivery never blocks on disk. The
    bounded queue drops on overflow; ``max_records`` bounds the file.
    A killed process leaves at most one torn trailing line —
    :func:`read_ledger_file` skips it.
    """

    def __init__(self, directory, ctx, max_records=1000000, queue_size=1024):
        from petastorm_tpu import metrics
        self.directory = directory
        self.path = None
        self._max_records = int(max_records)
        self._accepted = 0      # gated synchronously in append()
        self._written = 0
        self._record_bytes_ema = 512.0   # serialized-size estimate (drain)
        self.dropped = 0        # accepted but discarded (write failure/bound)
        self._failed = False
        self._closed = False
        self._file = None
        self._queue = queue.Queue(maxsize=max(1, int(queue_size)))
        # Per-ledger label child (the PR-6 autotune pattern): two armed
        # pipelines in one process must not clobber each other's lag
        # sample, and close() removes the child so a dead ledger's queue
        # object is neither retained nor scraped as live.
        self._label = '{}-{}'.format(os.getpid(), uuid.uuid4().hex[:8])
        self._m_lag = metrics.gauge(
            'pst_lineage_ledger_lag',
            'Provenance records accepted but not yet durable in the '
            'ledger (write-behind queue depth)', labelnames=('ledger',))
        self._m_lag.labels(self._label).set_function(self._queue.qsize)
        self._m_dropped = metrics.counter(
            'pst_lineage_dropped_total',
            'Provenance records lost (writer queue overflow, ledger line '
            'bound, or batches dropped before delivery)')
        try:
            os.makedirs(directory, exist_ok=True)
            self.path = os.path.join(
                directory, 'ledger-{}.jsonl'.format(self._label))
            # buffering=1: one flush per line — complete lines survive a
            # SIGKILL at batch granularity (trace-sidecar discipline).
            self._file = open(self.path, 'w', buffering=1)
            header = {_HEADER_KEY: 1, 'pid': os.getpid(),
                      'wall0': time.time(), 'ctx': ctx}
            self._file.write(json.dumps(header) + '\n')
        except (OSError, TypeError, ValueError):
            logger.warning('cannot open lineage ledger in %r; disabling '
                           'spill', directory, exc_info=True)
            self._failed = True
        self._thread = threading.Thread(target=self._drain, daemon=True,
                                        name='pst-lineage-writer')
        if not self._failed:
            self._thread.start()

    @property
    def lag(self):
        return self._queue.qsize()

    def queued_nbytes(self):
        """Estimated queued record bytes: depth x the serialized-size EMA
        the drain thread maintains (records are JSON dicts — re-serializing
        them here just to weigh them would double the writer's work)."""
        return int(self._queue.qsize() * self._record_bytes_ema)

    def append(self, record):
        """Enqueue one record for the writer; False when it was dropped
        (ledger closed, writer dead, queue full, or past the line bound).
        The line bound gates at accept time — the async writer must not
        let a burst overshoot the file bound just because its drain lags."""
        if self._failed or self._closed \
                or self._accepted >= self._max_records:
            return False
        try:
            self._queue.put_nowait(record)
            self._accepted += 1
            return True
        except queue.Full:
            return False

    def _drain(self):
        while True:
            record = self._queue.get()
            try:
                if record is None:
                    return
                if self._failed or self._written >= self._max_records:
                    # Accepted (append returned True) yet never durable:
                    # the loss must be counted, not silently consumed —
                    # the 'drops are counted, never silent' contract
                    # covers the write-failure path too.
                    self.dropped += 1
                    self._m_dropped.inc()
                    continue
                try:
                    line = json.dumps(record, default=repr) + '\n'
                    # Size EMA feeds queued_nbytes (governor accounting);
                    # float rebind is atomic, writer thread only.
                    self._record_bytes_ema += 0.2 * (len(line)
                                                     - self._record_bytes_ema)
                    self._file.write(line)
                    self._written += 1
                except (OSError, ValueError):
                    logger.warning('lineage ledger write failed; disabling',
                                   exc_info=True)
                    self._failed = True
                    self.dropped += 1
                    self._m_dropped.inc()
            finally:
                self._queue.task_done()

    def flush(self, timeout_s=5.0):
        """Best-effort drain wait (tests, self-checks): True when
        every accepted record reached the file within the timeout. Gates
        on the written count, not the queue depth — the writer pops a
        record (queue hits 0) before its bytes land."""
        deadline = time.monotonic() + timeout_s
        while not self._failed and self._written < self._accepted \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        return not self._failed and self._written >= self._accepted

    def close(self, join_timeout_s=5.0):
        # Refuse new records first (append returns False -> counted as
        # dropped, never silently swallowed by a dead writer); records
        # already accepted still drain before the sentinel lands.
        self._closed = True
        if self._thread.is_alive():
            try:
                self._queue.put(None, timeout=join_timeout_s)
            except queue.Full:
                pass
            self._thread.join(timeout=join_timeout_s)
        # Unbind the lag gauge child: a closed ledger must neither scrape
        # as a live 0 nor keep its queue object reachable via the registry.
        self._m_lag.remove(self._label)
        f, self._file = self._file, None
        if f is not None:
            try:
                f.flush()
                f.close()
            except OSError:  # pragma: no cover - disk already gone
                pass


# --------------------------------------------------------------------------
# ledger reading
# --------------------------------------------------------------------------

def read_ledger_file(path):
    """``(ctx_or_None, [records])`` from one ledger file. Torn trailing
    lines and corrupt lines (a trainer SIGKILLed mid-write) are skipped,
    not fatal — the file stays readable even if its writer died."""
    ctx = None
    records = []
    try:
        with open(path, 'r') as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    record = json.loads(line)
                except ValueError:
                    continue        # torn/corrupt line: skip, keep reading
                if not isinstance(record, dict):
                    continue
                if record.get(_HEADER_KEY):
                    ctx = record.get('ctx')
                else:
                    records.append(record)
    except OSError:
        logger.warning('cannot read lineage ledger %r', path, exc_info=True)
    return ctx, records


def read_ledger_dir(directory):
    """Every ledger under ``directory`` as ``[(path, ctx, records)]``."""
    import glob
    out = []
    for path in sorted(glob.glob(os.path.join(directory, LEDGER_GLOB))):
        ctx, records = read_ledger_file(path)
        if ctx is not None or records:
            out.append((path, ctx, records))
    return out


def find_record(directory, batch_id, pid=None):
    """Locate one batch record in a ledger directory. Returns
    ``(ctx, record)``; raises ``LookupError`` naming what exists when the
    id is absent or ambiguous (several pipelines ledgered into the same
    directory — disambiguate with ``pid``)."""
    matches = []
    for path, ctx, records in read_ledger_dir(directory):
        for record in records:
            if record.get('batch_id') == batch_id \
                    and (pid is None or record.get('pid') == pid):
                matches.append((path, ctx, record))
    if not matches:
        available = []
        for path, _, records in read_ledger_dir(directory):
            ids = [r.get('batch_id') for r in records]
            if ids:
                available.append('{}: batch ids {}..{} ({} records)'.format(
                    os.path.basename(path), min(ids), max(ids), len(ids)))
        raise LookupError(
            'batch_id {} not found under {!r}. Ledgers present: {}'.format(
                batch_id, directory, '; '.join(available) or 'none'))
    if len(matches) > 1:
        raise LookupError(
            'batch_id {} is ambiguous under {!r} ({} ledgers match — '
            'several pipelines share this directory); pass the producing '
            'pid (candidates: {})'.format(
                batch_id, directory, len(matches),
                sorted({m[2].get('pid') for m in matches})))
    _, ctx, record = matches[0]
    return ctx, record


# --------------------------------------------------------------------------
# replay
# --------------------------------------------------------------------------

class ReplayError(RuntimeError):
    """A record cannot be deterministically re-materialized (inexact
    accounting, unsupported reader mode, or dataset drift)."""


class ReplayMismatchError(ReplayError):
    """Assert-mode replay produced different bytes than the record's
    content digest — the dataset (or decode stack) drifted."""


def _check_replayable(ctx, record):
    if ctx is None:
        raise ReplayError('record has no reader context (ledger header '
                          'missing or torn)')
    if not record.get('exact', False):
        raise ReplayError(
            'record {} is not exact (shuffling buffer, predicate, ngram, '
            'or a reader without lineage attached) — replay would not be '
            'bit-identical'.format(record.get('batch_id')))
    if ctx.get('transform') is not None:
        raise ReplayError(
            'record was produced under a TransformSpec ({}); replay cannot '
            're-run user transform code — re-materialize without it or '
            'replay upstream of the transform'.format(ctx['transform']))
    if ctx.get('shape_policies'):
        raise ReplayError('record was produced under shape policies {}; '
                          'replay cannot reconstruct them'.format(
                              ctx['shape_policies']))
    mode = ctx.get('mode')
    if mode not in ('tensor', 'arrow', 'py_dict', 'mixture'):
        raise ReplayError('unsupported reader mode {!r}'.format(mode))


def _segment_ctx(ctx, segment):
    """The reader context a segment decodes under — for mixtures, the
    source reader's context (segments carry the draw's source index)."""
    if ctx.get('mode') != 'mixture':
        return ctx
    sources = ctx.get('sources') or []
    idx = segment.get('source')
    if idx is None or not 0 <= idx < len(sources):
        raise ReplayError('mixture segment carries no valid source index')
    source_ctx = sources[idx]
    if source_ctx.get('transform') is not None:
        raise ReplayError('mixture source {} was read under a TransformSpec; '
                          'replay cannot re-run user transform code'
                          .format(idx))
    return source_ctx


def _load_segment_table(store, ctx, segment, fields, piece_index):
    """One segment's row-group as a pa.Table restricted to ``fields``,
    partition columns appended — the worker's ``_load_table`` shape.
    ``piece_index`` is the store's ``(path, row_group) -> piece`` map,
    built once per store (a multi-segment batch must not re-list the
    dataset's row groups per segment)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    piece = piece_index.get((str(segment['path']), int(segment['row_group'])))
    if piece is None:
        raise ReplayError(
            'row-group {} of {} no longer exists in the dataset at {} '
            '(dataset drift since the record was written)'.format(
                segment['row_group'], segment['path'], ctx.get('url')))
    from urllib.parse import urlparse
    partition_names = set(store.partition_names)
    physical = [n for n in fields if n not in partition_names]
    # Same handle choice as the workers (rowgroup_worker_base): local
    # stores read via the OS path (memory-mapped), remote via fsspec.
    pf = pq.ParquetFile(str(piece.path), memory_map=True) \
        if urlparse(store.url).scheme == 'file' \
        else pq.ParquetFile(store.open_file(piece.path))
    try:
        table = pf.read_row_group(piece.row_group, columns=physical)
    finally:
        pf.close()
    for name, value in piece.partition_values.items():
        if name in fields and name not in table.column_names:
            table = table.append_column(name, pa.array([value] * table.num_rows))
    return table


def _replay_segment(store, stored_schema, ctx, segment, fields, x64,
                    piece_index):
    """Re-materialize one segment's rows as sanitized column blocks."""
    from petastorm_tpu.jax_loader import _sanitize_array
    from petastorm_tpu.workers.rowgroup_worker_base import (
        chunk_row_permutation, compute_row_slice)

    mode = ctx.get('mode')
    schema_fields = [f for f in ctx.get('fields') or fields
                    if f in stored_schema.fields]
    view = stored_schema.create_schema_view(schema_fields) \
        if schema_fields else stored_schema
    table = _load_segment_table(store, ctx, segment, list(view.fields),
                                piece_index)

    if mode in ('tensor', 'py_dict'):
        cols = _decode_view_to_blocks(table, view, mode)
    else:       # arrow: raw cells, the consumer-side numpy conversion
        from petastorm_tpu.arrow_worker import _arrow_column_to_numpy
        cols = {}
        for name in view.fields:
            if name in table.column_names:
                cols[name] = _arrow_column_to_numpy(
                    table.column(name), view.fields[name])
    n_rows = len(next(iter(cols.values()))) if cols else 0

    drop = segment.get('drop')
    if drop:
        row_slice = compute_row_slice(n_rows, (drop[0], drop[1]))
        if row_slice is not None:
            start, stop = row_slice
            cols = {k: v[start:stop] for k, v in cols.items()}
            n_rows = stop - start
    if segment.get('permuted'):
        perm = chunk_row_permutation(
            ctx.get('seed'), ctx.get('dataset_path_hash'),
            segment['path'], segment['row_group'],
            (drop[0], drop[1]) if drop else None, n_rows)
        cols = {k: v[perm] for k, v in cols.items()}
    if segment.get('chunk_rows') is not None \
            and n_rows != segment['chunk_rows']:
        raise ReplayError(
            'row-group {} of {} now decodes to {} rows; the record says {} '
            '(dataset rewritten in place?)'.format(
                segment['row_group'], segment['path'], n_rows,
                segment['chunk_rows']))
    start, stop = segment['row_start'], segment['row_stop']
    out = {}
    for name in fields:
        if name not in cols:
            raise ReplayError('field {!r} is no longer readable from the '
                              'dataset'.format(name))
        arr = _sanitize_array(np.asarray(cols[name][start:stop]), x64)
        if arr is None:
            raise ReplayError('field {!r} dtype cannot be sanitized the way '
                              'the loader did'.format(name))
        out[name] = arr
    return out


def _decode_view_to_blocks(table, view, mode):
    """Decoded column blocks for tensor/py_dict segments. The tensor path
    reuses the worker's columnar decoder verbatim; the per-row path
    decodes rows then stacks per field — both produce the exact bytes the
    live pipeline fed the loader."""
    if mode == 'tensor':
        from petastorm_tpu.tensor_worker import decode_table_to_blocks
        return decode_table_to_blocks(table, view, decode_threads=1)
    from petastorm_tpu.unischema import decode_rows
    encoded_rows = table.to_pylist()
    rows = decode_rows(encoded_rows, view, num_threads=1)
    cols = {}
    for name in view.fields:
        if rows and name in rows[0]:
            cols[name] = np.asarray([row[name] for row in rows])
    return cols


def replay_record(record, ctx, storage_options=None):
    """Deterministically re-materialize one recorded batch.

    Returns ``{field: np.ndarray}`` with the exact bytes the loader
    staged for that batch (pre-``device_put``). Raises
    :class:`ReplayError` for records outside the determinism contract.
    """
    from petastorm_tpu.etl.dataset_metadata import (get_schema,
                                                    infer_or_load_unischema)
    from petastorm_tpu.storage import ParquetStore

    _check_replayable(ctx, record)
    fields = record.get('fields')
    if not fields:
        raise ReplayError('record carries no field list')
    x64 = bool(ctx.get('x64'))

    stores = {}

    def store_for(seg_ctx):
        url = seg_ctx.get('url')
        if url is None:
            raise ReplayError('segment context carries no dataset url')
        if url not in stores:
            store = ParquetStore(url, storage_options)
            if seg_ctx.get('mode') == 'arrow':
                schema = infer_or_load_unischema(store)
            else:
                schema = get_schema(store)
            piece_index = {(str(p.path), int(p.row_group)): p
                           for p in store.row_groups()}
            stores[url] = (store, schema, piece_index)
        return stores[url]

    parts = []
    for segment in record.get('segments') or []:
        seg_ctx = _segment_ctx(ctx, segment)
        store, stored_schema, piece_index = store_for(seg_ctx)
        parts.append(_replay_segment(store, stored_schema, seg_ctx, segment,
                                     fields, x64, piece_index))
    if not parts:
        raise ReplayError('record {} has no segments'.format(
            record.get('batch_id')))
    batch = {name: (parts[0][name] if len(parts) == 1
                    else np.concatenate([p[name] for p in parts]))
             for name in fields}
    padded = int(record.get('padded') or 0)
    if padded:
        # Repeat-pad the final row, exactly as the loader's 'pad' mode.
        batch = {name: np.concatenate(
            [arr] + [arr[-1:]] * padded) for name, arr in batch.items()}
    rows = int(record.get('rows', 0))
    got = len(next(iter(batch.values())))
    if rows and got != rows:
        raise ReplayError('replay produced {} rows, record says {}'.format(
            got, rows))
    return batch


def verify_record(record, ctx, storage_options=None):
    """Replay + digest assert: returns the replayed batch, raising
    :class:`ReplayMismatchError` if any field's bytes differ from the
    record's CRC32 content digest (records without digests replay but
    cannot be verified — a :class:`ReplayError` says so)."""
    batch = replay_record(record, ctx, storage_options)
    digest = record.get('digest')
    if not digest:
        raise ReplayError(
            'record {} carries no content digest (tracker built with '
            'digest=False); replay succeeded but cannot be verified '
            'bit-identical'.format(record.get('batch_id')))
    bad = []
    for name, arr in batch.items():
        want = digest.get(name)
        have = _digest_array(arr)
        if want is not None and int(want) != have:
            bad.append('{} (recorded {:#010x}, replayed {:#010x})'.format(
                name, int(want), have))
    if bad:
        raise ReplayMismatchError(
            'replayed batch {} differs from the live batch: {}'.format(
                record.get('batch_id'), ', '.join(bad)))
    return batch
