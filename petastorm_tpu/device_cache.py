"""Device-resident dataset tier: decode once, train epochs out of HBM.

The reference caches *encoded row-groups on local disk*
(``local_disk_cache.py:22-63``) — every epoch still pays decode, collation,
and the host->device copy. On TPU the idiomatic place for a dataset that
fits device memory is HBM itself: stream epoch 0 through the normal
reader -> decode -> ``JaxLoader`` pipeline (training starts immediately, no
fill pass), keep the staged rows, and from epoch 1 on iterate entirely
on-device — zero host I/O, zero decode, zero h2d traffic, input stall
identically 0.

Storage is **incremental superbatches**: every ``superbatch_batches``
cached batches are consolidated into one contiguous ``[k*rows, ...]``
array per field as they stream, so the fill's transient double-hold is
one superbatch — not the whole dataset (the old single-consolidation
design held the dataset twice at epoch end). Superbatches are also the
**eviction unit**: the cache registers a ``device-cache`` pool with the
memory governor (``membudget``), and in partial mode the degrade rung
evicts the coldest superbatch while the advisory rung pauses further
fill.

**Partial-dataset mode** (``partial=True``) turns the budget from a hard
wall into a watermark: the hottest (earliest-streamed) superbatches stay
resident and the remainder streams through the source pipeline each
epoch (``loader_factory`` supplies a fresh deterministic pass; batches
whose indices are HBM-resident are served from the cache and the
source's copy of them is dropped — the streamed pass keeps the epoch
complete and bit-identical under live eviction). ``DeviceCacheOverflow``
is never raised in partial mode.

Epoch reshuffling happens **on the accelerator**: the cache draws a
fresh two-level permutation per epoch — superbatch visit order plus
row order within each superbatch, both from ``fold_in(key, epoch)`` —
and regathers each batch with a jitted ``take``. For mesh-sharded data
XLA lowers the gather to collectives over ICI; batch shapes (and
therefore the downstream train step's compiled program) never change,
and the sequence is reproducible across job restarts by construction.

Usage::

    with make_tensor_reader(url, num_epochs=1, seed=0) as reader:
        with JaxLoader(reader, batch, mesh=mesh) as loader:
            cache = DeviceDatasetCache(loader, shuffle=True, seed=0)
            for epoch in range(90):
                for batch in cache.epoch(epoch):
                    state, metrics = train_step(state, batch.image, batch.label)

The source loader must be finite (``num_epochs=1``); the cache
materializes exactly one pass.
"""

import logging
import threading

logger = logging.getLogger(__name__)

_DEFAULT_HBM_FRACTION = 0.4
_DEFAULT_SUPERBATCH_BATCHES = 8


class DeviceCacheOverflow(RuntimeError):
    """Staged bytes exceeded the cache budget (full mode only)."""


class _Superbatch(object):
    """One consolidated run of cached batches: ``columns[name]`` is a
    ``[n_batches * rows, ...]`` device array; ``start`` is the first
    source batch index the run covers. ``last_hit`` feeds coldest-first
    eviction."""

    __slots__ = ('columns', 'start', 'n_batches', 'rows', 'nbytes',
                 'last_hit', 'hits')

    def __init__(self, columns, start, n_batches, rows, nbytes):
        self.columns = columns
        self.start = start
        self.n_batches = n_batches
        self.rows = rows
        self.nbytes = nbytes
        self.last_hit = 0
        self.hits = 0

    def covers(self, batch_index):
        return self.start <= batch_index < self.start + self.n_batches


class DeviceDatasetCache(object):
    """Caches a finite loader's batches on device in superbatch units;
    reshuffles epochs with a jitted on-device gather.

    :param loader: a :class:`~petastorm_tpu.jax_loader.JaxLoader` over a
        finite reader (``num_epochs=1``). Consumed lazily during epoch 0;
        the loader can be closed afterwards. The cache attaches itself to
        the loader so ``loader.stats['device_cache']`` reports the tier.
    :param shuffle: reshuffle rows each epoch — two-level (superbatch
        visit order + rows within each superbatch), entirely on device.
        ``False`` replays cache order (batch boundaries preserved).
    :param seed: base of the per-epoch permutation key (the epoch index
        is folded in: every epoch differs, the sequence is reproducible).
        The permutation acts on *cache order* — for bit-identical epoch
        streams across job restarts the source pipeline must also be
        deterministic (``workers_count=1`` or a seeded single-reader
        setup; multi-worker pools interleave chunk arrival).
    :param max_bytes: **per-device** staging budget (sharded global bytes
        are normalized by the batch's addressable-shard size); ``None`` =
        40% of the first device's reported HBM (no limit when the backend
        reports no stats). Full mode raises :class:`DeviceCacheOverflow`
        past it; partial mode stops filling instead.
    :param partial: keep only the superbatches that fit and stream the
        remainder each epoch. Requires ``loader_factory`` for epochs past
        the fill pass unless everything fit after all.
    :param superbatch_batches: batches consolidated per superbatch — the
        fill's transient double-hold and the eviction granularity.
    :param loader_factory: zero-arg callable returning a fresh iterable
        over the SAME deterministic batch stream (a new reader + loader).
        Partial epochs walk it for the uncached indices; resident indices
        are served from HBM and the source's copy is dropped.
    """

    def __init__(self, loader, shuffle=True, seed=0, max_bytes=None,
                 partial=False, superbatch_batches=None, loader_factory=None):
        import jax

        from petastorm_tpu import membudget as membudget_mod
        from petastorm_tpu import metrics as metrics_mod

        self._jax = jax
        self._loader = loader
        self._shuffle = shuffle
        self._seed = seed
        self._partial = bool(partial)
        self._loader_factory = loader_factory
        self._superbatch_batches = max(1, int(
            superbatch_batches if superbatch_batches is not None
            else _DEFAULT_SUPERBATCH_BATCHES))
        self._lock = threading.Lock()   # governor thread vs consumer
        self._superbatches = []
        self._nt_type = None
        self._batch_rows = None
        self._total_batches = None
        self._bytes = 0
        self._per_dev_bytes = 0
        self._max_bytes = (max_bytes if max_bytes is not None
                           else _default_budget(jax))
        self._take = {}          # column sharding -> jitted row gather
        self._streaming = False
        self._materialized = False
        self._overflow_msg = None
        self._cleared = False
        self._fill_paused = False
        self._fill_stopped = False
        self._evictions = 0
        self._hits = 0
        self._hit_clock = 0
        self._m_bytes = metrics_mod.gauge(
            'pst_device_cache_bytes',
            'Global logical bytes resident in the device dataset cache '
            'across all caches (inc/dec per superbatch lifetime)')
        self._m_hits = metrics_mod.counter(
            'pst_device_cache_hits_total',
            'Batches served from the HBM-resident dataset tier')
        # Governor pool: accounting always; the degrade (evict coldest
        # superbatch) and advisory (pause fill) rungs only in partial
        # mode — acting on a full-mode cache would silently break the
        # "every epoch is the whole dataset" contract. On zero-copy CPU
        # backends these are genuine host bytes; on accelerators the
        # pool is the governor's leverage over the largest reclaimable
        # allocation the input pipeline owns.
        self._mem_handle = membudget_mod.register_pool(
            'device-cache', lambda: self._bytes,
            degrade_fn=self._evict_coldest if self._partial else None,
            advisory_fn=self._set_fill_paused if self._partial else None)
        try:
            loader._device_cache = self
        except Exception:  # noqa: BLE001 - duck-typed loaders in tests
            pass

    # -- introspection -----------------------------------------------------

    @property
    def materialized(self):
        return self._materialized

    @property
    def nbytes(self):
        """Global logical bytes resident (summed over superbatches)."""
        return self._bytes

    def stats(self):
        with self._lock:
            return {
                'materialized': self._materialized,
                'partial': self._partial,
                'superbatches': len(self._superbatches),
                'cached_batches': sum(sb.n_batches
                                      for sb in self._superbatches),
                'total_batches': self._total_batches,
                'nbytes': self._bytes,
                'max_bytes_per_device': self._max_bytes,
                'hits': self._hits,
                'evictions': self._evictions,
                'fill_paused': self._fill_paused,
                'fill_stopped': self._fill_stopped,
            }

    # -- governor hooks (partial mode) -------------------------------------

    def _set_fill_paused(self, active):
        with self._lock:
            self._fill_paused = bool(active)

    def _evict_coldest(self):
        """Degrade rung: drop the coldest superbatch (least-recently hit,
        earliest on ties). Idempotent per tick; the evicted run's batch
        indices fall back to the streamed remainder from the next epoch
        (and mid-epoch: coverage is re-read per batch)."""
        with self._lock:
            if not self._superbatches:
                return False
            coldest = min(self._superbatches,
                          key=lambda sb: (sb.last_hit, sb.start))
            self._superbatches.remove(coldest)
            self._bytes -= coldest.nbytes
            self._evictions += 1
        self._m_bytes.inc(-coldest.nbytes)
        logger.info('device cache evicted superbatch [%d, %d) under memory '
                    'pressure (%.2f GB freed)', coldest.start,
                    coldest.start + coldest.n_batches, coldest.nbytes / 1e9)
        return True

    # -- iteration ---------------------------------------------------------

    def epoch(self, epoch_index=0):
        """Iterate one epoch. The first call streams through the host
        pipeline while caching; later epochs run from HBM (plus the
        streamed remainder in partial mode)."""
        if self._cleared:
            raise RuntimeError('DeviceDatasetCache was cleared; construct a '
                               'new cache over a fresh loader')
        if not self._materialized:
            if self._overflow_msg is not None:
                # The caching epoch overflowed the budget — the "abandoned
                # mid-stream" message below would misleadingly suggest the
                # stream can be finished; it cannot (the source loader was
                # part-consumed). Point at the actual failure and the fix.
                raise DeviceCacheOverflow(
                    'the caching epoch previously overflowed: {} — this '
                    'cache cannot be retried; construct a new '
                    'DeviceDatasetCache (with a larger max_bytes) over a '
                    'fresh loader'.format(self._overflow_msg))
            if self._streaming:
                # A partially-consumed epoch-0 generator left the loader
                # mid-stream; restarting would silently cache a fraction of
                # the dataset and train 89 epochs on it.
                raise RuntimeError(
                    'the caching epoch was abandoned mid-stream; exhaust '
                    'epoch(0) fully (or construct a new cache) before '
                    'iterating further epochs')
            return self._first_epoch()
        return self._cached_epoch(epoch_index)

    def _first_epoch(self):
        self._streaming = True
        self._bytes = 0
        self._per_dev_bytes = 0
        pending = []          # batches awaiting consolidation
        pending_start = 0
        n = 0
        for batch in self._loader:
            rows = len(getattr(batch, batch._fields[0]))
            if self._batch_rows is None:
                self._batch_rows = rows
            elif rows != self._batch_rows:
                # A short tail (last_batch='partial') would make the
                # permutation index past the real row count — jnp.take
                # clamps silently and the final rows would train
                # duplicated every epoch.
                raise ValueError(
                    'device cache requires equal-size batches, but batch '
                    '{} has {} rows (expected {}); build the JaxLoader '
                    "with last_batch='drop' or 'pad'".format(
                        n, rows, self._batch_rows))
            self._nt_type = type(batch)
            if not self._cache_batch(batch, n, pending, pending_start):
                if not pending:
                    pending_start = n
                pending.append(batch)
                if len(pending) >= self._superbatch_batches:
                    self._consolidate(pending, pending_start)
                    del pending[:]
            n += 1
            yield batch
        if n == 0:
            raise ValueError('source loader yielded no batches to cache')
        if pending:
            self._consolidate(pending, pending_start)
            pending = []
        self._total_batches = n
        self._materialized = True
        self._streaming = False
        with self._lock:
            cached = sum(sb.n_batches for sb in self._superbatches)
        logger.info(
            'device cache materialized: %d/%d batches x %d rows in %d '
            'superbatch(es), %.2f GB%s', cached, n, self._batch_rows,
            len(self._superbatches), self._bytes / 1e9,
            ' (partial)' if cached < n else '')

    def _cache_batch(self, batch, index, pending, pending_start):
        """Budget/pause gate for one streamed batch. Returns True when
        the batch must NOT be cached (stream-only); flushes the pending
        run first so cached coverage stays contiguous per superbatch."""
        with self._lock:
            paused = self._fill_paused or self._fill_stopped
        if paused and self._partial:
            if pending:
                self._consolidate(pending, pending_start)
                del pending[:]
            return True
        per_dev = _per_device_nbytes(batch)
        if self._max_bytes and self._per_dev_bytes + per_dev > self._max_bytes:
            msg = ('device cache exceeded {:.2f} GB per-device budget after '
                   '{} batches ({:.2f} GB/device staged); raise max_bytes or '
                   'drop the cache for this dataset'.format(
                       self._max_bytes / 1e9, index + 1,
                       (self._per_dev_bytes + per_dev) / 1e9))
            if not self._partial:
                self._overflow_msg = msg
                self._drop_all()
                raise DeviceCacheOverflow(msg)
            with self._lock:
                if not self._fill_stopped:
                    self._fill_stopped = True
                    logger.info('device cache budget reached; streaming the '
                                'remainder (partial mode): %s', msg)
            if pending:
                self._consolidate(pending, pending_start)
                del pending[:]
            return True
        self._per_dev_bytes += per_dev
        return False

    def _consolidate(self, batches, start):
        """Per-field concat of one pending run into a superbatch. The
        transient double-hold is this run only — the per-batch arrays
        free as soon as the caller drops its list."""
        import jax.numpy as jnp
        jit_concat = self._jax.jit(lambda *xs: jnp.concatenate(xs))
        columns = {
            name: jit_concat(*[getattr(b, name) for b in batches])
            for name in self._nt_type._fields}
        nbytes = sum(col.nbytes for col in columns.values())
        sb = _Superbatch(columns, start, len(batches), self._batch_rows,
                         nbytes)
        with self._lock:
            self._superbatches.append(sb)
            self._superbatches.sort(key=lambda s: s.start)
            self._bytes += nbytes
        self._m_bytes.inc(nbytes)

    def _covering(self, batch_index):
        with self._lock:
            for sb in self._superbatches:
                if sb.covers(batch_index):
                    self._hit_clock += 1
                    sb.last_hit = self._hit_clock
                    sb.hits += 1
                    self._hits += 1
                    return sb
        return None

    def _sb_batch(self, sb, batch_index, perm):
        """One batch out of a resident superbatch: its rows in replay
        order, or the epoch permutation's rows for that slot — either way
        one jitted gather."""
        import jax.numpy as jnp

        start = (batch_index - sb.start) * sb.rows
        if perm is None:
            idx = start + jnp.arange(sb.rows)
        else:
            idx = self._jax.lax.dynamic_slice_in_dim(perm, start, sb.rows)
        return self._nt_type(**{name: self._gather(col, idx)
                                for name, col in sb.columns.items()})

    def _gather(self, col, idx):
        """Rows ``idx`` of a resident column, laid out like the column.
        The output sharding is explicit: left to itself XLA returns a
        gather over the sharded batch dim replicated, and every chip would
        hold every batch whole. Donation off: the column arrays are reused
        every epoch."""
        take = self._take.get(col.sharding)
        if take is None:
            import jax.numpy as jnp
            take = self._take[col.sharding] = self._jax.jit(
                lambda c, i: jnp.take(c, i, axis=0),
                out_shardings=col.sharding)
        return take(col, idx)

    def _epoch_perms(self, epoch_index):
        """Per-superbatch row permutations for one epoch (None each when
        shuffle is off), keyed by the superbatch's start index so live
        eviction never shifts another run's draw."""
        if not self._shuffle:
            return {}
        jax = self._jax
        key = jax.random.fold_in(jax.random.PRNGKey(self._seed), epoch_index)
        with self._lock:
            runs = [(sb.start, sb.n_batches * sb.rows)
                    for sb in self._superbatches]
        return {start: jax.random.permutation(
                    jax.random.fold_in(key, start), total)
                for start, total in runs}

    def _cached_epoch(self, epoch_index):
        import numpy as np

        jax = self._jax
        perms = self._epoch_perms(epoch_index)
        with self._lock:
            fully_cached = (sum(sb.n_batches for sb in self._superbatches)
                            == self._total_batches)
        if fully_cached:
            # Pure-HBM epoch: visit superbatches in a per-epoch permuted
            # order (shuffle's coarse level), batches within each run in
            # row-permuted order (the fine level). No host I/O at all.
            with self._lock:
                sbs = list(self._superbatches)
            order = range(len(sbs))
            if self._shuffle:
                key = jax.random.fold_in(
                    jax.random.PRNGKey(self._seed), epoch_index)
                # 0xffffffff cannot collide with a superbatch start (the
                # per-run row keys) — fold_in data must be uint32.
                order = np.asarray(jax.random.permutation(
                    jax.random.fold_in(key, 0xffffffff), len(sbs)))
            for sb_i in order:
                sb = sbs[int(sb_i)]
                perm = perms.get(sb.start)
                for local in range(sb.n_batches):
                    batch_index = sb.start + local
                    self._covering(batch_index)   # hit accounting
                    self._m_hits.inc()
                    yield self._sb_batch(sb, batch_index, perm)
            return
        # Partial epoch: merge HBM-resident runs with the streamed
        # remainder by batch index — the epoch stays complete (and, with
        # shuffle off, bit-identical to the streamed path) even when the
        # governor evicts mid-epoch. The source pass still PRODUCES the
        # resident indices; their streamed copies are dropped (a
        # skip-ahead source is future work — the chunk-store hot tier
        # makes the redundant pass cheap).
        if self._loader_factory is None:
            raise RuntimeError(
                'partial device cache needs loader_factory= to stream the '
                'uncached remainder (cached {}/{} batches)'.format(
                    sum(sb.n_batches for sb in self._superbatches),
                    self._total_batches))
        source = iter(self._loader_factory())
        for batch_index in range(self._total_batches):
            streamed = next(source, None)
            sb = self._covering(batch_index)
            if sb is not None:
                self._m_hits.inc()
                yield self._sb_batch(sb, batch_index,
                                     perms.get(sb.start))
            elif streamed is not None:
                yield streamed
            else:
                raise RuntimeError(
                    'loader_factory stream ended at batch {} of {} — the '
                    'remainder source must replay the full deterministic '
                    'pass'.format(batch_index, self._total_batches))
        close = getattr(source, 'close', None)
        if close is not None:
            close()

    # -- teardown ----------------------------------------------------------

    def _drop_all(self):
        with self._lock:
            freed = self._bytes
            self._superbatches = []
            self._bytes = 0
        if freed:
            self._m_bytes.inc(-freed)

    def clear(self):
        """Drop the cached device arrays (frees HBM) and unregister the
        governor pool. The cache is finished afterwards — ``epoch()``
        raises; build a new cache to train on."""
        self._drop_all()
        self._take = {}
        self._materialized = False
        self._cleared = True
        if self._mem_handle is not None:
            self._mem_handle.close()
            self._mem_handle = None


def _per_device_nbytes(batch):
    """Bytes one device holds for this batch.

    ``jax.Array.nbytes`` is the GLOBAL logical size, and dividing it by
    ``len(sharding.device_set)`` counts replicas as shards (a batch sharded
    over 'data' but replicated over 'model' would undercount 2x). The
    addressable-shard buffer size is the ground truth per device.
    """
    total = 0
    for name in batch._fields:
        arr = getattr(batch, name)
        try:
            total += arr.addressable_shards[0].data.nbytes
        except (AttributeError, IndexError):
            total += arr.nbytes
    return total


def _default_budget(jax):
    """40% of the first device's HBM; 0 (no limit) on a backend that
    reports no memory stats, which the CPU backend does not. A TPU that
    reports none is an error: an unbounded cache there ends in an HBM
    OOM in the middle of training."""
    device = jax.devices()[0]
    limit = (device.memory_stats() or {}).get('bytes_limit')
    if not limit:
        if device.platform == 'tpu':
            raise RuntimeError(
                'TPU device {} reports no memory_stats()["bytes_limit"]; '
                'pass max_bytes= to DeviceDatasetCache'.format(device))
        return 0
    return int(limit * _DEFAULT_HBM_FRACTION)
