"""Pipelined staging engine: recycled host-batch arenas + overlapped
assemble/dispatch.

A cache-warm input pipeline is collate/memcpy-bound, and staging done in
one loop overlaps nothing (``h2d_overlap_frac`` 0.0, ``stage_dispatch_s`` +
``consumer_wait_s`` making up the pipeline wall). This module is the fix,
in the tf.data (arXiv:2101.12127) / MinatoLoader
(arXiv:2509.10712) shape: software pipelining between batch assembly and
device dispatch, plus buffer reuse so the collate path stops allocating a
fresh host batch every step.

Three pieces, each independently testable without jax:

``ArenaPool`` / ``HostArena``
    A bounded pool of preallocated per-field host buffers sized to one
    batch. The batch assembler fills arena slices in place
    (``np.copyto``/``out=``) instead of ``np.stack``/``np.concatenate``
    allocating every batch; the pool recycles an arena only once the
    dispatch stage reports its transfer done AND every consumer-visible
    view of it has been dropped (``add_hold`` — on backends where
    ``device_put`` is zero-copy the staged array aliases the arena, so
    "transfer done" alone is not permission to overwrite). Exhaustion
    applies backpressure (bounded, stop-aware wait); a wait that outlives
    ``grow_timeout_s`` allocates past ``depth`` rather than deadlocking a
    consumer that legitimately holds many batches (e.g.
    ``superbatches(k)``). Growth is sticky — ``depth`` rises to the
    high-water mark, so the timeout is paid once per working-set
    increase, not per cycle — and every allocation is visible in
    ``arena_alloc``.

``OverlapMeter``
    Wall-clock co-activity of named pipeline stages. ``overlap_s`` is the
    time during which two or more stages were simultaneously inside their
    tracked section — the direct measurement of "collate of batch N+1
    overlaps the transfer of batch N".

``StagingEngine``
    Two threads replacing the single serial stage loop: an **assemble**
    thread that drives the host-batch iterator (filling arenas), and a
    **dispatch** thread that issues the device puts and keeps a bounded
    window of in-flight transfers, blocking on the oldest when the window
    fills. Delivery order is preserved; stop/fault semantics follow PR 1
    (stop-aware puts everywhere, no thread outlives ``stop()``, in-flight
    arenas are reclaimed on shutdown).
"""

import logging
import queue
import threading
import time
import weakref
from collections import deque
from contextlib import contextmanager, nullcontext

import numpy as np

from petastorm_tpu import trace

logger = logging.getLogger(__name__)

_DONE = object()        # assemble exhausted its iterator

#: Thread-name prefix of the per-device dispatch streams
#: (:class:`DeviceStager`); registered in
#: ``petastorm_tpu.analysis.registry`` so the conftest leak guard and the
#: pstlint thread-lifecycle checker both know who joins them.
DEVICE_PUT_THREAD_PREFIX = 'pst-device-put'

#: Per-field offset alignment inside a pinned arena slab. Page alignment
#: keeps every field's buffer on its own page boundary — the transfer
#: granularity DMA engines and ``mlock`` both work in.
PINNED_FIELD_ALIGN = 4096


def _pinned_slab_layout(spec):
    """``({name: (offset, size)}, total)`` for one arena slab: every field
    starts on a :data:`PINNED_FIELD_ALIGN` boundary."""
    offsets, total = {}, 0
    for name, (shape, dtype) in spec.items():
        size = int(np.prod(shape)) * np.dtype(dtype).itemsize
        offsets[name] = (total, size)
        padded = -(-max(size, 1) // PINNED_FIELD_ALIGN) * PINNED_FIELD_ALIGN
        total += padded
    return offsets, total


_alias_probe_memo = {}


def staging_aliases_host(jax):
    """True when ``jax.device_put`` on this backend may return an array
    aliasing the source host buffer (observed on the CPU backend for large
    aligned arrays) — recycling a staged-from arena would then corrupt
    batches the consumer still holds. Probed once per process per backend
    with a buffer large enough to take the zero-copy path; the transfer is
    fenced before the source is mutated so a copying backend whose DMA is
    still in flight can't be misread as aliasing. A probe that cannot run
    raises: guessing "aliases" would put a TPU loader in the GC-gated
    recycling mode and hide that its device is not working.
    """
    backend = jax.default_backend()
    if backend not in _alias_probe_memo:
        src = np.zeros(1 << 20, np.uint8)
        staged = jax.device_put(src)
        jax.block_until_ready(staged)
        src[0] = 1
        _alias_probe_memo[backend] = int(np.asarray(staged)[0]) == 1
    return _alias_probe_memo[backend]


def willneed_arrays(arrays, _mmap=None):
    """madvise(WILLNEED) the mmaps backing any mmap-based arrays.

    The NVMe chunk store (``petastorm_tpu.chunk_store``) serves decoded
    chunks as numpy views over a read-only mmap; the arena fill then
    copies mmap -> arena (``np.copyto``), and on a cold page cache every
    copied cache line is a blocking major fault inside the assemble
    thread. Hinting the whole backing mapping when the chunk *arrives*
    (one syscall per chunk) lets the kernel read the extents ahead while
    earlier batches collate. Non-mmap arrays walk a short ``.base`` chain
    and fall out — the call is safe (and near-free) on every chunk.
    Returns the number of distinct mappings hinted."""
    import mmap as mmap_mod
    if _mmap is None:
        _mmap = mmap_mod
    if not hasattr(_mmap.mmap, 'madvise'):  # pragma: no cover - py<3.8/win
        return 0
    hinted, seen = 0, set()
    for arr in arrays:
        base = arr
        while isinstance(base, np.ndarray) and base.base is not None:
            base = base.base
        if isinstance(base, memoryview):
            base = base.obj
        if isinstance(base, _mmap.mmap) and id(base) not in seen:
            seen.add(id(base))
            try:
                base.madvise(_mmap.MADV_WILLNEED)
                hinted += 1
            except (OSError, ValueError):  # pragma: no cover - advisory only
                continue
    return hinted


class HostArena(object):
    """One batch's worth of recyclable per-field host buffers.

    ``view_epoch`` is the arena's recycle generation: bumped every time
    the buffers return to the pool's free list, i.e. every time their
    bytes stop belonging to the batch a consumer may still be looking at.
    With the sanitizer armed (``PETASTORM_TPU_SANITIZE``,
    :mod:`petastorm_tpu.analysis.sanitize`) reclaim additionally poisons
    the buffers (0xCB fill) and views handed out via :meth:`borrow` carry
    the epoch as a borrow tag — touching one after reclaim raises
    ``StaleViewError`` at the stale access instead of silently reading a
    different batch's bytes."""

    def __init__(self, pool, spec, slab=None):
        # spec: {name: (shape, dtype)}; shape includes the batch dim.
        # With a pinned slab the buffers are page-aligned (optionally
        # mlocked) carve-outs of one DMA-friendly allocation; without one
        # they are plain np.empty — bit-for-bit the same to every consumer.
        if slab is not None:
            offsets, _ = _pinned_slab_layout(spec)
            self.buffers = {}
            for name, (shape, dtype) in spec.items():
                off, size = offsets[name]
                self.buffers[name] = (slab.array[off:off + size]
                                      .view(dtype).reshape(shape))
        else:
            self.buffers = {name: np.empty(shape, dtype)
                            for name, (shape, dtype) in spec.items()}
        self._slab = slab   # keeps the mapping alive while buffers exist
        self.pinned = slab is not None
        self._pool = pool
        self._lock = threading.Lock()
        self._holds = 0
        self._retired = False
        self._reclaimed = False
        self.view_epoch = 0
        # Device-sharded layout memo: per-device contiguous sub-slices of
        # each buffer, built once per arena and reused on every recycle
        # (the buffers persist, so the views stay valid) — zero re-layout
        # work at dispatch time. Keyed by (field, bounds) because a per-
        # field sharding dict may split fields across different device
        # counts.
        self._shard_views = {}

    def shard_views(self, name, bounds=None):
        """Per-device contiguous sub-slices of buffer ``name`` along the
        batch dim. ``bounds`` is a tuple of ``(start, stop)`` row ranges
        (default: the layout the pool learned via
        :meth:`ArenaPool.learn_shard_layout` — the dispatch path's form);
        the views are memoized on the arena, so after the first batch a
        dispatch pays zero slicing or layout work — the collate path
        already landed each device's rows contiguously in the recycled
        buffer."""
        if bounds is None:
            cached = self._shard_views.get((name, None))
            if cached is not None:
                return cached
            layout = self._pool.shard_layout if self._pool else None
            bounds = (layout or {}).get(name)
            if bounds is None:
                raise KeyError(
                    'no shard layout learned for field {!r}'.format(name))
            views = self.shard_views(name, bounds)
            self._shard_views[(name, None)] = views
            return views
        key = (name, tuple(bounds))
        views = self._shard_views.get(key)
        if views is None:
            buf = self.buffers[name]
            views = tuple(buf[start:stop] for start, stop in key[1])
            self._shard_views[key] = views
        return views

    def borrow(self, array):
        """Borrow-tag ``array`` (one of this arena's buffers or a view of
        one) against the current epoch. No-op passthrough unless the
        sanitizer is armed."""
        from petastorm_tpu.analysis import sanitize
        return sanitize.guard_view(array, self)

    def borrowed_buffers(self):
        """The buffer dict as handed to the batch assembler: borrow-tagged
        views when the sanitizer is armed, the raw buffers otherwise."""
        from petastorm_tpu.analysis import sanitize
        if not sanitize.sanitize_active():
            return self.buffers
        return {name: sanitize.guard_view(buf, self)
                for name, buf in self.buffers.items()}

    def _on_reclaim(self):
        """The buffers are about to rejoin the free list: any view still
        out there is now stale. Bump the borrow epoch (always — one int)
        and poison the bytes (sanitizer only)."""
        self.view_epoch += 1
        from petastorm_tpu.analysis import sanitize
        sanitize.poison(self.buffers.values())

    @property
    def nbytes(self):
        return sum(b.nbytes for b in self.buffers.values())

    def add_hold(self, obj):
        """Keep this arena out of the free list until ``obj`` is garbage
        collected (used when staged arrays alias the arena's memory)."""
        with self._lock:
            self._holds += 1
        weakref.finalize(obj, self._drop_hold)

    def _drop_hold(self):
        with self._lock:
            self._holds -= 1
            ready = (self._retired and self._holds == 0
                     and not self._reclaimed)
            if ready:
                self._retired = False
                self._reclaimed = True
        if ready:
            self._pool._reclaim(self)

    def retire(self):
        """Transfer done: return to the pool once no holds remain.
        Idempotent — stop-path drains can race the normal retire."""
        with self._lock:
            if self._reclaimed:
                return
            if self._holds:
                self._retired = True
                return
            self._reclaimed = True
        self._pool._reclaim(self)


class ArenaPool(object):
    """Bounded pool of :class:`HostArena` with backpressure and counters.

    The assembler calls :meth:`get_buffers` (blocking, stop-aware) and the
    engine pairs the yielded batch with :meth:`claim_pending`. Batches
    whose shapes differ from the pool's spec (e.g. a ``partial`` final
    batch) bypass the pool (``get_buffers`` returns ``None``).
    """

    def __init__(self, depth, stop_event=None, grow_timeout_s=0.5,
                 tracer=None, meter=None, meter_stage='assemble',
                 heartbeat=None, pinned=None):
        if depth < 1:
            raise ValueError('ArenaPool depth must be >= 1, got {}'.format(depth))
        self._depth = depth
        # Pinned (DMA-friendly) allocation mode: new arenas carve their
        # buffers out of page-aligned, pre-faulted, best-effort-mlocked
        # slabs (petastorm_tpu.native.pinned). None resolves the
        # PETASTORM_TPU_PINNED_ARENAS env ('1' arms it); allocation
        # failure falls back to np.empty per arena, so the mode can never
        # wedge a pipeline. set_pinned() retargets live (autotune toggle;
        # the governor's advisory rung unpins growth — mlocked pages are
        # exactly the ones the kernel cannot reclaim under pressure).
        if pinned is None:
            import os
            pinned = os.environ.get('PETASTORM_TPU_PINNED_ARENAS', '') == '1'
        self._pinned = bool(pinned)
        self._pinned_bytes = 0
        self._pinned_locked = 0
        self._pinned_mode = None
        self._pinned_fallback_logged = False
        self._stop = stop_event if stop_event is not None else threading.Event()
        self._grow_timeout_s = grow_timeout_s
        # Health hookup: while the assembler is parked waiting for an arena
        # its heartbeat reads 'arena-wait' and goes stale — the watchdog
        # then classifies the stall as arena-pool-wedged rather than
        # blaming collate work.
        self._heartbeat = heartbeat
        # Backpressure waits happen inside the assembler's tracked section;
        # pausing the meter keeps them out of busy/overlap accounting (an
        # arena-starved pipeline must not read as perfectly overlapped —
        # arena_wait_s reports the stall instead).
        self._meter = meter
        self._meter_stage = meter_stage
        self._tracer = trace.resolve(tracer)
        self._cond = threading.Condition()
        self._free = []
        self._spec = None
        self._allocated = 0
        self._pending = None
        # Device-sharded layout ({field: ((start, stop), ...)} row bounds),
        # learned once per schema from the NamedSharding by the loader;
        # arenas consult it to memoize per-device sub-slice views.
        self._shard_layout = None
        # counters (reset_stats() zeroes these, never the pool itself)
        self._alloc = 0
        self._reuse = 0
        # Fed by the ``collate.arena_wait`` span, as is the registry mirror
        # (petastorm_tpu.metrics): per-acquisition wait latency — the
        # machine-scrapable arena-backpressure signal.
        self._totals = {'arena_wait_s': 0.0}
        from petastorm_tpu import metrics as metrics_mod
        self._m_wait = metrics_mod.histogram(
            'pst_arena_wait_seconds',
            'Assembler blocked time per arena acquisition (backpressure)')
        self._m_pinned = metrics_mod.gauge(
            'pst_arena_pinned_bytes',
            'Host bytes in live pinned (page-aligned/mlocked) arena slabs '
            'across all pools (inc/dec per slab lifetime)')

    def _matches(self, spec):
        if self._spec is None:
            self._spec = dict(spec)
            return True
        return spec == self._spec

    def get_buffers(self, spec):
        """Buffers for one batch of ``spec`` ({name: (shape, dtype)}), or
        ``None`` when the spec mismatches the pool or the pool is stopping.
        Blocks (stop-aware) while every arena is out; waits longer than
        ``grow_timeout_s`` allocate past ``depth`` instead of deadlocking.
        """
        with self._cond:
            if not self._matches(spec) or self._stop.is_set():
                return None
            arena = self._acquire(0.0)
            if arena is None:
                arena = self._wait_for_arena()
            if arena is None:       # stopping
                return None
            self._pending = arena
            self._tracer.counter('arena_pool_free', len(self._free), 'collate')
            return arena.borrowed_buffers()

    def _acquire(self, waited):
        """(condition held) A free arena, or a new one while the pool may
        still grow or the wait has outlived the grow deadline; else None."""
        if self._free:
            arena = self._free.pop()
            arena._reclaimed = False
            self._reuse += 1
            return arena
        if self._allocated < self._depth or waited >= self._grow_timeout_s:
            arena = self._new_arena()
            self._allocated += 1
            self._alloc += 1
            # Growth is STICKY: depth tracks the high-water mark so a
            # consumer that legitimately pins more than the initial depth
            # (superbatches(k)) pays the grow timeout once, not once per
            # extra arena on every cycle.
            if self._allocated > self._depth:
                self._depth = self._allocated
            return arena
        return None

    def _wait_for_arena(self):
        """(condition held) Block until an arena can be had, or return None
        when the pool stops: one ``collate.arena_wait`` span however many
        times the condition woke, which is also what ``arena_wait_s`` and
        ``pst_arena_wait_seconds`` are fed by."""
        if self._heartbeat is not None:
            # One beat on entry, then let the age accrue: a wedged pool
            # must read as a stale 'arena-wait' heartbeat.
            self._heartbeat.beat('arena-wait')
        arena = None
        with self._tracer.span('collate.arena_wait', 'collate',
                               hist=self._m_wait,
                               total=(self._totals, 'arena_wait_s')) as span, \
                (self._meter.pause(self._meter_stage)
                 if self._meter is not None else nullcontext()):
            while arena is None and not self._stop.is_set():
                # Real wakeups: release and GC-settle notify the condition
                # (see _reclaim) and stop() paths call wake(), so acquire
                # latency is not quantized to a poll interval. The timeout
                # is the grow deadline, capped only so an EXTERNAL
                # stop_event set without wake() is still observed promptly
                # (that cap bounds stop latency, not acquire latency).
                waited = (time.perf_counter_ns() - span.start_ns) / 1e9
                arena = self._acquire(waited)
                if arena is None:
                    self._cond.wait(timeout=min(
                        max(self._grow_timeout_s - waited, 0.005), 0.25))
        if self._heartbeat is not None:
            self._heartbeat.beat('collate')
        return arena

    def _new_arena(self):
        """One arena in the pool's current allocation mode (called with
        the pool condition held). Pinned mode carves the buffers out of a
        DMA-friendly slab; any slab failure (no native tier, mmap limit,
        RLIMIT) falls back to a plain arena — logged once, never raised."""
        slab = None
        if self._pinned:
            try:
                from petastorm_tpu.native import pinned as pinned_mod
                _, total = _pinned_slab_layout(self._spec)
                slab = pinned_mod.allocate(total, lock=True)
            except Exception:  # noqa: BLE001 - pinned mode is best-effort
                slab = None
            if slab is None and not self._pinned_fallback_logged:
                self._pinned_fallback_logged = True
                logger.warning('pinned arena allocation unavailable; '
                               'falling back to unpinned host buffers')
        arena = HostArena(self, self._spec, slab=slab)
        if slab is not None:
            self._pinned_bytes += slab.nbytes
            self._pinned_mode = slab.mode
            if slab.locked:
                self._pinned_locked += 1
            self._m_pinned.inc(slab.nbytes)
            # The condition's lock is an RLock, so the finalizer (run at
            # GC time on an arbitrary thread, possibly mid-critical-
            # section) can re-enter safely — same contract _drop_hold
            # already relies on.
            weakref.finalize(arena, self._drop_pinned,
                             slab.nbytes, slab.locked)
        return arena

    def _drop_pinned(self, nbytes, locked):
        with self._cond:
            self._pinned_bytes -= nbytes
            if locked:
                self._pinned_locked -= 1
        self._m_pinned.inc(-nbytes)

    def set_pinned(self, enabled):
        """Toggle pinned allocation for arenas allocated from now on
        (autotune pinned-arena knob; the loader's governor advisory also
        drops it). Existing arenas keep their slabs — they drain as the
        working set cycles through ``set_depth``-style replacement."""
        with self._cond:
            self._pinned = bool(enabled)

    @property
    def pinned(self):
        with self._cond:
            return self._pinned

    @property
    def pinned_nbytes(self):
        """Bytes in live pinned slabs (page-padded actual mapping sizes;
        the membudget ``arena-pool`` pool already counts these buffers —
        this is the mlock-exposure view, not extra memory)."""
        with self._cond:
            return self._pinned_bytes

    def claim_pending(self):
        """The arena handed out by the latest ``get_buffers`` call (or
        ``None``): called by the engine right after the host iterator
        yields, pairing the batch with its backing arena."""
        with self._cond:
            arena, self._pending = self._pending, None
            return arena

    def _reclaim(self, arena):
        arena._on_reclaim()
        with self._cond:
            if len(self._free) < self._depth:
                self._free.append(arena)
            else:
                self._allocated -= 1   # grown-past-depth arena: let it die
            self._cond.notify_all()
            self._tracer.counter('arena_pool_free', len(self._free), 'collate')

    def reclaim_pending(self):
        """Shutdown path: an arena handed out but never claimed (the
        assembler died between fill and yield) must not leak."""
        arena = self.claim_pending()
        if arena is not None:
            arena.retire()

    def learn_shard_layout(self, field_bounds):
        """Teach the pool the device-sharded layout of its batches:
        ``{field: ((start, stop), ...)}`` per-device row bounds along the
        batch dim, computed ONCE per schema from the ``NamedSharding``
        (see ``parallel.mesh.device_shard_plan``). Arenas then hand the
        dispatch stage memoized contiguous sub-slice views
        (:meth:`HostArena.shard_views`) — the collate path needs no
        change because a batch-dim shard of a C-contiguous buffer IS a
        contiguous sub-slice of it. Incremental: fields merge into the
        layout as their shardings are first seen."""
        with self._cond:
            if self._shard_layout is None:
                self._shard_layout = {}
            for name, bounds in field_bounds.items():
                self._shard_layout[name] = tuple(
                    (int(start), int(stop)) for start, stop in bounds)

    @property
    def shard_layout(self):
        with self._cond:
            return dict(self._shard_layout) if self._shard_layout else None

    def wake(self):
        """Wake any waiter so it can observe the stop flag promptly (the
        condition is otherwise only notified on arena release)."""
        with self._cond:
            self._cond.notify_all()

    def set_depth(self, depth):
        """Retarget the pool depth at runtime (autotune hookup). Growing
        wakes a backpressured assembler to allocate immediately; shrinking
        lets excess arenas die on their next reclaim (``_reclaim`` drops
        frees beyond ``depth``) — memory drains as the working set cycles,
        with no arena yanked from under an in-flight transfer."""
        depth = max(1, int(depth))
        with self._cond:
            if depth == self._depth:
                return
            self._depth = depth
            while len(self._free) > depth:
                self._free.pop()
                self._allocated -= 1
            self._cond.notify_all()

    @property
    def depth(self):
        """Current pool depth (autotune knob getter — cheaper than a full
        :meth:`stats` sample on a sub-second tick)."""
        with self._cond:
            return self._depth

    @property
    def nbytes(self):
        """Bytes pinned by every allocated arena (free, filled, and
        in-flight alike: an arena waiting recycle is just as resident) —
        the memory governor's ``arena-pool`` accounting hook. This also
        covers the staging engine's in-flight window: staged batches are
        arena-backed, so window bytes ARE allocated-arena bytes."""
        with self._cond:
            if self._spec is None:
                return 0
            per_arena = sum(
                int(np.prod(shape)) * np.dtype(dtype).itemsize
                for shape, dtype in self._spec.values())
            return self._allocated * per_arena

    @property
    def wait_seconds(self):
        """Cumulative assembler backpressure seconds (the autotuner's
        arena-bound signal)."""
        return self._totals['arena_wait_s']

    def stats(self):
        with self._cond:
            return {'arena_alloc': self._alloc,
                    'arena_reuse': self._reuse,
                    'arena_wait_s': round(self._totals['arena_wait_s'], 4),
                    'arena_depth': self._depth,
                    'arena_allocated': self._allocated,
                    'arena_pinned': self._pinned,
                    'arena_pinned_bytes': self._pinned_bytes,
                    'arena_pinned_locked': self._pinned_locked,
                    'arena_pinned_mode': self._pinned_mode or 'off',
                    # Context for watchdog diagnoses: a wait can only
                    # outlive this before growth relieves it, so a pool
                    # that CAN grow shows wedges as climbing arena_alloc
                    # (memory), not as long arena-waits.
                    'arena_grow_timeout_s': self._grow_timeout_s}

    def reset_stats(self):
        with self._cond:
            self._alloc = 0
            self._reuse = 0
        trace.reset_totals(self._totals)


class OverlapMeter(object):
    """Wall-clock co-activity of named stages (assemble vs dispatch).

    ``reset()`` starts a new measurement window (a benchmark resets after
    warmup) but lifetime totals survive it — on zero-copy backends the
    cache-warm steady state has nearly nothing left to overlap (both
    stages are view handoffs), so the decode-bound phase where dispatch
    genuinely hides under assembly is only visible in the totals.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._active = 0
        self._mark = None
        self._busy = {}
        self._overlap_s = 0.0
        self._base_busy = {}
        self._base_overlap = 0.0
        # Spans currently open ({token: (name, t0)}): stats() credits
        # their elapsed time live. With fence pipelining the stager's
        # 'h2d' span is open whenever any stream window holds a transfer
        # — i.e. ~always in steady state — so exit-only accounting would
        # chronically report busy_s['h2d'] = 0 and overlap_frac = 0.0 at
        # every mid-stream stats read.
        self._live = {}

    def _transition(self, delta):
        now = time.perf_counter()
        if self._active >= 2 and self._mark is not None:
            self._overlap_s += now - self._mark
        self._active += delta
        self._mark = now
        return now

    def _busy_snapshot(self, now):
        busy = dict(self._busy)
        for name, t0 in self._live.values():
            busy[name] = busy.get(name, 0.0) + (now - t0)
        return busy

    def _overlap_snapshot(self, now):
        overlap = self._overlap_s
        if self._active >= 2 and self._mark is not None:
            overlap += now - self._mark
        return overlap

    @contextmanager
    def track(self, name):
        token = object()
        with self._lock:
            t0 = self._transition(+1)
            self._live[token] = (name, t0)
        try:
            yield
        finally:
            with self._lock:
                t1 = self._transition(-1)
                self._live.pop(token, None)
                self._busy[name] = self._busy.get(name, 0.0) + (t1 - t0)

    @contextmanager
    def pause(self, name):
        """Suspend a stage from inside its ``track`` section — used while
        the assembler is merely *blocked* (reader starvation) so idle wait
        doesn't masquerade as busy/overlapping collate time. The paused
        span is subtracted from the stage's busy seconds and stops overlap
        accrual for its duration."""
        with self._lock:
            t0 = self._transition(-1)
        try:
            yield
        finally:
            with self._lock:
                t1 = self._transition(+1)
                self._busy[name] = self._busy.get(name, 0.0) - (t1 - t0)

    @staticmethod
    def _frac(busy, overlap):
        floor = min(busy.values()) if len(busy) >= 2 else 0.0
        return min(1.0, overlap / floor) if floor > 1e-9 else 0.0

    def stats(self, total=False):
        with self._lock:
            now = time.perf_counter()
            busy = self._busy_snapshot(now)
            overlap = self._overlap_snapshot(now)
            if not total:
                busy = {k: v - self._base_busy.get(k, 0.0)
                        for k, v in busy.items()}
                overlap -= self._base_overlap
        return {'busy_s': {k: round(v, 4) for k, v in busy.items()},
                'overlap_s': round(overlap, 4),
                'overlap_frac': round(self._frac(busy, overlap), 4)}

    def reset(self):
        """Start a new window; lifetime totals (``stats(total=True)``)
        keep accumulating. Spans open across the reset contribute only
        their post-reset elapsed time to the new window (their
        elapsed-so-far is folded into the base)."""
        with self._lock:
            now = time.perf_counter()
            self._base_busy = self._busy_snapshot(now)
            self._base_overlap = self._overlap_snapshot(now)


class MeteredReader(object):
    """Iteration proxy reporting time blocked in the underlying reader as
    *paused* assemble time (``OverlapMeter.pause``): the assemble stage's
    busy/overlap accounting then covers collate work only, not reader
    starvation — an input-bound run must not read as perfectly overlapped
    pipelining. Every non-iteration attribute passes through."""

    def __init__(self, reader, meter, stage='assemble', heartbeat=None,
                 tracer=None):
        self._pst_reader = reader
        self._pst_meter = meter
        self._pst_stage = stage
        self._pst_hb = heartbeat
        self._pst_tracer = trace.resolve(tracer)
        # Cumulative seconds the assembler spent blocked in the reader —
        # the autotuner's reader-starved signal, fed by the
        # ``collate.reader_wait`` span (assemble thread only).
        self._pst_totals = {'reader_wait_s': 0.0}

    @property
    def reader_wait_s(self):
        return self._pst_totals['reader_wait_s']

    @reader_wait_s.setter
    def reader_wait_s(self, value):
        self._pst_totals['reader_wait_s'] = value

    def __iter__(self):
        return self

    def __next__(self):
        hb = self._pst_hb
        if hb is not None:
            # State labels bracket the reader pull so a stale heartbeat
            # tells the watchdog *what* starved: 'reader-wait' = the
            # decode/IO tier produced nothing (reader-starved); 'collate'
            # = the batch-assembly work itself wedged (assemble-stuck).
            hb.beat('reader-wait')
        try:
            with self._pst_tracer.span(
                    'collate.reader_wait', 'collate',
                    total=(self._pst_totals, 'reader_wait_s')), \
                    self._pst_meter.pause(self._pst_stage):
                return next(self._pst_reader)
        finally:
            if hb is not None:
                hb.beat('collate')

    def __getattr__(self, name):
        return getattr(self._pst_reader, name)


class DeviceStagerStopped(RuntimeError):
    """A shard wave was aborted because the stager (or its pipeline) is
    stopping — the batch never reached the device and must not be
    delivered."""


class DeviceStager(object):
    """One overlapped ``device_put`` stream per addressable device.

    Issuing a DMA-scale transfer on the dispatch thread blocks it for
    the whole transfer. This runs one dispatch stream (a
    ``pst-device-put-<k>`` thread) per device instead: the owner submits
    a field's whole per-device wave as one item to one of them, each
    stream keeps its own bounded in-flight window (blocking on its
    *oldest* transfer when full), and waves of concurrent fields issue
    from different threads. The item's put accounts itself
    (:meth:`record_inline_wave`); the stream keeps the window's books.

    jax-free by construction (``put_fn`` injected), so the stream
    discipline — ordering, windows, stop semantics — is unit-testable
    without a backend.

    :param stream_keys: one label per stream (device ids); sets the
        stream count and the ``device`` label on
        ``pst_device_put_seconds``.
    :param put_fn: ``item -> staged array``; called on the submitting
        stream's own thread, must be thread-safe across streams.
    :param inflight: per-stream in-flight transfer window (the autotune
        ``device_inflight`` knob; :meth:`set_inflight` retargets live).
    :param ready_fn: ``staged -> None`` blocking until the transfer
        completed; used for window backpressure only.
    :param stop_event: shared stop flag; no stream outlives it.
    """

    def __init__(self, stream_keys, put_fn, inflight=2, ready_fn=None,
                 stop_event=None, tracer=None, meter=None):
        self._keys = tuple(str(k) for k in stream_keys)
        if not self._keys:
            raise ValueError('DeviceStager needs at least one stream')
        self._put_fn = put_fn
        self._ready_fn = ready_fn or (lambda staged: None)
        self._inflight = max(1, int(inflight))
        # Streamed-path overlap measurement: the owner tracks its
        # host-side staging work as 'host' on this meter; the stager
        # keeps ONE refcounted 'h2d' span open while ANY stream holds an
        # unfenced transfer (all streams collapse into one logical h2d
        # lane — per-stream spans would measure stream-vs-stream
        # co-activity, not transfer-vs-host overlap). stats() then
        # reports h2d_overlap_frac for the streamed path.
        self.meter = meter
        self._h2d_tokens = 0
        self._h2d_span = None
        self._stop = stop_event if stop_event is not None else threading.Event()
        self._tracer = trace.resolve(tracer)
        from petastorm_tpu import metrics as metrics_mod
        self._m_put = metrics_mod.histogram(
            'pst_device_put_seconds',
            'Per-device shard device_put latency (issue time; window '
            'fences are reported separately)', labelnames=('device',))
        self._m_donated = metrics_mod.counter(
            'pst_shards_donated_total',
            'Arena-backed shards handed to the device transfer with no '
            'loader-side host copy')
        self._stats_lock = threading.Lock()
        self._put_s = {k: 0.0 for k in self._keys}
        self._put_bytes = {k: 0 for k in self._keys}
        self._shards_put = 0
        self._donated = 0
        self._totals = {'ready_wait_s': 0.0}    # the streams' fence spans
        self._window_bytes = 0
        self._leaked_threads = []
        # Bounded (pstlint bounded-queues): one submission wave queues at
        # most fields-per-batch items per stream before the submitter
        # blocks on the wave's completion, so 128 is generous headroom —
        # the bound exists so a bug can't grow an unbounded backlog.
        self._queues = [queue.Queue(maxsize=128) for _ in self._keys]
        self._start_lock = threading.Lock()
        self._started = False
        self._threads = [
            threading.Thread(target=self._stream_loop, args=(i,),
                             daemon=True,
                             name='pst-device-put-{}'.format(key))
            for i, key in enumerate(self._keys)]

    def start(self):
        """Start the stream threads. Idempotent, and called lazily from
        the first :meth:`put_shards` wave — an owner whose constructor
        fails after building the stager must not leak 8 parked threads
        with no reachable stop path (the inline tier never starts them
        at all)."""
        with self._start_lock:
            if not self._started:
                self._started = True
                for t in self._threads:
                    t.start()
        return self

    @property
    def n_streams(self):
        return len(self._keys)

    # -- submission --------------------------------------------------------

    def put_shards(self, items):
        """Dispatch one batch's waves: ``items`` is a list of
        ``(stream_index, item)``, an item being whatever ``put_fn`` takes
        with its host bytes as ``nbytes``; returns the staged arrays in
        the same order once every put has been *issued* (transfers
        complete in the background against the per-stream windows).
        Raises :class:`DeviceStagerStopped` when the stager is stopping
        mid-wave; re-raises the first ``put_fn`` failure otherwise."""
        if not self._started:
            self.start()
        results = [None] * len(items)
        state = {'remaining': len(items), 'error': None}
        done = threading.Event()
        lock = threading.Lock()
        for slot, (stream, array) in enumerate(items):
            self._enqueue(stream, (array, slot, results, state, lock, done))
        while not done.is_set():
            if self._stop.is_set():
                raise DeviceStagerStopped(
                    'device stager stopping mid-wave ({} item(s) '
                    'outstanding)'.format(state['remaining']))
            done.wait(0.1)
        if state['error'] is not None:
            raise state['error']
        return results

    def _enqueue(self, stream, item):
        q = self._queues[stream]
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue
        raise DeviceStagerStopped('device stager stopping')

    # -- per-stream loop ---------------------------------------------------

    def _stream_loop(self, index):
        window = deque()    # (staged, nbytes) — owned by this thread only
        q = self._queues[index]
        try:
            while True:
                try:
                    item = q.get(timeout=0.1)
                except queue.Empty:
                    if self._stop.is_set():
                        return
                    # Idle streams opportunistically drain their window so
                    # arenas retire without waiting for the next wave.
                    while window and not self._stop.is_set():
                        if not self._retire_oldest(window, block=False):
                            break
                    continue
                array, slot, results, state, lock, done = item
                try:
                    # Fence pipelining: make room at SUBMIT time, not
                    # after delivery. The window only gives up its oldest
                    # transfer when a new one is about to take the slot,
                    # so between waves every slot stays occupied by an
                    # in-flight transfer — the h2d stream never drains —
                    # and the fence is frequently free because the oldest
                    # transfer completed while the stream sat waiting for
                    # this wave.
                    while len(window) >= self._inflight:
                        self._retire_oldest(window, block=True)
                    # The item accounts itself (put_fn calls
                    # record_inline_wave with the wave's true per-device
                    # breakdown); the stream keeps the window's books.
                    staged = self._put_fn(array)
                    nbytes = int(getattr(array, 'nbytes', 0))
                    with self._stats_lock:
                        self._window_bytes += nbytes
                    window.append((staged, nbytes))
                    self._h2d_enter()
                    # Deliver immediately: the caller stitches (and the
                    # assemble thread collates the next batch) while the
                    # transfers ride the window.
                    with lock:
                        results[slot] = staged
                        state['remaining'] -= 1
                        if state['remaining'] <= 0:
                            done.set()
                except Exception as e:  # noqa: BLE001 - surfaced to the wave
                    with lock:
                        state['error'] = e
                        done.set()
        finally:
            # Stop path: drop the window's byte accounting (the staged
            # arrays keep their own memory alive; nothing to fence on a
            # pipeline that is going away).
            while window:
                self._retire_oldest(window, block=False)

    def _retire_oldest(self, window, block):
        """Retire the stream's oldest in-flight transfer. ``block=True``
        fences it (window backpressure); ``block=False`` only retires an
        already-complete transfer. Returns whether an entry retired."""
        staged, nbytes = window.popleft()
        if block and not self._stop.is_set():
            try:
                with self._tracer.span('dispatch.fence', 'dispatch',
                                       cause='device-stream',
                                       total=(self._totals, 'ready_wait_s')):
                    self._ready_fn(staged)
            except Exception:  # noqa: BLE001 - a dying fence must not kill the stream
                logger.debug('device stager ready_fn failed', exc_info=True)
            with self._stats_lock:
                self._window_bytes -= nbytes
            self._h2d_exit()
            return True
        if not block and not self._stop.is_set():
            try:
                if not self._probe_ready(staged):
                    window.appendleft((staged, nbytes))
                    return False
            except Exception:  # noqa: BLE001
                pass
        with self._stats_lock:
            self._window_bytes -= nbytes
        self._h2d_exit()
        return True

    @staticmethod
    def _probe_ready(staged):
        probe = getattr(staged, 'is_ready', None)
        return True if probe is None else bool(probe())

    # -- streamed-path overlap ---------------------------------------------

    def _h2d_enter(self):
        """A transfer entered some stream's window: open (or refcount)
        the single logical 'h2d' span on the stager's meter."""
        if self.meter is None:
            return
        with self._stats_lock:
            self._h2d_tokens += 1
            if self._h2d_tokens == 1:
                self._h2d_span = self.meter.track('h2d')
                self._h2d_span.__enter__()

    def _h2d_exit(self):
        """A transfer retired; close the 'h2d' span when no stream holds
        an unfenced transfer any more."""
        if self.meter is None:
            return
        with self._stats_lock:
            self._h2d_tokens -= 1
            if self._h2d_tokens == 0 and self._h2d_span is not None:
                span, self._h2d_span = self._h2d_span, None
                span.__exit__(None, None, None)

    def record_inline_wave(self, stream_indices, nbytes_list, elapsed,
                           donate):
        """Account one batched per-device wave — issued inline on the
        owner's thread (the small-shard fast tier) or from a stream
        thread as a self-accounting wave item (the streamed-batched
        tier) — so per-device put seconds/bytes and donation counts
        stay coherent across tiers. Issue time is attributed evenly
        across the wave's shards (the batched call is one C++ fan-out;
        per-shard splits are not observable)."""
        count = max(1, len(stream_indices))
        per_shard = elapsed / count
        for index, nbytes in zip(stream_indices, nbytes_list):
            key = self._keys[index]
            self._m_put.labels(key).observe(per_shard)
            if donate:
                self._m_donated.inc()
        with self._stats_lock:
            for index, nbytes in zip(stream_indices, nbytes_list):
                key = self._keys[index]
                self._put_s[key] += per_shard
                self._put_bytes[key] += int(nbytes)
                self._shards_put += 1
                if donate:
                    self._donated += 1

    # -- knobs / stats / lifecycle ----------------------------------------

    def set_inflight(self, n):
        """Retarget the per-stream in-flight window (the autotune
        ``device_inflight`` knob): each stream re-reads it at submit
        time, so widening takes effect on the next put and narrowing
        fences the excess oldest transfers before the next one issues."""
        self._inflight = max(1, int(n))

    @property
    def inflight_window(self):
        return self._inflight

    @property
    def ready_wait_seconds(self):
        """Cumulative seconds streams spent fenced on their oldest
        in-flight transfer — folded into the autotuner's dispatch-bound
        signal next to the engine's batch-level fence."""
        return self._totals['ready_wait_s']

    @property
    def window_nbytes(self):
        """Host bytes currently referenced by every stream's in-flight
        window (the membudget ``device-put-window`` pool; the loader
        reports 0 when the same bytes are already accounted by the arena
        pool)."""
        with self._stats_lock:
            return self._window_bytes

    def stats(self):
        # Meter first (its own lock) so nothing nests under _stats_lock.
        overlap = self.meter.stats() if self.meter is not None else None
        with self._stats_lock:
            out = {
                'n_devices': len(self._keys),
                'device_inflight': self._inflight,
                'shards_put': self._shards_put,
                'shards_donated': self._donated,
                'device_ready_wait_s': round(self._totals['ready_wait_s'],
                                             4),
                'device_put_s': {k: round(v, 4)
                                 for k, v in self._put_s.items()},
                'device_put_bytes': dict(self._put_bytes),
                'leaked_threads': list(self._leaked_threads)}
        if overlap is not None:
            # The streamed path's overlap: 'h2d' (any transfer unfenced
            # in a window) vs 'host' (the owner's staging work)
            # co-activity.
            out['h2d_overlap'] = overlap
            out['h2d_overlap_frac'] = overlap['overlap_frac']
        return out

    def reset_stats(self):
        if self.meter is not None:
            self.meter.reset()
        with self._stats_lock:
            self._put_s = {k: 0.0 for k in self._keys}
            self._put_bytes = {k: 0 for k in self._keys}
            self._shards_put = 0
            self._donated = 0
        trace.reset_totals(self._totals)

    @property
    def alive(self):
        return any(t.is_alive() for t in self._threads)

    def stop(self, join_timeout_s=10):
        """Idempotent: set stop, join every stream. A stream outliving
        the join (a put hung on a wedged device) is recorded in
        ``stats()['leaked_threads']`` and logged — mirroring
        :meth:`StagingEngine.stop`'s never-pretend-success contract."""
        self._stop.set()
        leaked = []
        with self._start_lock:
            started = self._started
        for t in self._threads:
            if not started:
                break
            t.join(timeout=join_timeout_s)
            if t.is_alive():
                leaked.append(t.name)
        if leaked:
            with self._stats_lock:
                self._leaked_threads.extend(
                    n for n in leaked if n not in self._leaked_threads)
            for name in leaked:
                self._tracer.instant('device-stager-leaked:{}'.format(name),
                                     cat='watchdog')
            logger.warning(
                'DeviceStager.stop: stream thread(s) %s still alive after '
                '%.1fs join — a hung device_put is leaking them past '
                'shutdown.', leaked, join_timeout_s)
        return leaked


class StagedBatch(dict):
    """A staged batch (field -> device array) that knows which batch it is:
    ``seq``, the loader's batch sequence number (the id of its collate,
    dispatch and consumer spans), and ``staged_ns``, when its puts had been
    issued on ``time.perf_counter_ns()`` — the consumer's ``deliver`` record
    says how long it then sat in the prefetch queue."""

    __slots__ = ('seq', 'staged_ns')

    def __init__(self, fields, seq=None):
        super().__init__(fields)
        self.seq = seq
        self.staged_ns = time.perf_counter_ns()


class _StageError(object):
    def __init__(self, exc):
        self.exc = exc


class StagingEngine(object):
    """Assemble/dispatch pipeline feeding a consumer queue.

    :param host_iter: iterator of host-batch dicts (typically
        ``iter_numpy_batches(..., batch_buffers=pool.get_buffers)`` so the
        batches land in pool arenas).
    :param stage_fn: host batch dict -> staged dict (async device puts).
    :param out_queue: bounded consumer queue; receives staged dicts in
        order, then ``end_sentinel`` (or an ``Exception`` on failure).
    :param stop_event: shared stop flag; no engine thread outlives it.
    :param pool: the :class:`ArenaPool` backing ``host_iter`` (or None).
    :param inflight: max staged batches whose transfers may be in flight
        before the dispatch thread blocks on the oldest (the backpressure
        window from the ISSUE; also bounds how much arena memory a burst
        can pin).
    :param ready_fn: staged dict -> blocks until its transfer completed
        (``jax.block_until_ready``). Called before an arena is retired.
    :param is_ready_fn: staged dict -> bool, non-blocking (opportunistic
        early retirement); optional.
    :param holds_mode: staged arrays alias arena memory (zero-copy
        backends): register GC holds so an arena is never recycled while
        the consumer can still observe it.
    :param on_drop: optional zero-arg callback fired when an assembled
        batch is discarded without reaching the consumer (stop-time
        races). The loader's provenance tracker pairs pending records
        FIFO with delivered batches, so a dropped batch must retract its
        record or every later record would describe the wrong batch.
    """

    def __init__(self, host_iter, stage_fn, out_queue, stop_event,
                 end_sentinel, pool=None, inflight=2, ready_fn=None,
                 is_ready_fn=None, holds_mode=False, tracer=None,
                 meter=None, health=None, on_drop=None,
                 stage_with_arena=False):
        self._host_iter = host_iter
        self._stage_fn = stage_fn
        # stage_with_arena: call ``stage_fn(batch, arena, span)`` so a
        # device-sharded stage can reuse the arena's memoized per-device
        # sub-slice views (HostArena.shard_views) instead of re-slicing
        # every batch, and say in the ``dispatch.stage`` span (whose id is
        # the batch's number) what carried it. The arena still joins the
        # in-flight window AFTER staging, exactly as before.
        self._stage_with_arena = bool(stage_with_arena)
        self._out = out_queue
        self._stop = stop_event
        self._end = end_sentinel
        self._pool = pool
        self._window = max(1, int(inflight))
        self._ready_fn = ready_fn or (lambda staged: None)
        self._is_ready_fn = is_ready_fn
        self._holds_mode = holds_mode
        self._on_drop = on_drop
        self._tracer = trace.resolve(tracer)
        # The meter measures co-activity of the two stages (overlap_frac),
        # which no single span holds; every reported interval is a span's.
        self.meter = meter if meter is not None else OverlapMeter()
        # Registry mirror (petastorm_tpu.metrics) of the two stages' spans.
        from petastorm_tpu import metrics as metrics_mod
        self._m_assemble = metrics_mod.histogram(
            'pst_assemble_seconds', 'Host-batch collate latency per batch')
        self._m_dispatch = metrics_mod.histogram(
            'pst_dispatch_seconds', 'Device staging dispatch latency per '
            'batch (put issue time, not transfer completion)')
        self._stats_lock = threading.Lock()
        self._retired = 0
        # assemble_s: the collate.batch spans' SELF seconds (what their
        # reader_wait and arena_wait children cover left out, so it cannot
        # go negative); dispatch_s: the dispatch.stage spans' seconds;
        # ready_wait_s: the dispatch.fence spans'.
        self._totals = {'assemble_s': 0.0, 'dispatch_s': 0.0,
                        'ready_wait_s': 0.0}
        self._leaked_threads = []
        # Health hookup (petastorm_tpu.health): both stage threads beat a
        # named heartbeat at every phase transition, so the watchdog can
        # tell a hung device_put ('device_put'/'ready-wait') from a full
        # consumer queue ('out-put') from waiting on upstream
        # ('stageq-get' — an innocent state; blame lands on assemble).
        self._hb_assemble = self._hb_dispatch = None
        if health is not None:
            self._hb_assemble = health.register('assemble')
            self._hb_dispatch = health.register('dispatch')
            health.register_probe('staging', self.stats)
        self._stage_q = queue.Queue(maxsize=2)
        self._threads = [
            threading.Thread(target=self._assemble_loop, daemon=True,
                             name='pst-staging-assemble'),
            threading.Thread(target=self._dispatch_loop, daemon=True,
                             name='pst-staging-dispatch'),
        ]

    def start(self):
        for t in self._threads:
            t.start()
        return self

    # -- stop-aware queue helpers ----------------------------------------

    def _put(self, q, obj):
        """Bounded-queue put that never outlives stop() (PR 1 semantics:
        an unbounded put can leak the thread forever if the consumer left).
        Returns whether ``obj`` was actually enqueued — the caller owns its
        cleanup ONLY on False, or a stop-time race would settle the same
        arena twice. When stopping, a final non-blocking attempt still
        wakes a consumer already parked in an untimed get()."""
        while not self._stop.is_set():
            try:
                q.put(obj, timeout=0.1)
                return True
            except queue.Full:
                continue
        try:
            q.put_nowait(obj)
            return True
        except queue.Full:
            return False

    def _get(self):
        while not self._stop.is_set():
            try:
                return self._stage_q.get(timeout=0.1)
            except queue.Empty:
                continue
        try:
            return self._stage_q.get_nowait()
        except queue.Empty:
            return None

    # -- assemble stage ---------------------------------------------------

    def _assemble_loop(self):
        hb = self._hb_assemble
        try:
            self._assemble_body(hb)
        finally:
            if hb is not None:
                hb.beat('idle')   # exited (done, stopped, or errored-and-
                                  # delivered): quiet is no longer a stall

    def _assemble_body(self, hb):
        try:
            seq = 0
            while not self._stop.is_set():
                if hb is not None:
                    hb.beat('collate')
                with self.meter.track('assemble'), self._tracer.span(
                        'collate.batch', 'collate', id=seq,
                        hist=self._m_assemble,
                        self_total=(self._totals, 'assemble_s')) as span:
                    batch = next(self._host_iter, _DONE)
                    if batch is _DONE:
                        span.id, span.cause = None, 'end-of-data'
                if batch is _DONE:
                    break
                arena = self._pool.claim_pending() if self._pool else None
                if hb is not None:
                    hb.beat('stageq-put')
                if not self._put(self._stage_q, (batch, arena, seq)):
                    if arena is not None:
                        arena.retire()
                    self._notify_drop()
                    return
                seq += 1
        except Exception as e:  # noqa: BLE001 - surfaced to consumer
            if self._pool is not None:
                self._pool.reclaim_pending()
            self._put(self._stage_q, _StageError(e))
            return
        self._put(self._stage_q, _DONE)

    def _notify_drop(self):
        """An assembled batch will never reach the consumer: tell the
        owner (provenance accounting) exactly once per dropped batch."""
        if self._on_drop is not None:
            try:
                self._on_drop()
            except Exception:  # noqa: BLE001 - advisory accounting only
                logger.debug('staging on_drop callback failed', exc_info=True)

    # -- dispatch stage ---------------------------------------------------

    def _head_ready(self, staged):
        if self._is_ready_fn is None:
            return False
        try:
            return bool(self._is_ready_fn(staged))
        except Exception:  # noqa: BLE001 - readiness probe must not kill dispatch
            return False

    def _retire(self, staged, arena, seq, wait):
        if arena is None:
            return
        if wait and not self._stop.is_set():
            if self._hb_dispatch is not None:
                self._hb_dispatch.beat('ready-wait')
            with self._tracer.span('dispatch.fence', 'dispatch', id=seq,
                                   total=(self._totals, 'ready_wait_s')):
                self._ready_fn(staged)
        # Seeded use-after-reclaim (fault site 'arena-stale-view'): keep a
        # borrow-tagged view across the retire and touch it after. Armed
        # (PETASTORM_TPU_SANITIZE) the touch raises StaleViewError at the
        # stale access; unarmed it silently reads recycled bytes — the
        # exact bug class the sanitizer exists to catch. (In holds mode a
        # reclaim defers to consumer GC, so the seeded proof drives the
        # engine with holds_mode=False; see tests/test_pstlint.py.)
        stale_probe = None
        from petastorm_tpu import faults
        if faults.faults_active() \
                and faults.get_injector().should_fire('arena-stale-view'):
            stale_probe = arena.borrow(next(iter(arena.buffers.values())))
        arena.retire()
        if stale_probe is not None:
            stale_probe.sum()   # raises StaleViewError when sanitizer armed
        with self._stats_lock:
            self._retired += 1

    def _dispatch_loop(self):
        hb = self._hb_dispatch
        try:
            self._dispatch_body(hb)
        finally:
            if hb is not None:
                hb.beat('idle')

    def _dispatch_body(self, hb):
        inflight = deque()
        arena = None    # the current batch's arena until the window owns it
        try:
            while True:
                if hb is not None:
                    hb.beat('stageq-get')
                item = self._get()
                if item is None:          # stopping
                    return
                if item is _DONE:
                    while inflight:
                        self._retire(*inflight.popleft(), wait=True)
                    self._put(self._out, self._end)
                    return
                if isinstance(item, _StageError):
                    while inflight:
                        self._retire(*inflight.popleft(), wait=True)
                    self._put(self._out, item.exc)
                    return
                batch, arena, seq = item
                if self._stop.is_set():
                    # Never issue device puts into a stopping pipe (the old
                    # stage loop's fetch/stage stop-check): on a wedged
                    # device a put can hang past the join timeout, leaving
                    # a leaked thread holding reader views whose teardown
                    # it races.
                    self._notify_drop()
                    return
                if hb is not None:
                    hb.beat('device_put')
                # Seeded lock-order inversion (fault site
                # 'lock-order-invert'): near-zero when inactive; armed,
                # the sanitizer's recorder raises before blocking and the
                # violation is delivered to the consumer like any
                # pipeline error.
                from petastorm_tpu.analysis import sanitize
                sanitize.maybe_inject_lock_inversion()
                # Dispatch time only (device_put is async): the transfer
                # overlaps the consumer's step and ends under a fence.
                with self.meter.track('dispatch'), self._tracer.span(
                        'dispatch.stage', 'dispatch', id=seq,
                        hist=self._m_dispatch,
                        total=(self._totals, 'dispatch_s')) as span:
                    if self._stage_with_arena:
                        staged = self._stage_fn(batch, arena, span)
                    else:
                        staged = self._stage_fn(batch)
                if arena is not None:
                    if self._holds_mode:
                        for value in staged.values():
                            arena.add_hold(value)
                    inflight.append((staged, arena, seq))
                    arena = None
                    self._tracer.counter('staging_inflight', len(inflight),
                                         'dispatch')
                del batch
                if hb is not None:
                    hb.beat('out-put')
                with self._tracer.span('dispatch.queue_put', 'dispatch',
                                       id=seq):
                    delivered = self._put(self._out, staged)
                if not delivered:
                    self._notify_drop()
                    return
                del staged
                # Opportunistic early retirement, then hard backpressure:
                # block on the OLDEST in-flight transfer once the window
                # is full — collate of batch N+1 proceeds in the assemble
                # thread meanwhile, which is the overlap this engine exists
                # to create.
                while inflight and self._head_ready(inflight[0][0]):
                    self._retire(*inflight.popleft(), wait=False)
                while len(inflight) > self._window:
                    self._retire(*inflight.popleft(), wait=True)
        except Exception as e:  # noqa: BLE001 - surfaced to consumer
            # Deliver first (the stop-aware put is reliable while the
            # consumer lives), THEN stop the whole engine: the assembler
            # must not keep retrying its bounded put forever (a leaked
            # stager holding reader refs), and with stop set no arena can
            # be handed out again, making the wait=False drain below safe.
            self._put(self._out, e)
            self._stop.set()
        finally:
            # Shutdown: no arena may leak — neither the failing batch's
            # (claimed but never appended to the window) nor the window's.
            # Stop is set on every path that reaches here with entries
            # outstanding, so a retired arena cannot be re-handed-out and
            # overwritten under a still-running transfer; the transfers
            # themselves keep their memory alive via their own references.
            if arena is not None:
                arena.retire()
            while inflight:
                self._retire(*inflight.popleft(), wait=False)

    # -- lifecycle / stats -------------------------------------------------

    def set_inflight(self, n):
        """Retarget the in-flight transfer window at runtime (autotune
        hookup): the dispatch loop re-reads the window every batch, so a
        widened window takes effect on the next dispatch and a narrowed
        one drains by blocking on the oldest transfers."""
        self._window = max(1, int(n))

    @property
    def inflight_window(self):
        return self._window

    @property
    def ready_wait_seconds(self):
        """Cumulative seconds the dispatch stage spent fenced on the
        oldest in-flight transfer — the autotuner's dispatch-bound signal
        (cheaper than a full :meth:`stats` sample on a sub-second tick)."""
        return self._totals['ready_wait_s']

    def stop(self, join_timeout_s=10):
        """Idempotent: set stop, unblock both threads, join them, settle
        arena bookkeeping. The caller drains ``out_queue`` (it owns it).

        A thread that outlives ``join_timeout_s`` (e.g. a ``device_put``
        hung on a wedged device) is NOT silently forgotten: it is recorded
        in ``stats()['leaked_threads']``, traced, and logged with the
        stuck thread's stack — shutdown must never pretend it succeeded.
        Returns the list of thread names leaked by *this* call.
        """
        self._stop.set()
        if self._pool is not None:
            self._pool.wake()   # waiters observe the stop flag immediately
        leaked = []
        for t in self._threads:
            t.join(timeout=join_timeout_s)
            if t.is_alive():
                leaked.append(t.name)
        if leaked:
            from petastorm_tpu.health import dump_all_stacks
            with self._stats_lock:
                self._leaked_threads.extend(
                    n for n in leaked if n not in self._leaked_threads)
            for name in leaked:
                self._tracer.instant('staging-leaked-thread:{}'.format(name),
                                     cat='watchdog')
            logger.warning(
                'StagingEngine.stop: thread(s) %s still alive after %.1fs '
                'join — a hung transfer is leaking them past shutdown. '
                'Thread stacks:\n%s', leaked, join_timeout_s,
                dump_all_stacks())
        if self._pool is not None:
            self._pool.reclaim_pending()
        # Drain whatever assemble left between the stages.
        while True:
            try:
                item = self._stage_q.get_nowait()
            except queue.Empty:
                break
            if isinstance(item, tuple) and item[1] is not None:
                item[1].retire()
        return leaked   # THIS call's leaks; stats() keeps the cumulative list

    @property
    def alive(self):
        return any(t.is_alive() for t in self._threads)

    def stats(self):
        m = self.meter.stats()
        total = self.meter.stats(total=True)
        with self._stats_lock:
            retired = self._retired
            leaked = list(self._leaked_threads)
        ready_wait = self._totals['ready_wait_s']
        return {'assemble_s': round(self._totals['assemble_s'], 4),
                'dispatch_s': round(self._totals['dispatch_s'], 4),
                'overlap_s': m['overlap_s'],
                'overlap_frac': m['overlap_frac'],
                'overlap_frac_total': total['overlap_frac'],
                'inflight_retired': retired,
                'ready_wait_s': round(ready_wait, 4),
                'leaked_threads': leaked}

    def reset_stats(self):
        self.meter.reset()
        with self._stats_lock:
            self._retired = 0
        trace.reset_totals(self._totals)
