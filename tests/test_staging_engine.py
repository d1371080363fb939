"""Staging-engine tests (ISSUE 2 tentpole): arena pool recycling, overlap
metering, the assemble/dispatch pipeline, and JaxLoader integration —
including the fault/stop semantics PR 1 established (no leaked staging
threads, no leaked in-flight arenas) and the recycling-correctness claim
(a consumed batch's contents must not change when its arena is reused).
"""

import gc
import queue
import threading
import time

import numpy as np
import pytest

from petastorm_tpu import make_tensor_reader
from petastorm_tpu.jax_loader import JaxLoader
from petastorm_tpu.staging import (ArenaPool, OverlapMeter, StagingEngine,
                                   staging_aliases_host)

_END = object()


def _spec(batch=4, width=3):
    return {'x': ((batch, width), np.dtype(np.float32)),
            'y': ((batch,), np.dtype(np.int32))}


# ---------------------------------------------------------------------------
# ArenaPool
# ---------------------------------------------------------------------------

def test_arena_pool_recycles_instead_of_allocating():
    pool = ArenaPool(depth=2)
    for i in range(10):
        bufs = pool.get_buffers(_spec())
        assert set(bufs) == {'x', 'y'}
        arena = pool.claim_pending()
        assert arena is not None
        arena.retire()
    stats = pool.stats()
    assert stats['arena_alloc'] == 1      # one arena round-trips forever
    assert stats['arena_reuse'] == 9


def test_arena_pool_spec_mismatch_bypasses():
    pool = ArenaPool(depth=2)
    assert pool.get_buffers(_spec(batch=4)) is not None
    assert pool.claim_pending() is not None
    # A partial final batch (different leading dim) gets no arena.
    assert pool.get_buffers(_spec(batch=3)) is None
    assert pool.claim_pending() is None


def test_arena_pool_grows_past_depth_instead_of_deadlocking():
    pool = ArenaPool(depth=1, grow_timeout_s=0.05)
    held = []
    for _ in range(3):   # never retired: a consumer holding many batches
        assert pool.get_buffers(_spec()) is not None
        held.append(pool.claim_pending())
    stats = pool.stats()
    assert stats['arena_alloc'] == 3
    assert stats['arena_wait_s'] > 0     # it backpressured before growing
    # Growth is sticky: after the working set cycles back, the next round
    # of the same size recycles without re-paying timeouts or allocations.
    for arena in held:
        arena.retire()
    pool.reset_stats()
    for _ in range(3):
        assert pool.get_buffers(_spec()) is not None
        pool.claim_pending()
    stats = pool.stats()
    assert stats['arena_alloc'] == 0
    assert stats['arena_reuse'] == 3
    assert stats['arena_wait_s'] == 0.0
    assert stats['arena_depth'] == 3     # high-water mark retained


def test_arena_pool_stop_aware_acquire():
    stop = threading.Event()
    pool = ArenaPool(depth=1, stop_event=stop, grow_timeout_s=60)
    assert pool.get_buffers(_spec()) is not None
    pool.claim_pending()                  # pool now empty, huge grow timeout
    result = {}

    def acquire():
        result['bufs'] = pool.get_buffers(_spec())

    t = threading.Thread(target=acquire)
    t.start()
    time.sleep(0.1)
    stop.set()
    t.join(timeout=5)
    assert not t.is_alive()
    assert result['bufs'] is None


def test_arena_holds_defer_reclaim_until_gc():
    """An arena whose staged arrays alias host memory must not be handed
    out again while the consumer can still observe those arrays."""
    pool = ArenaPool(depth=1, grow_timeout_s=0.01)
    assert pool.get_buffers(_spec()) is not None
    arena = pool.claim_pending()

    class Staged(object):
        pass

    consumer_view = Staged()
    arena.add_hold(consumer_view)
    arena.retire()                        # transfer done, but still held
    assert pool._free == []               # NOT back in the pool
    del consumer_view
    gc.collect()
    assert pool._free == [arena]          # hold dropped -> recycled


def test_arena_pool_reset_stats_keeps_arenas():
    pool = ArenaPool(depth=2)
    pool.get_buffers(_spec())
    pool.claim_pending().retire()
    pool.reset_stats()
    stats = pool.stats()
    assert stats['arena_alloc'] == 0 and stats['arena_reuse'] == 0
    pool.get_buffers(_spec())
    assert pool.claim_pending() is not None
    assert pool.stats()['arena_reuse'] == 1   # warm arena survived the reset


# ---------------------------------------------------------------------------
# OverlapMeter
# ---------------------------------------------------------------------------

def test_overlap_meter_concurrent_stages():
    meter = OverlapMeter()
    barrier = threading.Barrier(2)

    def stage(name):
        barrier.wait()
        with meter.track(name):
            time.sleep(0.1)

    threads = [threading.Thread(target=stage, args=(n,))
               for n in ('assemble', 'dispatch')]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = meter.stats()
    assert stats['overlap_s'] > 0.05
    assert stats['overlap_frac'] > 0.5
    assert stats['busy_s']['assemble'] >= 0.1


def test_overlap_meter_serial_stages_no_overlap():
    meter = OverlapMeter()
    with meter.track('assemble'):
        time.sleep(0.02)
    with meter.track('dispatch'):
        time.sleep(0.02)
    stats = meter.stats()
    assert stats['overlap_s'] == 0.0
    assert stats['overlap_frac'] == 0.0


# ---------------------------------------------------------------------------
# StagingEngine (no jax: injected stage/ready functions)
# ---------------------------------------------------------------------------

def _run_engine(batches, stage_fn=None, inflight=2, pool=None, **kw):
    out = queue.Queue(maxsize=4)
    stop = threading.Event()
    engine = StagingEngine(
        host_iter=iter(batches), stage_fn=stage_fn or (lambda b: dict(b)),
        out_queue=out, stop_event=stop, end_sentinel=_END, pool=pool,
        inflight=inflight, **kw).start()
    return engine, out, stop


def test_engine_preserves_order_and_terminates():
    batches = [{'x': np.full(3, i)} for i in range(20)]
    engine, out, _ = _run_engine(batches)
    got = []
    while True:
        item = out.get(timeout=10)
        if item is _END:
            break
        got.append(int(item['x'][0]))
    assert got == list(range(20))
    for _ in range(100):
        if not engine.alive:
            break
        time.sleep(0.05)
    assert not engine.alive


def test_engine_propagates_assembler_exception():
    def gen():
        yield {'x': np.zeros(2)}
        raise IOError('reader died')

    engine, out, _ = _run_engine(gen())
    assert isinstance(out.get(timeout=10), dict)
    err = out.get(timeout=10)
    assert isinstance(err, IOError)


def test_engine_propagates_stage_exception():
    def bad_stage(batch):
        raise RuntimeError('device wedged')

    engine, out, _ = _run_engine([{'x': np.zeros(2)}], stage_fn=bad_stage)
    err = out.get(timeout=10)
    assert isinstance(err, RuntimeError)


def test_stage_exception_releases_assembler_and_arenas():
    """A dispatch-stage failure must stop the WHOLE engine: the assembler
    cannot be left retrying its bounded put forever (a leaked stager
    holding reader refs), and the failing batch's arena must settle back
    into pool bookkeeping."""
    stop = threading.Event()
    pool = ArenaPool(depth=2, stop_event=stop)

    def gen():
        while True:   # endless: only engine-wide stop ends this
            bufs = pool.get_buffers({'x': ((2,), np.dtype(np.float32))})
            if bufs is None:
                return
            yield bufs

    def bad_stage(batch):
        raise RuntimeError('device wedged')

    out = queue.Queue(maxsize=4)
    engine = StagingEngine(host_iter=gen(), stage_fn=bad_stage,
                           out_queue=out, stop_event=stop, end_sentinel=_END,
                           pool=pool, inflight=2).start()
    assert isinstance(out.get(timeout=10), RuntimeError)
    for _ in range(200):
        if not engine.alive:
            break
        time.sleep(0.05)
    assert not engine.alive       # both threads exited on their own
    engine.stop()                 # settle leftovers (no-op joins)
    with pool._cond:
        assert pool._pending is None
        assert len(pool._free) == pool._allocated


def test_engine_stop_leaks_no_threads_or_arenas():
    stop = threading.Event()
    pool = ArenaPool(depth=3, stop_event=stop)

    def gen():
        i = 0
        while True:   # endless producer: only stop() ends this
            bufs = pool.get_buffers({'x': ((4,), np.dtype(np.float32))})
            if bufs is None:
                return
            bufs['x'][:] = i
            i += 1
            yield bufs

    out = queue.Queue(maxsize=1)   # tiny: engine blocks mid-put
    engine = StagingEngine(host_iter=gen(), stage_fn=lambda b: dict(b),
                           out_queue=out, stop_event=stop, end_sentinel=_END,
                           pool=pool, inflight=2).start()
    out.get(timeout=10)            # pipeline demonstrably running
    engine.stop()
    assert not engine.alive
    # Every allocated arena is accounted for: free, or pending-claimed-never
    # (none), but none dangling in engine structures.
    with pool._cond:
        assert pool._pending is None
        assert len(pool._free) == pool._allocated


def test_engine_backpressure_blocks_on_oldest():
    """With inflight=1, a second staged batch forces a ready-wait on the
    first before its arena recycles."""
    waited = []

    def slow_ready(staged):
        waited.append(staged['i'])

    stop = threading.Event()
    pool = ArenaPool(depth=8, stop_event=stop)

    def gen():
        for i in range(5):
            bufs = pool.get_buffers({'x': ((2,), np.dtype(np.float32))})
            yield {'x': bufs['x'], 'i': i} if bufs else {'x': np.zeros(2), 'i': i}

    out = queue.Queue(maxsize=8)
    engine = StagingEngine(host_iter=gen(), stage_fn=lambda b: dict(b),
                           out_queue=out, stop_event=stop, end_sentinel=_END,
                           pool=pool, inflight=1, ready_fn=slow_ready).start()
    got = []
    while True:
        item = out.get(timeout=10)
        if item is _END:
            break
        got.append(item['i'])
    assert got == list(range(5))
    assert waited  # the window actually forced ready-waits
    stats = engine.stats()
    assert stats['inflight_retired'] == 5


# ---------------------------------------------------------------------------
# JaxLoader integration
# ---------------------------------------------------------------------------

def _tensor_loader(url, batch, **kw):
    reader = make_tensor_reader(url, schema_fields=['id', 'matrix'],
                                reader_pool_type='dummy',
                                shuffle_row_groups=False, num_epochs=1)
    return JaxLoader(reader, batch, last_batch='drop', **kw)


def test_engine_loader_matches_consumer_staging(synthetic_dataset):
    with _tensor_loader(synthetic_dataset.url, 8, prefetch=0) as loader:
        serial = [(np.asarray(b.id), np.asarray(b.matrix)) for b in loader]
    with _tensor_loader(synthetic_dataset.url, 8, prefetch=2) as loader:
        piped = [(np.asarray(b.id), np.asarray(b.matrix)) for b in loader]
    assert len(serial) == len(piped) > 0
    for (id_a, m_a), (id_b, m_b) in zip(serial, piped):
        np.testing.assert_array_equal(id_a, id_b)
        np.testing.assert_array_equal(m_a, m_b)


def test_arena_recycling_never_mutates_delivered_batches(synthetic_dataset):
    """ISSUE 2 satellite: hold every delivered batch to the end of the
    epoch; late numpy reads must equal the snapshots taken at delivery.
    With chunks of 10 rows and batch 8, batches span chunks and recycle
    arenas; on zero-copy backends the staged arrays alias those arenas, so
    any premature recycle shows up as corruption here."""
    with _tensor_loader(synthetic_dataset.url, 8, prefetch=2,
                        arena_depth=2, inflight=1) as loader:
        held = []
        snapshots = []
        for b in loader:
            held.append(b)
            snapshots.append((np.array(b.id, copy=True),
                              np.array(b.matrix, copy=True)))
        stats = loader.stats
        for b, (ids, mat) in zip(held, snapshots):
            np.testing.assert_array_equal(np.asarray(b.id), ids)
            np.testing.assert_array_equal(np.asarray(b.matrix), mat)
    assert stats['batches'] == len(held) > 0


def test_loader_engine_stats_keys(synthetic_dataset):
    with _tensor_loader(synthetic_dataset.url, 8, prefetch=2) as loader:
        for _ in loader:
            pass
        stats = loader.stats
    for key in ('assemble_s', 'dispatch_s', 'overlap_s', 'overlap_frac',
                'ready_wait_s', 'arena_alloc', 'arena_reuse', 'arena_wait_s',
                'arena_depth'):
        assert key in stats, key
    assert stats['assemble_s'] > 0
    assert 0.0 <= stats['overlap_frac'] <= 1.0


def test_loader_stop_midstream_leaks_nothing(synthetic_dataset):
    before = {t.name for t in threading.enumerate()}
    reader = make_tensor_reader(synthetic_dataset.url,
                                schema_fields=['id', 'matrix'],
                                reader_pool_type='thread', workers_count=2,
                                num_epochs=None)   # endless: stop() must end it
    loader = JaxLoader(reader, 8, prefetch=2)
    next(iter(loader))
    loader.stop()
    gc.collect()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        leaked = {t.name for t in threading.enumerate()} - before
        if not any(n.startswith('pst-staging') for n in leaked):
            break
        time.sleep(0.05)
    assert not any(n.startswith('pst-staging') for n in leaked), leaked
    assert loader._engine is not None and not loader._engine.alive


def test_loader_engine_surfaces_reader_faults(synthetic_dataset, monkeypatch):
    """decode-corrupt with no error budget must raise through the engine
    into the consumer within one epoch (the PR 1 fault contract)."""
    from petastorm_tpu.errors import DecodeFieldError
    from petastorm_tpu.faults import ENV_VAR

    monkeypatch.setenv(ENV_VAR, 'decode-corrupt:p=1.0:seed=1')
    reader = make_tensor_reader(synthetic_dataset.url,
                                schema_fields=['id', 'matrix'],
                                reader_pool_type='thread', workers_count=2,
                                num_epochs=1, shuffle_row_groups=False)
    with JaxLoader(reader, 8, prefetch=2) as loader:
        with pytest.raises(DecodeFieldError, match='injected fault'):
            for _ in loader:
                pass
    assert not loader._engine.alive


def test_loader_engine_rides_through_queue_stall(synthetic_dataset, monkeypatch):
    from petastorm_tpu.faults import ENV_VAR

    monkeypatch.setenv(ENV_VAR, 'queue-stall:delay=0.01:max=3')
    with _tensor_loader(synthetic_dataset.url, 8, prefetch=2) as loader:
        ids = np.concatenate([np.asarray(b.id) for b in loader])
    assert sorted(ids.tolist()) == list(range(48))  # 50 rows, tail dropped


def test_loader_superbatches_with_engine(synthetic_dataset):
    """superbatches(k) holds k batches at once — the pool must grow (or be
    deep enough) rather than deadlock, and contents stay correct."""
    with _tensor_loader(synthetic_dataset.url, 5, prefetch=2,
                        arena_depth=2, inflight=1) as loader:
        supers = list(loader.superbatches(3))
    assert len(supers) == 3
    ids = np.concatenate([np.asarray(s.id) for s in supers])
    assert sorted(ids.tolist()) == list(range(45))


@pytest.mark.processpool
def test_loader_engine_survives_worker_kill(synthetic_dataset, tmp_path,
                                            monkeypatch):
    """The worker-kill fault site SIGKILLs a pool worker mid-epoch; the
    respawned worker's chunks flow through the staging engine and the
    epoch still delivers every row exactly once."""
    from petastorm_tpu.faults import ENV_VAR

    token = tmp_path / 'kill.token'
    monkeypatch.setenv(ENV_VAR, 'worker-kill:token={}'.format(token))
    reader = make_tensor_reader(synthetic_dataset.url,
                                schema_fields=['id', 'matrix'],
                                reader_pool_type='process-zmq',
                                workers_count=2, num_epochs=1,
                                shuffle_row_groups=False)
    with JaxLoader(reader, 5, prefetch=2, last_batch='drop') as loader:
        ids = np.concatenate([np.asarray(b.id) for b in loader])
        respawns = loader.stats['reader_diagnostics']['worker_respawns']
    assert token.exists()          # the injection actually fired
    assert respawns == 1
    assert sorted(ids.tolist()) == list(range(50))
    assert not loader._engine.alive


def test_staging_aliases_host_probe_runs():
    import jax
    assert staging_aliases_host(jax) in (True, False)

# ---------------------------------------------------------------------------
# pinned (DMA-friendly) arenas
# ---------------------------------------------------------------------------

def test_pinned_slab_layout_page_aligned():
    from petastorm_tpu.staging import PINNED_FIELD_ALIGN, _pinned_slab_layout
    offsets, total = _pinned_slab_layout(_spec(batch=4, width=3))
    assert all(off % PINNED_FIELD_ALIGN == 0 for off, _ in offsets.values())
    assert offsets['x'][1] == 4 * 3 * 4 and offsets['y'][1] == 4 * 4
    assert total % PINNED_FIELD_ALIGN == 0
    assert total >= sum(size for _, size in offsets.values())


def test_pinned_pool_carves_aligned_buffers_and_accounts():
    from petastorm_tpu.staging import PINNED_FIELD_ALIGN
    pool = ArenaPool(depth=1, pinned=True)
    bufs = pool.get_buffers(_spec())
    assert bufs is not None and set(bufs) == {'x', 'y'}
    arena = pool.claim_pending()
    assert arena is not None
    stats = pool.stats()
    if stats['arena_pinned_bytes'] == 0:
        pytest.skip('pinned allocation unavailable on this host')
    assert stats['arena_pinned'] is True
    assert stats['arena_pinned_mode'] in ('native', 'mmap')
    # Every field starts on its own page — the transfer granularity DMA
    # engines and mlock both work in.
    for buf in bufs.values():
        assert buf.__array_interface__['data'][0] % PINNED_FIELD_ALIGN == 0
    # To consumers the buffers behave exactly like np.empty arenas.
    bufs['x'][:] = 7.0
    np.testing.assert_array_equal(
        np.asarray(bufs['x']), np.full((4, 3), 7.0, np.float32))
    # Finalizer accounting: the slab's bytes leave the gauge when the
    # arena DIES, not when it recycles.
    assert pool.pinned_nbytes > 0
    del bufs, arena
    gc.collect()
    assert pool.pinned_nbytes == 0


def test_pinned_allocation_failure_falls_back(monkeypatch):
    from petastorm_tpu.native import pinned as pinned_mod
    monkeypatch.setattr(pinned_mod, 'allocate',
                        lambda nbytes, lock=True: None)
    pool = ArenaPool(depth=1, pinned=True)
    bufs = pool.get_buffers(_spec())
    assert bufs is not None and set(bufs) == {'x', 'y'}
    stats = pool.stats()
    assert stats['arena_pinned'] is True       # the mode stays armed...
    assert stats['arena_pinned_bytes'] == 0    # ...but nothing is pinned
    assert stats['arena_pinned_mode'] == 'off'
    pool.claim_pending().retire()


def test_pinned_env_default_and_live_toggle(monkeypatch):
    monkeypatch.setenv('PETASTORM_TPU_PINNED_ARENAS', '1')
    pool = ArenaPool(depth=1)
    assert pool.pinned                         # env arms the default
    pool.set_pinned(False)                     # autotune/advisory toggle
    assert pool.get_buffers(_spec()) is not None
    assert pool.claim_pending() is not None
    assert pool.stats()['arena_pinned_bytes'] == 0
    monkeypatch.delenv('PETASTORM_TPU_PINNED_ARENAS')
    assert not ArenaPool(depth=1).pinned


# ---------------------------------------------------------------------------
# DeviceStager fence pipelining (jax-free: fake put/ready functions)
# ---------------------------------------------------------------------------

class _FakeShard(object):
    nbytes = 10

    def __init__(self, tag):
        self.tag = tag


class _FakeStaged(object):
    def __init__(self, tag):
        self.tag = tag
        self.ready = False

    def is_ready(self):
        return self.ready


def _fence_stager(inflight, fences, staged_out, put_hook=None):
    from petastorm_tpu.staging import DeviceStager

    def put_fn(array):
        if put_hook is not None:
            put_hook()
        staged = _FakeStaged(array.tag)
        staged_out.append(staged)
        return staged

    return DeviceStager(['d0'], put_fn, inflight=inflight,
                        ready_fn=lambda staged: fences.append(staged.tag))


def test_fence_pipelining_window_never_drains():
    """The window fences its OLDEST transfer only when full at submit
    time: between waves every slot stays occupied by an in-flight
    transfer (the h2d stream never drains), fences run FIFO, and idle
    retirement only collects transfers that report ready."""
    fences, staged = [], []
    st = _fence_stager(2, fences, staged)
    try:
        for i in range(5):
            st.put_shards([(0, _FakeShard('s{}'.format(i)))])
            if i >= 1:
                # Not a drained stream: both slots in flight between waves.
                assert st.window_nbytes == 2 * _FakeShard.nbytes
        assert fences == ['s0', 's1', 's2']
        # Nothing reports ready, so the idle loop must not shrink the
        # window behind the fence discipline's back.
        time.sleep(0.3)
        assert st.window_nbytes == 2 * _FakeShard.nbytes
        # Transfers completing in the background retire WITHOUT a fence.
        for s in staged:
            s.ready = True
        deadline = time.time() + 5
        while st.window_nbytes and time.time() < deadline:
            time.sleep(0.01)
        assert st.window_nbytes == 0
        assert fences == ['s0', 's1', 's2']
    finally:
        st.stop()
    assert not any(t.name.startswith('pst-device-put-')
                   for t in threading.enumerate() if t.is_alive())


def test_four_streams_fencing_at_once_lose_no_wait_seconds():
    """``device_ready_wait_s`` is fed by the ``dispatch.fence`` spans of
    every stream's thread: with four streams fencing together it is still
    the sum of those spans."""
    import sys

    from petastorm_tpu.staging import DeviceStager
    from petastorm_tpu.trace import Tracer
    tracer = Tracer()
    st = DeviceStager(['d0', 'd1', 'd2', 'd3'],
                      lambda array: _FakeStaged(array.tag),
                      inflight=1, ready_fn=lambda staged: None, tracer=tracer)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for i in range(300):
            st.put_shards([(d, _FakeShard('s{}.{}'.format(i, d)))
                           for d in range(4)])
        waited = st.stats()['device_ready_wait_s']
        assert st.ready_wait_seconds == pytest.approx(waited, abs=1e-4)
    finally:
        sys.setswitchinterval(interval)
        st.stop()
    fences = [r for r in tracer.records() if r[0] == 'dispatch.fence']
    assert len(fences) == 4 * 299 and len({r[5] for r in fences}) == 4
    assert waited == pytest.approx(sum(r[3] for r in fences) / 1e9, abs=1e-4)
    st.reset_stats()
    assert st.ready_wait_seconds == 0.0


def test_fence_pipelining_under_device_put_delay(monkeypatch):
    """The device-put-delay fault site slows every transfer; the window
    discipline holds regardless — puts keep issuing behind a full
    window and the fence order stays FIFO."""
    from petastorm_tpu import faults
    monkeypatch.setenv(faults.ENV_VAR, 'device-put-delay:delay=0.02')
    fences, staged = [], []
    st = _fence_stager(1, fences, staged,
                       put_hook=lambda: faults.maybe_inject(
                           'device-put-delay'))
    try:
        for i in range(4):
            st.put_shards([(0, _FakeShard('s{}'.format(i)))])
            assert st.window_nbytes == _FakeShard.nbytes
        assert fences == ['s0', 's1', 's2']
    finally:
        st.stop()


def test_stager_stop_reclaims_inflight_window_without_fencing():
    """stop() mid-stream: every in-flight window entry is reclaimed (the
    byte accounting the arena pool's recycling rides returns to zero)
    without fencing transfers on a pipeline that is going away, and the
    stream threads join with nothing leaked."""
    fences, staged = [], []
    st = _fence_stager(4, fences, staged)
    for i in range(3):
        st.put_shards([(0, _FakeShard('s{}'.format(i)))])
    assert st.window_nbytes == 3 * _FakeShard.nbytes
    assert st.stop() == []                     # joined; nothing leaked
    assert st.window_nbytes == 0
    assert fences == []                        # reclaim, not fence
    assert not st.alive
