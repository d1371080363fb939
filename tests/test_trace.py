"""Tracer tests: the span record (clock, thread CPU, ids, self time), the
default ring, spans through a real reader and loader, chrome-trace export,
cross-process sidecar spill + merge (subprocess harness, torn-file
tolerance), and the trace_merge CLI."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import petastorm_tpu
from petastorm_tpu import trace
from petastorm_tpu.trace import (TRACE_DIR_ENV, NullTracer, Tracer,
                                 read_sidecar_file, set_global_tracer)

pytestmark = pytest.mark.observability

_REPO_ROOT = os.path.dirname(os.path.dirname(petastorm_tpu.__file__))


def _child_env():
    env = dict(os.environ)
    env['PYTHONPATH'] = _REPO_ROOT + os.pathsep + env.get('PYTHONPATH', '')
    return env


def test_spans_and_summary():
    tracer = Tracer()
    with tracer.span('decode', 'worker'):
        time.sleep(0.01)
    with tracer.span('decode', 'worker'):
        time.sleep(0.01)
    tracer.instant('epoch-end')
    s = tracer.summary()
    assert s['decode']['count'] == 2
    assert s['decode']['total_s'] >= 0.02
    assert len(tracer.events) == 3


def test_summary_percentiles():
    tracer = Tracer()
    # Synthesize spans with known durations: 100 at ~1ms, 1 at ~500ms.
    for dur_us in [1000.0] * 100 + [500000.0]:
        tracer._record(('op', 'x', 0, int(dur_us * 1e3), 0, 1, None, None))
    s = tracer.summary()['op']
    assert s['count'] == 101
    assert abs(s['p50_s'] - 0.001) < 1e-6
    assert s['p99_s'] >= 0.001        # tail pulled up, median not
    assert s['p99_s'] <= 0.5


def test_events_carry_real_pid():
    tracer = Tracer()
    with tracer.span('x'):
        pass
    tracer.instant('y')
    tracer.counter('z', 1)
    assert all(e['pid'] == os.getpid() for e in tracer.events)


def test_chrome_trace_export_atomic(tmp_path):
    tracer = Tracer()
    with tracer.span('stage', 'device'):
        pass
    path = tracer.export_chrome_trace(str(tmp_path / 'trace.json'))
    doc = json.load(open(path))
    (e,) = [x for x in doc['traceEvents'] if x['ph'] == 'X']
    assert e['name'] == 'stage' and 'dur' in e and 'ts' in e
    # process_name metadata labels this pid's track
    meta = [x for x in doc['traceEvents'] if x.get('ph') == 'M']
    assert any(m['pid'] == os.getpid() for m in meta)
    # atomic: no tmp leftovers next to the output
    assert [f for f in os.listdir(str(tmp_path))] == ['trace.json']


def test_bounded_events():
    tracer = Tracer(max_events=5)
    for i in range(10):
        tracer.instant('e{}'.format(i))
    assert len(tracer.events) == 5
    assert tracer.events[0]['name'] == 'e5'


def test_null_tracer_is_noop():
    t = NullTracer()
    with t.span('x'):
        pass
    t.instant('y')
    t.counter('z', 1)
    t.close()


# ---------------------------------------------------------------------------
# the record: clock, thread CPU, self time, ids
# ---------------------------------------------------------------------------

@pytest.fixture
def ring():
    """A fresh tracer installed as the global one for the test."""
    tracer = Tracer()
    previous = set_global_tracer(tracer)
    yield tracer
    set_global_tracer(previous)


def _spans(tracer, name=None):
    return [r for r in tracer.records()
            if len(r) == 8 and r[3] is not None and name in (None, r[0])]


def _busy():
    """Burn 30 ms of this thread's CPU, however long that takes."""
    t0 = time.thread_time_ns()
    while time.thread_time_ns() - t0 < 30e6:
        sum(range(1000))


def test_span_start_is_on_perf_counter_ns():
    """The absolute clock a training loop pins to its device trace: a span's
    start lies between a sighting before it and one taken inside it, within
    a millisecond of the latter (the best of five, on a loaded machine)."""
    import threading
    tracer = Tracer()
    gaps = []
    for n in range(5):
        before = time.perf_counter_ns()
        with tracer.span('x', 'layer', id=n, cause=['a']):
            inside = time.perf_counter_ns()
        name, layer, start_ns, dur_ns, _, tid, id_, cause = tracer.records()[-1]
        assert (name, layer, id_, cause) == ('x', 'layer', n, ['a'])
        assert tid == threading.get_ident()
        assert before <= start_ns <= inside <= start_ns + dur_ns
        gaps.append(inside - start_ns)
    assert min(gaps) < 1e6


@pytest.mark.parametrize('work, low, high', [
    (_busy, 29e6, 1e12), (lambda: time.sleep(0.05), 0, 5e6)],
    ids=['busy-loop', 'sleep'])
def test_a_worker_thread_s_span_carries_its_thread_s_cpu(work, low, high):
    tracer = Tracer()
    with tracer.span('x', 'reader'):
        work()
    (_, _, _, dur_ns, cpu_ns, _, _, _), = tracer.records()
    assert low <= cpu_ns <= high
    assert dur_ns >= 29e6 and cpu_ns <= dur_ns + 5e6


def test_the_loader_s_own_threads_do_not_pay_for_the_cpu_clock(monkeypatch):
    reads = []
    monkeypatch.setattr(trace, '_cpu_ns', lambda: reads.append(1) or 0)
    tracer = Tracer()
    for layer in ('collate', 'dispatch', 'consumer', 'step'):
        with tracer.span('x', layer):
            pass
        tracer.instant('y', layer)
    assert reads == [] and {r[4] for r in tracer.records()} == {None}
    assert all('cpu_us' not in e.get('args', {}) for e in tracer.events)
    for layer in trace.CPU_LAYERS:
        with tracer.span('x', layer):
            pass
        tracer.instant('y', layer)
    assert len(reads) == 3 * len(trace.CPU_LAYERS)


def test_self_time_is_duration_less_nested_spans_and_feeds_the_totals():
    tracer = Tracer()
    totals = {}

    class Hist(object):
        seen = []

        def observe(self, value):
            self.seen.append(value)

    with tracer.span('outer', self_total=(totals, 'outer_self_s'),
                     total=(totals, 'outer_s'), hist=Hist()) as outer:
        with tracer.span('inner', total=(totals, 'inner_s')):
            time.sleep(0.03)
        time.sleep(0.01)
    assert outer.self_ns == outer.dur_ns - _spans(tracer, 'inner')[0][3]
    assert totals['inner_s'] >= 0.03
    assert totals['outer_s'] == pytest.approx(outer.dur_ns / 1e9)
    assert totals['outer_self_s'] == pytest.approx(
        totals['outer_s'] - totals['inner_s'])
    assert Hist.seen == [outer.dur_ns / 1e9]
    # a null tracer clocks the same totals and records nothing
    off, off_totals = NullTracer(), {}
    with off.span('outer', self_total=(off_totals, 'self_s')):
        with off.span('inner', total=(off_totals, 'inner_s')):
            time.sleep(0.01)
    assert off_totals['inner_s'] >= 0.01 and 0 <= off_totals['self_s'] < 0.01
    assert off.records() == []


def test_spans_of_four_threads_feed_one_total_and_none_is_lost():
    """The per-device streams' fences all add to one ``ready_wait_s``: an
    unlocked read-add-write loses updates when threads switch inside it."""
    import threading
    tracer = Tracer(max_events=20000)
    totals = {'s': 0.0}

    def work():
        for _ in range(2000):
            with tracer.span('fence', total=(totals, 's')):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(interval)
    spans = _spans(tracer, 'fence')
    assert len(spans) == 8000
    assert totals['s'] == pytest.approx(sum(r[3] for r in spans) / 1e9,
                                        rel=1e-9)
    trace.reset_totals(totals)
    assert totals == {'s': 0.0}


def test_instants_carry_the_thread_s_cpu_clock_and_counters_their_value():
    tracer = Tracer()
    tracer.instant('mark', 'reader', {'k': 1}, id=3)
    _busy()
    tracer.instant('mark', 'reader')
    tracer.counter('depth', 5, 'layer')
    first, second, counter = tracer.records()
    assert first[3] is None and first[6] == 3 and first[7] == {'k': 1}
    assert 29e6 < second[4] - first[4] < 1e9      # CPU burnt in between
    assert counter[:2] == ('depth', 'layer') and counter[3] == 5
    assert abs(counter[2] - time.perf_counter_ns()) < 1e9
    kinds = [e['ph'] for e in tracer.events]
    assert kinds == ['i', 'i', 'C']
    assert tracer.events[0]['args'] == {'k': 1, 'id': 3}


# ---------------------------------------------------------------------------
# the default ring
# ---------------------------------------------------------------------------

def test_default_ring_is_bounded_and_survives_loader_stop(synthetic_dataset):
    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader

    previous = set_global_tracer(None)
    try:
        default = trace.get_global_tracer()
        assert default is trace.get_global_tracer()     # one a process
        assert default._events.maxlen == trace.DEFAULT_RING_EVENTS
        assert default.spill_path is None
        sighting = time.perf_counter_ns()
        with make_tensor_reader(synthetic_dataset.url,
                                schema_fields=['id', 'matrix'],
                                reader_pool_type='thread', workers_count=2,
                                shuffle_row_groups=False) as reader:
            loader = JaxLoader(reader, 10)      # nobody armed anything
            batches = sum(1 for _ in loader)
            loader.stop()
        mine = [r for r in default.records() if r[2] >= sighting]
        names = {r[0] for r in mine}
        assert {'reader.take', 'decode.decode', 'collate.batch',
                'dispatch.stage', 'consumer.deliver'} <= names
        assert sum(1 for r in mine if r[0] == 'consumer.deliver') == batches
        for i in range(trace.DEFAULT_RING_EVENTS + 10):
            default.counter('flood', i)
        assert len(default.records()) == trace.DEFAULT_RING_EVENTS
    finally:
        set_global_tracer(previous)


def test_null_global_tracer_records_nothing_and_stats_still_count(
        synthetic_dataset):
    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader

    off = NullTracer()
    previous = set_global_tracer(off)
    try:
        assert trace.get_global_tracer() is off
        with make_tensor_reader(synthetic_dataset.url,
                                schema_fields=['id', 'matrix'],
                                reader_pool_type='thread', workers_count=2,
                                shuffle_row_groups=False) as reader:
            with JaxLoader(reader, 10) as loader:
                assert loader._tracer is off
                batches = sum(1 for _ in loader)
                stats = loader.stats
        assert off.records() == []
        assert stats['batches'] == batches == 5
        assert stats['stage_dispatch_s'] > 0 and stats['assemble_s'] > 0
        assert stats['worker_stage_timings']['decode_s'] > 0
        assert stats['worker_stage_timings']['chunks'] == 5
    finally:
        set_global_tracer(previous)


# ---------------------------------------------------------------------------
# ids through a real reader and loader
# ---------------------------------------------------------------------------

def test_a_batch_keeps_its_number_from_collate_to_deliver(
        synthetic_dataset, ring):
    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader

    delivered = []
    with make_tensor_reader(synthetic_dataset.url,
                            schema_fields=['id', 'matrix'],
                            reader_pool_type='thread', workers_count=1,
                            shuffle_row_groups=False) as reader:
        with JaxLoader(reader, 15, last_batch='drop') as loader:
            for b in loader:
                delivered.append(np.asarray(b.id))
    assert len(delivered) == 3
    collate = {r[6]: r for r in _spans(ring, 'collate.batch')
               if r[6] is not None}
    delivers = [r for r in ring.records() if r[0] == 'consumer.deliver']
    stages = {r[6]: r for r in _spans(ring, 'dispatch.stage')}
    assert [r[6] for r in delivers] == [0, 1, 2]
    for seq in range(len(delivered)):
        # collated, then staged inside the dispatch span of that batch,
        # then taken
        staged_ns = delivers[seq][7]['staged_ns']
        stage = stages[seq]
        assert collate[seq][2] + collate[seq][3] <= stage[2]
        assert stage[2] <= staged_ns <= stage[2] + stage[3]
        assert staged_ns <= delivers[seq][2]
        assert stage[7]             # the tier that carried it
    # reader and decode spans carry their row group's key
    keys = {'{}:0'.format(g) for g in range(5)}
    for name in ('reader.cache_get', 'reader.read', 'decode.decode',
                 'reader.publish'):
        assert keys <= {r[6] for r in _spans(ring, name)}, name
    assert {r[7] for r in _spans(ring, 'reader.cache_get')} == {'miss'}
    # a pull's wait is a child of the batch that pulled it: same thread,
    # inside its interval
    wait = _spans(ring, 'collate.reader_wait')[0]
    parent = collate[0]
    assert wait[5] == parent[5]
    assert parent[2] <= wait[2] and wait[2] + wait[3] <= parent[2] + parent[3]


def test_idle_take_is_one_span_with_its_polls_counted(ring):
    """Ten wake-ups a millisecond apart are one ``reader.take`` record and
    one ``reader.vent_polls`` count, not ten records."""
    from petastorm_tpu.workers import WorkerBase
    from petastorm_tpu.workers.thread_pool import ThreadPool

    class Echo(WorkerBase):
        def process(self, value):
            self.publish_func(value)

    pool = ThreadPool(1)
    pool.start(Echo)
    time.sleep(0.1)                 # the worker polls an empty queue
    pool.ventilate(41)
    assert pool.get_results(timeout=10) == 41
    pool.stop()
    pool.join()
    takes = _spans(ring, 'reader.take')
    polls = [r for r in ring.records() if r[0] == 'reader.vent_polls']
    assert len(takes) == 2          # the item, then the stop
    assert takes[0][3] >= 90e6
    assert len(polls) >= 1 and polls[0][3] >= 20
    assert len(ring.records()) < 20
    cpu_marks = [r for r in ring.records() if r[0] == 'reader.thread_cpu']
    assert len(cpu_marks) == 2 and cpu_marks[0][5] == takes[0][5]


def test_assemble_s_cannot_go_negative_with_a_starved_arena_pool(
        synthetic_dataset, ring):
    """PERF.md's -0.19 s: ``arena_depth`` 1 on four devices keeps the
    assembler waiting for its one arena; ``assemble_s`` is the collate
    spans' self time and leaves those waits out."""
    import jax
    from jax.sharding import Mesh

    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader

    mesh = Mesh(np.asarray(jax.devices()[:4]), ('data',))
    with make_tensor_reader(synthetic_dataset.url,
                            schema_fields=['id', 'matrix'],
                            reader_pool_type='thread', workers_count=2,
                            num_epochs=4, shuffle_row_groups=False) as reader:
        with JaxLoader(reader, 8, mesh=mesh, arena_depth=1, prefetch=2,
                       inflight=1) as loader:
            held = []
            for n, b in enumerate(loader):
                held.append(b)          # the consumer pins what it took
                if n == 5:
                    loader.reset_stats()
                if n >= 12:
                    break
            stats = loader.stats
    assert stats['arena_wait_s'] > 0
    assert stats['assemble_s'] >= 0
    waits = _spans(ring, 'collate.arena_wait')
    assert waits and all(r[3] > 0 for r in waits)
    self_s = 0.0
    for batch in _spans(ring, 'collate.batch'):
        lo, hi = batch[2], batch[2] + batch[3]
        cover = sum(r[3] for name in ('collate.arena_wait',
                                      'collate.reader_wait')
                    for r in _spans(ring, name)
                    if r[5] == batch[5] and lo <= r[2] and r[2] + r[3] <= hi)
        assert cover <= batch[3]
        self_s += (batch[3] - cover) / 1e9
    assert stats['assemble_s'] <= self_s + 1e-3


def test_compilations_are_spans_of_the_step_layer(ring):
    import jax
    import jax.numpy as jnp

    trace.watch_jax_compiles()
    trace.watch_jax_compiles()          # once a process, however often asked

    @jax.jit
    def never_seen_before(x):
        return jnp.tanh(x) * 3.25 + 0.125

    never_seen_before(jnp.ones((3, 5))).block_until_ready()
    mine = [r for r in _spans(ring, 'jax.compile')
            if r[7] and 'never_seen_before' in r[7]]
    assert [r[6] for r in mine] == ['backend_compile']    # one a program
    assert all(r[1] == 'step' and r[3] > 0 for r in mine)
    before = len(_spans(ring, 'jax.compile'))
    never_seen_before(jnp.ones((3, 5))).block_until_ready()
    assert len(_spans(ring, 'jax.compile')) == before      # no recompile


# ---------------------------------------------------------------------------
# sidecar spill + merge
# ---------------------------------------------------------------------------

def test_sidecar_spill_writes_header_and_events(tmp_path):
    d = str(tmp_path / 'spill')
    tracer = Tracer(spill_dir=d, role='unit')
    with tracer.span('decode', 'worker'):
        pass
    tracer.instant('mark')
    tracer.close()
    (path,) = [os.path.join(d, f) for f in os.listdir(d)]
    header, events = read_sidecar_file(path)
    assert header['pid'] == os.getpid()
    assert header['role'] == 'unit'
    assert 'wall0' in header
    assert [e['name'] for e in events] == ['decode', 'mark']


def test_sidecar_spill_bounded(tmp_path):
    d = str(tmp_path / 'spill')
    tracer = Tracer(spill_dir=d, spill_max_events=3)
    for i in range(10):
        tracer.instant('e{}'.format(i))
    tracer.close()
    header, events = read_sidecar_file(tracer.spill_path)
    # 3 events + one truncation marker; memory ring still has all 10
    names = [e['name'] for e in events]
    assert names[:3] == ['e0', 'e1', 'e2']
    assert 'trace-spill-truncated' in names
    assert len(tracer.events) == 10


def test_merge_subprocess_sidecars(tmp_path):
    """Two child processes spill sidecars; the parent merges them into its
    own timeline under distinct real pids, aligned on the wall clock."""
    d = str(tmp_path / 'spill')
    child = (
        "import sys, time\n"
        "sys.path.insert(0, {root!r})\n"
        "from petastorm_tpu.trace import Tracer\n"
        "t = Tracer(spill_dir={d!r}, role='worker-t')\n"
        "with t.span('decode', 'worker'):\n"
        "    time.sleep(0.01)\n"
        "t.close()\n").format(root=_REPO_ROOT, d=d)
    for _ in range(2):
        subprocess.check_call([sys.executable, '-c', child],
                              env=_child_env())
    parent = Tracer(spill_dir=False)
    with parent.span('assemble', 'host'):
        pass
    assert parent.merge_process_files(d) == 2
    pids = {e['pid'] for e in parent.events}
    assert os.getpid() in pids and len(pids) == 3
    decode_pids = {e['pid'] for e in parent.events if e['name'] == 'decode'}
    assert os.getpid() not in decode_pids and len(decode_pids) == 2
    # merged spans land in the summary alongside local ones
    s = parent.summary()
    assert s['decode']['count'] == 2 and s['assemble']['count'] == 1
    # export labels every process track
    doc = json.load(open(parent.export_chrome_trace(
        str(tmp_path / 'merged.json'))))
    labeled = {m['pid'] for m in doc['traceEvents'] if m.get('ph') == 'M'}
    assert pids <= labeled


def test_merge_tolerates_torn_and_corrupt_lines(tmp_path):
    """A worker SIGKILLed mid-write leaves a torn trailing line; merge must
    read every complete line and skip the garbage."""
    d = str(tmp_path / 'spill')
    writer = Tracer(spill_dir=d, role='doomed')
    with writer.span('decode', 'worker'):
        pass
    with writer.span('decode', 'worker'):
        pass
    writer.close()
    with open(writer.spill_path, 'a') as f:
        f.write('{"name": "torn-eve')       # torn tail (no newline, cut JSON)
    with open(os.path.join(d, 'trace-999-deadbeef.jsonl'), 'w') as f:
        f.write('not json at all\n')        # fully corrupt sidecar
        f.write(json.dumps({'name': 'late', 'ph': 'i', 'ts': 1.0,
                            'pid': 999, 'tid': 1}) + '\n')
    parent = Tracer(spill_dir=False)
    assert parent.merge_process_files(d) == 2
    names = [e['name'] for e in parent.events]
    assert names.count('decode') == 2
    assert 'late' in names
    assert not any('torn' in n for n in names)


def test_merge_since_wall0_skips_stale_runs(tmp_path):
    """A reused trace dir holds a previous run's sidecars; since_wall0
    (an anchor captured before the pipeline was built) excludes them."""
    d = str(tmp_path / 'spill')
    old = Tracer(spill_dir=d, role='previous-run')
    old._wall0 -= 3600.0        # pretend it anchored an hour ago
    with old.span('decode', 'worker'):
        pass
    old.close()
    cutoff = __import__('time').time() - 60.0
    fresh = Tracer(spill_dir=d, role='current-run')
    with fresh.span('decode', 'worker'):
        pass
    fresh.close()
    parent = Tracer(spill_dir=False)
    assert parent.merge_process_files(d, since_wall0=cutoff) == 1
    assert sum(1 for e in parent.events if e['name'] == 'decode') == 1
    # and without the cutoff both runs merge (the documented hazard)
    parent2 = Tracer(spill_dir=False)
    assert parent2.merge_process_files(d) == 2


def test_merge_requires_a_directory(monkeypatch):
    monkeypatch.delenv(TRACE_DIR_ENV, raising=False)
    tracer = Tracer(spill_dir=False)
    with pytest.raises(ValueError, match='spill directory'):
        tracer.merge_process_files()


# ---------------------------------------------------------------------------
# pipeline wiring
# ---------------------------------------------------------------------------

def test_loader_records_pipeline_spans(synthetic_dataset):
    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader

    tracer = Tracer()
    with make_tensor_reader(synthetic_dataset.url, schema_fields=['id', 'matrix'],
                            reader_pool_type='thread', workers_count=2,
                            shuffle_row_groups=False) as reader:
        with JaxLoader(reader, 10, tracer=tracer, last_batch='drop') as loader:
            for b in loader:
                np.asarray(b.id)
    names = {e['name'] for e in tracer.events}
    assert {'collate.batch', 'collate.reader_wait', 'dispatch.stage',
            'dispatch.queue_put', 'consumer.wait', 'consumer.deliver'} <= names
    assert tracer.summary()['dispatch.stage']['total_s'] > 0


def test_thread_pool_worker_spans_via_global_tracer(synthetic_dataset):
    """Thread-pool workers run in-process: with a global tracer installed
    their read/decode/handoff spans land on the same timeline."""
    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.trace import set_global_tracer

    tracer = Tracer()
    previous = set_global_tracer(tracer)
    try:
        with make_tensor_reader(synthetic_dataset.url,
                                schema_fields=['id', 'matrix'],
                                reader_pool_type='thread', workers_count=2,
                                shuffle_row_groups=False) as reader:
            for _ in reader:
                pass
    finally:
        set_global_tracer(previous)
    names = {e['name'] for e in tracer.events}
    assert {'reader.take', 'reader.cache_get', 'reader.read', 'decode.decode',
            'reader.publish'} <= names


@pytest.mark.processpool
def test_process_pool_merged_trace(synthetic_dataset, tmp_path, monkeypatch):
    """The acceptance path: a process-pool tensor-reader run exports ONE
    merged Chrome trace where worker-process decode spans sit under
    distinct (non-parent) pids alongside the loader-side spans."""
    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader

    trace_dir = str(tmp_path / 'trace')
    monkeypatch.setenv(TRACE_DIR_ENV, trace_dir)
    tracer = Tracer(spill_dir=False)   # parent stays in-memory; workers spill
    with make_tensor_reader(synthetic_dataset.url,
                            schema_fields=['id', 'matrix'],
                            reader_pool_type='process-zmq', workers_count=2,
                            shuffle_row_groups=False) as reader:
        with JaxLoader(reader, 10, tracer=tracer, last_batch='drop') as loader:
            batches = sum(1 for _ in loader)
    assert batches == 5
    assert tracer.merge_process_files(trace_dir) >= 1
    decode_pids = {e['pid'] for e in tracer.events
                   if e['name'] == 'decode.decode'}
    assert decode_pids and os.getpid() not in decode_pids
    loader_spans = {e['name'] for e in tracer.events
                    if e['pid'] == os.getpid() and e['ph'] == 'X'}
    assert {'collate.batch', 'dispatch.stage'} <= loader_spans
    doc = json.load(open(tracer.export_chrome_trace(
        str(tmp_path / 'merged.json'))))
    trace_names = {e.get('name') for e in doc['traceEvents']}
    assert {'decode.decode', 'reader.read', 'reader.publish', 'collate.batch',
            'process_name'} <= trace_names


def test_trace_merge_cli(tmp_path):
    d = str(tmp_path / 'spill')
    writer = Tracer(spill_dir=d, role='worker-cli')
    with writer.span('decode', 'worker'):
        pass
    writer.close()
    out = str(tmp_path / 'merged.json')
    result = subprocess.run(
        [sys.executable, '-m', 'petastorm_tpu.tools.trace_merge',
         '--dir', d, '--out', out, '--summary'],
        env=_child_env(), capture_output=True, text=True, check=True)
    report = json.loads(result.stdout)
    assert report['merged_files'] == 1
    assert report['summary']['decode']['count'] == 1
    doc = json.load(open(out))
    assert any(e.get('name') == 'decode' for e in doc['traceEvents'])


def test_trace_merge_cli_empty_dir(tmp_path):
    result = subprocess.run(
        [sys.executable, '-m', 'petastorm_tpu.tools.trace_merge',
         '--dir', str(tmp_path)],
        env=_child_env(), capture_output=True, text=True)
    assert result.returncode == 1
    assert 'no sidecar files' in result.stderr
