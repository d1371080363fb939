"""The span reduction on hand-made records and on a small list recorded from
a real ``make_tensor_reader -> JaxLoader`` run on the CPU
(``data/small.spans.json``: the ring's records of twelve batches on four
virtual devices with ``arena_depth`` 1, the window's two ends and
``loader.stats`` at the close; ``python3 perfbench/tests/test_span_reduce.py
<out>`` records it again), and every span metric's reader on that list."""

import json
import os
import sys

import pytest
from conftest import HERE, PERFBENCH

from perfbench import harness, span_reduce

MS = 1000000
SPAN_METRICS = ['reader.idle_poll_cpu_share', 'collate.self_ms_per_batch',
                'collate.arena_wait_share', 'dispatch.fence_wait_ms_per_batch',
                'consumer.queue_lead_batches', 'step.compiles_in_window']


def span(name, start_ms, dur_ms, tid=1, cpu_ms=0.0, id=None, cause=None):
    return (name, name.split('.')[0], int(start_ms * MS), int(dur_ms * MS),
            None if cpu_ms is None else int(cpu_ms * MS), tid, id, cause)


def instant(name, at_ms, tid=1, cpu_ms=0.0, id=None, cause=None):
    return (name, name.split('.')[0], int(at_ms * MS), None, int(cpu_ms * MS),
            tid, id, cause)


def _by_hand():
    # Thread 1 collates: batch 0 from 10 to 30 ms with a reader wait (12..18)
    # and an arena wait (20..25) inside it; batch 1 from 40 to 60 ms.
    # Thread 2 is a pool worker: CPU clock marks at 0, 50 and 100 ms, a take
    # (0..40, 8 ms of CPU), a cache_get (40..48, 6 ms) holding a decode
    # (41..47, 5 ms), a publish (48..50, 1 ms); then another take (50..100,
    # 10 ms). It burnt 20 ms to the first mark and 34 ms to the second.
    # The list starts, as a ring does, with what closed first: a compilation
    # of set-up's.
    return [
        span('jax.compile', -5, 3, tid=4, id='backend_compile', cause='step'),
        span('collate.batch', 10, 20, cpu_ms=8, id=0),
        span('collate.reader_wait', 12, 6),
        span('collate.arena_wait', 20, 5),
        span('collate.batch', 40, 20, cpu_ms=19, id=1),
        # the loader's own threads do not read their CPU clock
        span('dispatch.stage', 31, 2, tid=3, cpu_ms=None, id=0,
             cause=['inline-batched']),
        span('dispatch.fence', 33, 4, tid=3, cpu_ms=None, id=0),
        span('dispatch.stage', 61, 2, tid=3, id=1, cause=['inline-batched']),
        instant('consumer.deliver', 45, tid=4, id=0, cause={'staged_ns': 33 * MS}),
        instant('consumer.deliver', 95, tid=4, id=1, cause={'staged_ns': 63 * MS}),
        instant('consumer.deliver', 96, tid=4, id=None, cause={'staged_ns': None}),
        instant('reader.thread_cpu', 0, tid=2, cpu_ms=100),
        span('reader.take', 0, 40, tid=2, cpu_ms=8),
        span('reader.cache_get', 40, 8, tid=2, cpu_ms=6, id='1:0', cause='miss'),
        span('decode.decode', 41, 6, tid=2, cpu_ms=5, id='1:0'),
        span('reader.publish', 48, 2, tid=2, cpu_ms=1, id='1:0'),
        instant('reader.thread_cpu', 50, tid=2, cpu_ms=120),
        span('reader.take', 50, 50, tid=2, cpu_ms=10),
        instant('reader.thread_cpu', 100, tid=2, cpu_ms=134),
        span('jax.compile', 70, 1, tid=4, id='trace', cause='late'),
        span('jax.compile', 71, 2, tid=4, id='backend_compile', cause='late'),
        ('arena_pool_free', 'collate', 26 * MS, 0),
        ('reader.vent_polls', 'reader', 120 * MS, 7),
    ]


def test_clip_cuts_spans_and_their_cpu_and_keeps_what_lies_inside():
    w = span_reduce.clip(_by_hand(), 20 * MS, 90 * MS)
    by_name = {}
    for s in w['spans']:
        by_name.setdefault(s[0], []).append(s)
    first = by_name['collate.batch'][0]
    # 10..30 cut to 20..30: half the span, half its CPU
    assert first[2:5] == (20 * MS, 10 * MS, 4 * MS)
    assert by_name['collate.batch'][1][2:5] == (40 * MS, 20 * MS, 19 * MS)
    assert 'collate.reader_wait' not in by_name            # ended at 18
    assert [s[3] for s in by_name['reader.take']] == [20 * MS, 40 * MS]
    assert [s[4] for s in by_name['dispatch.fence']] == [None]
    assert [i[2] for i in w['instants']] == [45 * MS, 50 * MS]
    assert [c[0] for c in w['counters']] == ['arena_pool_free']
    assert w['covered'] is True
    # a ring whose oldest record closed inside the window has dropped others
    assert span_reduce.clip(_by_hand()[1:], 20 * MS, 90 * MS)['covered'] \
        is False
    assert span_reduce.seconds(w['spans'], 'collate.arena_wait') == \
        pytest.approx(0.005)
    # JSON turns tuples into lists: the same answer from a recorded file
    again = span_reduce.clip(json.loads(json.dumps(_by_hand())), 20 * MS, 90 * MS)
    assert again['spans'] == w['spans']


def test_self_time_is_duration_less_what_children_cover():
    w = span_reduce.clip(_by_hand(), 0, 100 * MS)
    self_s, count = span_reduce.self_seconds(w['spans'], 'collate.batch')
    assert count == 2
    assert self_s == pytest.approx((20 - 6 - 5 + 20) / 1e3)
    # cache_get's self time leaves the decode inside it out
    assert span_reduce.self_seconds(w['spans'], 'reader.cache_get') == \
        (pytest.approx(0.002), 1)
    nested = {s[0]: top for s, _, top in span_reduce.nesting(w['spans'])
              if s[5] == 2}
    assert nested == {'reader.take': True, 'reader.cache_get': True,
                      'decode.decode': False, 'reader.publish': True}
    # in a window that cuts the batch, the children are cut with it
    cut = span_reduce.clip(_by_hand(), 15 * MS, 22 * MS)
    assert span_reduce.self_seconds(cut['spans'], 'collate.batch') == \
        (pytest.approx((7 - 3 - 2) / 1e3), 1)


def test_thread_cpu_inside_waits_inside_spans_and_under_no_span():
    w = span_reduce.clip(_by_hand(), 0, 100 * MS)
    cpu = span_reduce.thread_cpu(w, 'reader.thread_cpu',
                                 ('reader.take', 'reader.publish'))
    assert list(cpu) == [2]
    assert cpu[2] == {'total_ns': 34 * MS,
                      # take 8 + cache_get 6 (its decode inside) + publish 1
                      # + take 10; the decode's 5 are not counted again
                      'in_spans_ns': 25 * MS,
                      'in_waits_ns': 19 * MS}
    # one mark only in the window: nothing to difference
    assert span_reduce.thread_cpu(span_reduce.clip(_by_hand(), 60 * MS, 100 * MS),
                                  'reader.thread_cpu', ('reader.take',)) == {}


def test_residency_lead_and_quantiles():
    w = span_reduce.clip(_by_hand(), 0, 100 * MS)
    assert span_reduce.residency_ms(w) == [12.0, 32.0]
    # staged 22 ms before it was taken (median), a take every 25.5 ms
    assert span_reduce.lead_batches(w) == pytest.approx(22.0 / 25.5)
    assert span_reduce.lead_batches(
        span_reduce.clip(_by_hand(), 0, 50 * MS)) is None    # one take
    assert span_reduce.quantile([12.0, 32.0], 0.5) == 22.0
    assert span_reduce.quantile([3, 1, 2], 0.5) == 2
    assert span_reduce.quantile([1, 2, 3, 4], 0.95) == pytest.approx(3.85)
    assert span_reduce.quantile([], 0.5) is None


def _read(name, ctx, monkeypatch, records):
    monkeypatch.setattr(span_reduce, 'ring_records', lambda: records)
    mod = harness.load_module(os.path.join(PERFBENCH, 'metrics', name + '.py'))
    return mod.read(ctx)


@pytest.mark.parametrize('name, expected', [
    # waits 8 + 1 + 10 and 34 - 25 under no span, of 34
    ('reader.idle_poll_cpu_share', 100.0 * (19 + 9) / 34),
    ('collate.self_ms_per_batch', (9 + 20) / 2),
    ('collate.arena_wait_share', 5.0),
    ('dispatch.fence_wait_ms_per_batch', 2.0),
    ('consumer.queue_lead_batches', 22.0 / 25.5),
    # the compilation that ended at 73 ms; the one before 0 was set-up's
    ('step.compiles_in_window', 1)])
def test_each_reader_by_hand(name, expected, monkeypatch):
    ctx = {'begin': {'t': 0.0}, 'end': {'t': 0.100}}
    assert _read(name, ctx, monkeypatch, _by_hand()) == pytest.approx(expected)


@pytest.mark.parametrize('name', SPAN_METRICS)
def test_a_program_without_the_ring_reads_nothing_and_does_not_raise(
        name, monkeypatch):
    ctx = {'begin': {'t': 0.0}, 'end': {'t': 0.1}}
    assert _read(name, ctx, monkeypatch, None) is None
    assert _read(name, ctx, monkeypatch, []) is None
    # a ring that holds nothing of the metric's site
    other = [span('something.else', -1, 2)]
    if name != 'step.compiles_in_window':
        assert _read(name, ctx, monkeypatch, other) is None


@pytest.mark.parametrize('name', SPAN_METRICS)
def test_a_ring_that_wrapped_inside_the_window_reads_nothing(
        name, monkeypatch):
    ctx = {'begin': {'t': 0.0}, 'end': {'t': 0.100}}
    assert _read(name, ctx, monkeypatch, _by_hand()) is not None
    # its oldest record closed at 30 ms: what closed before that is gone
    assert _read(name, ctx, monkeypatch, _by_hand()[1:]) is None


def test_the_parent_s_tracer_has_no_records():
    class Old(object):          # the tracer a commit before the ring returns
        def span(self, name, cat='pipeline'):
            return None

    from petastorm_tpu import trace
    previous = trace.set_global_tracer(Old())
    try:
        assert span_reduce.ring_records() is None
    finally:
        trace.set_global_tracer(previous)


@pytest.fixture(scope='module')
def recorded():
    with open(os.path.join(HERE, 'data', 'small.spans.json')) as f:
        return json.load(f)


def test_recorded_list_agrees_with_the_loader_s_own_totals(recorded):
    w = span_reduce.clip(recorded['records'], recorded['t0_ns'],
                         recorded['t1_ns'])
    stats = recorded['stats']
    assert w['covered']
    self_s, count = span_reduce.self_seconds(w['spans'], 'collate.batch')
    # a span open where the window opens is cut by the clip and counted whole
    # by loader.stats, whose totals are fed when a span closes
    assert self_s == pytest.approx(stats['assemble_s'], abs=5e-3)

    def closed_in_the_window(name):
        return sum(r[3] for r in recorded['records']
                   if r[0] == name and r[3] is not None and
                   recorded['t0_ns'] <= r[2] + r[3] <= recorded['t1_ns']) / 1e9

    # (loader.stats rounds to a tenth of a millisecond)
    for name, key in (('collate.arena_wait', 'arena_wait_s'),
                      ('collate.reader_wait', 'reader_wait_s'),
                      ('dispatch.stage', 'stage_dispatch_s'),
                      ('consumer.wait', 'wait_s')):
        assert closed_in_the_window(name) == pytest.approx(stats[key],
                                                           abs=2e-4), name
    assert stats['arena_wait_s'] > 0.05 and stats['assemble_s'] >= 0
    delivered = [i for i in w['instants'] if i[0] == 'consumer.deliver']
    assert len(delivered) == stats['batches'] and count >= 1
    assert len(span_reduce.residency_ms(w)) == stats['batches']
    # one arena and a consumer that holds what it took: no lead to speak of
    assert 0 <= span_reduce.lead_batches(w) < 2


@pytest.mark.parametrize('name', SPAN_METRICS)
def test_each_reader_on_the_recorded_list(name, recorded, monkeypatch):
    ctx = {'begin': {'t': recorded['t0_ns'] / 1e9},
           'end': {'t': recorded['t1_ns'] / 1e9}}
    value = _read(name, ctx, monkeypatch, recorded['records'])
    assert value is not None and value >= 0
    if name.endswith('_share'):
        assert value <= 100.0
    if name == 'step.compiles_in_window':
        assert value == 0
    if name == 'collate.arena_wait_share':
        assert value > 5.0


def record(path):
    """Twelve batches of eight rows over four virtual CPU devices with one
    arena, the consumer holding what it took: the window opens after the
    fourth."""
    import tempfile
    import time

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from petastorm_tpu import make_tensor_reader, trace
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.jax_loader import JaxLoader
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('Small', [
        UnischemaField('id', np.int32, (), ScalarCodec(np.int32), False),
        UnischemaField('x', np.float32, (64, 64), NdarrayCodec(), False)])
    rng = np.random.default_rng(0)
    rows = [{'id': np.int32(i),
             'x': rng.random((64, 64), dtype=np.float32)} for i in range(96)]
    tracer = trace.Tracer()
    trace.set_global_tracer(tracer)
    with tempfile.TemporaryDirectory() as folder:
        url = 'file://' + folder + '/store'
        write_dataset(url, schema, rows, rows_per_row_group=12)
        mesh = Mesh(np.asarray(jax.devices()[:4]), ('data',))
        with make_tensor_reader(url, reader_pool_type='thread',
                                workers_count=2, num_epochs=None,
                                shuffle_row_groups=False) as reader:
            with JaxLoader(reader, 8, mesh=mesh, arena_depth=1, prefetch=2,
                           inflight=1) as loader:
                held = []
                for n, batch in enumerate(loader):
                    held.append(batch)
                    if n == 3:
                        loader.reset_stats()
                        t0_ns = time.perf_counter_ns()
                    if n == 15:
                        break
                t1_ns = time.perf_counter_ns()
                stats = loader.stats
    keep = ('batches', 'wait_s', 'stage_dispatch_s', 'assemble_s',
            'reader_wait_s', 'arena_wait_s')
    with open(path, 'w') as f:
        json.dump({'t0_ns': t0_ns, 't1_ns': t1_ns,
                   'stats': {k: stats[k] for k in keep},
                   'records': tracer.records()}, f)


if __name__ == '__main__':        # conftest has set the four CPU devices
    record(sys.argv[1])
