"""The trace reduction on hand-made events and on the small trace recorded on
the chip by ``perfbench/record_trace.py`` (``data/small.events.json``: the
recorded xplane's events as ``load_xplane`` read them there)."""

import json
import os

import pytest
from conftest import HERE, PERFBENCH

from perfbench import harness, trace_reduce

MS = 1000000


def _trace():
    # One chip. Steps of 10 ms at 0, 10, 30 and 40 ms: the device idles from
    # 20 to 30 ms while the host sits in next_batch (22..29) and
    # dispatch_step (29..30).
    ops = [('fusion.1', 0, 6 * MS), ('attn.2', 6 * MS, 4 * MS),
           ('fusion.1', 10 * MS, 6 * MS), ('attn.2', 16 * MS, 4 * MS),
           ('fusion.1', 30 * MS, 6 * MS), ('attn.2', 36 * MS, 4 * MS),
           ('fusion.1', 40 * MS, 6 * MS), ('attn.2', 46 * MS, 4 * MS)]
    host = {'next_batch': [('next_batch', 22 * MS, 7 * MS)],
            'dispatch_step': [('dispatch_step', 29 * MS, 1 * MS)],
            'await_step': [('await_step', 0, 0), ('await_step', 10 * MS, 10 * MS),
                           ('await_step', 30 * MS, 10 * MS),
                           ('await_step', 40 * MS, 10 * MS)]}
    return {'devices': {'/device:TPU:0': ops}, 'host': host}


def test_busy_idle_ops_and_gaps_by_hand():
    r = trace_reduce.reduce_trace(_trace())
    assert r['window_s'] == pytest.approx(0.050)
    assert r['busy_s'] == pytest.approx(0.040) and r['steps'] == 3
    assert dict(r['device_ops']) == pytest.approx({'fusion.1': 0.024,
                                                   'attn.2': 0.016})
    gaps = dict(r['idle_gaps'])
    assert gaps == pytest.approx({'next_batch': 0.007, 'dispatch_step': 0.001,
                                  'outside_loop_spans': 0.002})
    assert trace_reduce.kernel_seconds(r, r'^attn') == pytest.approx(0.016)
    assert trace_reduce.kernel_seconds(r, r'^nothing') is None


def _stages():
    # The gap (20..30 ms) by the program's own spans. Thread 1, the
    # consumer's: consumer.wait 22..28. Thread 2 collates: collate.batch
    # 18..29 holding collate.reader_wait 21..27. Threads 3 and 4 are pool
    # workers: decode.decode 19..24 inside reader.cache_get 18..25 on one,
    # decode.decode 23..26 on the other, then both in reader.take.
    return {1: [('consumer.wait', 22 * MS, 6 * MS)],
            2: [('collate.batch', 18 * MS, 11 * MS),
                ('collate.reader_wait', 21 * MS, 6 * MS)],
            3: [('reader.cache_get', 18 * MS, 7 * MS),
                ('decode.decode', 19 * MS, 5 * MS),
                ('reader.take', 25 * MS, 20 * MS)],
            4: [('decode.decode', 23 * MS, 3 * MS),
                ('reader.take', 26 * MS, 20 * MS)]}


def test_a_gap_is_charged_to_each_thread_s_span_and_the_loop_s_stay():
    plain = trace_reduce.reduce_trace(_trace())
    assert plain['stage_gaps'] is None
    r = trace_reduce.reduce_trace(dict(_trace(), stages=_stages()))
    # the loop's three entries and the rest come out as before
    for key in ('idle_gaps', 'busy_s', 'window_s', 'steps', 'device_ops'):
        assert r[key] == plain[key], key
    # every thread's span under the gap is charged, the innermost one of a
    # nest, and two workers in one stage are that stage once
    assert dict(r['stage_gaps']) == pytest.approx({
        'consumer.wait': 0.006,             # 22..28
        'collate.reader_wait': 0.006,       # 21..27
        'collate.batch': 0.003,             # 20..21 and 27..29: its self time
        'decode.decode': 0.006,             # 20..24 and 23..26: 20..26
        'reader.cache_get': 0.001,          # 24..25
        'reader.take': 0.005})              # 25..30 and 26..30: 25..30
    assert [name for name, _ in r['stage_gaps']][-1] == 'reader.cache_get'
    assert trace_reduce.reduce_trace(dict(_trace(), stages={}))['stage_gaps'] == []


def test_the_program_s_spans_take_the_loop_s_shift():
    ahead = 10 ** 12
    t = _trace()
    spans = [(tid, name, start + ahead, dur)
             for tid, events in _stages().items() for name, start, dur in events]
    assert trace_reduce.stages_on_the_trace_clock(
        spans, 50 * MS + ahead, t['devices']) == _stages()


def test_nested_spans_are_cut_to_the_innermost():
    cut = trace_reduce._innermost([('a', 0, 10), ('b', 2, 3), ('c', 3, 1),
                                   ('b', 6, 2), ('d', 12, 1)])
    assert cut == [(0, 2, 'a'), (2, 3, 'b'), (3, 4, 'c'), (4, 5, 'b'),
                   (5, 6, 'a'), (6, 8, 'b'), (8, 10, 'a'), (12, 13, 'd')]
    # a child that a clock read lets end after its parent loses nothing
    assert trace_reduce._innermost([('a', 0, 5), ('b', 3, 4)]) == [
        (0, 3, 'a'), (3, 7, 'b')]


def test_collective_time_counts_a_start_and_done_pair_once():
    mod = harness.load_module(os.path.join(
        PERFBENCH, 'metrics', 'step.collective_ms_per_step.py'))
    per_op = {'all-reduce-start.3_f32_64_': 0.002, 'all-reduce-done.3_f32_64_': 0.004,
              'all-reduce.7_f32_1000_': 0.001, 'all-gather.1_bf16_8_': 0.0005,
              'collective-permute-start_f32_2_': 0.0005,
              'fusion.14_f32_256_': 0.5, 'all-reduce-scatter-like-fusion': 0.3}
    # 8 ms over four steps: a pair's two operations are its time once, and a
    # name that merely holds the word is not a collective
    assert mod.read({'trace': {'per_op_s': per_op, 'steps': 4},
                     'trace_reduce': trace_reduce}) == pytest.approx(2.0)
    # one chip: no collective in the trace, nothing to read (never 0)
    assert mod.read({'trace': {'per_op_s': {'fusion.14_f32_256_': 0.5},
                               'steps': 4},
                     'trace_reduce': trace_reduce}) is None
    assert mod.read({'trace': None}) is None


def test_two_chips_are_averaged_and_overlap_is_not_counted_twice():
    t = _trace()
    t['devices']['/device:TPU:1'] = [('fusion.1', 0, 30 * MS),
                                     ('copy.3', 10 * MS, 10 * MS)]
    r = trace_reduce.reduce_trace(t, window=(0, 50 * MS))
    assert r['chips'] == 2
    assert r['busy_s'] == pytest.approx((0.040 + 0.030) / 2)


def test_host_spans_are_pinned_to_the_trace_where_the_last_step_ends():
    # The host's clock reads 1,000,000 ms more than the trace's; the host saw
    # the last step done when the last device operation ended, at 50 ms.
    t = _trace()
    ahead = 10 ** 12
    spans = [(name, start + ahead, dur)
             for events in t['host'].values() for name, start, dur in events]
    host = trace_reduce.spans_on_the_trace_clock(spans, 50 * MS + ahead,
                                                 t['devices'])
    assert host == t['host']
    with pytest.raises(RuntimeError):
        trace_reduce.spans_on_the_trace_clock(spans, 0, {})


def test_no_device_operation_is_an_error():
    with pytest.raises(RuntimeError):
        trace_reduce.reduce_trace({'devices': {}, 'host': {}})


def test_roofline_share_says_which_bound():
    peak = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
    share, bound = trace_reduce.roofline_share(197e12, 1e9, 2.0, peak)
    assert bound == 'compute' and share == pytest.approx(50.0)
    share, bound = trace_reduce.roofline_share(1e9, 819e9, 4.0, peak)
    assert bound == 'memory' and share == pytest.approx(25.0)


def test_recorded_chip_trace():
    path = os.path.join(HERE, 'data', 'small.events.json')
    trace = json.load(open(path))
    trace['devices'] = {k: [tuple(e) for e in v]
                        for k, v in trace['devices'].items()}
    trace['host'] = {k: [tuple(e) for e in v] for k, v in trace['host'].items()}
    assert len(trace['host']['await_step']) == 7
    r = trace_reduce.reduce_trace(trace)
    expected = json.load(open(os.path.join(HERE, 'data', 'small.reduced.json')))
    assert r['busy_s'] == pytest.approx(expected['busy_s'])
    assert r['window_s'] == pytest.approx(expected['window_s'])
    # every other next_batch slept 4 ms with one step in flight: the device
    # idled, and the gaps are charged to next_batch before anything else
    assert 0 < r['busy_s'] < r['window_s']
    assert r['idle_gaps'][0][0] == 'next_batch'
    # with the program's spans beside them the loop's entries do not move
    staged = trace_reduce.reduce_trace(dict(trace, stages={
        7: [('consumer.wait', s, d) for _, s, d in trace['host']['next_batch']]}))
    assert staged['idle_gaps'] == r['idle_gaps']
    assert dict(staged['stage_gaps'])['consumer.wait'] == pytest.approx(
        dict(r['idle_gaps'])['next_batch'])


def test_the_xplane_itself_reads_to_the_same_events():
    loaded = trace_reduce.load_xplane(os.path.join(HERE, 'data',
                                                   'small.xplane.pb'))
    kept = json.load(open(os.path.join(HERE, 'data', 'small.events.json')))
    assert {k: [list(e) for e in v] for k, v in loaded['devices'].items()} \
        == kept['devices']
    assert {k: [list(e) for e in v] for k, v in loaded['host'].items()} \
        == kept['host']
    assert list(loaded['devices']) == ['/device:TPU:0']


def test_short_names():
    assert trace_reduce.short_name(
        '%fusion.20 = (f32[768,50257]{0,1:T(8,128)}, f32[768]{0}) fusion(f32[1]'
    ) == 'fusion.20_f32_768_50257_'
    assert trace_reduce.short_name(
        '%attn.53 = (bf16[192,1024,64]{2,1,0}, bf16[1]) custom-call(bf16[1])'
    ) == 'attn.53_bf16_192_1024_64_'
    assert trace_reduce.short_name('%x = f32[]{:T(128)} add(f32[] %a)') == 'x_f32_'
