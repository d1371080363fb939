"""The Olmo-Hybrid configuration: its counts against hand sums, what
``BENCHMARK.json`` gained with it, and the harness end to end on the CPU at
a tiny size of the same files."""

import json
import os

import pytest
from conftest import PERFBENCH, ROOT, TINY, run_harness, tiny_benchmark

from perfbench import harness

NAME = 'olmo-hybrid-7b-ctx8192'
CUT = ['num_hidden_layers', 'layer_types', 'num_attention_heads',
       'num_key_value_heads', 'linear_num_key_heads', 'linear_num_value_heads',
       'vocab_size']


@pytest.fixture(scope='module')
def cfg():
    return json.load(open(os.path.join(PERFBENCH, 'configs', NAME + '.json')))


@pytest.fixture(scope='module')
def ref():
    return harness.load_module(os.path.join(PERFBENCH, 'configs',
                                            NAME + '.reference.py'))


@pytest.fixture(scope='module')
def bench():
    return json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))


def test_parameters_are_the_issue_s_table(cfg, ref):
    d, f, h = 3840, 11008, 15
    swiglu = 3 * d * f
    linear = (2 * d * h * 96 + 2 * d * h * 192 + 2 * d * h      # q k, v gate, a b
              + h * 192 * d                                     # out
              + 4 * h * (96 + 96 + 192)                         # conv taps
              + 2 * h + 192)                                    # A_log, dt_bias, norm
    full = 4 * d * h * 128 + 2 * h * 128                        # q k v out, q/k norms
    norms = 2 * d
    period = 3 * (linear + swiglu + norms) + full + swiglu + norms
    by_hand = period + 2 * 12544 * d + d                        # embedding, head, final norm
    count = sum(int(__import__('numpy').prod(s))
                for s in ref.param_shapes(cfg).values())
    assert count == by_hand == cfg['parameters'] == 766241946
    # the table of ISSUE 29, in millions: 126.8, 44.4, 29.5, 670 a period, 96.3
    assert round(swiglu / 1e6, 1) == 126.8 and round(linear / 1e6, 1) == 44.4
    assert round(full / 1e6, 1) == 29.5 and round(period / 1e6) == 670
    assert round(2 * 12544 * d / 1e6, 1) == 96.3
    # at 16 bytes a parameter (f32 weight, gradient, AdamW's two moments)
    assert round(16 * count / 1e9, 2) == 12.26


def test_operations_of_a_row_by_hand(cfg, ref):
    t, d, f, h, v = 8192, 3840, 11008, 15, 12544
    rule = 64 * 64 * (3 * 96 + 2 * 192) + 6 * 64 * 96 * 192     # a head's chunk
    linear = t * 2 * d * h * (2 * 96 + 3 * 192 + 2) + h * 128 * rule
    full = t * 2 * 4 * d * h * 128 + h * 2 * t * t * 128        # causal: halved
    forward = 4 * t * 2 * 3 * d * f + 3 * linear + full + t * 2 * d * v
    assert ref.forward_flops_per_row(cfg) == forward
    assert ref.train_flops_per_row(cfg) == 3 * forward
    assert round(3 * forward / 1e12, 1) == 36.2                 # ISSUE 29: 36 TFLOP a step


def test_the_kernels_work_by_hand(cfg, ref):
    k = ref.kernels(cfg, 1)
    chunks = 3 * 15 * 128                   # layers x heads x chunks a row
    assert k['gdn']['match'] == '^gdn'
    # the pass over chunks alone, which is what runs under the name: with
    # the state (96 x 192) three products forward and six in reverse, under
    # the mask (64 x 64, halved) one and two
    state, masked = 2 * 64 * 96 * 192, 64 * 64 * 192
    assert k['gdn']['flops'] == chunks * (9 * state + 3 * masked)
    # bf16, each array once: forward qg kg w, p, u in and o, v_new, h out;
    # in reverse do, v_new, qg kg w, p, h in and dqg dkg dw, dp, du out;
    # three f32 rows of 192 (the chunk's decay twice, its gradient)
    forward = 3 * 64 * 96 + 64 * 64 + 3 * 64 * 192 + 96 * 192
    reverse = 6 * 64 * 96 + 2 * 64 * 64 + 3 * 64 * 192 + 96 * 192
    assert k['gdn']['bytes'] == chunks * (2 * (forward + reverse) + 12 * 192)
    # memory-bound: 2.52 ms of HBM against 0.69 ms of MXU a step
    assert round(1e3 * k['gdn']['bytes'] / 819e9, 2) == 2.52
    assert round(1e3 * k['gdn']['flops'] / 197e12, 2) == 0.69
    # less than the whole rule's products, which step.mfu counts
    rule = 64 * 64 * (3 * 96 + 2 * 192) + 6 * 64 * 96 * 192
    assert k['gdn']['flops'] < 3 * chunks * rule
    assert k['flash'] == {'match': '^attn',
                          'flops': 15 * 7 * 8192 * 8192 * 128,
                          'bytes': 15 * 8 * 8192 * 128 * 2}
    assert ref.kernels(cfg, 2)['gdn']['flops'] == 2 * k['gdn']['flops']


def test_the_file_states_the_cut_and_the_source_s_keys(cfg, bench):
    entry = [c for c in bench['configs'] if c['name'] == NAME][0]
    assert entry['reduced'] == cfg['reduced'] == CUT
    assert entry['source'] == cfg['source']
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        row = [json.loads(line) for line in open(catalog)
               if '"Olmo-Hybrid-7B"' in line][0]
        assert row['source_url'] == cfg['source']
        for key, value in row['config'].items():
            if key in CUT:
                assert cfg[key] != value and key in cfg['published']
            else:
                assert cfg[key] == value, key
        # no width is cut, and a period is whole and in the published order
        assert cfg['layer_types'] == row['config']['layer_types'][:4]
    assert cfg['num_hidden_layers'] == len(cfg['layer_types']) == 4
    assert cfg['head_dim'] * cfg['published']['num_attention_heads'] == \
        cfg['hidden_size']
    assert 8 * cfg['vocab_size'] == cfg['published']['vocab_size']
    for key in ('deployment', 'departures', 'hbm_reckoning', 'limits_from'):
        assert cfg[key], key
    for key in ('sequence_length', 'rows_per_chip_per_step', 'chunk',
                'optimizer', 'init', 'rows_per_row_group'):
        assert key in cfg['assumed'], key
    assert (cfg['assumed']['sequence_length'], cfg['assumed']['chunk'],
            cfg['assumed']['rows_per_chip_per_step']) == (8192, 64, 1)


def test_limits_lie_between_their_readings(cfg):
    limits = cfg['limits']
    assert limits['rows_wrong'] == limits['rows_uneven'] == \
        limits['shards_misplaced'] == 0
    assert any(k.startswith('grad_gap') for k in limits)
    assert any(k.startswith('update_gap') for k in limits)
    for name, limit in limits.items():
        if limit == 0:
            continue
        read = cfg['limits_from'][name]
        assert read['lower'] < limit < read['upper'], name


def test_the_limits_part_the_recorded_readings(cfg):
    """The chip's readings, as ``perfbench.run`` (sound) and
    ``perfbench.calibrate`` (the fp8 control, half of the row left out)
    printed them, through ``check.verdict`` under the file's own limits:
    every sound run correct, the control and the fault not."""
    from perfbench import check
    readings = json.load(open(os.path.join(PERFBENCH, 'tests', 'data',
                                           'olmo-hybrid-readings.json')))
    limits = {k: v for k, v in cfg['limits'].items() if k.endswith('_gap')
              or '_gap_' in k}
    assert len(readings['sound']) >= 14 and len(readings['control_fp8']) >= 3
    for kind, rows in readings.items():
        if kind == 'what':
            continue
        for numbers in rows:
            table, correct = check.verdict(
                {k: v for k, v in numbers.items() if k != 'seed'}, limits)
            assert correct == (kind == 'sound'), (kind, numbers['seed'], table)
    # the control fails by the median leaf and by the loss, a fault by all
    for numbers in readings['control_fp8']:
        assert numbers['grad_gap_median'] > limits['grad_gap_median']
        assert numbers['loss_gap'] > limits['loss_gap']
    for numbers in readings['fault_half_batch']:
        assert all(numbers[k] > v for k, v in limits.items())
    # and limits_from quotes these readings
    for name in limits:
        read = cfg['limits_from'][name]
        assert read['lower'] >= max(r[name] for r in readings['sound']) * 0.999
        others = readings['control_fp8'] + readings['fault_half_batch']
        assert min(r[name] for r in others if r[name] > limits[name]) \
            >= read['upper'] * 0.999


def test_what_the_benchmark_gained(bench):
    cells = {w['name']: w for w in bench['workloads']}
    new = cells['olmohybrid.tokens8k']
    assert (new['config'], new['traffic'], new['chips']) == (
        NAME, 'token-rows-8k', 1)
    # ISSUE 29's second cell, resnet50.decode, is the four-chip cell's
    # configuration and traffic file on one chip: the same pair twice, which
    # the contract refuses, so it stays first among the kept cells (PERF.md)
    assert 'resnet50.decode' not in cells
    pairs = [(w['config'], w['traffic']) for w in bench['workloads']]
    assert len(pairs) == len(set(pairs))
    # additions only, at the end of their lists
    assert [w['name'] for w in bench['workloads']] == [
        'resnet50.ramcache', 'gpt2s.tokens', 'resnet50.decode.x4',
        'olmohybrid.tokens8k']
    assert [c['name'] for c in bench['configs']][-1] == NAME
    assert [m['name'] for m in bench['per_layer']][-2:] == [
        'kernel.gdn_ms_per_step', 'kernel.gdn_roofline']
    for m in bench['per_layer'][-2:]:
        assert m['workloads'] == ['olmohybrid.tokens8k']
        assert (m['source'], m['layer']) == ('device_trace', 'kernel')
    by_name = {m['name']: m for m in bench['per_layer']}
    assert by_name['kernel.gdn_roofline']['unit'] == '%'
    assert by_name['kernel.flash_roofline']['workloads'] == ['gpt2s.tokens']
    # the pool's workers sit in one reader.publish all through this cell's
    # window (a row group is 64 steps), so the two readers that need a row
    # group read or two reader.thread_cpu marks a thread find nothing there:
    # they keep to the accepted cells, and every other listless metric is
    # this cell's too
    accepted = ['resnet50.ramcache', 'gpt2s.tokens', 'resnet50.decode.x4']
    for name in ('reader.idle_poll_cpu_share', 'cache.hit_share'):
        assert by_name[name]['workloads'] == accepted
    assert sum('workloads' not in m for m in bench['per_layer']) == 18
    # one cell in four asks for four chips
    assert [w['name'] for w in bench['workloads'] if w['chips'] == 4] == [
        'resnet50.decode.x4']
    traffic = json.load(open(os.path.join(PERFBENCH, 'traffic',
                                          'token-rows-8k.json')))
    assert (traffic['store_rows'], traffic['reader']['workers_count'],
            traffic['reader']['results_queue_size'],
            traffic['loader']['prefetch'], traffic['reader']['cache_type']) \
        == (1024, 4, 4, 2, 'null')


def test_the_readers_leave_themselves_out_where_there_is_nothing_to_read(ref, cfg):
    from perfbench import trace_reduce
    peak = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
    ctx = {'trace': {'steps': 8, 'per_op_s': {'gdn.9': 0.016, 'gdn.12': 0.032,
                                              'attn.4': 0.1, 'fusion.1': 1.0}},
           'ref': ref, 'cfg': cfg, 'batch': 1, 'chips': 1, 'peak': peak,
           'trace_reduce': trace_reduce}
    ms = harness.load_module(os.path.join(
        PERFBENCH, 'metrics', 'kernel.gdn_ms_per_step.py')).read
    share = harness.load_module(os.path.join(
        PERFBENCH, 'metrics', 'kernel.gdn_roofline.py')).read
    assert ms(ctx) == pytest.approx(6.0)
    bytes_s = ref.kernels(cfg, 1)['gdn']['bytes'] / 819e9
    assert share(ctx) == pytest.approx(100 * bytes_s / 0.006)

    class Older(object):                    # a reference with no gdn kernel
        @staticmethod
        def kernels(cfg, rows):
            return {'flash': {}}

    for other in (dict(ctx, trace=None), dict(ctx, ref=Older),
                  dict(ctx, ref=object()),
                  dict(ctx, trace={'steps': 8, 'per_op_s': {'fusion.1': 1.0}})):
        assert ms(other) is None and share(other) is None
    assert share(dict(ctx, peak=None)) is None


def test_the_harness_runs_the_configuration_s_files_at_a_tiny_size(tmp_path):
    path = tiny_benchmark(tmp_path)
    bench = json.load(open(path))
    bench['configs'].append({
        'name': 'tiny-hybrid', 'source': 'tests', 'reduced': [], 'why': 'tests',
        'file': os.path.join(TINY, 'tiny-hybrid.json')})
    bench['workloads'].append({'name': 'tiny.hybrid', 'config': 'tiny-hybrid',
                               'traffic': 'tiny-tokens', 'chips': 1,
                               'why': 'tests'})
    for m in bench['per_layer']:
        if m['name'].startswith('kernel.gdn'):
            m['workloads'] = ['tiny.hybrid']
    json.dump(bench, open(path, 'w'), indent=1)
    rc, out, err = run_harness(path, 'tiny.hybrid', '--rehearse', trace=1,
                               seconds=6, seed=3000000019)
    assert rc == 0, err[-3000:]
    result = json.loads(out[-1])
    assert result['correct'] is True and result['failed'] == 0
    names = {n.replace('.cpu_rehearsal', '') for n in result['metrics']}
    # a CPU trace has no device plane: the two kernel metrics leave
    # themselves out, the host's are read
    assert 'kernel.gdn_ms_per_step' not in names
    assert 'kernel.gdn_roofline' not in names
    assert 'host.cpu_ms_per_row' in names
    for name, (value, limit) in result['compared'].items():
        assert limit is None or value <= limit, name
    tiny = json.load(open(os.path.join(TINY, 'tiny-hybrid.json')))
    real = json.load(open(os.path.join(PERFBENCH, 'configs', NAME + '.json')))
    assert set(tiny) - {'reference_file', 'program_file', 'limits_why'} \
        == set(real) - {'limits_notes'}
    assert tiny['layer_types'] == real['layer_types']
    assert set(tiny['limits']) == set(real['limits'])
    # and a step that hands back the state it was given is not correct
    rc, out, err = run_harness(path, 'tiny.hybrid', '--rehearse', '--fault',
                               'state_unchanged', seed=7)
    assert rc == 0, err[-3000:]
    faulty = json.loads(out[-1])
    assert faulty['correct'] is False
    assert faulty['compared']['update_gap'][0] > tiny['limits']['update_gap']
