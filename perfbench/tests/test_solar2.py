"""The Solar-Open2-250B configuration: its counts against hand sums, what
``BENCHMARK.json`` gained with it (pinned by name, not by place), its two
readers on a recorded table, its limits against the chip's readings, and the
harness end to end on the CPU at a tiny size of the same files."""

import json
import os

import numpy as np
import pytest
from conftest import PERFBENCH, ROOT, TINY, run_harness, tiny_benchmark

from perfbench import harness

NAME = 'solar-open2-250b-ctx8192'
CELL = 'solar2.tokens8k'
CUT = ['num_hidden_layers', 'gqa_layers', 'num_attention_heads',
       'num_key_value_heads', 'linear_attn_config', 'n_routed_experts',
       'vocab_size']
NEW_METRICS = ['kernel.kda_exact_ms_per_step', 'kernel.kda_exact_roofline']


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


def test_parameters_counted_by_hand(cfg, ref):
    d, v = 4096, 24576
    gqa = d * 128 * (16 + 2 + 2 + 16) + 16 * 128 * d + d
    kda = (3 * d * 2048 + 3 * 4 * 2048 + 2 * (d * 128 + 128 * 2048)
           + 16 + 2048 + d * 16 + 128 + 2048 * d + d)
    moe = d * 320 + 3 * d * 1280 + 8 * 3 * d * 1280 + d
    count = sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values())
    assert count == gqa + 3 * kda + 4 * moe + 2 * v * d + d \
        == cfg['parameters'] == 905759152


def test_operations_of_a_row_by_hand(cfg, ref):
    t, d, v, c, s, hd = 8192, 4096, 24576, 64, 16, 128
    pairs = 4 * 16 * 17 // 2                            # a chunk's own
    rule = 2 * c * (c - s) * hd + 7 * pairs * hd + 3 * c * c * hd \
        + 6 * c * hd * hd
    kda = t * 2 * (4 * d * 2048 + 2 * (d * 128 + 128 * 2048) + d * 16) \
        + 16 * (t // c) * rule
    gqa = t * 2 * d * 128 * (3 * 16 + 2 * 2) + 16 * t * t * 2 * 128
    expected = t * 8 * 8 // 320                         # 205 an expert held
    moe = t * 2 * (3 * d * 1280 + d * 320) + expected * 2 * 3 * d * 1280
    forward = t * 2 * d * v + gqa + 3 * kda + 4 * moe
    assert ref.expected_pairs_per_row(cfg) == expected == 1638
    assert ref.forward_flops_per_row(cfg) == forward
    assert ref.train_flops_per_row(cfg) == 3 * forward
    # the reckoning of the issue: mixers about 45 % of a row's forward work,
    # the head 30 %, the shared experts 19 %, the routed ones 4 %
    assert 0.40 < (gqa + 3 * kda) / forward < 0.50
    assert 0.27 < t * 2 * d * v / forward < 0.33
    assert 0.17 < 4 * t * 2 * 3 * d * 1280 / forward < 0.21
    assert 0.03 < 4 * expected * 2 * 3 * d * 1280 / forward < 0.05


def test_the_kernels_work_by_hand(cfg, ref):
    k = ref.kernels(cfg, 1)
    assert (k['kda_exact']['match'], k['moe']['match'],
            k['flash']['match']) == ('^kda_exact', '^moe', '^attn')
    assert 'kda' not in k               # the bounded path does not apply
    c, s, hd = 64, 16, 128
    pairs = 4 * 16 * 17 // 2
    forward = 2 * c * (c - s) * hd + 7 * pairs * hd + 3 * c * c * hd \
        + 6 * c * hd * hd
    reverse = 12 * c * hd * hd + 6 * c * c * hd + 8 * c * (c - s) * hd \
        + 15 * pairs * hd
    chunks = 3 * 16 * (8192 // c)                       # layers, heads, chunks
    assert k['kda_exact']['flops'] == chunks * (forward + reverse)
    wide = c * hd
    f_bytes = 3 * wide * 2 + wide * 4 + c * 4 + wide * 2 + hd * hd * 2 \
        + c * c * 2
    r_bytes = f_bytes + 3 * wide * 2 + wide * 4 + c * 4
    assert k['kda_exact']['bytes'] == chunks * (f_bytes + r_bytes)
    # memory-bound: more time at the HBM's rate than at the MXU's
    assert k['kda_exact']['bytes'] / 819e9 > k['kda_exact']['flops'] / 197e12
    assert ref.kernels(cfg, 2)['kda_exact']['flops'] == \
        2 * k['kda_exact']['flops']
    # four expert layers at 1,638 pairs: four passes of two products
    assert k['moe']['flops'] == 4 * 4 * 1638 * 2 * 3 * 4096 * 1280
    assert ref.kernels(cfg, 1, moe_pairs_per_step=4 * 1638) == k
    # one gated attention layer of 16 query heads at 8,192 x 128
    assert k['flash'] == {'match': '^attn',
                          'flops': 16 * 7 * 128 * 8192 * 8192,
                          'bytes': 16 * 8192 * 8 * 128 * 2}


def test_the_file_states_the_cut_and_the_source_s_keys(cfg, bench):
    entry = [c for c in bench['configs'] if c['name'] == NAME][0]
    assert entry['reduced'] == cfg['reduced'] == CUT
    assert entry['source'] == cfg['source']
    assert entry['file'] == 'perfbench/configs/' + NAME + '.json'
    # MODEL_CATALOG: a JSON-lines catalog of published model configs
    catalog = os.environ.get('MODEL_CATALOG', '')
    if catalog and os.path.exists(catalog):
        row = [json.loads(line) for line in open(catalog)
               if '"Solar-Open2-250B"' in line][0]
        assert row['source_url'] == cfg['source']
        for key, value in row['config'].items():
            if key in CUT:
                assert cfg[key] != value and cfg['published'][key] == value
            else:
                assert cfg[key] == value, key
    # one whole period, eight experts, an eighth of the vocabulary, a
    # quarter of the mixers' heads; no width among the cut keys
    assert cfg['num_hidden_layers'] == cfg['gqa_interval'] + 1 == 4
    assert cfg['gqa_layers'] == [0]
    assert cfg['published']['gqa_layers'][:2] == [0, 4]
    assert cfg['n_routed_experts'] == len(cfg['assumed']['experts_held']) == 8
    assert 8 * cfg['vocab_size'] == cfg['published']['vocab_size']
    assert 4 * cfg['num_attention_heads'] == \
        cfg['published']['num_attention_heads']
    assert 4 * cfg['num_key_value_heads'] == \
        cfg['published']['num_key_value_heads']
    lin, published = cfg['linear_attn_config'], \
        cfg['published']['linear_attn_config']
    assert 4 * lin['num_heads'] == published['num_heads']
    assert {k: v for k, v in lin.items() if k != 'num_heads'} == \
        {k: v for k, v in published.items() if k != 'num_heads'}
    # the published widths
    assert (cfg['hidden_size'], cfg['head_dim'], lin['head_dim'],
            cfg['moe_intermediate_size'], cfg['num_experts_per_tok'],
            cfg['assumed']['low_rank']) == (4096, 128, 128, 1280, 8, 128)
    assert cfg['published']['n_routed_experts'] == 320
    assert not any(key.endswith(('_dim', '_rank', '_size')) and key != 'vocab_size'
                   for key in CUT)
    for key in ('deployment', 'departures', 'hbm_reckoning', 'limits_from',
                'assumed'):
        assert cfg[key], key
    for key in ('sequence_length', 'rows_per_chip_per_step', 'optimizer',
                'init', 'rows_per_row_group', 'experts_held', 'chunk',
                'sub_block', 'low_rank', 'decay', 'beta', 'output_gate',
                'gqa_gate', 'router', 'routing_bias', 'precision'):
        assert key in cfg['assumed'], key
    assert '40 chips share each layer' in cfg['deployment']
    assert 'a tenth' in cfg['deployment']
    a = cfg['assumed']
    assert (a['sequence_length'], a['rows_per_chip_per_step'],
            a['rows_per_row_group'], a['chunk'], a['sub_block']) == (
                8192, 1, 8, 64, 16)


def test_what_the_benchmark_gained(bench):
    cells = {w['name']: w for w in bench['workloads']}
    new = cells[CELL]
    assert (new['config'], new['traffic'], new['chips']) == (
        NAME, 'token-rows-8k', 1)
    assert len(new['why']) <= 200 and '40-way' in new['why'] \
        and 'exact unbounded decay' in new['why']
    # one configuration, one cell, two per-layer metrics, each found by its
    # name wherever later additions put it
    assert [c['name'] for c in bench['configs']].count(NAME) == 1
    assert [w['config'] for w in bench['workloads']].count(NAME) == 1
    by_name = {m['name']: m for m in bench['per_layer']}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m['workloads'] == [CELL]
        assert m['moves'] == 'rows_per_s_per_chip'
        assert (m['source'], m['layer']) == ('device_trace', 'kernel')
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
        assert os.path.exists(os.path.join(PERFBENCH, 'metrics',
                                           name + '.py'))
    assert (by_name['kernel.kda_exact_roofline']['unit'],
            by_name['kernel.kda_exact_roofline']['better']) == ('%', 'higher')
    assert (by_name['kernel.kda_exact_ms_per_step']['unit'],
            by_name['kernel.kda_exact_ms_per_step']['better']) == ('ms',
                                                                   'lower')
    # no accepted list names the cell: a benchmark PR's to widen
    for m in bench['per_layer']:
        if m['name'] not in NEW_METRICS:
            assert CELL not in m.get('workloads', ())
    assert bench['run_seconds'] == 30 and len(bench['end_to_end']) == 3


def test_the_readers_on_a_recorded_table(ref, cfg):
    from perfbench import trace_reduce
    peak = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
    ctx = {'trace': {'steps': 8, 'per_op_s': {
        'kda_exact.22_bf16_1_8192_2048_': 0.4,
        'kda_exact.17_bf16_1_8192_2048_': 0.56, 'kda.3': 5.0,
        'attn.4': 0.1, 'moe.3': 0.05, 'fusion.1': 1.0}},
        'ref': ref, 'cfg': cfg, 'batch': 1, 'chips': 1, 'peak': peak,
        'trace_reduce': trace_reduce}
    ms, share = (harness.load_module(os.path.join(
        PERFBENCH, 'metrics', name + '.py')).read for name in NEW_METRICS)
    # the bounded path's events are not the exact path's
    assert ms(ctx) == pytest.approx(120.0)
    k = ref.kernels(cfg, 1)['kda_exact']
    assert share(ctx) == pytest.approx(100 * k['bytes'] / 819e9 / 0.120)

    class Older(object):                # a reference with no exact kernel
        @staticmethod
        def kernels(cfg, rows):
            return {'kda': {'match': '^kda'}}

    # a program before the exact path, or a trace without its events, gives
    # nothing and does not raise
    for other in (dict(ctx, trace=None), dict(ctx, ref=Older),
                  dict(ctx, ref=object()),
                  dict(ctx, trace={'steps': 8, 'per_op_s': {'kda.1': 1.0}})):
        assert ms(other) is None and share(other) is None
    assert share(dict(ctx, peak=None)) is None


def test_limits_lie_between_their_readings(cfg):
    limits = cfg['limits']
    assert limits['rows_wrong'] == limits['rows_uneven'] == \
        limits['shards_misplaced'] == 0
    for name, limit in limits.items():
        if limit == 0:
            continue
        read = cfg['limits_from'][name]
        assert read['lower'] < limit < read['upper'], name
        # room on both sides
        assert limit >= 1.4 * read['lower'] and read['upper'] >= 1.4 * limit, name


def test_the_limits_part_the_recorded_readings(cfg):
    """The chip's readings, as ``perfbench.run`` (sound) and
    ``perfbench.calibrate`` (the fp8 control, half of the row left out)
    printed them, through ``check.verdict`` under the file's own limits:
    every sound run correct, every control and fault not."""
    from perfbench import check
    readings = json.load(open(os.path.join(PERFBENCH, 'tests', 'data',
                                           'solar2-readings.json')))
    readings.pop('what')
    limits = {k: v for k, v in cfg['limits'].items() if k.endswith('_gap')
              or '_gap_' in k}
    assert len(readings['sound']) >= 5 and len(readings['control_fp8']) >= 2 \
        and len(readings['fault_half_batch']) >= 2
    assert len({r['seed'] for r in readings['sound']}) >= 5
    for kind, rows in readings.items():
        for numbers in rows:
            table, correct = check.verdict(
                {k: v for k, v in numbers.items() if k != 'seed'}, limits)
            assert correct == (kind == 'sound'), (kind, numbers['seed'], table)
    for name in limits:
        read = cfg['limits_from'][name]
        assert read['lower'] >= max(r[name] for r in readings['sound']) * 0.999


def test_the_harness_runs_the_configuration_s_files_at_a_tiny_size(tmp_path):
    path = tiny_benchmark(tmp_path)
    bench = json.load(open(path))
    bench['configs'].append({
        'name': 'tiny-solar2', 'source': 'tests', 'reduced': [],
        'why': 'tests', 'file': os.path.join(TINY, 'tiny-solar2.json')})
    bench['workloads'].append({'name': 'tiny.solar2',
                               'config': 'tiny-solar2',
                               'traffic': 'tiny-tokens', 'chips': 1,
                               'why': 'tests'})
    for m in bench['per_layer']:
        if m['name'] in NEW_METRICS:
            m['workloads'] = ['tiny.solar2']
    json.dump(bench, open(path, 'w'), indent=1)
    rc, out, err = run_harness(path, 'tiny.solar2', '--rehearse', trace=1,
                               seconds=4, seed=4200000019)
    assert rc == 0, err[-3000:]
    result = json.loads(out[-1])
    assert result['correct'] is True and result['failed'] == 0
    names = {n.replace('.cpu_rehearsal', '') for n in result['metrics']}
    # a CPU trace has no device plane: the two kernel metrics leave
    # themselves out, the host's metrics are read
    assert not set(NEW_METRICS) & names
    assert 'host.cpu_ms_per_row' in names
    for name, (value, limit) in result['compared'].items():
        assert limit is None or value <= limit, name
    tiny = json.load(open(os.path.join(TINY, 'tiny-solar2.json')))
    real = json.load(open(os.path.join(PERFBENCH, 'configs', NAME + '.json')))
    assert set(tiny) - {'reference_file', 'program_file', 'limits_why'} \
        == set(real) - {'limits_notes'}
    assert set(tiny['limits']) == set(real['limits'])
    for key in ('num_hidden_layers', 'gqa_layers', 'gqa_interval',
                'routed_scaling_factor', 'rms_norm_eps', 'use_rope',
                'kda_allow_neg_eigval', 'kda_use_full_proj'):
        assert tiny[key] == real[key], key
    # and a step that hands back the state it was given is not correct
    rc, out, err = run_harness(path, 'tiny.solar2', '--rehearse', '--fault',
                               'state_unchanged', seed=7)
    assert rc == 0, err[-3000:]
    faulty = json.loads(out[-1])
    assert faulty['correct'] is False
    assert faulty['compared']['update_gap_median'][0] > \
        tiny['limits']['update_gap_median']
