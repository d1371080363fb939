"""The Nemotron-3-Super configuration: its counts against hand sums, what
``BENCHMARK.json`` gained with it, its two readers on a recorded table, its
limits against the chip's readings, and the harness end to end on the CPU at
a tiny size of the same files."""

import json
import os

import numpy as np
import pytest
from conftest import PERFBENCH, ROOT, TINY, run_harness, tiny_benchmark

from perfbench import harness

NAME = 'nemotron3-super-ctx8192'
CELL = 'nemotron3.tokens8k'
CUT = ['num_hidden_layers', 'hybrid_override_pattern', 'mamba_num_heads',
       'n_groups', 'num_attention_heads', 'num_key_value_heads',
       'n_routed_experts', 'vocab_size', 'num_nextn_predict_layers']
NEW_METRICS = ['kernel.ssd_ms_per_step', 'kernel.ssd_roofline']


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
    d = 4096
    mamba = (d * (2 * 32 * 64 + 2 * 2 * 128 + 32)      # z, x, B, C, dt
             + 4 * (2048 + 512) + (2048 + 512)          # the convolution
             + 3 * 32 + 2048                            # dt_bias, A_log, D, norm
             + 2048 * d + d)                            # out_proj, block norm
    attention = d * 8 * 128 + 2 * d * 128 + 8 * 128 * d + d
    experts = (d * 512 + 2 * d * 1024 + 2 * d * 5376    # router, latent, shared
               + 8 * 2 * 1024 * 2688 + d)               # 8 experts, block norm
    vocabulary = 2 * 16384 * d + d
    assert (mamba, attention, experts) == (27413088, 9441280, 98570240)
    by_hand = 5 * mamba + attention + 5 * experts + vocabulary
    shapes = ref.param_shapes(cfg)
    count = sum(int(np.prod(s)) for s in shapes.values())
    assert count == by_hand == cfg['parameters'] == 773579744
    # at 16 bytes a parameter (f32 weight, gradient, AdamW's two moments)
    assert round(16 * count / 1e9, 2) == 12.38
    assert round(12 * count / 1e9, 2) == 9.28
    b = ('block_0', 'mixer')
    assert shapes[b + ('x_proj', 'kernel')] == (4096, 2048)
    assert shapes[b + ('b_proj', 'kernel')] == (4096, 256)
    assert shapes[b + ('dt_proj', 'kernel')] == (4096, 32)
    assert shapes[b + ('conv_x',)] == (4, 2048)
    assert shapes[b + ('norm', 'scale')] == (2048,)
    assert shapes[('block_7', 'attn', 'k_proj', 'kernel')] == (4096, 1, 128)
    assert shapes[('block_7', 'attn', 'o_proj', 'kernel')] == (8, 128, 4096)
    m = ('block_1', 'moe')
    assert shapes[m + ('router', 'kernel')] == (4096, 512)
    assert shapes[m + ('experts_up',)] == (8, 1024, 2688)
    assert shapes[m + ('experts_down',)] == (8, 2688, 1024)
    assert shapes[m + ('shared', 'up', 'kernel')] == (4096, 5376)
    assert ('block_1', 'mixer') + ('z_proj', 'kernel') not in shapes


def test_operations_of_a_row_by_hand(cfg, ref):
    t, d, v, c = 8192, 4096, 16384, 128
    rule = 2 * c * c * 128 + 32 * (c * c * 64 + 4 * c * 128 * 64)   # a chunk
    mamba = t * 2 * d * (3 * 2048 + 512 + 32) + (t // c) * rule
    attention = t * 2 * d * 128 * (16 + 2) + 8 * t * t * 2 * 128
    pairs = t * 22 * 8 // 512                       # 352 an expert held
    experts = t * 2 * (d * 512 + 2 * d * 1024 + 2 * d * 5376) \
        + pairs * 2 * 2 * 1024 * 2688
    forward = t * 2 * d * v + 5 * mamba + attention + 5 * experts
    assert ref.expected_pairs_per_row(cfg) == pairs == 2816
    assert ref.forward_flops_per_row(cfg) == forward
    assert ref.train_flops_per_row(cfg) == 3 * forward
    # 1.01 GFLOP a token forward
    assert round(forward / t / 1e9, 2) == 1.01
    # the shared expert the largest of an expert layer's products
    assert 2 * d * 5376 > max(d * 512, 2 * d * 1024,
                              pairs * 2 * 1024 * 2688 // t)


def test_the_kernels_work_by_hand(cfg, ref):
    k = ref.kernels(cfg, 1)
    assert k['ssd']['match'] == '^ssd' and k['moe']['match'] == '^moe' \
        and k['flash']['match'] == '^attn'
    c, p, n, h, g = 128, 64, 128, 32, 2
    chunks = 5 * (8192 // c)                            # layers, chunks
    forward = g * c * c * n + h * (c * c * p + 4 * c * n * p)
    reverse = g * 2 * c * c * n + h * (2 * c * c * p + 8 * c * n * p)
    assert k['ssd']['flops'] == chunks * (forward + reverse)
    # forward: x, dt in, y and the starting state out, B and C a group; in
    # reverse dy, x, dt and the state in, dx and d(dt) out, B, C in and dB,
    # dC out (float32)
    f_bytes = h * (c * p * 2 + c * 4 + c * p * 2 + n * p * 2) + g * 2 * c * n * 2
    r_bytes = h * (2 * c * p * 2 + c * 4 + n * p * 2 + c * p * 2 + c * 4) \
        + g * 2 * c * n * 6
    assert k['ssd']['bytes'] == chunks * (f_bytes + r_bytes)
    # memory-bound: more time at the HBM's rate than at the MXU's
    assert k['ssd']['bytes'] / 819e9 > k['ssd']['flops'] / 197e12
    assert ref.kernels(cfg, 2)['ssd']['flops'] == 2 * k['ssd']['flops']
    # five expert layers at 2,816 pairs: four passes of two products
    product = 5 * 2816 * 2 * 2 * 1024 * 2688
    assert k['moe']['flops'] == 4 * product
    assert ref.kernels(cfg, 1, moe_pairs_per_step=5 * 2816) == k
    # one attention layer of 8 query heads at 8,192 x 128
    assert k['flash'] == {'match': '^attn',
                          'flops': 8 * 7 * 128 * 8192 * 8192,
                          'bytes': 8 * 8192 * 8 * 128 * 2}


def test_the_file_states_the_cut_and_the_source_s_keys(cfg, bench):
    entry = [c for c in bench['configs'] if c['name'] == NAME][0]
    assert entry['reduced'] == cfg['reduced'] == CUT
    assert entry['source'] == cfg['source']
    assert entry['file'] == 'perfbench/configs/' + NAME + '.json'
    # MODEL_CATALOG: a JSON-lines catalog of published model configs
    catalog = os.environ.get('MODEL_CATALOG', '')
    if catalog and os.path.exists(catalog):
        row = [json.loads(line) for line in open(catalog)
               if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line][0]
        assert row['source_url'] == cfg['source']
        for key, value in row['config'].items():
            if key in CUT:
                assert cfg[key] != value and cfg['published'][key] == value
            else:
                assert cfg[key] == value, key
        assert row['config']['hybrid_override_pattern'][:11] == \
            cfg['hybrid_override_pattern']
    # one whole period, eight experts, an eighth of the vocabulary, a
    # quarter of the mixers' heads; no width among the cut keys
    assert cfg['num_hidden_layers'] == len(cfg['hybrid_override_pattern']) == 11
    assert sorted(cfg['hybrid_override_pattern']) == sorted('MMMMMEEEEE*')
    assert cfg['n_routed_experts'] == len(cfg['assumed']['experts_held']) == 8
    assert 8 * cfg['vocab_size'] == cfg['published']['vocab_size']
    for key in ('mamba_num_heads', 'n_groups', 'num_attention_heads'):
        assert 4 * cfg[key] == cfg['published'][key], key
    assert 2 * cfg['num_key_value_heads'] == cfg['published']['num_key_value_heads']
    assert not any(key.endswith(('_dim', '_rank', '_size')) and key != 'vocab_size'
                   for key in CUT)
    for key in ('deployment', 'departures', 'hbm_reckoning', 'limits_from'):
        assert cfg[key], key
    for key in ('sequence_length', 'rows_per_chip_per_step', 'optimizer',
                'init', 'rows_per_row_group', 'experts_held', 'chunk',
                'positions', 'dt', 'router', 'latent', 'shared_expert',
                'routing_bias', 'precision'):
        assert key in cfg['assumed'], key
    assert '64 chips share each layer' in cfg['deployment']
    assert 'a 16th' in cfg['deployment']
    a = cfg['assumed']
    assert (a['sequence_length'], a['rows_per_chip_per_step'],
            a['rows_per_row_group'], a['chunk']) == (8192, 1, 8, 128)
    assert a['chunk'] == cfg['chunk_size']


def test_what_the_benchmark_gained(bench):
    cells = {w['name']: w for w in bench['workloads']}
    new = cells[CELL]
    assert (new['config'], new['traffic'], new['chips']) == (
        NAME, 'token-rows-8k', 1)
    assert len(new['why']) <= 200 and '16th' in new['why'] \
        and 'full share' in new['why']
    # additions only, at the end of their lists: one configuration, one
    # cell, two per-layer metrics
    assert [w['name'] for w in bench['workloads']][-1] == CELL
    assert [c['name'] for c in bench['configs']][-1] == NAME
    assert [m['name'] for m in bench['per_layer']][-2:] == NEW_METRICS
    for m in bench['per_layer'][-2:]:
        assert m['workloads'] == [CELL]
        assert m['moves'] == 'rows_per_s_per_chip'
        assert (m['source'], m['layer']) == ('device_trace', 'kernel')
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    by_name = {m['name']: m for m in bench['per_layer']}
    assert by_name['kernel.ssd_roofline']['unit'] == '%'
    # the accepted lists stay as they were: a benchmark PR's to widen
    for m in bench['per_layer'][:-2]:
        assert CELL not in m.get('workloads', ())
    assert bench['run_seconds'] == 30 and len(bench['end_to_end']) == 3
    assert [w['name'] for w in bench['workloads'] if w['chips'] == 4] == [
        'resnet50.decode.x4']
    for name in NEW_METRICS:
        assert os.path.exists(os.path.join(PERFBENCH, 'metrics', name + '.py'))


def test_the_readers_on_a_recorded_table(ref, cfg):
    from perfbench import trace_reduce
    peak = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
    ctx = {'trace': {'steps': 8, 'per_op_s': {
        'ssd.22_bf16_1_8192_2048_': 0.4, 'ssd.17_bf16_1_8192_2048_': 0.56,
        'attn.4': 0.1, 'moe.3': 0.05, 'fusion.1': 1.0}},
        'ref': ref, 'cfg': cfg, 'batch': 1, 'chips': 1, 'peak': peak,
        'trace_reduce': trace_reduce}
    ms, share = (harness.load_module(os.path.join(
        PERFBENCH, 'metrics', name + '.py')).read for name in NEW_METRICS)
    assert ms(ctx) == pytest.approx(120.0)
    # the accepted held-pairs reader counts the expert blocks by kind
    assert sum('moe' in kind for kind in ref.layer_kinds(cfg)) == 5
    k = ref.kernels(cfg, 1)['ssd']
    assert share(ctx) == pytest.approx(100 * k['bytes'] / 819e9 / 0.120)

    class Older(object):                # a reference with no ssd kernel
        @staticmethod
        def kernels(cfg, rows):
            return {'flash': {}}

    # a program before the kernels, or a trace without their events, gives
    # nothing and does not raise
    for other in (dict(ctx, trace=None), dict(ctx, ref=Older),
                  dict(ctx, ref=object()),
                  dict(ctx, trace={'steps': 8, 'per_op_s': {'fusion.1': 1.0}})):
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
                                           'nemotron3-readings.json')))
    readings.pop('what')
    limits = {k: v for k, v in cfg['limits'].items() if k.endswith('_gap')
              or '_gap_' in k}
    assert len(readings['sound']) >= 2 and len(readings['control_fp8']) >= 2 \
        and len(readings['fault_half_batch']) >= 2
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
        'name': 'tiny-nemotron3', 'source': 'tests', 'reduced': [],
        'why': 'tests', 'file': os.path.join(TINY, 'tiny-nemotron3.json')})
    bench['workloads'].append({'name': 'tiny.nemotron3',
                               'config': 'tiny-nemotron3',
                               'traffic': 'tiny-tokens', 'chips': 1,
                               'why': 'tests'})
    for m in bench['per_layer']:
        if m['name'] in NEW_METRICS:
            m['workloads'] = ['tiny.nemotron3']
    json.dump(bench, open(path, 'w'), indent=1)
    rc, out, err = run_harness(path, 'tiny.nemotron3', '--rehearse', trace=1,
                               seconds=4, seed=3900000019)
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
    tiny = json.load(open(os.path.join(TINY, 'tiny-nemotron3.json')))
    real = json.load(open(os.path.join(PERFBENCH, 'configs', NAME + '.json')))
    assert set(tiny) - {'reference_file', 'program_file', 'limits_why'} \
        == set(real) - {'limits_notes'}
    assert set(tiny['limits']) == set(real['limits'])
    for key in ('num_hidden_layers', 'hybrid_override_pattern',
                'num_nextn_predict_layers', 'conv_kernel', 'routed_scaling_factor',
                'layer_norm_epsilon', 'mlp_hidden_act'):
        assert tiny[key] == real[key], key
    # and a step that hands back the state it was given is not correct
    rc, out, err = run_harness(path, 'tiny.nemotron3', '--rehearse', '--fault',
                               'state_unchanged', seed=7)
    assert rc == 0, err[-3000:]
    faulty = json.loads(out[-1])
    assert faulty['correct'] is False
    assert faulty['compared']['update_gap_median'][0] > \
        tiny['limits']['update_gap_median']
