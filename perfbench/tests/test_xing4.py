"""The Xing4.0-29B-A4B configuration: its counts against hand sums, what
``BENCHMARK.json`` gained with it, and the harness end to end on the CPU at
a tiny size of the same files."""

import json
import os

import numpy as np
import pytest
from conftest import PERFBENCH, ROOT, TINY, run_harness, tiny_benchmark

from perfbench import harness

NAME = 'xing4-29b-a4b-ctx4096'
CUT = ['num_hidden_layers', 'first_k_dense_replace', 'n_routed_experts',
       'num_attention_heads', 'num_key_value_heads', 'vocab_size']


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
    d, n, h = 3584, 4, 4
    attention = (d * 768 + 768 * h * 192          # W_DQ, W_UQ of the held heads
                 + d * 576 + 512 * h * 256        # W_DKV, W_UKV
                 + h * 128 * d)                   # W_O
    assert attention == 7766016
    # a sub-layer's stream maps: Phi_pre, Phi_post (nd x n), Phi_res (nd x
    # n^2); the norm over a token's n d values; three biases, three scalars
    maps = n * d * (2 * n + n * n) + n * d + 2 * n + n * n + 3
    norms = 768 + 512 + 2 * d                     # the latents', the sub-layers'
    block = attention + 2 * maps + norms
    dense = block + 3 * d * 9216
    expert_layer = block + 3 * d * 1024 + 8 * 3 * d * 1024 + d * 64
    vocabulary = 2 * 16384 * d
    next_token = expert_layer + 2 * d * d + 2 * d
    by_hand = dense + 4 * expert_layer + vocabulary + next_token + d
    shapes = ref.param_shapes(cfg)
    count = sum(int(np.prod(s)) for s in shapes.values())
    assert count == by_hand == cfg['parameters'] == 789778756
    # the table of ISSUE 33, in millions
    assert round(attention / 1e6, 2) == 7.77 and round(3 * d * 9216 / 1e6, 2) == 99.09
    assert round(dense / 1e6, 2) == 107.58 and round(expert_layer / 1e6, 2) == 107.81
    assert round(vocabulary / 1e6, 1) == 117.4 and round(next_token / 1e6, 1) == 133.5
    assert round(count / 1e6, 1) == 789.8
    # at 16 bytes a parameter (f32 weight, gradient, AdamW's two moments)
    assert round(16 * count / 1e9, 2) == 12.64 and round(12 * count / 1e9, 2) == 9.48
    # each leaf's shape, by name
    b = ('block_3',)
    assert shapes[b + ('attn', 'q_down', 'kernel')] == (3584, 768)
    assert shapes[b + ('attn', 'q_up', 'kernel')] == (768, 4, 192)
    assert shapes[b + ('attn', 'kv_down', 'kernel')] == (3584, 576)
    assert shapes[b + ('attn', 'kv_up', 'kernel')] == (512, 4, 256)
    assert shapes[b + ('attn', 'out', 'kernel')] == (4, 128, 3584)
    assert shapes[b + ('moe', 'router', 'kernel')] == (3584, 64)
    assert shapes[b + ('moe', 'experts_gate_up')] == (8, 3584, 2048)
    assert shapes[b + ('moe', 'experts_down')] == (8, 1024, 3584)
    assert shapes[b + ('moe', 'shared', 'down', 'kernel')] == (1024, 3584)
    assert shapes[b + ('ffn_hc', 'phi_res')] == (14336, 16)
    assert shapes[b + ('ffn_hc', 'norm', 'scale')] == (14336,)
    assert shapes[('block_0', 'mlp', 'gate', 'kernel')] == (3584, 9216)
    assert ('block_0', 'moe', 'router', 'kernel') not in shapes
    assert shapes[('mtp_0', 'eh_proj', 'kernel')] == (7168, 3584)
    assert shapes[('mtp_0', 'block', 'moe', 'experts_down')] == (8, 1024, 3584)
    assert ref.layer_kinds(cfg) == ['dense', 'moe', 'moe', 'moe', 'moe']


def test_operations_of_a_row_by_hand(cfg, ref):
    t, d, n, v = 4096, 3584, 4, 16384
    attention = t * 2 * 7766016 + 4 * t * t * (192 + 128)   # causal: halved
    maps = t * 2 * (n * d * 24 + d * 24)        # x~ Phi, and the three mixings
    block = attention + 2 * maps
    dense = t * 2 * 3 * d * 9216
    pairs = t * 4 * 8 // 64                     # 2,048: 256 an expert held
    experts = t * 2 * (3 * d * 1024 + d * 64) + pairs * 2 * 3 * d * 1024
    forward = (2 * t * 2 * d * v + 5 * block + dense + 4 * experts
               + block + experts + t * 2 * 2 * d * d)
    assert ref.expected_pairs_per_row(cfg) == pairs == 2048
    assert ref.forward_flops_per_row(cfg) == forward
    assert ref.train_flops_per_row(cfg) == 3 * forward
    assert round(3 * forward / 1e12, 2) == 9.67         # ISSUE 33: 9.6 TFLOP a step
    # ISSUE 33's parts, TFLOP a step: the dense feed-forward, the two
    # heads, the shared experts, the routed ones, W_eh
    assert round(3 * dense / 1e12, 2) == 2.44
    assert round(3 * 2 * t * 2 * d * v / 1e12, 2) == 2.89
    assert round(3 * 5 * t * 2 * 3 * d * 1024 / 1e12, 2) == 1.35
    assert round(3 * 5 * pairs * 2 * 3 * d * 1024 / 1e12, 2) == 0.68
    assert round(3 * t * 2 * 2 * d * d / 1e12, 2) == 0.63
    # what this PR adds (expert blocks, latent attention, streams, second
    # head with its module) is over half the count; the routed experts 7 %
    new = 3 * (6 * block + 5 * experts + t * 2 * d * v + t * 2 * 2 * d * d)
    assert new / (3 * forward) > 0.5
    assert round(100 * 3 * 5 * pairs * 2 * 3 * d * 1024 / (3 * forward)) == 7


def test_the_kernels_work_by_hand(cfg, ref):
    k = ref.kernels(cfg, 1)
    d, f, pairs = 3584, 1024, 2048
    assert k['moe']['match'] == '^moe' and k['flash']['match'] == '^attn'
    # five expert layers; forward, recomputed forward and the two gradient
    # products are four times the three products of an expert at its pairs
    product = pairs * 2 * 3 * d * f
    assert k['moe']['flops'] == 5 * 4 * product
    # bf16, every array once a pass: rows in and out of both products
    # ([P, d] -> [P, 2 f], [P, f] -> [P, d]) and the eight experts' weights
    rows = pairs * (d + 2 * f) + pairs * (f + d)
    weights = 8 * 3 * d * f
    assert k['moe']['bytes'] == 5 * 4 * 2 * (rows + weights)
    # at the chip's ridge: 4.58 ms of MXU against 5.33 ms of HBM a step
    assert round(1e3 * k['moe']['flops'] / 197e12, 2) == 4.58
    assert round(1e3 * k['moe']['bytes'] / 819e9, 2) == 5.33
    assert ref.kernels(cfg, 2)['moe']['flops'] == 2 * k['moe']['flops']
    # six attention sub-layers of four heads: four products 192 wide, three
    # 128 wide, 2 T T w each, halved by the mask; q k 192, v o 128 and
    # their gradients once each in bf16
    assert k['flash'] == {'match': '^attn',
                          'flops': 6 * 4 * (4 * 192 + 3 * 128) * 4096 * 4096,
                          'bytes': 6 * 4 * 4096 * 4 * (192 + 128) * 2}


def test_the_file_states_the_cut_and_the_source_s_keys(cfg, bench):
    entry = [c for c in bench['configs'] if c['name'] == NAME][0]
    assert entry['reduced'] == cfg['reduced'] == CUT
    assert entry['source'] == cfg['source']
    assert entry['file'] == 'perfbench/configs/' + NAME + '.json'
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        row = [json.loads(line) for line in open(catalog)
               if '"Xing4.0-29B-A4B"' in line][0]
        assert row['source_url'] == cfg['source']
        for key, value in row['config'].items():
            if key in CUT:
                assert cfg[key] != value and cfg['published'][key] == value
            else:
                assert cfg[key] == value, key
    # the floors: four expert layers after the leading dense one, eight
    # experts, an eighth of the vocabulary; no width among the cut keys
    assert cfg['num_hidden_layers'] - cfg['first_k_dense_replace'] == 4
    assert cfg['n_routed_experts'] == len(cfg['assumed']['experts_held']) == 8
    assert 8 * cfg['vocab_size'] == cfg['published']['vocab_size']
    assert 8 * cfg['num_attention_heads'] == cfg['published']['num_attention_heads']
    assert not any(key.endswith(('_dim', '_rank', '_size')) and key != 'vocab_size'
                   for key in CUT)
    for key in ('deployment', 'departures', 'hbm_reckoning', 'limits_from'):
        assert cfg[key], key
    for key in ('sequence_length', 'rows_per_chip_per_step', 'optimizer',
                'init', 'rows_per_row_group', 'experts_held', 'sinkhorn',
                'routing_bias', 'mtp_loss', 'rope', 'streams'):
        assert key in cfg['assumed'], key
    assert 'Eight chips share each layer' in cfg['deployment']
    assert (cfg['assumed']['sequence_length'],
            cfg['assumed']['rows_per_chip_per_step'],
            cfg['assumed']['rows_per_row_group'],
            cfg['assumed']['mtp_loss_weight']) == (4096, 1, 8, 0.3)


def test_limits_lie_between_their_readings(cfg):
    limits = cfg['limits']
    assert limits['rows_wrong'] == limits['rows_uneven'] == \
        limits['shards_misplaced'] == 0
    # the loss, the gradients and the update each have a limit: REVIEW of
    # PR 33 (a number with an upper reading is compared)
    assert {'loss_gap', 'grad_gap_median', 'grad_gap_weights',
            'grad_gap_weights_worst', 'update_gap_median',
            'update_gap_weights'} <= set(limits)
    for name, limit in limits.items():
        if limit == 0:
            continue
        read = cfg['limits_from'][name]
        assert read['lower'] < limit < read['upper'], name
    # where the fp8 control sets the upper end the limit stands below it,
    # not above (update_gap_median stood above its control in the first round)
    for name in ('loss_gap', 'grad_gap_median', 'grad_gap_weights',
                 'update_gap_median', 'update_gap_weights'):
        assert cfg['limits_from'][name]['upper_from'].startswith(
            'smallest of the fp8 control'), name


def test_the_limits_part_the_recorded_readings(cfg):
    """The chip's readings, as ``perfbench.run`` (sound) and
    ``perfbench.calibrate`` (the fp8 control as it is and as it was before
    it rounded the maps' product, half of the row left out) printed them,
    through ``check.verdict`` under the file's own limits: every sound run
    correct, every control and fault not; the control by every limit whose
    upper end it sets."""
    from perfbench import check
    readings = json.load(open(os.path.join(PERFBENCH, 'tests', 'data',
                                           'xing4-readings.json')))
    readings.pop('what')
    limits = {k: v for k, v in cfg['limits'].items() if k.endswith('_gap')
              or '_gap_' in k}
    assert len(readings['sound']) >= 12 and len(readings['control_fp8']) >= 2
    for kind, rows in readings.items():
        for numbers in rows:
            table, correct = check.verdict(
                {k: v for k, v in numbers.items() if k != 'seed'}, limits)
            assert correct == (kind == 'sound'), (kind, numbers['seed'], table)
    others = [r for kind, rows in readings.items() if kind != 'sound'
              for r in rows]
    for name in limits:
        read = cfg['limits_from'][name]
        assert read['lower'] >= max(r[name] for r in readings['sound']) * 0.999
        assert min(r[name] for r in others if r[name] > limits[name]) \
            >= read['upper'] * 0.999
        if read['upper_from'].startswith('smallest of the fp8 control'):
            for kind in ('control_fp8', 'control_fp8_maps_float32'):
                assert all(r[name] > limits[name] for r in readings[kind]), \
                    (name, kind)


def test_what_the_benchmark_gained(bench):
    cells = {w['name']: w for w in bench['workloads']}
    new = cells['xing4.tokens4k']
    assert (new['config'], new['traffic'], new['chips']) == (
        NAME, 'token-rows-4k', 1)
    assert len(new['why']) <= 200
    # additions only, at the end of their lists: one configuration, one
    # cell, three per-layer metrics
    assert [w['name'] for w in bench['workloads']] == [
        'resnet50.ramcache', 'gpt2s.tokens', 'resnet50.decode.x4',
        'olmohybrid.tokens8k', 'xing4.tokens4k']
    assert [c['name'] for c in bench['configs']] == [
        'resnet50-imagenet224', 'gpt2-small-ctx1024', 'olmo-hybrid-7b-ctx8192',
        NAME]
    assert [m['name'] for m in bench['per_layer']][-3:] == [
        'kernel.moe_ms_per_step', 'kernel.moe_roofline',
        'moe.load_max_over_mean']
    assert len(bench['per_layer']) == 31
    for m in bench['per_layer'][-3:]:
        assert m['workloads'] == ['xing4.tokens4k']
        assert m['moves'] == 'rows_per_s_per_chip'
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    by_name = {m['name']: m for m in bench['per_layer']}
    assert (by_name['kernel.moe_roofline']['unit'],
            by_name['kernel.moe_roofline']['source'],
            by_name['kernel.moe_roofline']['layer']) == (
                '%', 'device_trace', 'kernel')
    assert by_name['moe.load_max_over_mean']['source'] == 'program_counter'
    # the accepted metrics' lists as they were; the 18 without a list are
    # owed in the new cell's traced line
    assert by_name['kernel.flash_roofline']['workloads'] == ['gpt2s.tokens']
    assert by_name['kernel.gdn_roofline']['workloads'] == ['olmohybrid.tokens8k']
    assert sum('workloads' not in m for m in bench['per_layer']) == 18
    assert bench['run_seconds'] == 30 and len(bench['end_to_end']) == 3
    # one cell in five asks for four chips
    assert [w['name'] for w in bench['workloads'] if w['chips'] == 4] == [
        'resnet50.decode.x4']
    traffic = json.load(open(os.path.join(PERFBENCH, 'traffic',
                                          'token-rows-4k.json')))
    assert (traffic['store_rows'], traffic['reader']['workers_count'],
            traffic['reader']['results_queue_size'],
            traffic['loader']['prefetch'], traffic['reader']['cache_type'],
            traffic['warm_steps'], traffic['sample_rows']) \
        == (2048, 4, 4, 2, 'null', 8, 512)
    for name in ('kernel.moe_ms_per_step', 'kernel.moe_roofline',
                 'moe.load_max_over_mean'):
        assert os.path.exists(os.path.join(PERFBENCH, 'metrics', name + '.py'))


def test_the_readers_leave_themselves_out_where_there_is_nothing_to_read(ref, cfg):
    from perfbench import span_reduce, trace_reduce
    peak = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
    ctx = {'trace': {'steps': 8, 'per_op_s': {'moe.9': 0.048, 'moe.12': 0.032,
                                              'attn.4': 0.1, 'fusion.1': 1.0}},
           'ref': ref, 'cfg': cfg, 'batch': 1, 'chips': 1, 'peak': peak,
           'trace_reduce': trace_reduce}
    ms = harness.load_module(os.path.join(
        PERFBENCH, 'metrics', 'kernel.moe_ms_per_step.py')).read
    share = harness.load_module(os.path.join(
        PERFBENCH, 'metrics', 'kernel.moe_roofline.py')).read
    load = harness.load_module(os.path.join(
        PERFBENCH, 'metrics', 'moe.load_max_over_mean.py')).read
    window_of = span_reduce.window_of

    def window(records):
        return window_of({'begin': {'t': 1.0}, 'end': {'t': 2.0}}, records)

    # the program's counters: running totals, one record a step and a held
    # expert; the window's first and last are three steps apart
    first = ('reader.read', 'reader', int(0.5e9), 10, None, 1, None, None)
    counters = [('moe.expert_load.e{}'.format(e), 'step', int((1.1 + 0.2 * s) * 1e9),
                 (100 + 10 * e) * (s + 1)) for s in range(4) for e in range(2)]
    got = window([first] + counters)
    assert [c[3] for c in got['counters'] if c[0].endswith('e1')] == [
        110, 220, 330, 440]
    try:
        span_reduce.window_of = lambda ctx: got
        assert ms(ctx) == pytest.approx(10.0)
        assert load({}) == pytest.approx(330 * 2 / (300 + 330))
        # the share counts the pairs routed (210 a step here), not the
        # expectation: rows by the pairs, the five layers' weights whole
        k = ref.kernels(cfg, 1, moe_pairs_per_step=210.0)['moe']
        assert k['flops'] == pytest.approx(4 * 210 * 2 * 3 * 3584 * 1024)
        assert k['bytes'] == pytest.approx(4 * 2 * (
            210 * (2 * 3584 + 3 * 1024) + 5 * 8 * 3 * 3584 * 1024))
        assert share(ctx) == pytest.approx(100 * k['bytes'] / 819e9 / 0.010)
        assert ref.kernels(cfg, 1, moe_pairs_per_step=5 * 2048) == \
            ref.kernels(cfg, 1)

        class Older(object):                # a reference with no moe kernel
            @staticmethod
            def kernels(cfg, rows):
                return {'flash': {}}

        for other in (dict(ctx, trace=None), dict(ctx, ref=Older),
                      dict(ctx, ref=object()),
                      dict(ctx, trace={'steps': 8, 'per_op_s': {'fusion.1': 1.0}})):
            assert ms(other) is None and share(other) is None
        assert share(dict(ctx, peak=None)) is None
        # a program that writes no such counter (every commit before this
        # one) gives nothing and does not raise
        for nothing in (window([first]), window([first] + counters[:2]), None):
            span_reduce.window_of = lambda ctx, nothing=nothing: nothing
            assert load({}) is None and share(ctx) is None
    finally:
        span_reduce.window_of = window_of


def test_the_harness_runs_the_configuration_s_files_at_a_tiny_size(tmp_path):
    path = tiny_benchmark(tmp_path)
    bench = json.load(open(path))
    bench['configs'].append({
        'name': 'tiny-xing4', 'source': 'tests', 'reduced': [], 'why': 'tests',
        'file': os.path.join(TINY, 'tiny-xing4.json')})
    bench['workloads'].append({'name': 'tiny.xing4', 'config': 'tiny-xing4',
                               'traffic': 'tiny-tokens', 'chips': 1,
                               'why': 'tests'})
    for m in bench['per_layer']:
        if m['name'].startswith(('kernel.moe', 'moe.')):
            m['workloads'] = ['tiny.xing4']
    json.dump(bench, open(path, 'w'), indent=1)
    rc, out, err = run_harness(path, 'tiny.xing4', '--rehearse', trace=1,
                               seconds=6, seed=3000000019)
    assert rc == 0, err[-3000:]
    result = json.loads(out[-1])
    assert result['correct'] is True and result['failed'] == 0
    names = {n.replace('.cpu_rehearsal', '') for n in result['metrics']}
    # a CPU trace has no device plane: the two kernel metrics leave
    # themselves out, the counter and the host's metrics are read
    assert 'kernel.moe_ms_per_step' not in names
    assert 'kernel.moe_roofline' not in names
    assert 'host.cpu_ms_per_row' in names
    assert 1.0 <= result['metrics']['moe.load_max_over_mean.cpu_rehearsal'][
        'value'] < 2.0
    for name, (value, limit) in result['compared'].items():
        assert limit is None or value <= limit, name
    tiny = json.load(open(os.path.join(TINY, 'tiny-xing4.json')))
    real = json.load(open(os.path.join(PERFBENCH, 'configs', NAME + '.json')))
    assert set(tiny) - {'reference_file', 'program_file', 'limits_why'} \
        == set(real) - {'limits_notes'}
    assert set(tiny['limits']) == set(real['limits'])
    for key in ('num_hidden_layers', 'first_k_dense_replace', 'hc_mult',
                'num_experts_per_tok', 'num_nextn_predict_layers',
                'rope_scaling', 'hc_sinkhorn_iters', 'n_routed_experts'):
        assert tiny[key] == real[key], key
    # and a step that hands back the state it was given is not correct
    rc, out, err = run_harness(path, 'tiny.xing4', '--rehearse', '--fault',
                               'state_unchanged', seed=7)
    assert rc == 0, err[-3000:]
    faulty = json.loads(out[-1])
    assert faulty['correct'] is False
    assert faulty['compared']['update_gap_median'][0] > \
        tiny['limits']['update_gap_median']
