"""The Ling-3.0-flash configuration: its counts against hand sums, what
``BENCHMARK.json`` gained with it, its three readers on a recorded table and
ring, its limits against the chip's readings, and the harness end to end on
the CPU at a tiny size of the same files."""

import json
import os

import numpy as np
import pytest
from conftest import PERFBENCH, ROOT, TINY, run_harness, tiny_benchmark

from perfbench import harness

NAME = 'ling3-flash-ctx8192'
CUT = ['num_hidden_layers', 'first_k_dense_replace', 'num_experts',
       'vocab_size', 'num_nextn_predict_layers']
NEW_METRICS = ['kernel.kda_ms_per_step', 'kernel.kda_roofline',
               'moe.held_pairs_over_expected']


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
    d, h = 2560, 32
    kda = (5 * d * h * 128          # W_q, W_k, W_v, W_f and W_o
           + 2 * d * h              # W_b, W_g: one column a head
           + 3 * 4 * h * 128        # three convolutions of four taps
           + h + h * 128 + 128)     # A_log, dt_bias, the head norm's scale
    latent = d * h * 192 + d * 576 + 512 + 512 * h * 256 + h * 128 * d
    experts = 8 * 3 * d * 768 + 3 * d * 768 + d * 512
    dense = 3 * d * 6144
    vocabulary = 2 * 19648 * d
    norms = 6 * 2 * d + d
    by_hand = (kda + dense) + 4 * (kda + experts) + (latent + experts) \
        + vocabulary + norms
    shapes = ref.param_shapes(cfg)
    count = sum(int(np.prod(s)) for s in shapes.values())
    assert count == by_hand == cfg['parameters'] == 714905376
    # the table of ISSUE 35, in millions
    assert round(kda / 1e6, 2) == 52.65 and round(latent / 1e6, 2) == 31.88
    assert round(experts / 1e6, 2) == 54.39 and round(dense / 1e6, 2) == 47.19
    assert round(vocabulary / 1e6, 2) == 100.60
    assert round(count / 1e6, 1) == 714.9
    # at 16 bytes a parameter (f32 weight, gradient, AdamW's two moments)
    assert round(16 * count / 1e9, 2) == 11.44 and round(12 * count / 1e9, 2) == 8.58
    # the expert leaves AdamW runs over: 236 M in five layers
    assert round(5 * 8 * 3 * d * 768 / 1e6) == 236
    b = ('block_3',)
    assert shapes[b + ('mixer', 'q_proj', 'kernel')] == (2560, 32, 128)
    assert shapes[b + ('mixer', 'f_proj', 'kernel')] == (2560, 32, 128)
    assert shapes[b + ('mixer', 'conv_k')] == (4, 32, 128)
    assert shapes[b + ('mixer', 'dt_bias')] == (32, 128)
    assert shapes[b + ('mixer', 'g_proj', 'kernel')] == (2560, 32)
    assert shapes[b + ('mixer', 'o_proj', 'kernel')] == (32, 128, 2560)
    assert shapes[b + ('moe', 'router', 'kernel')] == (2560, 512)
    assert shapes[b + ('moe', 'experts_gate_up')] == (8, 2560, 1536)
    assert shapes[b + ('moe', 'experts_down')] == (8, 768, 2560)
    assert shapes[('block_5', 'attn', 'q_proj', 'kernel')] == (2560, 32, 192)
    assert shapes[('block_5', 'attn', 'kv_up', 'kernel')] == (512, 32, 256)
    assert ('block_5', 'attn', 'q_down', 'kernel') not in shapes
    assert shapes[('block_0', 'mlp', 'gate', 'kernel')] == (2560, 6144)
    assert ('block_0', 'moe', 'router', 'kernel') not in shapes
    assert not any(path[0].startswith('mtp') for path in shapes)


def test_operations_of_a_row_by_hand(cfg, ref):
    t, d, h, v, c = 8192, 2560, 32, 19648, 64
    rule = c * c * 5 * 128 + 6 * c * 128 * 128       # one head's chunk
    kda = t * 2 * d * h * (5 * 128 + 2) + h * (t // c) * rule
    latent = t * 2 * (d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d) \
        + h * t * t * (192 + 128)                     # causal: halved
    pairs = t * 8 * 8 // 512                          # 128 an expert held
    experts = t * 2 * (3 * d * 768 + d * 512) + pairs * 2 * 3 * d * 768
    dense = t * 2 * 3 * d * 6144
    forward = t * 2 * d * v + 5 * kda + latent + dense + 5 * experts
    assert ref.expected_pairs_per_row(cfg) == pairs == 1024
    assert ref.forward_flops_per_row(cfg) == forward
    assert ref.train_flops_per_row(cfg) == 3 * forward
    assert round(3 * forward / 1e12, 1) == 23.8     # ISSUE 35: 23.9 TFLOP a step
    # ISSUE 35's parts, TFLOP a step: flash, the rule, the rest products
    assert round(3 * h * t * t * 320 / 1e12, 2) == 2.06
    assert round(5 * h * (t // c) * rule / 1e12, 2) == 0.18    # thrice a step
    # the KDA mixers' projections against an expert layer's other products
    assert round(100 * (t * 2 * d * h * 642) / (t * 2 * d * h * 642 + experts)) \
        in range(80, 95)
    # the same functions at a tiny size, by hand again
    tiny = json.load(open(os.path.join(TINY, 'tiny-ling3.json')))
    rule = 16 * 16 * 5 * 16 + 6 * 16 * 16 * 16
    kda = 64 * 2 * 64 * 2 * (5 * 16 + 2) + 2 * 4 * rule
    latent = 64 * 2 * (64 * 2 * 24 + 64 * 40 + 32 * 2 * 32 + 2 * 16 * 64) \
        + 2 * 64 * 64 * 40
    pairs = 64 * 4 * 8 // 16
    experts = 64 * 2 * (3 * 64 * 32 + 64 * 16) + pairs * 2 * 3 * 64 * 32
    assert ref.forward_flops_per_row(tiny) == 64 * 2 * 64 * 128 + 5 * kda \
        + latent + 64 * 2 * 3 * 64 * 96 + 5 * experts


def test_the_kernels_work_by_hand(cfg, ref):
    k = ref.kernels(cfg, 1)
    assert k['kda']['match'] == '^kda' and k['moe']['match'] == '^moe' \
        and k['flash']['match'] == '^attn'
    c, hd = 64, 128
    chunks = 5 * 32 * (8192 // c)                       # layers, heads, chunks
    forward = c * c * 5 * hd + 6 * c * hd * hd
    reverse = 12 * c * hd * hd + 2 * c * c * hd + 4 * c * c * hd + 4 * c * c * hd
    assert k['kda']['flops'] == chunks * (forward + reverse)
    # forward: q k v bf16, g f32, beta in; o, the state and T out; in
    # reverse those again and do in, dq dk dv bf16, dg f32, dbeta out
    f_bytes = 3 * c * hd * 2 + c * hd * 4 + c * 4 + c * hd * 2 \
        + hd * hd * 2 + c * c * 2
    r_bytes = f_bytes + 3 * c * hd * 2 + c * hd * 4 + c * 4
    assert k['kda']['bytes'] == chunks * (f_bytes + r_bytes)
    # memory-bound: 9.0 ms of HBM against 2.8 ms of MXU a step
    assert round(1e3 * k['kda']['bytes'] / 819e9, 1) == 9.0
    assert round(1e3 * k['kda']['flops'] / 197e12, 1) == 2.8
    assert ref.kernels(cfg, 2)['kda']['flops'] == 2 * k['kda']['flops']
    # five expert layers at 1,024 pairs: four passes of three products
    product = 5 * 1024 * 2 * 3 * 2560 * 768
    assert k['moe']['flops'] == 4 * product
    assert ref.kernels(cfg, 1, moe_pairs_per_step=5 * 1024) == k
    # one latent-attention layer of 32 heads at 8,192 x 192/128
    assert k['flash'] == {'match': '^attn',
                          'flops': 32 * (4 * 192 + 3 * 128) * 8192 * 8192,
                          'bytes': 32 * 8192 * 4 * (192 + 128) * 2}


def test_the_file_states_the_cut_and_the_source_s_keys(cfg, bench):
    entry = [c for c in bench['configs'] if c['name'] == NAME][0]
    assert entry['reduced'] == cfg['reduced'] == CUT
    assert entry['source'] == cfg['source']
    assert entry['file'] == 'perfbench/configs/' + NAME + '.json'
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        row = [json.loads(line) for line in open(catalog)
               if '"Ling-3.0-flash"' in line][0]
        assert row['source_url'] == cfg['source']
        for key, value in row['config'].items():
            if key in CUT:
                assert cfg[key] != value and cfg['published'][key] == value
            else:
                assert cfg[key] == value, key
    # one whole period, eight experts, an eighth of the vocabulary, every
    # head; no width among the cut keys
    assert cfg['num_hidden_layers'] == cfg['layer_group_size'] == 6
    assert cfg['num_experts'] == len(cfg['assumed']['experts_held']) == 8
    assert 8 * cfg['vocab_size'] == cfg['published']['vocab_size']
    assert cfg['num_attention_heads'] == 32
    assert not any(key.endswith(('_dim', '_rank', '_size')) and key != 'vocab_size'
                   for key in CUT)
    for key in ('deployment', 'departures', 'hbm_reckoning', 'limits_from'):
        assert cfg[key], key
    for key in ('sequence_length', 'rows_per_chip_per_step', 'optimizer',
                'init', 'rows_per_row_group', 'experts_held', 'chunk',
                'sub_block', 'unit', 'gate', 'output_gate', 'qk_norm', 'rope',
                'routing_bias', 'routing_groups'):
        assert key in cfg['assumed'], key
    assert '64 chips share each expert layer' in cfg['deployment']
    assert 'a 64th' in cfg['deployment'] and 'no clamp' in cfg['deployment']
    a = cfg['assumed']
    assert (a['sequence_length'], a['rows_per_chip_per_step'],
            a['rows_per_row_group'], a['chunk'], a['sub_block']) == (
                8192, 1, 8, 64, 16)
    # the kept layers clamp nothing
    assert not any(cfg['expert_swiglu_limit_list'][:7]
                   + cfg['share_expert_swiglu_limit_list'][:7])


def test_what_the_benchmark_gained(bench):
    cells = {w['name']: w for w in bench['workloads']}
    new = cells['ling3.tokens8k']
    assert (new['config'], new['traffic'], new['chips']) == (
        NAME, 'token-rows-8k', 1)
    assert len(new['why']) <= 200 and '64th' in new['why']
    # additions only, at the end of their lists: one configuration, one
    # cell, three per-layer metrics
    assert [w['name'] for w in bench['workloads']] == [
        'resnet50.ramcache', 'gpt2s.tokens', 'resnet50.decode.x4',
        'olmohybrid.tokens8k', 'xing4.tokens4k', 'ling3.tokens8k']
    assert [c['name'] for c in bench['configs']] == [
        'resnet50-imagenet224', 'gpt2-small-ctx1024', 'olmo-hybrid-7b-ctx8192',
        'xing4-29b-a4b-ctx4096', NAME]
    assert [m['name'] for m in bench['per_layer']][-3:] == NEW_METRICS
    assert len(bench['per_layer']) == 34
    for m in bench['per_layer'][-3:]:
        assert m['workloads'] == ['ling3.tokens8k']
        assert m['moves'] == 'rows_per_s_per_chip'
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    by_name = {m['name']: m for m in bench['per_layer']}
    assert (by_name['kernel.kda_roofline']['unit'],
            by_name['kernel.kda_roofline']['source'],
            by_name['kernel.kda_roofline']['layer']) == (
                '%', 'device_trace', 'kernel')
    assert by_name['moe.held_pairs_over_expected']['source'] == 'program_counter'
    # the accepted metrics' lists as they were; the 18 without a list are
    # owed in the new cell's traced line
    assert by_name['kernel.flash_roofline']['workloads'] == ['gpt2s.tokens']
    assert by_name['kernel.gdn_roofline']['workloads'] == ['olmohybrid.tokens8k']
    assert by_name['kernel.moe_roofline']['workloads'] == ['xing4.tokens4k']
    assert by_name['moe.load_max_over_mean']['workloads'] == ['xing4.tokens4k']
    assert sum('workloads' not in m for m in bench['per_layer']) == 18
    assert bench['run_seconds'] == 30 and len(bench['end_to_end']) == 3
    # one cell in six asks for four chips
    assert [w['name'] for w in bench['workloads'] if w['chips'] == 4] == [
        'resnet50.decode.x4']
    # the traffic file the benchmark had, shared with olmohybrid.tokens8k
    assert cells['olmohybrid.tokens8k']['traffic'] == 'token-rows-8k'
    for name in NEW_METRICS:
        assert os.path.exists(os.path.join(PERFBENCH, 'metrics', name + '.py'))


def test_limits_lie_between_their_readings(cfg):
    limits = cfg['limits']
    assert limits['rows_wrong'] == limits['rows_uneven'] == \
        limits['shards_misplaced'] == 0
    assert {'loss_gap', 'grad_gap_median', 'grad_gap_weights',
            'update_gap_median', 'update_gap_weights'} <= set(limits)
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
                                           'ling3-readings.json')))
    readings.pop('what')
    limits = {k: v for k, v in cfg['limits'].items() if k.endswith('_gap')
              or '_gap_' in k}
    assert len(readings['sound']) >= 12 and len(readings['control_fp8']) >= 5 \
        and len(readings['fault_half_batch']) >= 5
    for kind, rows in readings.items():
        for numbers in rows:
            table, correct = check.verdict(
                {k: v for k, v in numbers.items() if k != 'seed'}, limits)
            assert correct == (kind == 'sound'), (kind, numbers['seed'], table)
    for name in limits:
        read = cfg['limits_from'][name]
        assert read['lower'] >= max(r[name] for r in readings['sound']) * 0.999


def test_the_readers_on_a_recorded_table_and_ring(ref, cfg):
    from perfbench import span_reduce, trace_reduce
    peak = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
    ctx = {'trace': {'steps': 8, 'per_op_s': {
        'kda.22_bf16_1_8192_4096_': 0.4, 'kda.17_bf16_1_8192_4096_': 0.56,
        'attn.4': 0.1, 'moe.3': 0.05, 'fusion.1': 1.0}},
        'ref': ref, 'cfg': cfg, 'batch': 1, 'chips': 1, 'peak': peak,
        'trace_reduce': trace_reduce, 'begin': {'t': 1.0}, 'end': {'t': 2.0}}
    ms, share, held = (harness.load_module(os.path.join(
        PERFBENCH, 'metrics', name + '.py')).read for name in NEW_METRICS)
    assert ms(ctx) == pytest.approx(120.0)
    k = ref.kernels(cfg, 1)['kda']
    assert share(ctx) == pytest.approx(100 * k['bytes'] / 819e9 / 0.120)
    assert 5 < share(ctx) < 10

    class Older(object):                # a reference with no kda kernel
        @staticmethod
        def kernels(cfg, rows):
            return {'flash': {}}

    for other in (dict(ctx, trace=None), dict(ctx, ref=Older),
                  dict(ctx, ref=object()),
                  dict(ctx, trace={'steps': 8, 'per_op_s': {'fusion.1': 1.0}})):
        assert ms(other) is None and share(other) is None
    assert share(dict(ctx, peak=None)) is None

    # the program's counters: running totals, one record a step and a held
    # expert; the window's first and last are three steps apart
    first = ('reader.read', 'reader', int(0.5e9), 10, None, 1, None, None)
    counters = [('moe.expert_load.e{}'.format(e), 'step',
                 int((1.1 + 0.2 * s) * 1e9), (500 + 24 * e) * (s + 1))
                for s in range(4) for e in range(8)]
    window_of = span_reduce.window_of
    try:
        span_reduce.window_of = lambda ctx, records=None: window_of(
            ctx, [first] + counters)
        # 8 experts sent 500 .. 668 pairs a step over five layers, 4,672 in
        # all, where 5 x 1,024 are expected
        assert held(ctx) == pytest.approx(4672 / 5120)
        assert held(dict(ctx, ref=object())) is None    # no expectation
        # a program that writes no such counter (every commit before this
        # one) gives nothing and does not raise
        for records in ([first], [first] + counters[:8], None):
            span_reduce.window_of = lambda ctx, records=records: \
                window_of(ctx, records) if records else None
            assert held(ctx) is None
    finally:
        span_reduce.window_of = window_of


def test_the_harness_runs_the_configuration_s_files_at_a_tiny_size(tmp_path):
    path = tiny_benchmark(tmp_path)
    bench = json.load(open(path))
    bench['configs'].append({
        'name': 'tiny-ling3', 'source': 'tests', 'reduced': [], 'why': 'tests',
        'file': os.path.join(TINY, 'tiny-ling3.json')})
    bench['workloads'].append({'name': 'tiny.ling3', 'config': 'tiny-ling3',
                               'traffic': 'tiny-tokens', 'chips': 1,
                               'why': 'tests'})
    for m in bench['per_layer']:
        if m['name'] in NEW_METRICS:
            m['workloads'] = ['tiny.ling3']
    json.dump(bench, open(path, 'w'), indent=1)
    rc, out, err = run_harness(path, 'tiny.ling3', '--rehearse', trace=1,
                               seconds=4, seed=3000000019)
    assert rc == 0, err[-3000:]
    result = json.loads(out[-1])
    assert result['correct'] is True and result['failed'] == 0
    names = {n.replace('.cpu_rehearsal', '') for n in result['metrics']}
    # a CPU trace has no device plane: the two kernel metrics leave
    # themselves out, the counter and the host's metrics are read
    assert 'kernel.kda_ms_per_step' not in names
    assert 'kernel.kda_roofline' not in names
    assert 'host.cpu_ms_per_row' in names
    assert 0.5 < result['metrics'][
        'moe.held_pairs_over_expected.cpu_rehearsal']['value'] < 1.5
    for name, (value, limit) in result['compared'].items():
        assert limit is None or value <= limit, name
    tiny = json.load(open(os.path.join(TINY, 'tiny-ling3.json')))
    real = json.load(open(os.path.join(PERFBENCH, 'configs', NAME + '.json')))
    assert set(tiny) - {'reference_file', 'program_file', 'limits_why'} \
        == set(real) - {'limits_notes'}
    assert set(tiny['limits']) == set(real['limits'])
    for key in ('num_hidden_layers', 'first_k_dense_replace',
                'layer_group_size', 'num_nextn_predict_layers', 'rope_theta',
                'kda_lower_bound', 'short_conv_kernel_size', 'num_experts',
                'routed_scaling_factor', 'q_lora_rank'):
        assert tiny[key] == real[key], key
    # and a step that hands back the state it was given is not correct
    rc, out, err = run_harness(path, 'tiny.ling3', '--rehearse', '--fault',
                               'state_unchanged', seed=7)
    assert rc == 0, err[-3000:]
    faulty = json.loads(out[-1])
    assert faulty['correct'] is False
    assert faulty['compared']['update_gap_median'][0] > \
        tiny['limits']['update_gap_median']
