"""``perfbench/scope_reduce.py``: the join of a traced window's time per
operation with the program's table of its compiled step, on a pair recorded
from one chip run of ``gpt2s.tokens`` (``data/gpt2s.per_op_s.json`` and
``data/gpt2s.op_scopes.json``, PR 37, trimmed to the operations that took
most of the step and a few of every other kind) and on small literal ones."""

import json
import os

import pytest
from conftest import HERE, PERFBENCH

from perfbench import harness, scope_reduce, trace_reduce

DATA = os.path.join(HERE, 'data')


def row(opcode='fusion', part='mixer', which='forward', path='M/attn/add',
        result=None, parts_fused=None):
    return {'opcode': opcode, 'result': result, 'part': part, 'pass': which,
            'path': path, 'parts_fused': parts_fused}


def tables(**instructions):
    return {'train_step': {'module': 'jit_train_step',
                           'instructions': instructions}}


@pytest.fixture(scope='module')
def recorded():
    per_op = json.load(open(os.path.join(DATA, 'gpt2s.per_op_s.json')))
    scopes = json.load(open(os.path.join(DATA, 'gpt2s.op_scopes.json')))
    return per_op, scopes


# -- the join --------------------------------------------------------------------------

def test_the_key_s_first_part_is_the_instruction():
    known = {'fusion.8': row(), 'fusion.85': row(),
             'compare_select_fusion.3': row(), 'copy-done.12': row(),
             'attn.36': row()}
    find = scope_reduce.instruction_of
    assert find('fusion.8_f32_4_', known) == 'fusion.8'
    assert find('fusion.85_f32_3584_16384_', known) == 'fusion.85'
    assert find('fusion.850_f32_3584_16384_', known) is None
    assert find('fusion.8', known) is None              # no type: no event
    assert find('compare_select_fusion.3_pred_8_128_', known) == \
        'compare_select_fusion.3'
    assert find('copy-done.12_bf16_', known) == 'copy-done.12'   # a scalar
    assert find('attn.36_bf16_16_1024_768_', known) == 'attn.36'
    # what short_name could not read keeps the event's first 80 characters
    assert find('%copy-done.12 = ((f32[2560,512]{1,0:T(8,128)}), f32[640',
                known) == 'copy-done.12'
    assert find('%slice-start.6 = ((f32[2560,512]{1,0:T(8,128)}), f32[64',
                known) is None
    assert find('$core.py:331 train_step', known) is None


def test_the_keys_are_short_name_s():
    """``trace_reduce.short_name`` makes the keys this reads."""
    known = {'fusion.20': row(result='f32[768,50257]'),
             'copy.3': row(result='f32[]')}
    key = trace_reduce.short_name(
        '%fusion.20 = (f32[768,50257]{1,0:T(8,128)}, f32[50257]{0}) '
        'fusion(%p), kind=kOutput')
    assert key == 'fusion.20_f32_768_50257_'
    assert scope_reduce.instruction_of(key, known) == 'fusion.20'
    assert scope_reduce.instruction_of(
        trace_reduce.short_name('%copy.3 = f32[]{:T(128)} copy(%x)'),
        known) == 'copy.3'


def test_another_program_s_instruction_of_the_same_name_is_outside():
    known = {'fusion.1': row(result='bf16[16,1024,768]')}
    assert scope_reduce.instruction_of('fusion.1_bf16_16_1024_768_',
                                       known) == 'fusion.1'
    # the harness's checksum has a fusion.1 of its own
    assert scope_reduce.instruction_of('fusion.1_u32_16_', known) is None
    reduced = scope_reduce.reduce_scopes(
        {'fusion.1_bf16_16_1024_768_': 0.3, 'fusion.1_u32_16_': 0.1},
        tables(**known), steps=2)
    assert reduced['parts'] == {
        ('mixer', 'forward'): pytest.approx(150.0),
        ('outside_step', '-'): pytest.approx(50.0)}


def test_a_cond_is_left_out_and_its_branch_counted_once():
    known = tables(**{
        'cond.109': row('conditional', 'ffn.routed', 'forward', 'M/moe/cond'),
        'moe.3': row('custom-call', 'ffn.routed', 'forward',
                     'M/moe/pallas_call'),
        'fusion.7': row('fusion', 'ffn.routed', 'forward',
                        'M/moe/gather/gather'),
        'while.2': row('while', 'mixer', 'backward', 'M/attn/while'),
        'exp.6': row('exponential', 'mixer', 'backward', 'M/attn/exp')})
    reduced = scope_reduce.reduce_scopes(
        {'cond.109_bf16_4096_3584_': 0.010, 'moe.3_bf16_9216_2048_': 0.006,
         'fusion.7_bf16_9216_3584_': 0.003, 'while.2_s32_': 0.004,
         'exp.6_f32_8_': 0.004}, known, steps=1)
    assert reduced['containers_ms'] == pytest.approx(14.0)
    assert reduced['total_ms'] == pytest.approx(13.0)
    assert reduced['parts'] == {('ffn.routed', 'forward'): pytest.approx(9.0),
                                ('mixer', 'backward'): pytest.approx(4.0)}
    # the Pallas call apart from the rest of its part
    assert reduced['pallas'] == {('ffn.routed', 'forward'): pytest.approx(6.0)}
    assert reduced['matched'] == 3 and reduced['events'] == 5


def test_what_no_table_knows_is_outside_the_step():
    reduced = scope_reduce.reduce_scopes(
        {'fusion.1_f32_8_': 0.5, 'fusion.2_f32_8_': 0.25,
         'copy-start.4_f32_8_': 0.25},
        tables(**{'fusion.1': row(part='other', path='M/add'),
                  'copy-start.4': row('copy-start', 'unscoped', None, '')}),
        steps=5)
    assert reduced['parts'] == {('other', 'forward'): pytest.approx(100.0),
                                ('outside_step', '-'): pytest.approx(50.0),
                                ('unscoped', '-'): pytest.approx(50.0)}
    assert reduced['matched_ms'] == pytest.approx(150.0)
    assert [k for _, k, _ in reduced['largest']['outside_step']] == [
        'fusion.2_f32_8_']
    assert reduced['largest']['other'] == [
        (pytest.approx(100.0), 'fusion.1_f32_8_', 'M/add')]
    assert scope_reduce.ms_of(reduced, ('other', 'unscoped',
                                        'outside_step')) == pytest.approx(200.0)


def test_none_without_a_table(monkeypatch):
    assert scope_reduce.reduce_scopes({'fusion.1_f32_8_': 0.5}, None, 5) is None
    assert scope_reduce.step_parts({'trace': None}) is None
    ctx = {'trace': {'per_op_s': {'fusion.1_f32_8_': 0.5}, 'steps': 5,
                     'busy_s': 0.5}}
    monkeypatch.setattr(scope_reduce, 'tables', lambda: None)
    assert scope_reduce.step_parts(ctx) is None
    assert scope_reduce.read(ctx, ('mixer',)) is None
    assert ctx['step_parts'] is None        # asked once a run


def test_a_fusion_over_two_parts_is_said():
    reduced = scope_reduce.reduce_scopes(
        {'fusion.26_f32_768_50257_': 0.012, 'fusion.3_f32_8_': 0.004},
        tables(**{
            'fusion.26': row(part='head', which='backward',
                             parts_fused=['head', 'optimizer']),
            'fusion.3': row(part='optimizer', which='update',
                            parts_fused=['optimizer'])}), steps=4)
    assert reduced['mixed_ms'] == pytest.approx(3.0)
    assert reduced['mixed'] == {'head+optimizer': pytest.approx(3.0)}
    assert reduced['mixed_parts'] == {'head': pytest.approx(3.0)}
    assert reduced['parts'][('head', 'backward')] == pytest.approx(3.0)
    assert reduced['parts'][('optimizer', 'update')] == pytest.approx(1.0)


# -- the recorded pair -----------------------------------------------------------------

def test_the_recorded_run_joins_by_name(recorded):
    per_op, scopes = recorded
    reduced = scope_reduce.reduce_scopes(per_op['per_op_s'], scopes,
                                         per_op['steps'])
    # every operation of the step found its instruction: what is left is
    # the program file's ``prepare`` and the harness's checksum
    assert reduced['matched_ms'] >= 0.999 * reduced['total_ms']
    assert reduced['events'] - reduced['matched'] <= 8
    assert reduced['containers_ms'] == 0.0      # no loop, no branch here
    parts = {part for part, _ in reduced['parts']}
    assert parts >= {'embed', 'mixer', 'ffn.dense', 'head', 'loss',
                     'optimizer', 'unscoped'}
    assert scope_reduce.ms_of(reduced, ('other',)) <= 0.02 * reduced['total_ms']
    # the kept operations are most of the step: the parts add up to them
    assert scope_reduce.ms_of(reduced) == pytest.approx(reduced['total_ms'])
    assert reduced['total_ms'] == pytest.approx(
        1e3 * sum(per_op['per_op_s'].values()) / per_op['steps'])
    # the flash kernels are the mixer's Pallas calls, forward and backward
    pallas = reduced['pallas']
    assert set(pallas) == {('mixer', 'forward'), ('mixer', 'backward')}
    flash = 1e3 * trace_reduce.kernel_seconds(
        {'per_op_s': per_op['per_op_s']}, 'attn') / per_op['steps']
    assert sum(pallas.values()) == pytest.approx(flash)
    # the weight gradients fused with AdamW are said, not hidden
    assert reduced['mixed_ms'] > 0


def test_the_nine_metrics_on_the_recorded_run(recorded, monkeypatch):
    per_op, scopes = recorded
    said = []
    monkeypatch.setattr(scope_reduce, 'tables', lambda: scopes)
    monkeypatch.setattr(harness, 'say', lambda *parts: said.append(parts))
    ctx = {'trace': {'per_op_s': per_op['per_op_s'], 'steps': per_op['steps'],
                     'busy_s': per_op['busy_s']}}
    names = ['scoped_share', 'mixer_ms_per_step',
             'mixer_outside_kernels_ms_per_step', 'ffn_ms_per_step',
             'routed_ms_per_step', 'head_loss_ms_per_step',
             'optimizer_ms_per_step', 'recompute_ms_per_step',
             'mixed_fusions_ms_per_step']
    got = {name: harness.load_module(os.path.join(
        PERFBENCH, 'metrics', 'step.' + name + '.py')).read(ctx)
        for name in names}
    reduced = ctx['step_parts']
    assert all(isinstance(v, float) for v in got.values())
    assert 85.0 <= got['scoped_share'] <= 100.0
    assert got['mixer_ms_per_step'] > got['mixer_outside_kernels_ms_per_step'] > 0
    assert got['mixer_ms_per_step'] - got['mixer_outside_kernels_ms_per_step'] \
        == pytest.approx(sum(reduced['pallas'].values()))
    # a model with no routed experts and no recomputation
    assert got['routed_ms_per_step'] == got['recompute_ms_per_step'] == 0.0
    left = scope_reduce.ms_of(reduced, ('unscoped', 'other',
                                        scope_reduce.OUTSIDE))
    assert got['scoped_share'] == pytest.approx(
        100.0 * (1.0 - left / reduced['total_ms']))
    every = (got['mixer_ms_per_step'] + got['ffn_ms_per_step']
             + got['head_loss_ms_per_step'] + got['optimizer_ms_per_step']
             + left)
    assert every == pytest.approx(reduced['total_ms'])
    # the weight gradients fused with AdamW: said once, and part by part
    assert got['mixed_fusions_ms_per_step'] == reduced['mixed_ms'] \
        == pytest.approx(sum(reduced['mixed_parts'].values())) \
        == pytest.approx(sum(reduced['mixed'].values()))
    assert 0 < reduced['mixed_parts']['ffn.dense'] \
        <= scope_reduce.ms_of(reduced, ('ffn.dense',))
    # the table was printed once, through the harness's say
    text = [' '.join(str(p) for p in parts) for parts in said]
    assert sum(line.startswith('step parts:') for line in text) == 1
    assert any(line.startswith('mixer ') for line in text)
    assert any(line.startswith('largest unscoped:') for line in text)


def instant(program, at_ns):
    return ('step.program', 'step', at_ns, None, None, 1, None,
            {'function': 'train_step', 'program': program, 'leaves': 3,
             'bytes': 24})


def test_the_ring_s_instants_say_which_program_the_window_ran(monkeypatch):
    """``step.program`` instants order the tables: the newest before the
    window's end first; one inside the window is reported as a retrace."""
    records = [instant('train_step', 10), ('x', 'step', 15, 2.0),
               ('jax.compile', 'step', 20, 5, 0, 1, 'backend_compile', None),
               instant('train_step#2', 2_500_000_000),
               instant('train_step#3', 9_000_000_000)]
    assert scope_reduce.programs_announced(records, 3e9) == [
        ('train_step#2', 2_500_000_000), ('train_step', 10)]
    assert scope_reduce.programs_announced(None, 3e9) == []
    both = {'train_step': {'module': 'a', 'instructions': {
                'fusion.1': row(part='mixer', result='f32[8]')}},
            'train_step#2': {'module': 'b', 'instructions': {
                'fusion.1': row(part='head', result='f32[8]')}}}
    said = []
    monkeypatch.setattr(scope_reduce, 'tables', lambda: both)
    monkeypatch.setattr(scope_reduce.span_reduce, 'ring_records',
                        lambda: records)
    monkeypatch.setattr(harness, 'say', lambda *parts: said.append(parts[0]))
    ctx = {'trace': {'per_op_s': {'fusion.1_f32_8_': 0.5}, 'steps': 5,
                     'busy_s': 0.5}, 'begin': {'t': 2.0}, 'end': {'t': 3.0}}
    reduced = scope_reduce.step_parts(ctx)
    assert set(reduced['parts']) == {('head', 'forward')}
    assert 'step.program instants: train_step#2, train_step' in said[0]
    assert said[1].startswith('step.program: train_step#2 was traced 0.500 s '
                              'into the window')
    # a window that closed before the second program came ran the first
    ctx = dict(ctx, begin={'t': 1.0}, end={'t': 2.0})
    del ctx['step_parts']
    assert set(scope_reduce.step_parts(ctx)['parts']) == {
        ('mixer', 'forward')}


def test_the_table_s_lines():
    reduced = scope_reduce.reduce_scopes(
        {'fusion.1_f32_8_': 0.5, 'attn.2_bf16_8_': 0.25, 'x_f32_': 0.25},
        tables(**{'fusion.1': row(part='optimizer', which='update',
                                  path='optimizer/mul'),
                  'attn.2': row('custom-call', path='M/attn/pallas_call')}),
        steps=5)
    text = scope_reduce.lines(reduced, busy_ms=199.0)
    assert text[0].startswith('step parts: 2 of 3 events matched, '
                              '150.000 of 200.000 ms a step (75.00 %), '
                              'busy 199.000')
    assert text[1].split() == ['part', 'forward', 'recompute', 'backward',
                               'update', '-', 'ms', '%', 'pallas', 'mixed']
    assert text[2].split() == ['optimizer', '.', '.', '.', '100.000', '.',
                               '100.000', '50.00', '0.000', '0.000']
    assert text[3].split()[0] == 'mixer' and text[3].split()[-2] == '50.000'
    assert text[-1] == 'largest outside_step: 50.000 ms x_f32_ '
