"""What ``BENCHMARK.json`` gained with PR 37: nine ``step.*`` metrics that read
the program's table of its compiled step (``Tracer.op_scopes``) through
``perfbench/scope_reduce.py``, their lists of cells, their files, and what
they read where the program has no table (the parent commit)."""

import json
import os

import pytest
from conftest import PERFBENCH, ROOT

from perfbench import harness, scope_reduce

ALL = ['resnet50.ramcache', 'gpt2s.tokens', 'resnet50.decode.x4',
       'olmohybrid.tokens8k', 'xing4.tokens4k', 'ling3.tokens8k']
LMS = ['gpt2s.tokens', 'olmohybrid.tokens8k', 'xing4.tokens4k',
       'ling3.tokens8k']
NEW = [('step.scoped_share', '%', 'higher', ALL),
       ('step.mixer_ms_per_step', 'ms', 'lower', LMS),
       ('step.mixer_outside_kernels_ms_per_step', 'ms', 'lower', LMS),
       ('step.ffn_ms_per_step', 'ms', 'lower', LMS),
       ('step.routed_ms_per_step', 'ms', 'lower',
        ['xing4.tokens4k', 'ling3.tokens8k']),
       ('step.head_loss_ms_per_step', 'ms', 'lower', ALL),
       ('step.optimizer_ms_per_step', 'ms', 'lower',
        ['xing4.tokens4k', 'ling3.tokens8k']),
       ('step.recompute_ms_per_step', 'ms', 'lower',
        ['olmohybrid.tokens8k', 'xing4.tokens4k', 'ling3.tokens8k']),
       ('step.mixed_fusions_ms_per_step', 'ms', 'lower', ALL)]
# where PR 37's entries lie: after the 34 that PR 35 left, whatever follows
MINE = slice(34, 34 + len(NEW))


@pytest.fixture(scope='module')
def bench():
    return json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))


def test_what_the_benchmark_gained(bench):
    # additions only, after what was there: no cell, no configuration; a
    # later PR's entries and cells come after these
    assert [w['name'] for w in bench['workloads']][:6] == ALL
    assert len(bench['configs']) >= 5 and len(bench['end_to_end']) == 3
    assert bench['run_seconds'] == 30
    assert len(bench['per_layer']) >= 43
    gained = bench['per_layer'][MINE]
    assert [(m['name'], m['unit'], m['better'], m['workloads'])
            for m in gained] == NEW
    for m in gained:
        assert (m['layer'], m['source'], m['moves']) == (
            'step', 'device_trace', 'rows_per_s_per_chip')
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
        assert len(m['name']) <= 64
    # what was there is as it was: the tail PR 35 left, 18 with no list
    assert [m['name'] for m in bench['per_layer']][31:34] == [
        'kernel.kda_ms_per_step', 'kernel.kda_roofline',
        'moe.held_pairs_over_expected']
    assert sum('workloads' not in m for m in bench['per_layer'][:34]) == 18
    by_name = {m['name']: m for m in bench['per_layer']}
    assert by_name['kernel.flash_roofline']['workloads'] == ['gpt2s.tokens']
    assert by_name['step.collective_ms_per_step']['workloads'] == [
        'resnet50.decode.x4']


@pytest.mark.parametrize('name', [m[0] for m in NEW])
def test_a_metric_s_file_reads_nothing_without_a_table(name, monkeypatch):
    """On the parent commit the program has no ``op_scopes``: the reader
    hands back ``None`` and raises nothing, with a trace and without."""
    module = harness.load_module(os.path.join(PERFBENCH, 'metrics',
                                              name + '.py'))
    monkeypatch.setattr(scope_reduce, 'tables', lambda: None)
    trace = {'per_op_s': {'fusion.1_f32_8_': 0.5}, 'steps': 5, 'busy_s': 0.5}
    assert module.read({'trace': trace}) is None
    assert module.read({'trace': None}) is None


def test_tables_without_the_program_s_method(monkeypatch):
    """A tracer with no ``op_scopes`` (the parent's), and one switched off."""
    from petastorm_tpu import trace as program

    class Old(object):
        pass

    monkeypatch.setattr(program, 'get_global_tracer', lambda: Old())
    assert scope_reduce.tables() is None
    monkeypatch.setattr(program, 'get_global_tracer', program.NullTracer)
    assert scope_reduce.tables() is None


def test_every_cell_a_metric_lists_reports_what_it_moves(bench):
    cells = {w['name'] for w in bench['workloads']}
    for m in bench['per_layer'][MINE]:
        assert set(m['workloads']) <= cells
        assert os.path.exists(os.path.join(PERFBENCH, 'metrics',
                                           m['name'] + '.py'))
