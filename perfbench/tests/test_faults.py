"""The rest of a run with the timed path broken underneath: ``correct`` has to
come out false, once for each fault a cell can have. The harness's look for a
chip is skipped (``--rehearse``); everything after it is the run's own."""

import json
import os

import pytest
from conftest import ROOT, run_harness


def _result(out):
    return json.loads(out[-1])


# ``fails``: a number the real cell of that configuration holds too.
@pytest.mark.parametrize('cell,devices,fault,fails', [
    ('tiny.ramcache', 1, 'state_unchanged', 'update_gap_weights_worst'),
    ('tiny.ramcache', 1, 'half_batch', 'grad_gap_weights'),
    ('tiny.ramcache', 1, 'row_altered', 'rows_wrong'),
    ('tiny.tokens', 1, 'state_unchanged', 'update_gap'),
    ('tiny.tokens', 1, 'half_batch', 'grad_gap'),
    ('tiny.tokens', 1, 'row_altered', 'rows_wrong'),
    ('tiny.decode.x4', 4, 'no_exchange', 'grad_gap_weights'),
])
def test_a_planted_fault_reads_not_correct(tiny, cell, devices, fault, fails):
    rc, out, err = run_harness(tiny, cell, '--rehearse', '--fault', fault,
                               devices=devices)
    assert rc == 0, err[-3000:]
    result = _result(out)
    assert result['correct'] is False
    value, limit = result['compared'][fails]
    assert value > limit, (fails, value, limit)
    real = json.load(open(os.path.join(
        ROOT, 'perfbench', 'configs', {'tiny.tokens': 'gpt2-small-ctx1024'}.get(
            cell, 'resnet50-imagenet224') + '.json')))
    assert fails in real['limits']
    if fault == 'row_altered':
        assert result['failed'] >= 1
    if fault == 'state_unchanged':
        # a state left unchanged reads 1 by the measure of norms
        assert value == pytest.approx(1.0, abs=1e-6)
