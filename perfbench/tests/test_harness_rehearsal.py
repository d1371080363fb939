"""The harness end to end on the CPU at a tiny size: one chip and four virtual
devices, the result line's keys, the row count, no TPU no result, and a cell,
a traffic mix and a metric added as files with no edit to a file that is there."""

import json
import os

import pytest
from conftest import ROOT, run_harness, tiny_benchmark

KEYS = ['correct', 'attempted', 'failed', 'metrics', 'device']


def _lines(out):
    return json.loads(out[-2]), json.loads(out[-1])


def test_one_chip_run(tiny):
    rc, out, err = run_harness(tiny, 'tiny.ramcache', '--rehearse',
                               seed=3000000019)
    assert rc == 0, err[-3000:]
    window, result = _lines(out)
    assert list(result)[:5] == KEYS and list(result)[-1] == 'compared'
    assert result['correct'] is True and result['failed'] == 0
    assert result['device']['platform'] == 'cpu' and result['rehearsal'] is True
    w = window['window']
    assert w['rows'] == w['steps'] * w['batch'] == result['attempted']
    assert window['settings']['traffic']['reader']['cache_type'] == 'memory'
    # the store is written anew in every run and none is left behind, so that
    # set-up is the same work whatever ran in the checkout before
    assert sorted(w['store']) == ['bytes', 'rows']
    stores = os.path.join(os.path.dirname(tiny), '.perfbench_work', 'stores')
    assert os.listdir(stores) == []
    # no number of a CPU run under a device metric's name
    assert set(result['metrics']) == {
        'rows_per_s_per_chip.cpu_rehearsal', 'step_interval_p95_ms.cpu_rehearsal',
        'setup_s.cpu_rehearsal'}
    for name, (value, limit) in result['compared'].items():
        assert limit is None or value <= limit, name
    assert 'compared loss_gap' in err and 'correct = True' in err.splitlines()[-1]


def test_four_virtual_devices_traced(tiny):
    rc, out, err = run_harness(tiny, 'tiny.decode.x4', '--rehearse', devices=4,
                               trace=1, seconds=6)
    assert rc == 0, err[-3000:]
    window, result = _lines(out)
    assert result['correct'] is True and result['device']['count'] == 4
    w = window['window']
    assert w['batch'] == 32 and w['rows'] == w['steps'] * 32
    names = {n.replace('.cpu_rehearsal', '') for n in result['metrics']}
    assert {'collate.reader_wait_share', 'collate.assemble_ms_per_batch',
            'dispatch.ms_per_batch', 'consumer.input_stall_frac'} <= names
    assert result['metrics']['cache.hit_share.cpu_rehearsal']['value'] == 0.0
    # the streamed cell's own lists: read and decode carry load, the batch is
    # staged through the per-device streams, and the ring held the window
    assert {'reader.read_s_per_krow', 'decode.decode_s_per_krow',
            'dispatch.h2d_overlap_frac', 'collate.self_ms_per_batch',
            'reader.idle_poll_cpu_share', 'consumer.queue_lead_batches'} <= names
    assert result['metrics']['decode.decode_s_per_krow.cpu_rehearsal']['value'] > 0
    traffic = window['settings']['traffic']
    assert traffic['reader']['cache_type'] == 'null'
    assert traffic['fill_cache_rows'] == 0
    # device-trace metrics find nothing to read on a CPU and are left out,
    # the collectives' time among them; with no device plane no trace is
    # reduced, so there is no breakdown, but the program's spans of the
    # traced window were taken from the ring and handed over
    assert 'device.idle_share' not in names and 'step.mfu' not in names
    assert 'step.collective_ms_per_step' not in names
    assert 'breakdown' not in result
    handed = [line for line in err.splitlines()
              if 'spans of the program on' in line]
    assert len(handed) == 1 and int(handed[0].split()[2]) > 0, err[-3000:]


def test_token_cell(tiny):
    rc, out, err = run_harness(tiny, 'tiny.tokens', '--rehearse', trace=1,
                               seconds=6)
    assert rc == 0, err[-3000:]
    _, result = _lines(out)
    assert result['correct'] is True
    assert 'host.cpu_ms_per_row.cpu_rehearsal' in result['metrics']


def test_no_tpu_and_no_rehearsal_flag_is_an_error(tiny):
    rc, out, err = run_harness(tiny, 'tiny.ramcache')
    assert rc != 0 and out == [] and 'no TPU' in err


def test_too_few_chips_is_an_error(tiny):
    rc, out, err = run_harness(tiny, 'tiny.decode.x4', '--rehearse', devices=2)
    assert rc != 0 and out == []


def test_outside_variables_do_not_steer_a_run(tiny, monkeypatch):
    monkeypatch.setenv('PETASTORM_TPU_AUTOTUNE', '0.05')
    monkeypatch.setenv('BENCH_RUN', 'x')
    rc, out, err = run_harness(tiny, 'tiny.tokens', '--rehearse')
    assert rc == 0, err[-3000:]


def test_a_later_pr_adds_files_and_edits_none(tmp_path):
    extra = tmp_path / 'extra'
    (extra / 'traffic').mkdir(parents=True)
    (extra / 'metrics').mkdir()
    traffic = json.load(open(os.path.join(
        ROOT, 'perfbench', 'tests', 'tiny', 'traffic', 'tiny-tokens.json')))
    traffic.update(name='dummy-mix', warm_steps=2)
    (extra / 'traffic' / 'dummy-mix.json').write_text(json.dumps(traffic))
    (extra / 'metrics' / 'dummy.steps.py').write_text(
        "def read(ctx):\n    return ctx['steps']\n")
    bench = tiny_benchmark(
        tmp_path, extra_paths=[str(extra)],
        extra_cells=[{'name': 'dummy.cell', 'config': 'tiny-gpt2',
                      'traffic': 'dummy-mix', 'chips': 1, 'why': 'tests'}],
        extra_metrics=[{'name': 'dummy.steps', 'unit': 'steps',
                        'better': 'higher', 'source': 'program_counter',
                        'layer': 'consumer', 'moves': 'rows_per_s_per_chip',
                        'workloads': ['dummy.cell']}])
    rc, out, err = run_harness(bench, 'dummy.cell', '--rehearse', trace=1,
                               seconds=6)
    assert rc == 0, err[-3000:]
    window, result = _lines(out)
    assert result['metrics']['dummy.steps.cpu_rehearsal']['value'] == \
        window['window']['steps']
    assert window['settings']['traffic']['name'] == 'dummy-mix'
