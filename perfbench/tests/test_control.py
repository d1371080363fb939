"""The control: the reference put in the program's place at the nearest
precision below the configuration's (fp8 operands for bfloat16) has to come
out as not correct, by the limits of the tiny configurations, while the
reference at the program's own precision (bfloat16 operands) passes. Also
ties the reference to the program: same tree, and losses that agree."""

import json
import os

import numpy as np
import pytest
from conftest import TINY

from perfbench import check, harness, lowprec

SEEDS = (1, 2, 3)


def _setup(name, seed):
    cfg = json.load(open(os.path.join(TINY, name + '.json')))
    # no window here, so no rows to count: the training numbers' limits alone
    cfg['limits'] = {k: v for k, v in cfg['limits'].items() if v != 0}
    folder = TINY
    ref = harness.load_module(os.path.join(folder, cfg['reference_file']))
    rng = np.random.default_rng(seed)
    batch = 4 * cfg['assumed']['rows_per_chip_per_step']
    if cfg['store'] == 'jpeg_images':
        size = cfg['image_size']
        batches = [{'image': rng.integers(0, 255, (batch, size, size, 3),
                                          dtype=np.uint8),
                    'label': rng.integers(0, cfg['num_classes'], batch)}
                   for _ in range(3)]
    else:
        length = cfg['assumed']['sequence_length'] + 1
        batches = [{'tokens': rng.integers(0, cfg['vocab_size'],
                                           (batch, length), dtype=np.int32)}
                   for _ in range(3)]
    return cfg, ref, batches


@pytest.mark.parametrize('name', ['tiny-resnet', 'tiny-gpt2'])
def test_control_fails_and_own_precision_passes(name):
    failed = 0
    for seed in SEEDS:
        cfg, ref, batches = _setup(name, seed)
        truth = check.follow_reference(ref, cfg, seed, batches)
        own = check.follow_reference(ref, cfg, seed, batches,
                                     quant=lowprec.BF16)
        numbers, _ = check.training_numbers(own, truth)
        _, ok = check.verdict(numbers, cfg['limits'])
        assert ok, (seed, numbers)
        control = check.follow_reference(
            ref, cfg, seed, batches, quant=lowprec.CONTROLS[cfg['compute_dtype']])
        numbers, _ = check.training_numbers(control, truth)
        _, ok = check.verdict(numbers, cfg['limits'])
        failed += not ok
    assert failed == len(SEEDS)


@pytest.mark.parametrize('name', ['tiny-resnet', 'tiny-gpt2'])
def test_half_batch_fault_in_the_reference_fails(name):
    cfg, ref, batches = _setup(name, 4)
    truth = check.follow_reference(ref, cfg, 4, batches)
    rows = len(next(iter(batches[0].values())))
    half = check.follow_reference(ref, cfg, 4, batches, rows_used=rows // 2)
    numbers, _ = check.training_numbers(half, truth)
    assert not check.verdict(numbers, cfg['limits'])[1]


@pytest.mark.parametrize('name', ['tiny-resnet', 'tiny-gpt2'])
def test_reference_tree_is_the_program_s_and_losses_agree(name):
    import jax
    cfg, ref, batches = _setup(name, 7)
    program = harness.load_module(os.path.join(TINY, cfg['program_file']))
    params = ref.init_params(cfg, 7)
    state, step = program.build(cfg, params, ref.init_batch_stats(cfg), None,
                                interpret=True)
    # the layout the program's own init gives
    if cfg['store'] == 'jpeg_images':
        example = np.zeros((1, cfg['image_size'], cfg['image_size'], 3),
                           np.float32)
    else:
        example = np.zeros((1, cfg['assumed']['sequence_length']), np.int32)
    own = jax.eval_shape(lambda: state.apply_fn.__self__.init(
        jax.random.PRNGKey(0), example, train=False))['params']
    want = jax.tree_util.tree_map(lambda a: a.shape, own)
    got = jax.tree_util.tree_map(lambda a: a.shape, params)
    assert want == got
    Batch = __import__('collections').namedtuple('Batch', sorted(batches[0]))
    _, metrics = step(state, Batch(**{k: jax.numpy.asarray(v)
                                      for k, v in batches[0].items()}))
    reference, _ = ref.loss_and_grad(ref.init_params(cfg, 7), batches[0], cfg)
    assert float(metrics['loss']) == pytest.approx(float(reference), rel=0.05)


def test_exact_rows_comparison_catches_one_altered_byte():
    rows = {'id': np.arange(4), 'image': np.zeros((4, 8, 8, 3), np.uint8),
            'label': np.arange(4)}
    good = check.host_checksum(rows, 'image')
    rows['image'][2, 3, 3, 1] ^= 1
    bad = check.host_checksum(rows, 'image')
    assert (good != bad).tolist() == [False, False, True, False]


@pytest.mark.parametrize('cell,readings', [
    ('tiny.ramcache', 3), ('tiny.tokens', 3), ('tiny.decode.x4', 4)])
def test_calibrate_holds_control_and_fault_to_the_configuration_s_limits(
        tiny, cell, readings):
    """``calibrate.py``, which reads the control and the faults at a cell's own
    size on the chip, puts each through ``check.verdict`` with the limits of
    the configuration and exits 1 where one of them comes out correct."""
    import subprocess
    import sys

    from conftest import ROOT
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    done = subprocess.run(
        [sys.executable, '-m', 'perfbench.calibrate', '--benchmark', tiny,
         '--workload', cell, '--seeds', '1,2', '--witness', '--rehearse'],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    # control, half a batch, the witness; on four chips the exchange left out
    assert len(lines) == 2 * readings
    for line in lines:
        assert line['correct'] is line['variant'].startswith('witness'), line
        assert bool(line['failed']) is not line['correct']
