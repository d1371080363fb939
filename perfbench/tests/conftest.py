"""The benchmark's own tests: ``python -m pytest perfbench/tests -q``.

They run on the CPU: in-process tests see four virtual devices, and the
harness runs in child processes at the tiny sizes of ``tests/tiny``.
"""

import json
import os
import subprocess
import sys

os.environ['JAX_PLATFORMS'] = 'cpu'
if 'xla_force_host_platform_device_count' not in os.environ.get('XLA_FLAGS', ''):
    os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '')
                               + ' --xla_force_host_platform_device_count=4').strip()

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(PERFBENCH)
TINY = os.path.join(HERE, 'tiny')
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def tiny_benchmark(folder, extra_paths=(), extra_cells=(), extra_metrics=()):
    """A BENCHMARK.json in ``folder`` whose cells are the tiny ones."""
    real = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))

    def config(name):
        return {'name': name, 'source': 'tests', 'reduced': [], 'why': 'tests',
                'file': os.path.join(TINY, name + '.json')}

    def cell(name, cfg, traffic, chips):
        return {'name': name, 'config': cfg, 'traffic': traffic,
                'chips': chips, 'why': 'tests'}

    cells = [cell('tiny.ramcache', 'tiny-resnet', 'tiny-ram-cached', 1),
             cell('tiny.tokens', 'tiny-gpt2', 'tiny-tokens', 1),
             cell('tiny.decode.x4', 'tiny-resnet', 'tiny-decode', 4)]
    cells += list(extra_cells)
    images = [c['name'] for c in cells if c['config'] == 'tiny-resnet']
    tokens = [c['name'] for c in cells if c['config'] == 'tiny-gpt2']
    streamed = [c['name'] for c in cells if c['traffic'] != 'tiny-ram-cached']
    per_layer = []
    for m in real['per_layer']:
        m = dict(m)
        if m['name'].startswith('kernel.'):
            m['workloads'] = tokens
        elif m['name'] == 'dispatch.h2d_overlap_frac':
            m['workloads'] = images
        elif m['name'] == 'step.collective_ms_per_step':
            m['workloads'] = [c['name'] for c in cells if c['chips'] == 4]
        elif 'workloads' in m:
            m['workloads'] = streamed
        per_layer.append(m)
    end_to_end = []
    for m in real['end_to_end']:
        m = dict(m)
        if 'workloads' in m:
            m['workloads'] = images
        end_to_end.append(m)
    bench = dict(real, paths=[TINY] + list(extra_paths),
                 configs=[config('tiny-resnet'), config('tiny-gpt2')],
                 workloads=cells, end_to_end=end_to_end,
                 per_layer=per_layer + list(extra_metrics))
    path = os.path.join(str(folder), 'BENCHMARK.json')
    with open(path, 'w') as f:
        json.dump(bench, f, indent=1)
    return path


def run_harness(benchmark, workload, *flags, devices=1, seed=5, seconds=1.0,
                trace=0, timeout=600):
    """One harness run in a child; (returncode, stdout lines, stderr)."""
    env = {k: v for k, v in os.environ.items() if k != 'XLA_FLAGS'}
    env['JAX_PLATFORMS'] = 'cpu'
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count={}'.format(devices)
    cmd = [sys.executable, '-m', 'perfbench.run', '--benchmark', benchmark,
           '--workload', workload, '--seed', str(seed), '--seconds',
           str(seconds), '--trace', str(trace)] + list(flags)
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=timeout)
    return done.returncode, done.stdout.strip().splitlines(), done.stderr


@pytest.fixture(scope='session')
def tiny(tmp_path_factory):
    return tiny_benchmark(tmp_path_factory.mktemp('tinybench'))
