"""Gated end-to-end smoke of the benchmark's imagenet child (bench.py).

Heavy (ResNet compiles at 224x224): runs only with ``PST_BENCH_SMOKE=1`` so
the default suite stays fast. It pins the child's explicit-CPU path
(``JAX_PLATFORMS=cpu``, output labelled ``platform: cpu``) and its JSON
contract; what runs on the chip is proven by ``chip_smoke.py``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get('PST_BENCH_SMOKE') != '1',
    reason='set PST_BENCH_SMOKE=1 to run the bench child smoke')

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_imagenet_child_cpu(tmp_path):
    sys.path.insert(0, REPO)
    import bench
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('ImagenetBenchSchema', [
        UnischemaField('image', np.uint8, (224, 224, 3),
                       CompressedImageCodec('jpeg', 90), False),
        UnischemaField('label', np.int64, (), ScalarCodec(np.int64), False)])
    rng = np.random.default_rng(7)
    url = 'file://' + str(tmp_path / 'store')
    write_dataset(url, schema,
                  ({'image': bench._synthetic_image(rng, 224),
                    'label': int(rng.integers(0, 1000))} for _ in range(64)),
                  rows_per_row_group=16)

    env = dict(os.environ,
               JAX_PLATFORMS='cpu', BENCH_IMAGENET_MODEL='tiny',
               BENCH_IMAGENET_BATCH='8', BENCH_IMAGENET_WARMUP='2',
               BENCH_IMAGENET_STEPS='4', BENCH_IMAGENET_SCAN_K='2',
               BENCH_IMAGENET_PREFETCH='2')
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'bench.py'), '--_child',
         'imagenet', url, '2'],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.strip().splitlines() if l.startswith('{')][-1]
    out = json.loads(line)

    # The JSON contract the driver and the judge read.
    assert out['platform'] == 'cpu'
    assert out['imagenet_img_per_sec_per_chip'] > 0
    assert 0.0 <= out['input_stall_frac'] <= 1.0
    for key in ('read_s', 'decode_s', 'cache_s', 'stage_dispatch_s',
                'consumer_wait_s', 'wall_s'):
        assert key in out['stage_profile']
    assert out['bench_config']['scan_microbatches'] == 2
    assert out['imagenet_hbm_cached_img_per_sec_per_chip'] > 0
    assert out['h2d_sustained_GBps'] > 0
