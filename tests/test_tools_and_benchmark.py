"""Tests for CLIs, copy tool, benchmark harness, reader mock, generator.

Parity: reference ``tests/test_benchmark.py``, ``tests/test_copy_dataset.py``,
``tests/test_reader_mock.py``, ``tests/test_generate_metadata.py``.
"""

import numpy as np
import pytest

from petastorm_tpu import make_reader
from petastorm_tpu.benchmark.throughput import reader_throughput
from petastorm_tpu.etl.metadata_cli import generate_metadata, print_metadata
from petastorm_tpu.generator import generate_datapoint
from petastorm_tpu.test_util.reader_mock import ReaderMock
from petastorm_tpu.test_util.shuffling_analysis import \
    compute_correlation_distribution
from petastorm_tpu.tools.copy_dataset import copy_dataset
from tests.conftest import TestSchema


def test_benchmark_harness_smoke(synthetic_dataset):
    result = reader_throughput(synthetic_dataset.url, warmup_cycles_count=10,
                               measure_cycles_count=50, pool_type='thread',
                               loaders_count=2)
    assert result.samples_per_second > 0
    assert result.memory_rss_mb > 0


def test_benchmark_jax_read_path(synthetic_dataset):
    from petastorm_tpu.jax_loader import PadTo
    result = reader_throughput(
        synthetic_dataset.url, warmup_cycles_count=8, measure_cycles_count=24,
        pool_type='dummy', read_method='jax', jax_batch_size=8,
        shuffling_queue_size=20, min_after_dequeue=10,
        shape_policies={'varlen': PadTo((8,))})
    assert result.samples_per_second > 0


def test_copy_dataset_full(synthetic_dataset, tmp_path):
    target = 'file://' + str(tmp_path / 'copy')
    count = copy_dataset(synthetic_dataset.url, target, rows_per_row_group=25)
    assert count == 50
    with make_reader(target, reader_pool_type='dummy') as reader:
        ids = sorted(r.id for r in reader)
    assert ids == list(range(50))


def test_copy_dataset_subset_and_filter(synthetic_dataset, tmp_path):
    target = 'file://' + str(tmp_path / 'copy_subset')
    count = copy_dataset(synthetic_dataset.url, target,
                         field_regex=['id', 'nullable_field'],
                         not_null_fields=['nullable_field'])
    expected = [r for r in synthetic_dataset.data if r['nullable_field'] is not None]
    assert count == len(expected)
    with make_reader(target, reader_pool_type='dummy') as reader:
        rows = list(reader)
    assert set(rows[0]._fields) == {'id', 'nullable_field'}
    assert all(r.nullable_field is not None for r in rows)


def test_generate_metadata_recovers_dropped_metadata(synthetic_dataset, tmp_path):
    import shutil
    work = tmp_path / 'regen'
    shutil.copytree(synthetic_dataset.path, work)
    (work / '_common_metadata').unlink()
    (work / '_metadata').unlink()
    url = 'file://' + str(work)
    with pytest.raises(RuntimeError):
        make_reader(url)
    generate_metadata(url, unischema_class='tests.conftest.TestSchema')
    with make_reader(url, reader_pool_type='dummy') as reader:
        ids = sorted(r.id for r in reader)
    assert ids == list(range(50))


def test_print_metadata_smoke(synthetic_dataset, capsys):
    print_metadata(synthetic_dataset.url, show_index=True)
    out = capsys.readouterr().out
    assert 'TestSchema' in out
    assert 'row-groups' in out


def test_reader_mock():
    with ReaderMock(TestSchema, seed=1) as reader:
        rows = [next(reader) for _ in range(5)]
    assert rows[0].image_png.shape == (32, 16, 3)
    assert isinstance(rows[0].id, np.int64)
    assert rows[0].matrix.dtype == np.float32


def test_generate_datapoint_matches_schema():
    rng = np.random.default_rng(0)
    row = generate_datapoint(TestSchema, rng)
    assert set(row) == set(TestSchema.fields)
    assert row['varlen'].ndim == 1


def test_shuffling_analysis(synthetic_dataset):
    ordered = list(range(50))
    streams = []
    for seed in range(3):
        with make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                         shuffle_row_groups=True, seed=seed,
                         shuffle_row_drop_partitions=2) as reader:
            streams.append([r.id for r in reader])
    mean_corr, correlations = compute_correlation_distribution(ordered, streams)
    assert len(correlations) == 3
    assert mean_corr < 0.9  # shuffled streams decorrelate from ordered


def test_throughput_cli(synthetic_dataset, capsys):
    from petastorm_tpu.benchmark.cli import main
    assert main([synthetic_dataset.url, '-w', '5', '-m', '20', '-p', 'dummy']) == 0
    assert 'samples/sec' in capsys.readouterr().out


def test_benchmark_tensor_read_path(synthetic_dataset):
    result = reader_throughput(
        synthetic_dataset.url, field_regex=['id', 'matrix'],
        warmup_cycles_count=10, measure_cycles_count=30,
        pool_type='dummy', read_method='tensor')
    assert result.samples_per_second > 0


def test_benchmark_profile_threads(synthetic_dataset, capsys):
    """--profile-threads parity: per-worker cProfile aggregated on join."""
    result = reader_throughput(
        synthetic_dataset.url, field_regex=['id'], warmup_cycles_count=5,
        measure_cycles_count=20, pool_type='thread', loaders_count=2,
        read_method='python', profile_threads=True)
    assert result.samples_per_second > 0
    out = capsys.readouterr().out
    assert 'cumulative' in out  # pstats table printed on pool join


def test_benchmark_tf_read_path(synthetic_dataset):
    pytest.importorskip('tensorflow')
    result = reader_throughput(
        synthetic_dataset.url, field_regex=['id', 'matrix'],
        warmup_cycles_count=5, measure_cycles_count=20,
        pool_type='dummy', read_method='tf')
    assert result.samples_per_second > 0
