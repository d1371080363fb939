"""Shared fixtures: synthetic datasets written once per session.

Mirrors the reference's fixture strategy (``petastorm/tests/conftest.py`` +
``test_common.py:97-294``): session-scoped synthetic stores — a full-unischema
dataset (images, matrices, scalars, nullables, partitioned), a plain-parquet
scalar dataset, and a many-columns store — generated with pyarrow (no Spark).

JAX runs on a virtual 8-device CPU platform so multi-chip sharding is testable
without TPU hardware.
"""

import os

# Must be set before jax import (anywhere in the test process). Force CPU even
# if the environment points at real TPU hardware — tests run on a virtual
# 8-device CPU platform so multi-chip sharding is exercised without a pod.
os.environ['JAX_PLATFORMS'] = 'cpu'
xla_flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in xla_flags:
    os.environ['XLA_FLAGS'] = (xla_flags + ' --xla_force_host_platform_device_count=8').strip()

import numpy as np
import pytest

from petastorm_tpu.codecs import (CompressedImageCodec, CompressedNdarrayCodec,
                                  NdarrayCodec, ScalarCodec)
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.unischema import Unischema, UnischemaField

TestSchema = Unischema('TestSchema', [
    UnischemaField('id', np.int64, (), ScalarCodec(np.int64), False),
    UnischemaField('id2', np.int32, (), ScalarCodec(np.int32), False),
    UnischemaField('partition_key', np.str_, (), ScalarCodec(np.str_), False),
    UnischemaField('image_png', np.uint8, (32, 16, 3), CompressedImageCodec('png'), False),
    UnischemaField('matrix', np.float32, (4, 5), NdarrayCodec(), False),
    UnischemaField('matrix_compressed', np.float64, (3, 3), CompressedNdarrayCodec(), False),
    UnischemaField('varlen', np.int64, (None,), NdarrayCodec(), False),
    UnischemaField('sensor_name', np.str_, (), ScalarCodec(np.str_), False),
    UnischemaField('nullable_field', np.int32, (), ScalarCodec(np.int32), True),
])


def _row(i, rng):
    return {
        'id': i,
        'id2': i % 5,
        'partition_key': 'p_{}'.format(i % 4),
        'image_png': rng.integers(0, 255, (32, 16, 3), dtype=np.uint8),
        'matrix': rng.random((4, 5), dtype=np.float32),
        'matrix_compressed': rng.random((3, 3)),
        'varlen': np.arange(i % 7 + 1, dtype=np.int64),
        'sensor_name': 'sensor_{}'.format(i % 3),
        'nullable_field': None if i % 3 == 0 else i * 2,
    }


ROWS_COUNT = 50


@pytest.fixture(scope='session')
def synthetic_dataset(tmp_path_factory):
    path = tmp_path_factory.mktemp('synthetic') / 'dataset'
    url = 'file://' + str(path)
    rng = np.random.default_rng(42)
    rows = [_row(i, rng) for i in range(ROWS_COUNT)]
    write_dataset(url, TestSchema, rows, rows_per_row_group=10)

    class _Dataset:
        pass

    ds = _Dataset()
    ds.url = url
    ds.path = str(path)
    ds.data = rows
    return ds


@pytest.fixture(scope='session')
def scalar_dataset(tmp_path_factory):
    """Plain Parquet store with no unischema metadata (for make_batch_reader)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    path = tmp_path_factory.mktemp('scalar') / 'dataset'
    os.makedirs(path, exist_ok=True)
    rng = np.random.default_rng(0)
    n = 100
    table = pa.table({
        'id': pa.array(np.arange(n, dtype=np.int64)),
        'float_col': pa.array(rng.random(n)),
        'int_fixed': pa.array(rng.integers(0, 100, n, dtype=np.int32)),
        'string_col': pa.array(['value_{}'.format(i % 10) for i in range(n)]),
        'list_col': pa.array([[float(i), float(i + 1)] for i in range(n)]),
    })
    pq.write_table(table, str(path / 'data.parquet'), row_group_size=20)

    class _Dataset:
        pass

    ds = _Dataset()
    ds.url = 'file://' + str(path)
    ds.path = str(path)
    ds.table = table
    return ds


@pytest.fixture(scope='session')
def partitioned_synthetic_dataset(tmp_path_factory):
    """Unischema dataset hive-partitioned by partition_key."""
    path = tmp_path_factory.mktemp('partitioned') / 'dataset'
    url = 'file://' + str(path)
    rng = np.random.default_rng(7)
    rows = [_row(i, rng) for i in range(ROWS_COUNT)]
    write_dataset(url, TestSchema, rows, rows_per_row_group=5,
                  partition_fields=('partition_key',))

    class _Dataset:
        pass

    ds = _Dataset()
    ds.url = url
    ds.path = str(path)
    ds.data = rows
    return ds


def pytest_configure(config):
    # Also declared in pytest.ini; registering here too keeps direct
    # `pytest tests/...` invocations from other rootdirs warning-free.
    config.addinivalue_line('markers', 'processpool: spawns real worker processes (slower)')
    config.addinivalue_line(
        'markers',
        'chaos: fault-injection tests (tests/test_chaos.py) driving '
        'PETASTORM_TPU_FAULTS sites and worker-kill recovery.')
    config.addinivalue_line(
        'markers',
        'slow: heavyweight tests (interpret-mode Pallas, transformer/MoE/'
        'pipeline training, timing gates). The fast CI lane skips them: '
        'pytest -m "not slow" finishes in minutes; run the full suite '
        'before shipping.')
    config.addinivalue_line(
        'markers',
        'autotune: adaptive-autotuner tests (tests/test_autotune.py) '
        'driving the feedback controller, live pool resize, and '
        'ventilator backpressure.')
    config.addinivalue_line(
        'markers',
        'timeout(seconds): per-test wall-clock budget override for the '
        'SIGALRM hang guard (see _per_test_timeout in conftest.py).')
    config.addinivalue_line(
        'markers',
        'chunkstore: NVMe decoded-chunk-store tests '
        '(tests/test_chunk_store.py); the conftest guard deletes any '
        'leaked pst-chunk-store-* temp dirs after them.')
    config.addinivalue_line(
        'markers',
        'observability: tracing/metrics/flight-recorder tests '
        '(tests/test_trace.py, tests/test_metrics.py); the conftest guard '
        'sweeps leaked trace sidecar and flight-dump temp dirs after them.')
    config.addinivalue_line(
        'markers',
        'lineage: batch-provenance/replay tests (tests/test_lineage.py); '
        'the conftest guard sweeps leaked pst-lineage-* ledger temp dirs '
        'after them.')
    config.addinivalue_line(
        'markers',
        'determinism: deterministic-mode tests (tests/test_determinism.py) '
        'proving bit-identical streams across restarts/reshards; the '
        'conftest guard fails on leaked pst-det* threads after them.')
    config.addinivalue_line(
        'markers',
        'pstlint: static-analyzer + runtime-sanitizer tests '
        '(tests/test_pstlint.py); includes the tier-1 CI gate running the '
        'full analyzer over petastorm_tpu/ and failing on findings.')


# ---------------------------------------------------------------------------
# Per-test hang guard: a reintroduced pipeline hang must fail ONE test fast
# (with a full thread dump naming the stuck stage) instead of eating the
# whole tier-1 wall-clock budget. pytest-timeout provides this when
# installed; this SIGALRM fixture is the stdlib fallback, honoring the
# existing markers: plain tests get a tight budget, `chaos` (fault
# injection, worker respawn) a wider one, `slow` the widest. Override per
# test with @pytest.mark.timeout(seconds).
# ---------------------------------------------------------------------------

_TIMEOUT_DEFAULT_S = 120
_TIMEOUT_CHAOS_S = 240
_TIMEOUT_SLOW_S = 600


class TestHangTimeout(Exception):
    """The per-test SIGALRM budget expired: the test is hung, not slow."""


@pytest.fixture(autouse=True)
def _per_test_timeout(request):
    import signal
    import threading

    if (not hasattr(signal, 'SIGALRM')
            or threading.current_thread() is not threading.main_thread()
            or request.config.pluginmanager.hasplugin('timeout')):
        yield
        return
    budget = _TIMEOUT_DEFAULT_S
    if request.node.get_closest_marker('chaos') is not None:
        budget = _TIMEOUT_CHAOS_S
    if request.node.get_closest_marker('slow') is not None:
        budget = _TIMEOUT_SLOW_S
    override = request.node.get_closest_marker('timeout')
    if override is not None and override.args:
        budget = float(override.args[0])

    def on_alarm(signum, frame):
        from petastorm_tpu.health import dump_all_stacks
        raise TestHangTimeout(
            'test exceeded its {}s hang-guard budget. All-thread stacks:\n'
            '{}'.format(budget, dump_all_stacks()))

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, budget)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


# ---------------------------------------------------------------------------
# Consolidated leak sweep, driven by the canonical registry
# (petastorm_tpu/analysis/registry.py). One fixture replaces the per-feature
# guards that accreted over PRs 4-8 (autotuner, metrics exporter, lineage
# writer, determinism threads, chunk-store/trace/flight temp dirs):
#
# * ThreadGuard entries with action='fail' FAIL the test when a matching
#   pst-* thread survives it (scoped by marker; marker=None runs on every
#   test). A shared 2s grace lets stop()/close() joins land first.
# * DirGuard entries snapshot-diff the shared tempdir and delete only what
#   appeared during the test — the tempdir is host-shared, and deleting a
#   store/ledger another process holds open would corrupt IT mid-run.
#
# The same registry backs pstlint's thread-lifecycle checker, so a new
# background thread cannot ship without declaring its join path here;
# tests/test_pstlint.py pins the registry's dir prefixes against the owning
# modules' constants. Thread waits run BEFORE dir sweeps (a live writer may
# still hold files inside a dir about to be swept).
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _registry_leak_sweep(request):
    import glob
    import shutil
    import tempfile
    import threading
    import time as _time

    from petastorm_tpu.analysis.registry import DIR_GUARDS, THREAD_GUARDS

    def applies(guard):
        return guard.marker is None or \
            request.node.get_closest_marker(guard.marker) is not None

    thread_guards = [g for g in THREAD_GUARDS
                     if g.action == 'fail' and applies(g)]
    tmp = tempfile.gettempdir()
    # A guard may anchor its patterns off the tempdir (base attr — e.g.
    # /dev/shm for the wire's segment rings); older registry entries
    # without the attr keep the tempdir default.
    patterns = [os.path.join(getattr(g, 'base', None) or tmp, pat)
                for g in DIR_GUARDS if applies(g) for pat in g.patterns]
    before = {p for pat in patterns for p in glob.glob(pat)}
    yield
    leaked_threads = []
    if thread_guards:
        deadline = _time.monotonic() + 2.0
        while _time.monotonic() < deadline:
            leaked_threads = sorted(
                t.name for t in threading.enumerate()
                if t.is_alive()
                and any(t.name.startswith(g.prefix) for g in thread_guards))
            if not leaked_threads:
                break
            _time.sleep(0.05)   # stop() joins with a timeout: let it land
    for pat in patterns:
        for leaked in set(glob.glob(pat)) - before:
            if os.path.isdir(leaked):
                shutil.rmtree(leaked, ignore_errors=True)
            else:
                try:
                    os.unlink(leaked)
                except OSError:
                    pass
    if leaked_threads:
        owners = {g.prefix: g.owner for g in thread_guards}
        pytest.fail(
            'registered pst-* thread(s) leaked past the test: {} — see the '
            'owning module(s) {} and the join-path rationale in '
            'petastorm_tpu/analysis/registry.py'.format(
                leaked_threads,
                sorted({owner for prefix, owner in owners.items()
                        if any(name.startswith(prefix)
                               for name in leaked_threads)})))


TimeseriesSchema = Unischema('TimeseriesSchema', [
    UnischemaField('timestamp', np.int64, (), ScalarCodec(np.int64), False),
    UnischemaField('sensor', np.float32, (3,), NdarrayCodec(), False),
    UnischemaField('label', np.int32, (), ScalarCodec(np.int32), False),
])


@pytest.fixture(scope='session')
def timeseries_dataset(tmp_path_factory):
    """Ordered timestamped rows (one gap at ts=25->35) for NGram tests."""
    path = tmp_path_factory.mktemp('timeseries') / 'dataset'
    url = 'file://' + str(path)
    rng = np.random.default_rng(3)
    rows = []
    ts = 0
    for i in range(40):
        ts += 1 if i != 25 else 10  # a delta_threshold-violating gap
        rows.append({'timestamp': ts,
                     'sensor': rng.random(3, dtype=np.float32),
                     'label': i % 4})
    write_dataset(url, TimeseriesSchema, rows, rows_per_row_group=20)

    class _Dataset:
        pass

    ds = _Dataset()
    ds.url = url
    ds.data = rows
    return ds


@pytest.fixture(scope='session')
def many_columns_dataset(tmp_path_factory):
    """1000-column plain Parquet store (no unischema metadata).

    Parity: reference ``tests/test_common.py:248-294``
    (``many_columns_non_petastorm_dataset``) — exercises namedtuple codegen
    and column pruning at schema width.
    """
    import pyarrow as pa
    import pyarrow.parquet as pq

    path = tmp_path_factory.mktemp('many_columns') / 'dataset'
    os.makedirs(path, exist_ok=True)
    n_cols, n_rows = 1000, 30
    data = {'col_{}'.format(c): np.arange(c, c + n_rows, dtype=np.int64)
            for c in range(n_cols)}
    table = pa.table(data)
    pq.write_table(table, str(path / 'data.parquet'), row_group_size=10)

    class _Dataset:
        pass

    ds = _Dataset()
    ds.url = 'file://' + str(path)
    ds.path = str(path)
    ds.n_cols = n_cols
    ds.n_rows = n_rows
    return ds
