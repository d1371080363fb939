"""Metrics registry tests: instrument semantics, Prometheus exposition,
scrape-endpoint lifecycle, pipeline wiring, fleet aggregation over the
data-service ``metrics`` RPC, and the flight recorder (unit + an injected
stall producing a post-mortem dump directory)."""

import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

from petastorm_tpu import metrics
from petastorm_tpu.metrics import (DEFAULT_LATENCY_BUCKETS, MetricsExporter,
                                   MetricsRegistry, aggregate_snapshots,
                                   render_text)

pytestmark = pytest.mark.observability


@pytest.fixture
def fresh_registry():
    """Swap in an empty default registry (and restore after): pipeline
    objects built inside the test then report into an isolated namespace."""
    previous = metrics.set_registry(MetricsRegistry())
    yield metrics.get_registry()
    metrics.set_registry(previous)


# ---------------------------------------------------------------------------
# instrument semantics
# ---------------------------------------------------------------------------

def test_counter_semantics():
    r = MetricsRegistry()
    c = r.counter('pst_c_total', 'help text')
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError, match='only go up'):
        c.inc(-1)
    # get-or-create is idempotent: same object back
    assert r.counter('pst_c_total') is c


def test_type_and_label_conflicts_rejected():
    r = MetricsRegistry()
    r.counter('pst_x_total')
    with pytest.raises(ValueError, match='already registered'):
        r.gauge('pst_x_total')
    r.counter('pst_labeled_total', labelnames=('a',))
    with pytest.raises(ValueError, match='already registered'):
        r.counter('pst_labeled_total', labelnames=('b',))
    with pytest.raises(ValueError, match='invalid metric name'):
        r.counter('bad name')


def test_gauge_semantics():
    r = MetricsRegistry()
    g = r.gauge('pst_g')
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2
    g.set_function(lambda: 41 + 1)
    assert g.value == 42
    snap = r.collect()
    assert snap['pst_g']['samples'][0]['value'] == 42


def test_remove_label_child():
    r = MetricsRegistry()
    g = r.gauge('pst_rm', labelnames=('pipeline',))
    g.labels('a').set(1)
    g.labels('b').set(2)
    g.remove('a')
    g.remove('never-existed')           # no-op, no raise
    samples = r.collect()['pst_rm']['samples']
    assert [s['labels']['pipeline'] for s in samples] == ['b']


def test_autotuner_stop_retires_its_gauges(fresh_registry):
    from petastorm_tpu.autotune import AutoTuner, AutotuneConfig, Knob

    state = {'x': 2}
    tuner = AutoTuner(lambda: {'batches': 0, 'wait_s': 0.0},
                      {'workers': Knob('workers', lambda: state['x'],
                                       lambda n: state.update(x=n), 1, 8)},
                      AutotuneConfig(interval_s=60))
    tuner.tick(now=0.0)
    tuner.tick(now=1.0)                 # classifies -> enum gauge at 1
    snap = fresh_registry.collect()
    assert any(s['value'] == 1
               for s in snap['pst_autotune_bottleneck']['samples'])
    tuner.stop()
    snap = fresh_registry.collect()
    # a stopped pipeline's labeled children are gone, not stuck at 1
    assert snap['pst_autotune_bottleneck']['samples'] == []
    assert snap['pst_autotune_knob']['samples'] == []


def test_labels_create_independent_children():
    r = MetricsRegistry()
    c = r.counter('pst_lbl_total', labelnames=('op',))
    c.labels('read').inc(2)
    c.labels('decode').inc(1)
    c.labels(op='read').inc()       # keyword form hits the same child
    snap = r.collect()['pst_lbl_total']
    by_op = {s['labels']['op']: s['value'] for s in snap['samples']}
    assert by_op == {'read': 3, 'decode': 1}
    with pytest.raises(ValueError, match='expects labels'):
        c.labels('a', 'b')


def test_histogram_buckets_cumulative():
    r = MetricsRegistry()
    h = r.histogram('pst_h_seconds')
    assert h.buckets == tuple(sorted(DEFAULT_LATENCY_BUCKETS))
    for v in (0.0002, 0.003, 0.2, 99.0):
        h.observe(v)
    (sample,) = [s for s in r.collect()['pst_h_seconds']['samples']]
    assert sample['count'] == 4
    assert abs(sample['sum'] - 99.2032) < 1e-9
    buckets = sample['buckets']
    assert buckets['+Inf'] == 4                 # the 99s outlier
    assert buckets['0.00025'] == 1
    assert buckets['0.25'] == 3
    # cumulative: non-decreasing along the bound order
    ordered = [buckets['{:g}'.format(b)] for b in h.buckets]
    assert ordered == sorted(ordered)


def test_histogram_labeled_children_share_buckets():
    r = MetricsRegistry()
    h = r.histogram('pst_hl_seconds', labelnames=('stage',),
                    buckets=(0.1, 1.0))
    h.labels('a').observe(0.05)
    h.labels('b').observe(5.0)
    samples = r.collect()['pst_hl_seconds']['samples']
    by_stage = {s['labels']['stage']: s for s in samples}
    assert by_stage['a']['buckets'] == {'0.1': 1, '1': 1, '+Inf': 1}
    assert by_stage['b']['buckets'] == {'0.1': 0, '1': 0, '+Inf': 1}


# ---------------------------------------------------------------------------
# exposition + exporters
# ---------------------------------------------------------------------------

def test_prometheus_text_exposition_format():
    r = MetricsRegistry()
    r.counter('pst_events_total', 'Things that happened').inc(7)
    r.gauge('pst_depth', labelnames=('queue',)).labels('out').set(3)
    h = r.histogram('pst_lat_seconds', buckets=(0.5, 1.0))
    h.observe(0.25)
    text = r.render_text()
    assert '# HELP pst_events_total Things that happened' in text
    assert '# TYPE pst_events_total counter' in text
    assert 'pst_events_total 7' in text
    assert 'pst_depth{queue="out"} 3' in text
    assert '# TYPE pst_lat_seconds histogram' in text
    assert 'pst_lat_seconds_bucket{le="0.5"} 1' in text
    assert 'pst_lat_seconds_bucket{le="+Inf"} 1' in text
    assert 'pst_lat_seconds_sum 0.25' in text
    assert 'pst_lat_seconds_count 1' in text
    assert text.endswith('\n')


def test_label_value_escaping():
    r = MetricsRegistry()
    r.counter('pst_esc_total', labelnames=('path',)).labels(
        'a"b\\c\nd').inc()
    text = r.render_text()
    assert r'pst_esc_total{path="a\"b\\c\nd"} 1' in text


def test_write_textfile_atomic(tmp_path):
    r = MetricsRegistry()
    r.counter('pst_w_total').inc(2)
    target = str(tmp_path / 'metrics.prom')
    assert r.write_textfile(target) == target
    assert 'pst_w_total 2' in open(target).read()
    assert os.listdir(str(tmp_path)) == ['metrics.prom']   # no tmp leftover


def test_scrape_endpoint_lifecycle():
    r = MetricsRegistry()
    r.counter('pst_scrape_total').inc(9)
    exporter = MetricsExporter(registry=r, port=0).start()
    try:
        reply = urllib.request.urlopen(exporter.address, timeout=5)
        assert reply.status == 200
        assert 'text/plain' in reply.headers['Content-Type']
        assert 'pst_scrape_total 9' in reply.read().decode()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                'http://127.0.0.1:{}/nope'.format(exporter.port), timeout=5)
    finally:
        exporter.stop()
    # the listener is really gone (port refuses; thread joined)
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(exporter.address, timeout=1)


def test_aggregate_snapshots_sums_counters_and_histograms():
    def make(n):
        r = MetricsRegistry()
        r.counter('pst_a_total', labelnames=('op',)).labels('x').inc(n)
        h = r.histogram('pst_l_seconds', buckets=(1.0,))
        h.observe(0.5)
        r.gauge('pst_depth').set(n)
        return r.collect()

    merged = aggregate_snapshots([make(2), make(5)])
    (counter_sample,) = merged['pst_a_total']['samples']
    assert counter_sample['value'] == 7
    (hist_sample,) = merged['pst_l_seconds']['samples']
    assert hist_sample['count'] == 2
    assert hist_sample['buckets']['1'] == 2
    (gauge_sample,) = merged['pst_depth']['samples']
    assert gauge_sample['value'] == 7       # gauges sum = fleet total
    # an aggregate renders like any local snapshot
    assert 'pst_a_total{op="x"} 7' in render_text(merged)


# ---------------------------------------------------------------------------
# pipeline wiring: one collect() covers every subsystem
# ---------------------------------------------------------------------------

def test_loader_run_populates_registry(synthetic_dataset, fresh_registry):
    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader

    with make_tensor_reader(synthetic_dataset.url,
                            schema_fields=['id', 'matrix'],
                            reader_pool_type='thread', workers_count=2,
                            shuffle_row_groups=False) as reader:
        with JaxLoader(reader, 10, last_batch='drop',
                       watchdog=True, stall_timeout_s=30.0,
                       autotune=True) as loader:
            batches = sum(1 for _ in loader)
    snap = fresh_registry.collect()
    assert snap['pst_loader_batches_total']['samples'][0]['value'] == batches
    assert snap['pst_batch_wait_seconds']['samples'][0]['count'] >= batches
    assert snap['pst_decode_seconds']['samples'][0]['count'] >= 5
    assert snap['pst_staged_bytes_total']['samples'][0]['value'] > 0
    assert snap['pst_assemble_seconds']['samples'][0]['count'] >= batches
    # watchdog + autotune instruments registered (quiet run: zero stalls)
    assert 'pst_watchdog_soft_recoveries_total' in snap
    assert 'pst_autotune_bottleneck' in snap
    assert 'pst_autotune_decisions_total' in snap
    # the whole snapshot is valid exposition + JSON-safe
    text = render_text(snap)
    assert 'pst_loader_batches_total' in text
    json.dumps(snap)


def test_chunk_store_counters_reach_registry(tmp_path, synthetic_dataset,
                                             fresh_registry):
    from petastorm_tpu import make_tensor_reader

    store_dir = str(tmp_path / 'store')
    for _ in range(2):      # epoch 0 fills, epoch 1 hits
        with make_tensor_reader(synthetic_dataset.url,
                                schema_fields=['id', 'matrix'],
                                reader_pool_type='thread', workers_count=2,
                                shuffle_row_groups=False,
                                cache_type='chunk-store',
                                cache_location=store_dir) as reader:
            for _ in reader:
                pass
            reader.chunk_store.flush()
    snap = fresh_registry.collect()
    assert snap['pst_chunk_store_misses_total']['samples'][0]['value'] >= 5
    assert snap['pst_chunk_store_hits_total']['samples'][0]['value'] >= 1
    assert snap['pst_chunk_store_writes_total']['samples'][0]['value'] >= 1


def test_data_service_metrics_rpc_and_fleet_aggregate(synthetic_dataset,
                                                      fresh_registry):
    from petastorm_tpu.data_service import RemoteReader, serve_dataset

    with serve_dataset(synthetic_dataset.url, 'tcp://127.0.0.1:*',
                       schema_fields=['id', 'matrix'], num_epochs=1,
                       shuffle_row_groups=False, workers_count=2) as server:
        with RemoteReader(server.data_endpoint) as remote:
            chunks = sum(1 for _ in remote)
            fleet = remote.fleet_metrics()
    assert chunks > 0
    assert not fleet['unreachable']
    (endpoint,) = fleet['servers']
    served = fleet['aggregate']['pst_data_service_chunks_served_total']
    assert served['samples'][0]['value'] == chunks
    # server-side decode counters ride the same snapshot (same process
    # here; in a real fleet each server reports its own registry)
    assert 'pst_decode_seconds' in fleet['servers'][endpoint]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_dump_contents(tmp_path, fresh_registry):
    from petastorm_tpu.flight_recorder import FlightRecorder
    from petastorm_tpu.trace import Tracer

    fresh_registry.counter('pst_fr_total').inc(3)
    tracer = Tracer(spill_dir=False)
    with tracer.span('decode', 'worker'):
        pass
    recorder = FlightRecorder(str(tmp_path), tracer=tracer,
                              sample_min_interval_s=0.0)
    assert recorder.sample()
    diagnosis = {'classification': 'dispatch-hung', 'stage': 'dispatch',
                 'detail': 'synthetic', 'beats': {}, 'probes': {},
                 'stacks': 'Thread MainThread (1):\n  fake frame'}
    dump = recorder.dump(diagnosis, reason='dispatch-hung')
    assert dump is not None and 'dispatch-hung' in os.path.basename(dump)
    files = set(os.listdir(dump))
    assert {'trace.json', 'metrics.prom', 'metrics_ring.json',
            'diagnosis.json', 'stacks.txt'} <= files
    trace_doc = json.load(open(os.path.join(dump, 'trace.json')))
    assert any(e.get('name') == 'decode' for e in trace_doc['traceEvents'])
    assert 'pst_fr_total 3' in open(os.path.join(dump, 'metrics.prom')).read()
    ring = json.load(open(os.path.join(dump, 'metrics_ring.json')))
    assert ring and 'pst_fr_total' in ring[0]['metrics']
    diag = json.load(open(os.path.join(dump, 'diagnosis.json')))
    assert diag['classification'] == 'dispatch-hung'
    assert 'stacks' not in diag          # large dump lives in stacks.txt
    assert 'fake frame' in open(os.path.join(dump, 'stacks.txt')).read()
    assert recorder.dumps == [dump]


def test_flight_recorder_dump_on_injected_stall(synthetic_dataset, tmp_path,
                                                monkeypatch, fresh_registry):
    """The acceptance path: an injected stall (faults.py device-put-delay)
    escalates through the watchdog and leaves a flight-recorder dump
    directory — trace ring + metrics snapshot + diagnosis — with its path
    on the error's diagnosis."""
    from petastorm_tpu import flight_recorder, make_tensor_reader
    from petastorm_tpu.errors import PipelineStallError
    from petastorm_tpu.jax_loader import JaxLoader

    flight_dir = str(tmp_path / 'flight')
    monkeypatch.setenv(flight_recorder.ENV_VAR, flight_dir)
    monkeypatch.setenv('PETASTORM_TPU_FAULTS',
                       'device-put-delay:delay=30:max=1')
    reader = make_tensor_reader(synthetic_dataset.url,
                                schema_fields=['id', 'matrix'],
                                reader_pool_type='thread', workers_count=2,
                                shuffle_row_groups=False)
    loader = JaxLoader(reader, 10, watchdog=True, stall_timeout_s=0.3)
    try:
        with pytest.raises(PipelineStallError) as exc_info:
            for _ in loader:
                pass
    finally:
        monkeypatch.delenv('PETASTORM_TPU_FAULTS')
        loader.stop()
    dump = exc_info.value.diagnosis.get('flight_dump')
    assert dump is not None and os.path.isdir(dump)
    assert os.path.basename(dump).startswith('pst-flight-')
    files = set(os.listdir(dump))
    assert {'trace.json', 'metrics.prom', 'diagnosis.json',
            'stacks.txt'} <= files
    diag = json.load(open(os.path.join(dump, 'diagnosis.json')))
    assert diag['classification'] == 'dispatch-hung'
    # the dump also rides stats for a post-mortem that kept the loader
    assert loader.stats['watchdog']['flight_dumps'] == [dump]
    # and the metrics textfile carries the stall counter
    prom = open(os.path.join(dump, 'metrics.prom')).read()
    assert 'pst_watchdog_stalls_total' in prom


# ---------------------------------------------------------------------------
# metric-name documentation lint (ISSUE 7 satellite)
# ---------------------------------------------------------------------------

#: pst_-prefixed string literals that are NOT metric names (native shared-
#: library build targets).
# Non-metric pst_* literals the source scanner must ignore: native module
# names and the deterministic-mode item/chunk tag key (workers/ventilator).
_NON_METRIC_PST_LITERALS = {'pst_image', 'pst_parquet', 'pst_shm_ring',
                            'pst_det', 'pst_pinned',
                            # prefix filter in tools/fleet.py --status, not
                            # an instrument name
                            'pst_fleet_tenant_',
                            # Arrow IPC field/schema metadata keys of the
                            # fleet wire codec (fleet/wire.py), not
                            # instrument names
                            'pst_dtype', 'pst_shape', 'pst_object',
                            'pst_sidecar'}


def _source_metric_names():
    """Every pst_* instrument name registrable by the package source:
    plain literals plus the chunk store's formatted family."""
    import glob
    import re

    import petastorm_tpu

    root = os.path.dirname(petastorm_tpu.__file__)
    paths = glob.glob(os.path.join(root, '**', '*.py'), recursive=True)
    names = set()
    for path in paths:
        with open(path) as f:
            text = f.read()
        names.update(re.findall(r"['\"](pst_[a-z0-9_]+)['\"]", text))
        # Formatted family: 'pst_chunk_store_{}_total'.format(name) over a
        # literal tuple — expand it so a newly added counter must be
        # documented too.
        fmt = re.search(
            r"['\"](pst_[a-z0-9_]*)\{\}([a-z0-9_]*)['\"][\s\S]{0,200}?"
            r"for name in \(([^)]+)\)", text)
        if fmt:
            prefix, suffix, tuple_body = fmt.groups()
            for item in re.findall(r"'([a-z0-9_]+)'", tuple_body):
                names.add('{}{}{}'.format(prefix, item, suffix))
    return names - _NON_METRIC_PST_LITERALS


def _documented_metric_names():
    import re
    docs = os.path.join(os.path.dirname(__file__), os.pardir, 'docs',
                        'tpu_guide.rst')
    with open(docs) as f:
        text = f.read()
    start = text.index('Metric name reference')
    end = text.index('Input-bound escape hatches', start)
    return set(re.findall(r"``(pst_[a-z0-9_]+)``", text[start:end]))


@pytest.mark.observability
def test_every_registered_metric_is_documented():
    """Lint: the docs/tpu_guide.rst canonical metric table must cover
    every pst_* instrument the source can register — a new metric without
    a documented meaning fails here, and a table row whose metric was
    removed fails the other direction (the table claims to be canonical)."""
    source = _source_metric_names()
    documented = _documented_metric_names()
    undocumented = sorted(source - documented)
    stale = sorted(documented - source)
    assert not undocumented, (
        'metrics registered in source but missing from the docs table '
        '(docs/tpu_guide.rst "Metric name reference"): {}'.format(
            undocumented))
    assert not stale, (
        'docs table rows with no registering source site (remove them or '
        're-add the metric): {}'.format(stale))
