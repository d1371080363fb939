"""Per-device sharded staging (ISSUE 14): true multi-device dispatch on
the forced 8-device CPU platform, and simulated multi-host equivalence.

The conftest pins ``--xla_force_host_platform_device_count=8``, so every
test here runs against eight real (virtual) devices: shard planning,
per-device streams, global-array stitching, donation accounting, and the
deterministic multi-"host" story are all exercised without TPU time.
"""

import json
import os
import subprocess
import sys
import threading
import zlib

import jax
import numpy as np
import pytest

from petastorm_tpu import make_pod_reader, make_tensor_reader
from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
from petastorm_tpu.etl.writer import write_dataset
from petastorm_tpu.jax_loader import JaxLoader, iter_numpy_batches
from petastorm_tpu.parallel import make_mesh
from petastorm_tpu.parallel.mesh import device_shard_plan
from petastorm_tpu.unischema import Unischema, UnischemaField

pytestmark = pytest.mark.multichip

ROWS = 64
ROWS_PER_GROUP = 8

MCSchema = Unischema('MCSchema', [
    UnischemaField('id', np.int64, (), ScalarCodec(np.int64), False),
    UnischemaField('vec', np.float32, (6,), NdarrayCodec(), False),
])


@pytest.fixture(scope='module')
def mc_dataset(tmp_path_factory):
    path = tmp_path_factory.mktemp('multichip') / 'dataset'
    url = 'file://' + str(path)
    rng = np.random.default_rng(11)
    rows = [{'id': i, 'vec': rng.random(6).astype(np.float32)}
            for i in range(ROWS)]
    write_dataset(url, MCSchema, rows, rows_per_row_group=ROWS_PER_GROUP)

    class _DS(object):
        pass

    ds = _DS()
    ds.url = url
    ds.rows = rows
    return ds


def _reader(url, **kw):
    # workers_count=1: bitwise parity tests compare two separate runs, so
    # chunk ARRIVAL order must be deterministic (a 2-worker pool may
    # deliver chunk k+1 first and swap halves of a collated batch —
    # legitimate, but it would make run-vs-run comparisons racy).
    defaults = dict(reader_pool_type='thread', workers_count=1,
                    num_epochs=1, shuffle_row_groups=False)
    defaults.update(kw)
    return make_tensor_reader(url, **defaults)


# ---------------------------------------------------------------------------
# shard planning
# ---------------------------------------------------------------------------

def test_shard_plan_batch_dim_only():
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = make_mesh({'data': 4, 'model': 2})
    plan = device_shard_plan(NamedSharding(mesh, PartitionSpec('data')),
                             (16, 3), process_count=1)
    assert plan is not None and plan.n_devices == 8
    assert plan.global_shape == (16, 3)
    # 4 distinct 4-row spans, each bound shared by its 2 'model' replicas.
    assert sorted(set(plan.bounds)) == [(0, 4), (4, 8), (8, 12), (12, 16)]
    counts = {b: plan.bounds.count(b) for b in set(plan.bounds)}
    assert set(counts.values()) == {2}


def test_shard_plan_rejects_non_batch_dims_and_uneven():
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = make_mesh({'data': 4, 'model': 2})
    # Sequence dim sharded: ineligible (slices a non-batch dim).
    seq = NamedSharding(mesh, PartitionSpec('data', 'model'))
    assert device_shard_plan(seq, (16, 8), process_count=1) is None
    # Addressable shards that don't tile the local rows: ineligible.
    data = NamedSharding(mesh, PartitionSpec('data'))
    assert device_shard_plan(data, (6, 3), process_count=1) is None


def test_shard_plan_replicated_sharding():
    from petastorm_tpu.parallel.mesh import replicated_sharding
    mesh = make_mesh({'data': 8})
    plan = device_shard_plan(replicated_sharding(mesh), (16, 3),
                             process_count=1)
    assert plan is not None and plan.n_devices == 8
    assert set(plan.bounds) == {(0, 16)}   # every device gets the batch


# ---------------------------------------------------------------------------
# per-device dispatch: engagement, parity, fallbacks
# ---------------------------------------------------------------------------

def _collect(url, batch=16, **loader_kw):
    with _reader(url) as reader:
        with JaxLoader(reader, batch, mesh=make_mesh({'data': 8}),
                       **loader_kw) as loader:
            batches = [(np.asarray(b.id), np.asarray(b.vec)) for b in loader]
            stats = loader.stats
    return batches, stats


def _host_batches(url, batch=16):
    """The reference: the same rows collated on the host by a second
    reader, touching no device path."""
    with _reader(url) as reader:
        return [(b['id'], b['vec']) for b in iter_numpy_batches(reader, batch)]


def _assert_same_batches(got, ref):
    assert len(got) == len(ref) > 0
    for (gid, gvec), (rid, rvec) in zip(got, ref):
        np.testing.assert_array_equal(gid, rid)
        np.testing.assert_array_equal(gvec, rvec)


def test_per_device_path_dispatches_global_arrays(mc_dataset):
    mesh = make_mesh({'data': 8})
    with _reader(mc_dataset.url) as reader:
        with JaxLoader(reader, 16, mesh=mesh) as loader:
            batch = next(iter(loader))
            assert len(batch.vec.sharding.device_set) == 8
            # Every addressable shard holds exactly its slice of the batch.
            expected = np.asarray(batch.vec)
            for shard in batch.vec.addressable_shards:
                np.testing.assert_array_equal(np.asarray(shard.data),
                                              expected[shard.index])
            stats = loader.stats
    assert stats['n_devices'] == 8
    assert stats['shards_put'] >= 8


def test_per_device_matches_host_batches_bit_identical(mc_dataset):
    fast, fast_stats = _collect(mc_dataset.url)
    assert fast_stats['n_devices'] == 8
    assert len(fast) == ROWS // 16
    _assert_same_batches(fast, _host_batches(mc_dataset.url))


# One 'vec' shard of a 16-row batch on eight devices: 2 rows x 6 float32.
_VEC_SHARD_BYTES = 2 * 6 * 4


@pytest.mark.parametrize('min_bytes, fields_a_batch', [
    (_VEC_SHARD_BYTES + 1, {'inline-batched': 2}),
    (_VEC_SHARD_BYTES, {'inline-batched': 1, 'streamed-batched': 1}),
    (_VEC_SHARD_BYTES - 1, {'inline-batched': 1, 'streamed-batched': 1}),
], ids=['below', 'at', 'above'])
def test_tier_follows_shard_bytes(mc_dataset, min_bytes, fields_a_batch):
    """The one staging decision of a planned field: its shard's bytes
    against ``device_stream_min_bytes``, below / at / above. 'id' (8-byte
    shards) stays inline throughout, so it is 'vec' that moves."""
    from petastorm_tpu.trace import Tracer
    tracer = Tracer()
    got, stats = _collect(mc_dataset.url, device_stream_min_bytes=min_bytes,
                          tracer=tracer)
    assert stats['stage_tiers'] == {tier: k * len(got)
                                    for tier, k in fields_a_batch.items()}
    causes = [r[7] for r in tracer.records()
              if r[0] == 'dispatch.stage' and len(r) == 8]
    assert causes == [sorted(fields_a_batch)] * len(got)
    _assert_same_batches(got, _host_batches(mc_dataset.url))


def test_stream_tier_forced_and_threads_join(mc_dataset):
    """``device_stream_min_bytes=0`` routes every shard through the
    ``pst-device-put-*`` stream threads; values stay identical and the
    threads join at stop (the conftest leak guard enforces the latter on
    every test — this one also asserts it explicitly)."""
    mesh = make_mesh({'data': 8})
    with _reader(mc_dataset.url) as reader:
        with JaxLoader(reader, 16, mesh=mesh, device_stream_min_bytes=0,
                       device_inflight=1) as loader:
            batches = [(np.asarray(b.id), np.asarray(b.vec)) for b in loader]
            # Streams start lazily on the first streamed wave.
            names = {t.name for t in threading.enumerate()}
            assert any(n.startswith('pst-device-put-') for n in names)
            stats = loader.stats
    _assert_same_batches(batches, _host_batches(mc_dataset.url))
    assert stats['shards_put'] >= 8
    assert stats['device_inflight'] == 1
    assert not any(t.name.startswith('pst-device-put-')
                   for t in threading.enumerate() if t.is_alive())


def test_streamed_overlap_reported_in_stats(mc_dataset):
    """The stager's OverlapMeter surfaces the streamed-path h2d/host
    co-activity in ``loader.stats``."""
    mesh = make_mesh({'data': 8})
    with _reader(mc_dataset.url) as reader:
        with JaxLoader(reader, 16, mesh=mesh,
                       device_stream_min_bytes=0) as loader:
            for _ in loader:
                pass
            stats = loader.stats
    assert 0.0 <= stats['h2d_overlap_frac'] <= 1.0
    busy = stats['h2d_overlap']['busy_s']
    assert busy.get('h2d', 0) > 0      # transfers rode the windows
    assert busy.get('host', 0) > 0     # staging tracked as host work


def test_streamed_stop_midstream_reclaims_window_and_threads(mc_dataset,
                                                             monkeypatch):
    """stop() mid-stream on the streamed tier: in-flight window bytes
    are reclaimed (the arenas those bytes pin can recycle or die) and
    zero ``pst-device-put-*`` threads outlive the loader."""
    from petastorm_tpu import faults
    monkeypatch.setenv(faults.ENV_VAR, 'device-put-delay:delay=0.02')
    mesh = make_mesh({'data': 8})
    reader = _reader(mc_dataset.url, num_epochs=None)
    loader = JaxLoader(reader, 16, mesh=mesh, device_stream_min_bytes=0,
                       device_inflight=2)
    it = iter(loader)
    next(it)
    next(it)
    loader.stop()
    assert loader.stats['device_put_leaked_threads'] == []
    assert loader._stager is not None
    assert loader._stager.window_nbytes == 0
    assert not any(t.name.startswith('pst-device-put-')
                   for t in threading.enumerate() if t.is_alive())


def test_sequence_sharded_field_falls_back_per_field(mc_dataset):
    """A per-field dict where one field's sharding splits a non-batch dim:
    that field takes the one-shot path, the rest stay per-device, and the
    delivered values are right either way."""
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = make_mesh({'data': 4, 'model': 2})
    sharding = {'vec': NamedSharding(mesh, PartitionSpec('data', 'model')),
                'id': NamedSharding(mesh, PartitionSpec('data'))}
    with _reader(mc_dataset.url) as reader:
        with JaxLoader(reader, 16, mesh=mesh, sharding=sharding) as loader:
            batches = [(np.asarray(b.id), np.asarray(b.vec)) for b in loader]
            stats = loader.stats
    ids = [i for b in batches for i in b[0].tolist()]
    assert sorted(ids) == list(range(ROWS))
    # Only 'id' is per-device-planned (4 distinct shards x 2 replicas);
    # 'vec' shards a non-batch dim and must not be counted.
    assert stats['shards_put'] == len(batches) * 8


# ---------------------------------------------------------------------------
# donation + membudget accounting
# ---------------------------------------------------------------------------

def test_donated_arena_shards_not_double_accounted(mc_dataset, monkeypatch):
    """Arena-backed shards are donated (no defensive host copy) and the
    membudget governor accounts their bytes ONCE: the arena pool owns
    them, the device-put-window pool reports zero."""
    from petastorm_tpu import membudget
    monkeypatch.setenv(membudget.ENV_VAR, '8g')
    mesh = make_mesh({'data': 8})
    with _reader(mc_dataset.url) as reader:
        # batch 24 never aligns with the 8-row chunks: every batch
        # collates into an arena (chunk views can't cover it), so the
        # dispatched shards are donated arena sub-slices.
        with JaxLoader(reader, 24, mesh=mesh) as loader:
            for _ in loader:
                pass
            stats = loader.stats
            governor = membudget.get_governor()
            governor.check()
            pools = {entry['pool']: entry['nbytes']
                     for entry in governor.pool_ranking()}
    assert stats['shards_donated'] > 0
    assert pools.get('arena-pool', 0) > 0
    assert pools.get('device-put-window') == 0
    from petastorm_tpu import metrics
    snapshot = metrics.get_registry().collect()
    donated = snapshot.get('pst_shards_donated_total')
    assert donated is not None
    assert sum(s['value'] for s in donated['samples']) \
        >= stats['shards_donated']


# ---------------------------------------------------------------------------
# autotune: per-device inflight steps before global inflight
# ---------------------------------------------------------------------------

def test_dispatch_bound_steps_device_inflight_first():
    from petastorm_tpu.autotune import AutotuneConfig, AutoTuner, Knob
    cfg = AutotuneConfig(interval_s=0.1, hysteresis=1, cooldown=0,
                         max_device_inflight=3)
    values = {'device_inflight': 2, 'inflight': 2}
    knobs = {name: Knob(name, lambda n=name: values[n],
                        lambda v, n=name: values.__setitem__(n, v),
                        lo=1, hi=(3 if name == 'device_inflight' else 8))
             for name in values}
    state = {'t': 0.0, 'ready': 0.0}

    def telemetry():
        state['ready'] += 0.9      # transfer fences dominate every tick
        return {'batches': state['t'] * 10, 'wait_s': state['t'] * 0.5,
                'ready_wait_s': state['ready'], 'queue_depth': 0,
                'queue_capacity': 4}

    tuner = AutoTuner(telemetry, knobs, config=cfg)
    decisions = []
    for _ in range(12):
        state['t'] += 1.0
        decision = tuner.tick(now=state['t'])
        if decision:
            decisions.append(decision)
    tuner.stop()
    stepped = [name for d in decisions for name, _old, _new in d['changes']]
    # device_inflight climbs to its clamp FIRST; only then inflight moves.
    assert stepped[0] == 'device_inflight'
    assert values['device_inflight'] == 3
    assert 'inflight' in stepped
    assert stepped.index('device_inflight') < stepped.index('inflight')


def test_loader_autotune_exposes_device_inflight(mc_dataset):
    from petastorm_tpu.autotune import AutotuneConfig
    mesh = make_mesh({'data': 8})
    with _reader(mc_dataset.url) as reader:
        with JaxLoader(reader, 16, mesh=mesh,
                       autotune=AutotuneConfig(interval_s=0.05)) as loader:
            for _ in loader:
                pass
            at = loader.stats['autotune']
    assert 'device_inflight' in at['knobs']
    assert all('device_inflight' in point for point in at['trajectory'])


# ---------------------------------------------------------------------------
# multi-host equivalence on CPU (simulated hosts via make_pod_reader)
# ---------------------------------------------------------------------------

def _host_digests(url, pod_shard, mesh, ledger_dir=None, stop_after=None,
                  resume=None, batch=ROWS_PER_GROUP):
    """Drive one simulated host's loader; per-batch per-field CRC32s (and
    optionally the PR-7 ledger + a mid-stream cursor). ``batch`` defaults
    to the chunk size so host batch k IS global chunk
    ``k * shard_count + cur_shard`` — the alignment that makes per-host
    streams interleave to the single-host stream at batch granularity."""
    reader = make_pod_reader(url, pod_shard=pod_shard, deterministic=True,
                             seed=7, num_epochs=1, shuffle_row_groups=True,
                             reader_pool_type='thread', workers_count=2,
                             resume_state=resume)
    digests, state = [], None
    kw = {'lineage': str(ledger_dir)} if ledger_dir else {}
    with JaxLoader(reader, batch, mesh=mesh, **kw) as loader:
        for b in loader:
            digests.append(tuple(
                zlib.crc32(np.ascontiguousarray(np.asarray(
                    getattr(b, f))).tobytes())
                for f in sorted(b._fields)))
            if stop_after is not None and len(digests) >= stop_after:
                state = loader.state_dict()
                break
    return digests, state


def _interleave(per_host):
    total = sum(len(p) for p in per_host)
    merged, pos = [], 0
    while len(merged) < total:
        host, k = pos % len(per_host), pos // len(per_host)
        if k < len(per_host[host]):
            merged.append(per_host[host][k])
        pos += 1
    return merged


def test_two_simulated_hosts_interleave_to_single_host_stream(mc_dataset):
    single, _ = _host_digests(mc_dataset.url, (0, 1), make_mesh({'data': 8}))
    devices = jax.devices()
    per_host = []
    for host in (0, 1):
        mesh = make_mesh({'data': 4},
                         devices=devices[host * 4:(host + 1) * 4])
        digests, _ = _host_digests(mc_dataset.url, (host, 2), mesh)
        per_host.append(digests)
    assert _interleave(per_host) == single


def test_two_host_ledgers_diff_clean_against_single_host(mc_dataset,
                                                         tmp_path):
    """ACCEPTANCE: the deterministic 2-simulated-host stream, merged in
    round-robin global order, passes ``replay --diff-ledgers`` exit 0
    against the 1-host run — bit-identity at the per-field digest level,
    through the per-device staging path on both sides."""
    single_dir = tmp_path / 'single'
    os.makedirs(str(single_dir))
    _host_digests(mc_dataset.url, (0, 1), make_mesh({'data': 8}),
                  ledger_dir=single_dir)
    devices = jax.devices()
    merged_dir = tmp_path / 'merged'
    os.makedirs(str(merged_dir))
    for host in (0, 1):
        host_dir = tmp_path / 'host{}'.format(host)
        os.makedirs(str(host_dir))
        mesh = make_mesh({'data': 4},
                         devices=devices[host * 4:(host + 1) * 4])
        _host_digests(mc_dataset.url, (host, 2), mesh, ledger_dir=host_dir)
        # Round-robin concatenation: host h's k-th batch is global batch
        # k*2 + h. Rewrite the ledger ids accordingly into one merged dir
        # (the header line rides along untouched).
        for name in os.listdir(str(host_dir)):
            out_lines = []
            with open(str(host_dir / name)) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    record = json.loads(line)
                    if 'batch_id' in record:
                        record['batch_id'] = record['batch_id'] * 2 + host
                    out_lines.append(json.dumps(record))
            with open(str(merged_dir / 'ledger-host{}-{}'.format(
                    host, name.split('-', 1)[-1])), 'w') as f:
                f.write('\n'.join(out_lines) + '\n')
    proc = subprocess.run(
        [sys.executable, '-m', 'petastorm_tpu.tools.replay',
         '--diff-ledgers', str(merged_dir), str(single_dir)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report['diverged'] is None
    assert report['common_batches'] == ROWS // ROWS_PER_GROUP


def test_merge_cursors_two_hosts_to_one_resume(mc_dataset):
    """2 -> 1: both simulated hosts checkpoint mid-stream; merge_cursors
    folds their frontiers and a single-host resume continues the global
    stream exactly where the pair left off."""
    from petastorm_tpu.determinism import merge_cursors
    single, _ = _host_digests(mc_dataset.url, (0, 1), make_mesh({'data': 8}))
    devices = jax.devices()
    states, per_host = [], []
    stop = 2   # batches (== chunks) per host
    for host in (0, 1):
        mesh = make_mesh({'data': 4},
                         devices=devices[host * 4:(host + 1) * 4])
        digests, state = _host_digests(mc_dataset.url, (host, 2), mesh,
                                       stop_after=stop)
        assert state is not None
        states.append(state)
        per_host.append(digests)
    consumed = _interleave(per_host)
    cursor = merge_cursors(states)
    tail, _ = _host_digests(mc_dataset.url, (0, 1), make_mesh({'data': 8}),
                            resume=cursor)
    assert consumed + tail == single


def test_one_host_checkpoint_resumes_on_two_hosts(mc_dataset):
    """1 -> 2: a single-host mid-stream cursor resumes as two strided
    hosts whose interleaved continuation equals the single stream's
    remainder."""
    single, _ = _host_digests(mc_dataset.url, (0, 1), make_mesh({'data': 8}))
    head, state = _host_digests(mc_dataset.url, (0, 1),
                                make_mesh({'data': 8}), stop_after=3)
    assert state is not None
    devices = jax.devices()
    per_host = []
    for host in (0, 1):
        mesh = make_mesh({'data': 4},
                         devices=devices[host * 4:(host + 1) * 4])
        digests, _ = _host_digests(mc_dataset.url, (host, 2), mesh,
                                   resume=dict(state))
        per_host.append(digests)
    assert head + _interleave(per_host) == single


# ---------------------------------------------------------------------------
# make_pod_reader surface
# ---------------------------------------------------------------------------

def test_make_pod_reader_owns_sharding_args(mc_dataset):
    with pytest.raises(ValueError, match='cur_shard'):
        make_pod_reader(mc_dataset.url, cur_shard=0, shard_count=2)


def test_make_pod_reader_defaults_to_process_shard(mc_dataset):
    # Single-process jax: process_shard() is (0, 1) — the unsharded
    # stream, with the sharding args elided entirely.
    with make_pod_reader(mc_dataset.url, reader_pool_type='thread',
                         workers_count=1, num_epochs=1,
                         shuffle_row_groups=False) as reader:
        ids = [i for chunk in reader for i in chunk.id.tolist()]
    assert sorted(ids) == list(range(ROWS))
