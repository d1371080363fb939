"""Disaggregated input service: decode on CPU hosts, train elsewhere —
and survive a preemption of BOTH tiers mid-epoch.

The reference parallelizes decode only inside the training process
(``petastorm/workers_pool/process_pool.py``); on TPU-VM pods the CPU:chip
ratio is fixed, so an input-bound trainer has nowhere to grow. This
example runs the petastorm_tpu answer end to end, in one process for
demonstration (each tier is normally its own host):

* two :class:`~petastorm_tpu.data_service.DataServer` s decode the store
  (the decode tier — scale horizontally by adding servers),
* one trainer pulls the merged stream through
  :class:`~petastorm_tpu.data_service.RemoteReader` +
  :class:`~petastorm_tpu.jax_loader.JaxLoader` (zmq PULL fair-queues
  across the servers; a slow server simply contributes fewer chunks),
* mid-epoch the trainer calls ``reader.state_dict()`` — the servers pause
  at a chunk boundary, in-flight chunks drain into the snapshot, the
  prefetch queue's rows stay accounted — then the WHOLE service (servers
  and trainer) is torn down,
* fresh servers restart from ``state['server_states'][i]``, a fresh
  trainer from ``resume_state=state``, and together they deliver exactly
  the rows the first session had not consumed: no duplicates, no losses.

``--demo crash`` runs the UNPLANNED-death variant instead: two real
server subprocesses with self-snapshots armed
(``serve_dataset(snapshot_path=...)``), one SIGKILLed mid-stream and
restarted from its snapshot on the same endpoint — the trainer never
restarts, dedupes the replay ring by ``(server_id, seq)``, and finishes
the epoch with every row delivered exactly once.

Run: ``python examples/data_service/serve_and_train.py [--demo crash]``
(any JAX backend; loopback tcp).
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..')))

import argparse
import tempfile

import numpy as np


def _write_store(url, n_rows):
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    rng = np.random.default_rng(0)
    schema = Unischema('SvcExample', [
        UnischemaField('x', np.float32, (8,), NdarrayCodec(), False),
        UnischemaField('label', np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField('sample_id', np.int64, (), ScalarCodec(np.int64), False),
    ])
    write_dataset(url, schema,
                  ({'x': rng.standard_normal(8).astype(np.float32),
                    'label': int(i % 4), 'sample_id': i}
                   for i in range(n_rows)),
                  rows_per_row_group=8)


def _start_servers(url, n_servers, states=None):
    """The decode tier. Servers shard the STORE between them (static shard
    per server; the trainers see dynamic chunk-level sharding on top)."""
    from petastorm_tpu.data_service import serve_dataset

    servers = []
    for i in range(n_servers):
        servers.append(serve_dataset(
            url, 'tcp://127.0.0.1:*', num_epochs=1, seed=0, workers_count=1,
            cur_shard=i, shard_count=n_servers,
            resume_state=None if states is None else states[i]))
    return servers


def run(dataset_url=None, batch=8, n_rows=96, n_servers=2, preempt_after=3):
    """Serve + train + checkpoint + preempt everything + resume.

    Returns (losses, seen_ids, pending_chunks_in_snapshot)."""
    import jax

    from petastorm_tpu.data_service import RemoteReader
    from petastorm_tpu.jax_loader import JaxLoader
    from petastorm_tpu.models.mlp import MLP
    from petastorm_tpu.models.train import create_train_state, make_train_step

    url = dataset_url or 'file://' + tempfile.mkdtemp(prefix='svc_example_ds_')
    if not os.path.exists(url.replace('file://', '', 1) + '/_common_metadata'):
        _write_store(url, n_rows)
    model = MLP(features=(16, 4))
    train_step = make_train_step()
    state = create_train_state(jax.random.PRNGKey(0), model, (1, 8))
    losses, seen = [], []

    # ---- session 1: decode tier + trainer, killed mid-epoch -------------
    servers = _start_servers(url, n_servers)
    reader = RemoteReader([s.data_endpoint for s in servers])
    svc_state = None
    try:
        with JaxLoader(reader, batch, last_batch='drop', prefetch=4) as loader:
            for step_i, b in enumerate(loader):
                state, metrics = train_step(state, b.x, b.label)
                losses.append(float(metrics['loss']))
                seen.extend(np.asarray(b.sample_id).tolist())
                if step_i + 1 >= preempt_after:
                    # Checkpoint the SERVICE (server reader positions +
                    # drained in-flight chunks + prefetch accounting)...
                    svc_state = loader.state_dict()
                    break   # ...then the "preemption" tears it all down
    finally:
        reader.stop()
        reader.join()
        for s in servers:
            s.stop()
    assert svc_state is not None

    # ---- session 2: fresh servers + fresh trainer from the snapshot -----
    servers = _start_servers(url, n_servers,
                             states=svc_state['server_states'])
    reader = RemoteReader([s.data_endpoint for s in servers],
                          resume_state=svc_state)
    try:
        with JaxLoader(reader, batch, last_batch='drop', prefetch=4) as loader:
            for b in loader:
                state, metrics = train_step(state, b.x, b.label)
                losses.append(float(metrics['loss']))
                seen.extend(np.asarray(b.sample_id).tolist())
    finally:
        reader.stop()
        reader.join()
        for s in servers:
            s.stop()

    # Exactly-once across the service preemption (modulo the <batch tail
    # dropped for static shapes).
    assert len(seen) == len(set(seen)), 'duplicate rows across service resume'
    assert n_rows - len(set(seen)) < batch * 2, 'rows lost across service resume'
    print('data service example: {} servers, {} steps, {} distinct rows '
          'of {}, {} chunks were in flight at the checkpoint'.format(
              n_servers, len(losses), len(set(seen)), n_rows,
              len(svc_state['pending'])))
    return losses, seen, len(svc_state['pending'])


def _serve_subprocess(url, bind, snapshot_path, resume):
    """Child entry for --demo crash: a real decode-tier process. Armed
    with self-snapshots so a SIGKILL is recoverable; ``workers_count=1``
    because crash recovery's seq dedupe needs chunk-deterministic resume
    (see DataServer's snapshot_path doc)."""
    import json

    from petastorm_tpu.data_service import load_server_snapshot, serve_dataset

    snapshot = load_server_snapshot(snapshot_path) if resume else None
    server = serve_dataset(url, bind,
                           snapshot_path=snapshot_path, snapshot_every=2,
                           snapshot_resume=snapshot,
                           num_epochs=1, seed=0, workers_count=1,
                           shuffle_row_groups=False)
    print(json.dumps({'data_endpoint': server.data_endpoint}), flush=True)
    import time
    while True:         # serve threads run until this process is killed
        time.sleep(0.5)


def run_crash_recovery(n_rows=192):
    """Two server subprocesses, one SIGKILLed mid-stream and restarted
    from its self-snapshot; the sole trainer rides through the crash.
    (Chunk granularity comes from the store's ``rows_per_row_group``;
    the child re-runs this file, whose module top already puts the repo
    on ``sys.path``.)"""
    import collections
    import json
    import subprocess
    import tempfile

    from petastorm_tpu.data_service import RemoteReader

    url = 'file://' + tempfile.mkdtemp(prefix='svc_crash_ds_')
    _write_store(url, n_rows)
    workdir = tempfile.mkdtemp(prefix='svc_crash_')

    def spawn(bind, snap, resume=False):
        cmd = [sys.executable, os.path.abspath(__file__), '--_serve', url,
               bind, snap] + (['--resume'] if resume else [])
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
        return proc, json.loads(proc.stdout.readline())

    snaps = [os.path.join(workdir, 'a.pkl'), os.path.join(workdir, 'b.pkl')]
    procs = []
    try:
        proc_a, info_a = spawn('tcp://127.0.0.1:*', snaps[0])
        proc_b, info_b = spawn('tcp://127.0.0.1:*', snaps[1])
        procs += [proc_a, proc_b]
        seen = []
        with RemoteReader([info_a['data_endpoint'], info_b['data_endpoint']],
                          rcvhwm=1, end_grace_s=10.0) as remote:
            for _ in range(4):                      # consume a little...
                seen.extend(np.asarray(next(remote).sample_id).tolist())
            proc_a.kill()                           # ...SIGKILL a server...
            proc_a.wait()
            proc_a2, _ = spawn(info_a['data_endpoint'], snaps[0],
                               resume=True)         # ...restart from snapshot
            procs.append(proc_a2)
            for chunk in remote:                    # trainer never restarted
                seen.extend(np.asarray(chunk.sample_id).tolist())
            dups = remote.diagnostics['duplicate_chunks']
        counts = collections.Counter(seen)
        assert sorted(counts) == list(range(n_rows)), 'rows lost in crash'
        assert set(counts.values()) == {2}, 'unexpected duplicate rows'
        print('crash-recovery example: every one of {} rows delivered '
              'exactly twice (once per server) across a SIGKILL; {} replayed '
              'chunk(s) deduped by (server_id, seq)'.format(n_rows, dups))
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


def main():
    if '--_serve' in sys.argv:      # crash-demo server subprocess
        i = sys.argv.index('--_serve')
        _serve_subprocess(sys.argv[i + 1], sys.argv[i + 2], sys.argv[i + 3],
                          '--resume' in sys.argv)
        return
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset-url', default=None)
    parser.add_argument('--batch', type=int, default=8)
    parser.add_argument('--rows', type=int, default=96)
    parser.add_argument('--servers', type=int, default=2)
    parser.add_argument('--preempt-after', type=int, default=3)
    parser.add_argument('--demo', choices=['preempt', 'crash'],
                        default='preempt')
    args = parser.parse_args()
    # The trainer side only: the --_serve subprocesses above never import jax.
    from petastorm_tpu.utils import enable_compile_cache
    enable_compile_cache()
    if args.demo == 'crash':
        run_crash_recovery(n_rows=args.rows if args.rows != 96 else 192)
        return
    run(dataset_url=args.dataset_url, batch=args.batch, n_rows=args.rows,
        n_servers=args.servers, preempt_after=args.preempt_after)


if __name__ == '__main__':
    main()
