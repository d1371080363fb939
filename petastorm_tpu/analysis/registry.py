"""Canonical leak-guard registry: every long-lived ``pst-*`` resource.

One table, three consumers:

* ``tests/conftest.py`` drives its **consolidated leak sweep** from this
  registry (one thread-guard fixture + one temp-dir fixture replacing the
  per-feature guards that accreted over PRs 4-8).
* The pstlint **thread-lifecycle checker**
  (:mod:`petastorm_tpu.analysis.threads`) requires every
  ``threading.Thread`` name literal in the package to resolve to a prefix
  registered here — a new background thread cannot ship without declaring
  who joins it and which tests would catch a leak.
* Humans: the ``rationale`` fields are the documentation of why each
  thread is allowed to exist and how it dies.

Keep this module import-light (stdlib only): the static analyzer and
conftest both import it, and neither should drag in jax/pyarrow.

``action`` semantics for thread guards:

``'fail'``
    The conftest sweep fails the test when a matching thread survives it
    (scoped by ``marker``; ``marker=None`` runs on every test).
``'note'``
    Registered and lint-checked, but not leak-failed at test granularity —
    the rationale records the lifecycle that makes a sweep wrong or
    redundant (e.g. leaks are recorded in owner ``stats()`` and asserted
    by dedicated tests, or the thread is bounded by a worker *process*).

Dir guards always sweep (delete what appeared during the test) — they are
hygiene for the CI host's shared tempdir, not correctness assertions. The
prefix literals are duplicated from their owning modules on purpose (this
module must not import them); ``tests/test_pstlint.py`` pins the values
against the module constants so they cannot drift silently.
"""


class ThreadGuard(object):
    def __init__(self, prefix, owner, rationale, marker=None, action='fail'):
        self.prefix = prefix        # thread-name prefix ('pst-autotune')
        self.owner = owner          # module owning the thread's lifecycle
        self.marker = marker        # pytest marker scoping the sweep
        self.action = action        # 'fail' | 'note'
        self.rationale = rationale

    def __repr__(self):
        return 'ThreadGuard({!r}, action={!r})'.format(self.prefix,
                                                       self.action)


class DirGuard(object):
    def __init__(self, patterns, owner, rationale, marker=None, base=None):
        # glob patterns relative to tempfile.gettempdir(), or to ``base``
        # when the guarded resource lives elsewhere (e.g. /dev/shm for
        # the wire's POSIX shm segments)
        self.patterns = tuple(patterns)
        self.owner = owner
        self.marker = marker
        self.base = base
        self.rationale = rationale

    def __repr__(self):
        return 'DirGuard({!r})'.format(self.patterns)


THREAD_GUARDS = (
    ThreadGuard(
        'pst-autotune', 'petastorm_tpu.autotune',
        'AutoTuner.stop() joins; a leaked tuner keeps resizing a pool '
        'whose owner is gone. Armable by any factory knob or the '
        'PETASTORM_TPU_AUTOTUNE env, so the sweep runs on every test.',
        marker=None, action='fail'),
    ThreadGuard(
        'pst-metrics-exporter', 'petastorm_tpu.metrics',
        'MetricsExporter.stop() closes the listener; a leak holds a port '
        'and a registry reference for the rest of the session. Startable '
        'from any test, so the sweep runs on every test.',
        marker=None, action='fail'),
    ThreadGuard(
        'pst-mem-governor', 'petastorm_tpu.membudget',
        'Refcount-armed process-wide sampler: every pipeline built while '
        'PETASTORM_TPU_HOST_MEM_BUDGET is set takes an arm reference and '
        'releases it at teardown; the last release joins the thread. '
        'Armable by env from any factory, so the sweep runs on every '
        'test — a leak means an owner skipped its release.',
        marker=None, action='fail'),
    ThreadGuard(
        'pst-lineage-writer', 'petastorm_tpu.lineage',
        'LineageLedger.close() joins the write-behind drain; a leak holds '
        'the ledger file open.', marker='lineage', action='fail'),
    ThreadGuard(
        'pst-det', 'petastorm_tpu.determinism',
        'The resequencer is deliberately thread-free (consumer-driven); '
        'this guard exists to catch a future threaded helper outliving '
        'its reader.', marker='determinism', action='fail'),
    ThreadGuard(
        'pst-chunk-store-writer', 'petastorm_tpu.chunk_store',
        'DecodedChunkStore.close() drains and joins the spill writer; a '
        'leaked writer keeps appending decoded chunks to NVMe.',
        marker='chunkstore', action='fail'),
    ThreadGuard(
        'pst-device-put', 'petastorm_tpu.staging',
        'DeviceStager.stop() (called from JaxLoader.stop after the '
        'engine joins) joins every per-device dispatch stream with a '
        'timeout and records survivors in stats()["leaked_threads"]; on '
        'the CPU test platform puts never wedge, so a thread outliving '
        'its loader is a real leak the sweep should fail. Armable by any '
        'mesh/sharded JaxLoader, so the sweep runs on every test.',
        marker=None, action='fail'),
    ThreadGuard(
        'pst-staging', 'petastorm_tpu.staging',
        'StagingEngine.stop() joins with a timeout and RECORDS leaks in '
        'stats()["leaked_threads"] (a device_put hung on a wedged device '
        'is deliberately survivable); tests assert on that surface, so a '
        'blanket per-test failure would fight the designed semantics.',
        action='note'),
    ThreadGuard(
        'pst-ventilator', 'petastorm_tpu.workers.ventilator',
        'Daemon; completes when ventilation finishes and is joined via '
        'Ventilator.stop() on every pool stop path.', action='note'),
    ThreadGuard(
        'pst-watchdog', 'petastorm_tpu.health',
        'Watchdog.stop() joins; owned by Reader/JaxLoader teardown which '
        'every test already exercises, and dedicated watchdog tests '
        'assert the join.', action='note'),
    ThreadGuard(
        'pst-data-service', 'petastorm_tpu.data_service',
        'Daemon serve/rpc loops bounded by DataServer.close(); '
        'data-service tests assert server shutdown explicitly.',
        action='note'),
    ThreadGuard(
        'pst-lookup', 'petastorm_tpu.serving.server',
        'Lookup-tier rpc/worker/lease threads (pst-lookup-rpc, '
        'pst-lookup-worker-<i>, pst-lookup-lease) are daemons joined by '
        'LookupServer.stop(); serving tests assert server shutdown, and '
        'the sweep fails a server leaked past its test.',
        marker='serving', action='fail'),
    ThreadGuard(
        'pst-fleet-scatter', 'petastorm_tpu.serving.client',
        'Per-partition scatter-gather workers of LookupClient — '
        'daemons joined before the scattering call returns, so one '
        'alive after a test means a wedged partition request escaped '
        'its deadline.',
        marker='fleet', action='fail'),
    ThreadGuard(
        'pst-fleet-registry', 'petastorm_tpu.fleet.registry',
        'FleetRegistry.watch() SUB loop folding worker heartbeats into '
        'membership; stop() joins. A leak keeps a SUB socket connected '
        'to workers that the test already tore down.',
        marker='fleet', action='fail'),
    ThreadGuard(
        'pst-fleet-autoscaler', 'petastorm_tpu.fleet.autoscaler',
        'FleetAutoscaler.start() control loop (and its bounded announce '
        'readers); stop() joins. A leaked loop keeps launching/draining '
        'workers for a fleet whose test is over.',
        marker='fleet', action='fail'),
    ThreadGuard(
        'pst-wire', 'petastorm_tpu.fleet.wire',
        'The negotiated data-plane wire is deliberately thread-free '
        '(encode/decode run on the owning server/consumer threads; acks '
        'ride the existing client control thread); this guard catches a '
        'future threaded helper outliving its reader.',
        marker='wire', action='fail'),
    ThreadGuard(
        'pst-pool-worker', 'petastorm_tpu.workers.thread_pool',
        'Daemon pool workers joined by ThreadPool.join(); retirement '
        'between items is the resize contract, tested in '
        'test_workers_pool.py.', action='note'),
    ThreadGuard(
        'pst-orphan-watch', 'petastorm_tpu.workers.process_pool',
        'Lives inside a spawned worker process only (kills it when the '
        'parent dies); never present in the test process itself.',
        action='note'),
)

DIR_GUARDS = (
    DirGuard(
        ('pst-chunk-store-*',), 'petastorm_tpu.chunk_store',
        'Env-armed readers create prefix-named stores '
        'under the shared tempdir; a test dying mid-write must not leave '
        'GBs of decoded chunks on the CI NVMe. Snapshot-diff: only dirs '
        'that appeared during the test are its leaks.',
        marker='chunkstore'),
    DirGuard(
        ('pst-lineage-*',), 'petastorm_tpu.lineage',
        'Ledgers created without an explicit directory land under the '
        'tempdir with the pst-lineage- prefix.', marker='lineage'),
    DirGuard(
        ('pst-trace*', 'trace-*.jsonl', 'pst-flight-*'),
        'petastorm_tpu.trace / petastorm_tpu.flight_recorder',
        'Trace sidecar dirs, bare sidecar files from PETASTORM_TPU_'
        'TRACE_DIR pointed at the tempdir, and flight-recorder dump '
        'dirs.', marker='observability'),
    DirGuard(
        ('pst-wire-*',), 'petastorm_tpu.fleet.wire',
        'Per-consumer shm segment rings of the negotiated data-plane '
        'wire live under /dev/shm, not the tempdir. Servers unlink them '
        'on release/stop and sweep stale ones (boot-id + pid liveness) '
        'at start; the guard deletes what a test leaked anyway so one '
        'SIGKILL drill cannot strand 64MB segments on the CI host.',
        marker='wire', base='/dev/shm'),
)


def thread_prefixes():
    """All registered thread-name prefixes (the thread-lifecycle checker's
    allow-list)."""
    return tuple(g.prefix for g in THREAD_GUARDS)
