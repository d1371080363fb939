"""Stall flight recorder: post-mortem dumps that need no live process.

The watchdog (``health.py``) already *diagnoses* a stall — classification,
beat table, probe snapshots, all-thread stacks — but the evidence lived
only inside the dying process: by the time a human looked, the trace ring
and the metric counters were gone with it. The flight recorder keeps a
bounded ring of recent trace events (the :class:`~petastorm_tpu.trace.
Tracer`'s own ring) plus periodic metric samples, and on watchdog
escalation (the moment a :class:`~petastorm_tpu.errors.PipelineStallError`
is minted) dumps everything to a timestamped directory::

    <base_dir>/pst-flight-20260803-141557-dispatch-hung-ab12cd34/
        trace.json        # chrome://tracing timeline of the event ring
        metrics.prom      # Prometheus text exposition at dump time
        metrics_ring.json # recent periodic registry samples (wall-clocked)
        diagnosis.json    # classification, stage, detail, beats, probes
        stacks.txt        # the all-thread stack dump
        lineage.json      # every live provenance ring (petastorm_tpu.
                          # lineage): the exact rows in flight at the stall

Arm it process-wide by pointing the ``PETASTORM_TPU_FLIGHT_RECORDER``
environment variable at a directory (the watchdog-owning Reader/JaxLoader
builds one automatically), or pass a :class:`FlightRecorder` to
:class:`~petastorm_tpu.health.HealthMonitor` directly. Dumping is
best-effort by construction: a recorder failure must never worsen the
stall it is documenting.
"""

import json
import logging
import os
import threading
import time
import uuid
from collections import deque

logger = logging.getLogger(__name__)

#: Directory that arms a flight recorder for every supervised pipeline
#: built while it is set.
ENV_VAR = 'PETASTORM_TPU_FLIGHT_RECORDER'

DUMP_DIR_PREFIX = 'pst-flight-'


class FlightRecorder(object):
    """Bounded trace/metrics ring + timestamped post-mortem dumps.

    :param base_dir: where dump directories are created.
    :param tracer: the pipeline's :class:`~petastorm_tpu.trace.Tracer`
        (its bounded event ring IS the trace flight ring). ``None`` means
        the process-wide default ring, which is on unless somebody switched
        it off, so a dump holds the last seconds of spans though nobody
        armed anything; only ``set_global_tracer(NullTracer())`` yields an
        empty ``trace.json``.
    :param registry: the :class:`~petastorm_tpu.metrics.MetricsRegistry`
        to snapshot (default: the process-wide registry).
    :param metric_ring: periodic samples retained (oldest dropped).
    :param sample_min_interval_s: :meth:`sample` throttle — the watchdog
        calls it every supervision tick, which can be sub-100ms in tests.
    """

    def __init__(self, base_dir, tracer=None, registry=None, metric_ring=256,
                 sample_min_interval_s=0.25):
        self._base_dir = base_dir
        from petastorm_tpu.trace import resolve
        self._tracer = resolve(tracer)
        if registry is None:
            from petastorm_tpu import metrics
            registry = metrics.get_registry()
        self._registry = registry
        self._lock = threading.Lock()
        self._samples = deque(maxlen=metric_ring)
        self._sample_min_interval_s = float(sample_min_interval_s)
        self._last_sample_t = 0.0
        self.dumps = []

    @property
    def base_dir(self):
        return self._base_dir

    def sample(self):
        """Append one wall-clocked registry snapshot to the metric ring
        (throttled; the watchdog calls this every check pass). Returns
        True when a sample was taken."""
        now = time.monotonic()
        with self._lock:
            if now - self._last_sample_t < self._sample_min_interval_s:
                return False
            self._last_sample_t = now
        try:
            snapshot = self._registry.collect()
        except Exception:  # noqa: BLE001 - recording must not hurt the pipeline
            logger.debug('flight recorder sample failed', exc_info=True)
            return False
        with self._lock:
            self._samples.append({'wall_time': time.time(),
                                  'metrics': snapshot})
        return True

    def dump(self, diagnosis=None, reason='stall'):
        """Write the rings + ``diagnosis`` to a fresh timestamped dump
        directory; returns its path (``None`` if even the mkdir failed —
        dumping is best-effort, a recorder error must never mask the
        stall it documents)."""
        stamp = time.strftime('%Y%m%d-%H%M%S')
        safe_reason = ''.join(c if c.isalnum() or c == '-' else '-'
                              for c in str(reason))[:48] or 'stall'
        path = os.path.join(self._base_dir, '{}{}-{}-{}'.format(
            DUMP_DIR_PREFIX, stamp, safe_reason, uuid.uuid4().hex[:8]))
        try:
            os.makedirs(path, exist_ok=True)
        except OSError:
            logger.warning('flight recorder cannot create dump dir under %r',
                           self._base_dir, exc_info=True)
            return None
        self._write_trace(os.path.join(path, 'trace.json'))
        self._write_metrics(path)
        self._write_diagnosis(path, diagnosis)
        self._write_lineage(path)
        with self._lock:
            self.dumps.append(path)
        logger.warning('flight recorder dumped stall evidence to %s', path)
        return path

    # -- pieces (each best-effort, isolated) -------------------------------

    def _write_trace(self, path):
        try:
            # (a NullTracer exports an empty-but-valid timeline)
            self._tracer.export_chrome_trace(path)
        except Exception:  # noqa: BLE001
            logger.debug('flight recorder trace dump failed', exc_info=True)

    def _write_metrics(self, dump_dir):
        try:
            self._registry.write_textfile(
                os.path.join(dump_dir, 'metrics.prom'))
        except Exception:  # noqa: BLE001
            logger.debug('flight recorder metrics dump failed', exc_info=True)
        try:
            with self._lock:
                samples = list(self._samples)
            with open(os.path.join(dump_dir, 'metrics_ring.json'), 'w') as f:
                json.dump(samples, f, default=repr)
        except Exception:  # noqa: BLE001
            logger.debug('flight recorder ring dump failed', exc_info=True)

    def _write_lineage(self, dump_dir):
        """Every live tracker's provenance ring (the last N batch records,
        with their reader contexts) — what names the exact rows that were
        in flight when the pipeline stalled. Trackers register themselves
        process-wide (``lineage.live_rings``), so no construction-order
        coupling with the watchdog; an unarmed pipeline writes ``[]``."""
        try:
            from petastorm_tpu import lineage
            rings = lineage.live_rings()
            with open(os.path.join(dump_dir, 'lineage.json'), 'w') as f:
                json.dump(rings, f, default=repr)
        except Exception:  # noqa: BLE001
            logger.debug('flight recorder lineage dump failed', exc_info=True)

    def _write_diagnosis(self, dump_dir, diagnosis):
        if diagnosis is None:
            return
        try:
            stacks = diagnosis.get('stacks') if hasattr(diagnosis, 'get') \
                else None
            summary = {k: v for k, v in dict(diagnosis).items()
                       if k != 'stacks'}
            with open(os.path.join(dump_dir, 'diagnosis.json'), 'w') as f:
                # default=repr: probe snapshots may carry numpy scalars or
                # exception objects; a post-mortem wants them legible, not
                # a serializer crash.
                json.dump(summary, f, default=repr, indent=1)
            if stacks:
                with open(os.path.join(dump_dir, 'stacks.txt'), 'w') as f:
                    f.write(stacks)
        except Exception:  # noqa: BLE001
            logger.debug('flight recorder diagnosis dump failed',
                         exc_info=True)


def maybe_from_env(tracer=None, registry=None):
    """A :class:`FlightRecorder` when ``PETASTORM_TPU_FLIGHT_RECORDER``
    names a directory, else ``None`` (the Reader/JaxLoader watchdog
    wiring calls this so supervised pipelines record automatically)."""
    base_dir = os.environ.get(ENV_VAR, '').strip()
    if not base_dir:
        return None
    return FlightRecorder(base_dir, tracer=tracer, registry=registry)
