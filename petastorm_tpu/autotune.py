"""Adaptive pipeline autotuner: feedback-driven knob control.

The pipeline's speed knobs — ``workers_count``, ``prefetch``,
``arena_depth``, ``inflight``, ventilation depth — are fixed at
construction, yet the optimum moves at runtime: the first (decode-bound)
epoch and the cache-warm (collate-bound) steady state want different
settings, and shared-host load swings capacity severalfold between runs.
tf.data's autotuning (Murray et al., VLDB 2021) and DALI's
pipeline-depth tuning both show a feedback controller over stage latencies
recovers near-hand-tuned throughput without per-workload sweeps. Every
signal such a controller needs already exists here (PR-3 heartbeats, PR-2
staging counters, consumer wait accounting); this module closes the loop:

:class:`AutoTuner`
    A control thread that samples a telemetry function every
    ``interval_s``, computes per-tick deltas of the cumulative wait
    counters, classifies the **dominant bottleneck** (reader-starved /
    dispatch-bound / arena-bound / consumer-bound / balanced), and nudges
    one knob per decision in an AIMD/hill-climbing loop:

    * reader-starved -> grow the worker pool (``ThreadPool.resize``) and
      loosen ventilation;
    * dispatch-bound -> widen the per-device ``device_put`` windows
      (the per-device sharded staging path), then the batch-level
      in-flight window, then prefetch depth;
    * arena-bound -> deepen the host-arena pool;
    * consumer-bound -> shrink everything one step and tighten the
      ventilator's results-queue watermark — release memory instead of
      racing ahead of a consumer that isn't draining.

    Safeguards: per-knob min/max clamps, hysteresis (a classification
    must repeat for ``hysteresis`` consecutive ticks before any action),
    a post-action cooldown, a throughput guard that *reverts* the last
    action when the delivered rate drops past ``throughput_tolerance``,
    and a hard pause whenever the watchdog (``health.py``) has an active
    stall episode — the tuner must never fight stall recovery. Every
    decision lands in a bounded log (surfaced as
    ``Reader.diagnostics()['autotune']`` / loader ``stats['autotune']``)
    plus per-knob trace counter events.

Enable with ``autotune=True`` (or an :class:`AutotuneConfig`) on
``make_reader`` / ``make_batch_reader`` / ``make_tensor_reader`` /
``JaxLoader``, or process-wide via the ``PETASTORM_TPU_AUTOTUNE``
environment variable (``1``/``true`` = on with defaults; a number = on
with that tick interval in seconds; ``0``/``off``/unset = off). A
``JaxLoader`` wrapping an autotuned reader adopts its knobs so one
controller tunes the whole pipeline (mirroring the watchdog's
``attach_health`` ownership rule).
"""

import logging
import os
import threading
import time
from collections import deque

logger = logging.getLogger(__name__)

ENV_VAR = 'PETASTORM_TPU_AUTOTUNE'

# Bottleneck classification labels (the vocabulary tests and docs assert
# against; deliberately overlapping with health.py's stall vocabulary where
# the meaning matches).
READER_STARVED = 'reader-starved'
DISPATCH_BOUND = 'dispatch-bound'
ARENA_BOUND = 'arena-bound'
CONSUMER_BOUND = 'consumer-bound'
INPUT_BOUND = 'input-bound'     # consumer waits but no stage blames a wait:
                                # the pipeline's own work is the limit
BALANCED = 'balanced'


def active_bottleneck_classes(snapshot):
    """Read the ``pst_autotune_bottleneck`` enum gauge out of a metrics
    snapshot (one process's ``collect()``, or a fleet aggregate from
    :func:`petastorm_tpu.metrics.aggregate_snapshots`): ``{pipeline:
    class}`` for every pipeline whose active class reads >= 1. The
    shared vocabulary bridge between the in-process tuner and the fleet
    autoscaler — both sides consume the classification through this one
    parse instead of re-reading gauge samples by hand."""
    metric = (snapshot or {}).get('pst_autotune_bottleneck') or {}
    active = {}
    for sample in metric.get('samples', ()):
        if sample.get('value', 0) >= 1:
            labels = sample.get('labels') or {}
            active[labels.get('pipeline', '')] = labels.get('class')
    return active


def autotune_enabled(explicit=None):
    """Resolve the ``autotune=`` knob against the environment default.

    ``explicit`` wins when not None (an :class:`AutotuneConfig` counts as
    True); otherwise ``PETASTORM_TPU_AUTOTUNE`` decides
    (unset/empty/0/off = disabled)."""
    if explicit is not None:
        return bool(explicit)
    raw = os.environ.get(ENV_VAR, '').strip().lower()
    return raw not in ('', '0', 'off', 'false', 'no')


def env_interval():
    """A numeric ``PETASTORM_TPU_AUTOTUNE`` value is the tick interval in
    seconds; any other truthy value keeps the built-in default. ``'1'``
    is the documented plain on-switch, NOT a 1-second interval."""
    raw = os.environ.get(ENV_VAR, '').strip()
    if raw == '1':
        return None
    try:
        value = float(raw)
    except ValueError:
        return None
    return value if value > 0 else None


class AutotuneConfig(object):
    """Bounds and pacing for the :class:`AutoTuner` control loop.

    Pass an instance as ``autotune=`` to any reader/loader factory. Every
    knob has a ``[min, max]`` clamp the tuner never crosses; ``hysteresis``
    and ``cooldown`` are in ticks; ``throughput_tolerance`` is the
    fractional rate drop past which the last action is reverted.
    """

    def __init__(self, interval_s=0.5, hysteresis=2, cooldown=2,
                 throughput_tolerance=0.15, log_size=256,
                 min_workers=1, max_workers=None,
                 min_prefetch=1, max_prefetch=8,
                 min_inflight=1, max_inflight=8,
                 min_device_inflight=1, max_device_inflight=8,
                 min_device_stream_mb=1, max_device_stream_mb=64,
                 min_arena_depth=2, max_arena_depth=16,
                 min_watermark=4,
                 min_decode_threads=1, max_decode_threads=None,
                 starve_frac=0.05, signal_frac=0.05):
        if interval_s <= 0:
            raise ValueError('interval_s must be positive, got {}'.format(interval_s))
        self.interval_s = float(interval_s)
        self.hysteresis = max(1, int(hysteresis))
        self.cooldown = max(0, int(cooldown))
        self.throughput_tolerance = float(throughput_tolerance)
        self.log_size = int(log_size)
        self.min_workers = max(1, int(min_workers))
        if max_workers is None:
            # Threads beyond a few per core only add GIL ping-pong; the
            # decode path releases the GIL, so oversubscribe moderately.
            max_workers = min(32, 4 * (os.cpu_count() or 4))
        self.max_workers = max(self.min_workers, int(max_workers))
        self.min_prefetch = max(1, int(min_prefetch))
        self.max_prefetch = max(self.min_prefetch, int(max_prefetch))
        self.min_inflight = max(1, int(min_inflight))
        self.max_inflight = max(self.min_inflight, int(max_inflight))
        self.min_device_inflight = max(1, int(min_device_inflight))
        self.max_device_inflight = max(self.min_device_inflight,
                                       int(max_device_inflight))
        self.min_device_stream_mb = max(0, int(min_device_stream_mb))
        self.max_device_stream_mb = max(self.min_device_stream_mb,
                                        int(max_device_stream_mb))
        self.min_arena_depth = max(1, int(min_arena_depth))
        self.max_arena_depth = max(self.min_arena_depth, int(max_arena_depth))
        self.min_watermark = max(2, int(min_watermark))
        self.min_decode_threads = max(1, int(min_decode_threads))
        if max_decode_threads is None:
            # Decode threads are GIL-free C++ — mild oversubscription
            # hides IO bubbles, heavy oversubscription just context-
            # switches (2605.08731's single-thread-decode analysis).
            max_decode_threads = 2 * (os.cpu_count() or 4)
        self.max_decode_threads = max(self.min_decode_threads,
                                      int(max_decode_threads))
        # Below this fraction of wall time blocked, the consumer counts as
        # "kept fed"; above it, the biggest stage-wait fraction must also
        # clear signal_frac to earn the blame.
        self.starve_frac = float(starve_frac)
        self.signal_frac = float(signal_frac)


def resolve_config(explicit=None):
    """The effective config for an ``autotune=`` value: pass through an
    :class:`AutotuneConfig`, else defaults with any env-var interval."""
    if isinstance(explicit, AutotuneConfig):
        return explicit
    interval = env_interval()
    return AutotuneConfig(interval_s=interval) if interval else AutotuneConfig()


class Knob(object):
    """One tunable pipeline parameter: live getter/setter plus clamps.

    ``get``/``set`` must be thread-safe — they run on the tuner thread
    against state owned by pipeline threads (``ThreadPool.resize``, queue
    maxsize under its mutex, plain atomic attribute writes)."""

    def __init__(self, name, get, set, lo, hi):
        self.name = name
        self.get = get
        self.set = set
        self.lo = int(lo)
        self.hi = int(hi)

    def clamp(self, value):
        return max(self.lo, min(self.hi, int(value)))


# --------------------------------------------------------------------------
# bottleneck classification
# --------------------------------------------------------------------------

def classify_loader(deltas, gauges, dt, config):
    """Dominant bottleneck of a JaxLoader pipeline from one tick's wait
    deltas (seconds blocked per stage) and queue gauges.

    Returns ``(label, detail)``. The rule set mirrors the stats doc: the
    consumer's own blocked fraction says whether the pipeline keeps up;
    when it doesn't, whichever stage spent the biggest fraction of the
    tick *waiting* (reader pull / arena acquire / transfer fence) is the
    bottleneck its knob can relieve."""
    wait_frac = deltas.get('wait_s', 0.0) / dt
    reader_frac = deltas.get('reader_wait_s', 0.0) / dt
    arena_frac = deltas.get('arena_wait_s', 0.0) / dt
    ready_frac = deltas.get('ready_wait_s', 0.0) / dt
    capacity = gauges.get('queue_capacity') or 1
    fill = (gauges.get('queue_depth') or 0) / capacity
    if wait_frac < config.starve_frac:
        if fill >= 0.5:
            return (CONSUMER_BOUND,
                    'consumer blocked {:.0%} of the tick with the staging '
                    'queue {:.0%} full — pipeline is ahead of the trainer'
                    .format(wait_frac, fill))
        return (BALANCED, 'consumer blocked only {:.0%} of the tick'
                .format(wait_frac))
    candidates = [(READER_STARVED, reader_frac),
                  (ARENA_BOUND, arena_frac),
                  (DISPATCH_BOUND, ready_frac)]
    label, frac = max(candidates, key=lambda kv: kv[1])
    if frac < config.signal_frac:
        return (INPUT_BOUND,
                'consumer blocked {:.0%} of the tick but no stage reports '
                'waiting — pipeline work itself is the limit'.format(wait_frac))
    return (label, 'consumer blocked {:.0%}; dominant stage wait: {} '
            '{:.0%} of the tick'.format(wait_frac, label, frac))


def classify_reader(deltas, gauges, dt, config):
    """Bottleneck of a standalone Reader (no staging engine): judged from
    the worker pool's results-queue occupancy — a full queue means the
    consumer is the limit, an empty one with work still ventilated means
    the decode tier is."""
    capacity = gauges.get('results_queue_capacity') or 0
    if capacity <= 0:
        # Unbounded results queue: occupancy carries no saturation signal
        # (any backlog would read as "full" against a fake capacity) — do
        # nothing rather than shrink a pool on garbage evidence.
        return (BALANCED, 'results queue unbounded: no fill signal')
    fill = (gauges.get('results_queue_depth') or 0) / capacity
    pending = gauges.get('ventilated_unprocessed') or 0
    if fill >= 0.6:
        return (CONSUMER_BOUND,
                'results queue {:.0%} full — consumer is the limit'.format(fill))
    if fill <= 0.1 and pending > 0:
        return (READER_STARVED,
                'results queue {:.0%} full with {} ventilated item(s) still '
                'unprocessed — decode tier is the limit'.format(fill, pending))
    return (BALANCED, 'results queue {:.0%} full'.format(fill))


# Per-classification grow preferences: the first listed knob that exists
# and is not already at its clamp takes one additive step. ``input-bound``
# (the pipeline's own work is the limit — on image workloads that work IS
# decode) grows native decode parallelism FIRST: widening the GIL-free
# C++ decode pool attacks the bottleneck directly, where another Python
# worker mostly adds scheduling overhead; workers remain the fallback
# once the thread budget clamps. ``reader-starved`` keeps workers first
# (a standalone reader's signal — the queue is empty because too few
# row-groups are in flight) with decode threads as its second lever.
_GROW_ACTIONS = {
    READER_STARVED: (('workers', 1), ('decode_threads', 2),
                     ('results_watermark', 8)),
    INPUT_BOUND: (('decode_threads', 2), ('workers', 1)),
    # dispatch-bound steps the PER-DEVICE in-flight window first (the
    # per-device sharded staging path, ISSUE 14): transfer backpressure
    # forms per device stream, so widening every stream's window attacks
    # it directly. Next come the dispatch-cost levers: pinned arenas
    # (DMA-friendly host slabs make each transfer cheaper) and the
    # inline/batched threshold (growing it routes more fields through
    # the single C++ batched transfer per wave); the batch-level window
    # and prefetch depth remain the fallbacks once those clamp (and the
    # only levers on single-device pipelines, which have none of the
    # per-device knobs).
    DISPATCH_BOUND: (('device_inflight', 1), ('arena_pinned', 1),
                     ('device_stream_min_mb', 8), ('inflight', 1),
                     ('prefetch', 1)),
    ARENA_BOUND: (('arena_depth', 2),),
}

# Consumer-bound shrink: one step down on every present knob (release
# memory/CPU), with the ventilation watermark tightened hardest — over-
# ventilating row-groups into a saturated results queue only pins memory
# and stretches tail latency. decode_threads participates (incl. the
# governor's mem-shrink sweep): a pipeline ahead of its consumer has no
# business saturating the host's cores either.
_SHRINK_STEPS = (('workers', 1), ('prefetch', 1), ('inflight', 1),
                 ('device_inflight', 1), ('arena_depth', 2),
                 ('arena_pinned', 1),
                 ('decode_threads', 2), ('results_watermark', 8))

# Cumulative telemetry counters (everything else is a gauge).
_CUMULATIVE_KEYS = ('batches', 'wait_s', 'reader_wait_s', 'arena_wait_s',
                    'ready_wait_s')

#: Classifications during which the NVMe chunk store's write-behind writer
#: is throttled (PACED to one entry per ``throttle_delay_s``, never fully
#: paused — fill epochs are naturally reader-starved, and a hard pause
#: would keep the store cold forever): dispatch-bound (transfers already
#: saturate the host's IO/DMA paths), reader-starved and input-bound
#: (decode/pipeline work is the limit — epoch-0 spill must not steal CPU
#: or NVMe bandwidth from it). Balanced/consumer-bound ticks restore full
#: writer speed: the pipeline is ahead, spill is free.
WRITER_THROTTLE_CLASSES = (DISPATCH_BOUND, READER_STARVED, INPUT_BOUND)


def writer_throttle_listener(store):
    """A classification listener (see :meth:`AutoTuner.add_listener`)
    driving ``store.set_writer_throttled``: armed (paced spill) while the
    tick's bottleneck class is in :data:`WRITER_THROTTLE_CLASSES`,
    released otherwise. Wired automatically by ``Reader``/``JaxLoader``
    when the pipeline carries a
    :class:`~petastorm_tpu.chunk_store.DecodedChunkStore`.
    """
    def listener(label, detail=None):
        store.set_writer_throttled(label in WRITER_THROTTLE_CLASSES)
    return listener


_tuner_id_lock = threading.Lock()
_tuner_id_next = 0


def _next_tuner_id():
    """Process-unique tuner index for the metrics ``pipeline`` label."""
    global _tuner_id_next
    with _tuner_id_lock:
        tuner_id, _tuner_id_next = _tuner_id_next, _tuner_id_next + 1
        return tuner_id


class AutoTuner(object):
    """Feedback control thread over a set of :class:`Knob`\\ s.

    :param telemetry_fn: ``() -> dict`` sampled once per tick. Keys in
        ``_CUMULATIVE_KEYS`` are treated as monotonically increasing
        counters (the tuner differences them); everything else is a gauge.
        Must be cheap and must not block.
    :param knobs: dict name -> :class:`Knob`.
    :param config: :class:`AutotuneConfig` (defaults applied when None).
    :param classify_fn: ``(deltas, gauges, dt, config) -> (label, detail)``.
    :param watchdog_active_fn: ``() -> bool``; True pauses tuning for the
        tick (an active stall episode — recovery owns the pipeline).
    :param memory_state_fn: ``() -> int`` pressure-ladder level of the
        host memory governor (``membudget.get_governor().pressure_level``;
        0 while unarmed). At advisory or worse the tuner stops growing and
        instead takes one ``mem-shrink`` step per cooldown — prefetch,
        in-flight window, arena depth, workers, watermark all step down —
        releasing host memory ahead of the governor's harder rungs.
    """

    def __init__(self, telemetry_fn, knobs, config=None, tracer=None,
                 classify_fn=classify_loader, watchdog_active_fn=None,
                 memory_state_fn=None, name='pst-autotune'):
        self._telemetry_fn = telemetry_fn
        self.knobs = dict(knobs)
        self.config = config if config is not None else AutotuneConfig()
        from petastorm_tpu.trace import resolve
        self._tracer = resolve(tracer)
        self._classify_fn = classify_fn
        self._watchdog_active_fn = watchdog_active_fn
        self._memory_state_fn = memory_state_fn
        self.mem_shrinks = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=name)
        self._lock = threading.Lock()
        self._log = deque(maxlen=self.config.log_size)
        self._trajectory = deque(maxlen=self.config.log_size)
        self._t0 = None
        self._prev = None
        self._prev_t = None
        self._streak = (None, 0)
        self._cooldown = 0
        self._pending = None      # last action awaiting its throughput verdict
        self._paused_streak = False
        self._listeners = []
        self.ticks = 0
        self.paused_ticks = 0
        self.reverts = 0
        self.last_class = None
        # Registry mirror (petastorm_tpu.metrics): the bottleneck class as
        # an enum gauge (per pipeline, exactly one class label at 1 — the
        # service-level signal ROADMAP-1 autoscaling consumes), knob values
        # as gauges, and a per-action decision counter. Gauges carry a
        # per-tuner ``pipeline`` label: two controllers in one process
        # (train + eval loaders) must not overwrite each other's class or
        # flap each other's knob values.
        from petastorm_tpu import metrics as metrics_mod
        self._pipeline_label = 'tuner-{}'.format(_next_tuner_id())
        self._m_decisions = metrics_mod.counter(
            'pst_autotune_decisions_total',
            'Autotuner knob decisions, by action', labelnames=('action',))
        self._m_bottleneck = metrics_mod.gauge(
            'pst_autotune_bottleneck',
            'Current bottleneck classification (enum gauge: per pipeline, '
            'the active class reads 1, every other 0)',
            labelnames=('pipeline', 'class'))
        self._m_knobs = metrics_mod.gauge(
            'pst_autotune_knob', 'Current autotuner knob values',
            labelnames=('pipeline', 'knob'))
        self._metric_class = None
        self._metric_classes_seen = set()

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        self._thread.start()
        return self

    def stop(self, join_timeout_s=5):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=join_timeout_s)
        # Retire this pipeline's gauge children: a stopped tuner must not
        # keep scraping as a live bottleneck (class stuck at 1), and a
        # trainer building loaders per epoch must not grow 'tuner-N'
        # label children in the process registry without bound.
        for label in self._metric_classes_seen:
            self._m_bottleneck.remove(self._pipeline_label, label)
        self._metric_classes_seen.clear()
        self._metric_class = None
        for name in self.knobs:
            self._m_knobs.remove(self._pipeline_label, name)

    @property
    def alive(self):
        return self._thread.is_alive()

    def add_listener(self, fn):
        """Register ``fn(label, detail)`` to run after every classified
        tick (not while the watchdog pause holds). Listeners observe the
        bottleneck class without being knobs — e.g. the chunk store's
        write-behind throttle (:func:`writer_throttle_listener`). Must be
        cheap; exceptions are logged and swallowed."""
        self._listeners.append(fn)
        return fn

    def _loop(self):
        while not self._stop.wait(self.config.interval_s):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 - the tuner must not die of a bug
                logger.exception('autotune tick failed')

    # -- control loop ------------------------------------------------------

    def tick(self, now=None):
        """One control pass (called by the thread; tests drive it directly
        with a synthetic clock). Returns the decision dict when a knob
        changed, else None."""
        now = now if now is not None else time.monotonic()
        if self._t0 is None:
            self._t0 = now
        snap = self._telemetry_fn() or {}
        prev, prev_t = self._prev, self._prev_t
        self._prev, self._prev_t = snap, now
        self.ticks += 1
        if self._watchdog_active_fn is not None and self._watchdog_active_fn():
            # A diagnosed stall episode is in progress: recovery owns the
            # pipeline. Tuning against it would blur the diagnosis (and a
            # knob change can mask the stall the watchdog is escalating).
            self.paused_ticks += 1
            self._streak = (None, 0)
            self._pending = None
            if not self._paused_streak:
                self._paused_streak = True
                self._record({'action': 'paused',
                              'detail': 'watchdog stall episode active'}, now)
            return None
        self._paused_streak = False
        if self._memory_state_fn is not None and self._mem_pressure():
            # Advisory-or-worse memory pressure: the governor's ladder
            # owns the pipeline's direction. Growing any knob would add
            # bytes against the budget, and the throughput guard would
            # "revert" memory relief the moment rate dipped — so both are
            # suspended, and one additive shrink step runs per cooldown
            # instead (the same AIMD step _shrink uses, applied for bytes
            # rather than for a consumer-bound classification).
            self._pending = None
            self._streak = (None, 0)
            if self._cooldown > 0:
                self._cooldown -= 1
                return None
            changes = self._shrink()
            if not changes:
                return None   # every knob already at its floor
            self.mem_shrinks += 1
            decision = {'action': 'mem-shrink', 'class': 'memory-pressure',
                        'changes': changes,
                        'detail': 'host memory governor at advisory or '
                                  'worse: biasing every knob down one step'}
            self._record(decision, now)
            self._snapshot_trajectory(now)
            self._cooldown = self.config.cooldown
            return decision
        if prev is None:
            self._snapshot_trajectory(now)
            return None
        dt = now - prev_t
        if dt <= 0:
            return None
        deltas = {k: snap.get(k, 0) - prev.get(k, 0) for k in _CUMULATIVE_KEYS}
        if any(v < 0 for v in deltas.values()):
            # A cumulative counter went BACKWARD: someone reset the stats
            # mid-run (a benchmark's reset_stats() after warmup). The tick's
            # deltas — and any pending action verdict judged on them —
            # are garbage; discard both and re-baseline from this sample.
            self._pending = None
            self._streak = (None, 0)
            return None
        rate = deltas.get('batches', 0) / dt
        label, detail = self._classify_fn(deltas, snap, dt, self.config)
        self.last_class = label
        if label != self._metric_class:
            if self._metric_class is not None:
                self._m_bottleneck.labels(
                    self._pipeline_label, self._metric_class).set(0)
            self._m_bottleneck.labels(self._pipeline_label, label).set(1)
            self._metric_class = label
            self._metric_classes_seen.add(label)
        for listener in self._listeners:
            try:
                listener(label, detail)
            except Exception:  # noqa: BLE001 - a listener must not kill the tuner
                logger.exception('autotune classification listener failed')

        # Throughput guard first: the verdict on the previous action is due
        # once its cooldown expired (one settling window after the change).
        if self._pending is not None and self._cooldown <= 1:
            pending, self._pending = self._pending, None
            base = pending['base_rate']
            tol = self.config.throughput_tolerance
            if base > 0 and rate < base * (1.0 - tol):
                for name, old, _new in pending['changes']:
                    self.knobs[name].set(old)
                self.reverts += 1
                decision = {'action': 'revert', 'class': label,
                            'changes': [(n, new, old)
                                        for n, old, new in pending['changes']],
                            'rate': round(rate, 2),
                            'detail': 'rate {:.1f}/s fell past {:.0%} of '
                                      'pre-action {:.1f}/s'.format(
                                          rate, 1.0 - tol, base)}
                self._record(decision, now)
                self._snapshot_trajectory(now)
                self._cooldown = self.config.cooldown
                self._streak = (None, 0)
                return decision

        if self._cooldown > 0:
            self._cooldown -= 1
            return None

        streak_label, streak_count = self._streak
        if label != streak_label:
            self._streak = (label, 1)
        else:
            self._streak = (label, streak_count + 1)
        if self._streak[1] < self.config.hysteresis:
            return None
        if label in (BALANCED,):
            return None

        changes = (self._shrink() if label == CONSUMER_BOUND
                   else self._grow(label))
        if not changes:
            self._streak = (label, 0)
            return None
        decision = {'action': 'shrink' if label == CONSUMER_BOUND else 'grow',
                    'class': label, 'changes': changes,
                    'rate': round(rate, 2), 'detail': detail}
        self._record(decision, now)
        self._snapshot_trajectory(now)
        self._pending = {'changes': changes, 'base_rate': rate}
        self._cooldown = self.config.cooldown
        self._streak = (label, 0)
        return decision

    def _mem_pressure(self):
        """True at advisory (level 1) or worse; a dying probe reads 0."""
        try:
            return int(self._memory_state_fn()) >= 1
        except Exception:  # noqa: BLE001 - a dying probe must not kill the tuner
            return False

    def _grow(self, label):
        for name, step in _GROW_ACTIONS.get(label, ()):
            knob = self.knobs.get(name)
            if knob is None:
                continue
            old = knob.get()
            if old >= knob.hi:
                # At (or hand-set above) the clamp: clamping old+step would
                # MOVE THE KNOB DOWN — shrinking the very resource the
                # classifier wants more of. Out-of-range stays untouched.
                continue
            new = knob.clamp(old + step)
            if new != old:
                knob.set(new)
                return [(name, old, new)]
        return []

    def _shrink(self):
        changes = []
        for name, step in _SHRINK_STEPS:
            knob = self.knobs.get(name)
            if knob is None:
                continue
            old = knob.get()
            if old <= knob.lo:   # mirror of _grow: never clamp upward
                continue
            # One additive step, floored at lo — deliberately NOT hi-
            # clamped: a hand-set above-range value must step down
            # gradually, not collapse to the clamp in one decision.
            new = max(knob.lo, old - step)
            if new != old:
                knob.set(new)
                changes.append((name, old, new))
        return changes

    # -- bookkeeping -------------------------------------------------------

    def _record(self, decision, now):
        decision = dict(decision)
        decision['t'] = round(now - self._t0, 3)
        decision['tick'] = self.ticks
        self._m_decisions.labels(decision['action']).inc()
        with self._lock:
            self._log.append(decision)
        self._tracer.instant(
            'autotune:{}:{}'.format(decision['action'],
                                    decision.get('class', '-')),
            cat='autotune',
            args={k: v for k, v in decision.items() if k != 'detail'})
        logger.debug('autotune decision: %s', decision)

    def _snapshot_trajectory(self, now):
        point = {'t': round(now - self._t0, 3)}
        for name, knob in self.knobs.items():
            try:
                point[name] = knob.get()
                self._tracer.counter('autotune_{}'.format(name), point[name],
                                     'autotune')
                self._m_knobs.labels(self._pipeline_label, name).set(
                    point[name])
            except Exception:  # noqa: BLE001 - a dying getter must not kill it
                point[name] = None
        with self._lock:
            self._trajectory.append(point)

    def stats(self):
        """Decision log + knob trajectory + current values (what rides in
        ``stats['autotune']`` / ``diagnostics()['autotune']``)."""
        knobs = {}
        for name, knob in self.knobs.items():
            try:
                knobs[name] = knob.get()
            except Exception:  # noqa: BLE001
                knobs[name] = None
        with self._lock:
            return {'ticks': self.ticks,
                    'paused_ticks': self.paused_ticks,
                    'reverts': self.reverts,
                    'mem_shrinks': self.mem_shrinks,
                    'last_class': self.last_class,
                    'knobs': knobs,
                    'decisions': list(self._log),
                    'trajectory': list(self._trajectory)}
