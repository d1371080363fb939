"""Pipeline health supervision: heartbeats, stall diagnosis, recovery.

PR 1 made *discrete* failures survivable (retries, worker respawn,
row-group quarantine) and PR 2 made the staging hot path fast — but a
*stalled* pipeline (a hung ``device_put``, a dead data-service server, a
consumer that stopped draining, an arena pool wedged on GC holds) still
either hung the epoch silently or died with a bare timeout naming no
culprit. The tf.data-service literature (PAPERS.md) treats "which stage is
the bottleneck / which server is unhealthy" as first-class runtime state;
this module gives petastorm_tpu the same property:

:class:`Heartbeat` / :class:`HeartbeatRegistry`
    Every pipeline stage (reader ventilator, pool result handoff, staging
    assemble/dispatch threads, the JaxLoader consumer, the RemoteReader
    receive loop) registers a named heartbeat and *beats* on the hot path
    for the cost of two attribute writes — a ``time.monotonic()`` stamp
    plus a state label (``'reader-wait'``, ``'device_put'``, ...). No
    locks, no allocation: CPython attribute stores are atomic, and each
    heartbeat is written by exactly one thread. The state label is what
    turns a stale timestamp into a *diagnosis*: it says what the stage was
    last doing when it went quiet.

:class:`Watchdog`
    A supervisor thread with per-stage stall deadlines. On expiry it

    (a) **classifies** the stall (:func:`classify_stall`) from the beat
        ages + state labels + registered probe snapshots (queue depths,
        staging counters, worker liveness, per-server chunk ages);
    (b) emits a **diagnosis report** — an all-thread stack dump
        (``sys._current_frames``), the last-beat table, and every probe's
        snapshot — through the tracer and into
        ``Reader.diagnostics()`` / loader ``stats``;
    (c) runs **escalating recovery**: soft actions first (nudge queues,
        wake ventilators, fail a RemoteReader over to surviving servers),
        then — if the same stall persists past the escalation deadline —
        delivers a :class:`~petastorm_tpu.errors.PipelineStallError`
        carrying the full diagnosis instead of an anonymous hang.

Enable via ``watchdog=True`` (or per-stage ``stall_timeout_s``) on the
reader/loader factories, or process-wide with the
``PETASTORM_TPU_WATCHDOG`` environment variable (``1``/``true`` = on with
default deadlines; a number = on with that stall deadline in seconds;
``0``/``off``/unset = off). ``tests/test_chaos.py`` proves every
classification deterministically against the ``faults.py`` sites.
"""

import logging
import os
import sys
import threading
import time
import traceback

from petastorm_tpu.errors import PipelineStallError
from petastorm_tpu.membudget import (STATE_BREACH, STATE_DEGRADE,
                                     STATE_SHED)

logger = logging.getLogger(__name__)

ENV_VAR = 'PETASTORM_TPU_WATCHDOG'

#: Default per-stage stall deadline. Deliberately generous: a production
#: input pipeline that produces nothing for a minute is genuinely stuck,
#: while XLA compilation or a cold object-store read can take tens of
#: seconds without being a fault.
DEFAULT_STALL_TIMEOUT_S = 60.0

#: A stall that survives soft recovery for this multiple of its stage
#: deadline escalates to a hard :class:`PipelineStallError`.
DEFAULT_ESCALATION_FACTOR = 2.0

# Classification labels (the vocabulary tests and docs assert against).
READER_STARVED = 'reader-starved'
WORKER_POOL_DEAD = 'worker-pool-dead'
ASSEMBLE_STUCK = 'assemble-stuck'
DISPATCH_HUNG = 'dispatch-hung'
CONSUMER_NOT_DRAINING = 'consumer-not-draining'
ARENA_POOL_WEDGED = 'arena-pool-wedged'
REMOTE_SERVER_DEAD = 'remote-server-dead'
SERVER_DRAINING = 'server-draining'
SERVER_OVERLOADED = 'server-overloaded'
RESEQUENCER_STALLED = 'resequencer-stalled'
#: The host memory governor (``membudget.py``) sits at degrade-or-worse:
#: a quiet pipeline under active memory degradation is the *governor's*
#: episode (caches evicting, spill paused, ventilation paced), not a
#: stage fault. SOFT at degrade/shed — the governor owns the hard path
#: (a budget breach raises its own typed ``HostMemoryExceededError``
#: with a flight dump; escalating to a PipelineStallError here would
#: race it with a worse diagnosis).
MEMORY_PRESSURE = 'memory-pressure'

#: Governor ladder states that flip classification to MEMORY_PRESSURE
#: (the canonical constants — membudget's module surface is stdlib-only,
#: so the import is cycle-free and a renamed/added rung cannot silently
#: stop matching here). Breach is included: while the governor's typed
#: HostMemoryExceededError is in flight, a quiet pipeline must not be
#: hard-escalated as an ordinary stage stall racing it.
_MEM_DEGRADED_STATES = (STATE_DEGRADE, STATE_SHED, STATE_BREACH)

#: Classifications the memory ladder REINTERPRETS as memory-pressure
#: while degrade-or-worse holds: the starvation-shaped symptoms active
#: degradation deliberately causes (paced ventilation starves the
#: reader, shrunk pools starve the assembler, shedding servers refuse
#: consumers). Deliberately NOT the whole vocabulary: a dead worker, a
#: wedged publish behind the resequencer, or a hung device_put is a
#: genuine fault that memory pressure does not explain — those keep
#: their own classification (and their hard escalation), or a pipeline
#: parked at 90% of budget could hang forever behind a soft-only label.
_MEM_REINTERPRETED = frozenset({READER_STARVED, ARENA_POOL_WEDGED,
                                SERVER_OVERLOADED})
#: Pseudo-classification: every stale stage is parked in a *waiting* state
#: (on upstream or the consumer) and no culpable stage has crossed its own
#: deadline yet — not an actionable stall, so the watchdog records nothing
#: and re-checks next tick.
PIPELINE_WAITING = 'pipeline-waiting'

#: Classifications that never escalate to a hard error: a consumer that
#: stopped draining is the *trainer's* choice (long compile, eval loop,
#: checkpoint write) — killing the pipeline under it would turn normal
#: training-loop pauses into failures; a draining data-service server is
#: an *operator's* choice mid-rollout and ends in a clean END broadcast
#: (or a failover) on its own. The diagnosis is still recorded.
SOFT_ONLY = frozenset({CONSUMER_NOT_DRAINING, SERVER_DRAINING,
                       MEMORY_PRESSURE})

#: States in which a stage is parked waiting on its *upstream* (or on the
#: consumer) rather than doing its own work: a stale heartbeat in one of
#: these is a symptom, not a culprit — classification walks past it.
_WAITING_STATES = frozenset({'stageq-get', 'stageq-put', 'queue-wait',
                             'poll', 'idle'})


def watchdog_enabled(explicit=None):
    """Resolve the ``watchdog=`` knob against the environment default.

    ``explicit`` wins when not None; otherwise ``PETASTORM_TPU_WATCHDOG``
    decides (unset/empty/0/off = disabled)."""
    if explicit is not None:
        return bool(explicit)
    raw = os.environ.get(ENV_VAR, '').strip().lower()
    return raw not in ('', '0', 'off', 'false', 'no')


def env_stall_timeout():
    """A numeric ``PETASTORM_TPU_WATCHDOG`` value is the default stall
    deadline in seconds; any other truthy value keeps the built-in."""
    raw = os.environ.get(ENV_VAR, '').strip()
    try:
        value = float(raw)
    except ValueError:
        return None
    return value if value > 0 else None


def dump_all_stacks():
    """Formatted stack traces of every live thread (the ``faulthandler``
    view, but as a string we can embed in errors and diagnostics)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    chunks = []
    for ident, frame in sorted(sys._current_frames().items()):
        name = names.get(ident, '?')
        chunks.append('Thread {} ({}):\n{}'.format(
            name, ident, ''.join(traceback.format_stack(frame))))
    return '\n'.join(chunks)


class Heartbeat(object):
    """One stage's liveness record. Beaten by exactly one thread; read by
    the watchdog. ``beat()`` is two attribute writes — safe and cheap on
    any hot path."""

    __slots__ = ('name', 'stall_timeout_s', 'last_beat', 'state', 'beats')

    def __init__(self, name, stall_timeout_s):
        self.name = name
        self.stall_timeout_s = stall_timeout_s
        self.last_beat = time.monotonic()
        self.state = 'idle'
        self.beats = 0

    def beat(self, state=None):
        if state is not None:
            self.state = state
        self.last_beat = time.monotonic()
        self.beats += 1

    def age(self, now=None):
        return (now if now is not None else time.monotonic()) - self.last_beat

    def stalled(self, now=None):
        # 'idle' is explicit quiescence (stage not started yet, or cleanly
        # finished) — a loader built long before its first fetch, or an
        # exhausted epoch, must never read as a stall.
        if self.state == 'idle':
            return False
        return (self.stall_timeout_s is not None
                and self.age(now) > self.stall_timeout_s)


class HeartbeatRegistry(object):
    """Named heartbeats + probes + recovery actions for one pipeline.

    Stage threads call :meth:`register` once and then beat lock-free;
    everything else (probes, recoveries, snapshots) runs off the hot path
    under a lock. ``stall_timeouts`` maps stage name (or ``'default'``) to
    a deadline in seconds; a scalar applies to every stage.
    """

    def __init__(self, stall_timeouts=None):
        # Sanitizer hookup: lock-order-recorded when PETASTORM_TPU_SANITIZE
        # is armed (name matches pstlint's static graph node).
        from petastorm_tpu.analysis import sanitize
        self._lock = sanitize.tracked_lock(
            'petastorm_tpu.health:HeartbeatRegistry._lock')
        self._beats = {}
        self._probes = {}
        self._recoveries = {}     # classification label -> [fn, ...]
        env_default = env_stall_timeout()
        if stall_timeouts is None:
            stall_timeouts = {}
        elif not isinstance(stall_timeouts, dict):
            stall_timeouts = {'default': float(stall_timeouts)}
        self._timeouts = dict(stall_timeouts)
        if 'default' not in self._timeouts:
            self._timeouts['default'] = (env_default
                                         if env_default is not None
                                         else DEFAULT_STALL_TIMEOUT_S)

    def timeout_for(self, name):
        return self._timeouts.get(name, self._timeouts['default'])

    def register(self, name, stall_timeout_s=None):
        """Create (or return the existing) heartbeat for ``name``."""
        with self._lock:
            hb = self._beats.get(name)
            if hb is None:
                hb = Heartbeat(name, stall_timeout_s
                               if stall_timeout_s is not None
                               else self.timeout_for(name))
                self._beats[name] = hb
            return hb

    def unregister(self, name):
        with self._lock:
            self._beats.pop(name, None)
            self._probes.pop(name, None)

    def register_probe(self, name, fn):
        """``fn() -> dict`` sampled into every diagnosis (queue depths,
        staging counters, worker liveness...). Must be cheap-ish and must
        not block; exceptions are swallowed into the snapshot."""
        with self._lock:
            self._probes[name] = fn

    def register_recovery(self, classification, fn):
        """``fn(diagnosis) -> bool`` soft-recovery action for a stall
        classified as ``classification`` (True = acted). Runs on the
        watchdog thread: it must only touch thread-safe state."""
        with self._lock:
            self._recoveries.setdefault(classification, []).append(fn)

    def recoveries_for(self, classification):
        with self._lock:
            return list(self._recoveries.get(classification, ()))

    def beat_table(self, now=None):
        now = now if now is not None else time.monotonic()
        with self._lock:
            return {name: {'age_s': round(hb.age(now), 3),
                           'state': hb.state,
                           'beats': hb.beats,
                           'stall_timeout_s': hb.stall_timeout_s}
                    for name, hb in self._beats.items()}

    def probe_snapshot(self):
        with self._lock:
            probes = list(self._probes.items())
        out = {}
        for name, fn in probes:
            try:
                out[name] = fn()
            except Exception as e:  # noqa: BLE001 - probes must not kill the dog
                out[name] = {'probe_error': repr(e)}
        return out

    def stalled(self, now=None):
        """Heartbeats past their deadline, most-stale first."""
        now = now if now is not None else time.monotonic()
        with self._lock:
            late = [hb for hb in self._beats.values() if hb.stalled(now)]
        return sorted(late, key=lambda hb: hb.age(now), reverse=True)

    def min_timeout(self):
        with self._lock:
            timeouts = [hb.stall_timeout_s for hb in self._beats.values()
                        if hb.stall_timeout_s is not None]
        timeouts.append(self._timeouts['default'])
        return min(timeouts)


def classify_stall(beats, probes):
    """(classification, stage, detail) for a stall, from the beat table
    (name -> {age_s, state, stall_timeout_s}) and probe snapshots.

    Walks from the most upstream culpable stage down: a stage parked in a
    *waiting* state (on its upstream or its consumer) is a symptom, so
    blame lands on whoever was last seen doing (or failing to do) actual
    work. The returned ``detail`` is one human sentence.

    Memory-pressure overlay: with the governor armed at degrade-or-worse,
    starvation-shaped results (:data:`_MEM_REINTERPRETED`) reinterpret as
    the soft-only ``memory-pressure`` — intended load-shedding, not a
    fault — while genuine faults (dead workers, wedged publishes, hung
    transfers) keep their own classification and escalation.
    """
    classification, stage, detail = _classify_stall_stages(beats, probes)
    memory = probes.get('memory') or {}
    if memory.get('armed') and memory.get('state') in _MEM_DEGRADED_STATES \
            and classification in _MEM_REINTERPRETED:
        return (MEMORY_PRESSURE, 'memory',
                'host memory governor at {!r} ({} of {} budget bytes, '
                '{:.0%}) — would otherwise classify {}: {}'.format(
                    memory.get('state'), memory.get('accounted_bytes'),
                    memory.get('budget_bytes'), memory.get('frac') or 0.0,
                    classification, detail))
    return classification, stage, detail


def _classify_stall_stages(beats, probes):
    def stale(name):
        entry = beats.get(name)
        return (entry is not None and entry['stall_timeout_s'] is not None
                and entry['state'] != 'idle'     # explicit quiescence
                and entry['age_s'] > entry['stall_timeout_s'])

    def state(name):
        entry = beats.get(name, None)
        return entry['state'] if entry else None

    # A dead worker process outranks every downstream symptom: whatever
    # else went quiet sits downstream of a decode tier that lost a
    # process (respawn pending on the consumer thread, or budget spent).
    pool = probes.get('worker-pool', {})
    dead_workers = pool.get('dead_workers') or []
    if dead_workers:
        return (WORKER_POOL_DEAD, 'worker-pool',
                'worker process(es) {} are dead (PR-1 supervision will '
                'respawn on the next get_results poll if budget remains)'
                .format(dead_workers))

    # Deterministic mode: chunks buffered behind a ventilation-seq hole
    # while the handoff went quiet means the stream is held hostage by ONE
    # unpublished item (a wedged worker publish) — the other workers kept
    # producing, so worker-pool/reader symptoms look healthy. Checked
    # after dead-workers (a respawned worker re-delivers the hole) and
    # before the starvation rules (which would mis-blame the decode tier).
    resequencer = probes.get('resequencer') or {}
    if resequencer.get('buffered', 0) > 0 \
            and resequencer.get('waiting_s', 0) > 0 \
            and (stale('reader-handoff') or stale('consumer')
                 or (stale('assemble')
                     and state('assemble') == 'reader-wait')):
        return (RESEQUENCER_STALLED, 'resequencer',
                'deterministic resequencer has held {} chunk(s) for {}s '
                'waiting for ventilation seq {} — one item never '
                'published'.format(resequencer.get('buffered'),
                                   resequencer.get('waiting_s'),
                                   resequencer.get('expected_seq')))

    if stale('assemble'):
        st = state('assemble')
        if st == 'arena-wait':
            return (ARENA_POOL_WEDGED, 'assemble',
                    'assemble thread has waited {}s for a free host arena '
                    '(all arenas pinned by GC holds / undelivered batches)'
                    .format(beats['assemble']['age_s']))
        # 'reader-wait' is handled BELOW the remote-recv check: on a
        # data-service pipeline a starved assembler is the downstream echo
        # of a quiet receive loop, and the rpc probe must get to decide
        # dead-server vs merely-slow first.
        if st != 'reader-wait' and st not in _WAITING_STATES:
            return (ASSEMBLE_STUCK, 'assemble',
                    'assemble thread silent for {}s inside {!r} (collate/'
                    'shape-policy/transform work wedged)'.format(
                        beats['assemble']['age_s'], st))

    if stale('dispatch'):
        st = state('dispatch')
        if st in ('device_put', 'ready-wait'):
            return (DISPATCH_HUNG, 'dispatch',
                    'dispatch thread stuck {}s in {!r} — a device_put/'
                    'transfer fence never completed (wedged device or '
                    'interconnect)'.format(beats['dispatch']['age_s'], st))
        if st == 'out-put':
            return (CONSUMER_NOT_DRAINING, 'dispatch',
                    'dispatch thread blocked {}s handing a staged batch to '
                    'a full consumer queue'.format(
                        beats['dispatch']['age_s']))

    if stale('consumer'):
        st = state('consumer')
        # Inline staging (prefetch=0): the consumer thread runs the
        # pipeline itself, so its states carry the same meanings as the
        # engine threads' and classify identically.
        if st == 'device_put':
            return (DISPATCH_HUNG, 'consumer',
                    'inline device staging (prefetch=0) stuck {}s in a '
                    'device_put that never completed'.format(
                        beats['consumer']['age_s']))
        if st == 'reader-wait':
            return (READER_STARVED, 'consumer',
                    'inline consumer (prefetch=0) has waited {}s for the '
                    'reader'.format(beats['consumer']['age_s']))
        # Consumer walked away: stale in the 'delivered' state (it took a
        # batch and never came back). Always the soft-only classification
        # — a paused training loop is a choice, not a fault.
        if st == 'delivered':
            depth = probes.get('consumer', {}).get('queue_depth')
            return (CONSUMER_NOT_DRAINING, 'consumer',
                    'consumer has not requested a batch for {}s ({} staged '
                    'batch(es) waiting)'.format(
                        beats['consumer']['age_s'], depth))

    # Remote tier — checked only AFTER the downstream rules: a paused
    # consumer also quiets the receive loop (backpressure), and blaming
    # the servers for that would escalate a healthy pipeline. Reaching
    # here means nothing downstream explains the quiet, so the receive
    # loop's silence is genuine: a server fault when an rpc liveness
    # probe agrees, merely-slow servers otherwise.
    if stale('remote-recv'):
        remote = probes.get('remote-recv', {})
        dead = remote.get('dead_endpoints') or []
        if dead:
            return (REMOTE_SERVER_DEAD, 'remote-recv',
                    'data-service server(s) dead (lease expired or '
                    'unreachable over rpc): {}'.format(sorted(dead)))
        draining = remote.get('draining_endpoints') or []
        if draining:
            # An operator event, not a fault: the server announced the
            # drain in its lease heartbeats and will END (or a failover
            # will cover it) on its own. Soft-only.
            return (SERVER_DRAINING, 'remote-recv',
                    'data-service server(s) draining (graceful shutdown '
                    'announced in lease heartbeats): {}'.format(
                        sorted(draining)))
        refused = remote.get('refused_endpoints') or {}
        if refused:
            return (SERVER_OVERLOADED, 'remote-recv',
                    'data-service server(s) refused this consumer '
                    '(admission control at capacity): {}'.format(
                        sorted(refused)))
        return (READER_STARVED, 'remote-recv',
                'no chunks from any data-service server for {}s but all '
                'rpc probes answer — decode tier is slow, not dead'
                .format(beats['remote-recv']['age_s']))

    if stale('assemble') and state('assemble') == 'reader-wait':
        return (READER_STARVED, 'assemble',
                'assemble thread has waited {}s for the reader '
                '(decode/IO tier produced nothing)'
                .format(beats['assemble']['age_s']))

    # Reader-only pipelines (no staging engine): the handoff heartbeat is
    # beaten 'poll' entering the pool wait and 'handoff' when a row leaves
    # the reader — stale 'poll' is starvation, stale 'handoff' means the
    # consumer stopped pulling.
    if stale('reader-handoff'):
        st = state('reader-handoff')
        if st == 'handoff':
            return (CONSUMER_NOT_DRAINING, 'reader-handoff',
                    'no one has pulled a row from the reader for {}s'.format(
                        beats['reader-handoff']['age_s']))
        if st != 'idle':        # 'poll': parked waiting on the decode tier
            return (READER_STARVED, 'reader-handoff',
                    'reader produced nothing for {}s'.format(
                        beats['reader-handoff']['age_s']))
    if stale('ventilator') and state('ventilator') not in _WAITING_STATES:
        return (READER_STARVED, 'ventilator',
                'ventilator made no progress for {}s'.format(
                    beats['ventilator']['age_s']))

    # Fallback: name the most-stale stage doing actual work; stages parked
    # in waiting states are symptoms (the culprit's own deadline simply
    # hasn't expired yet) — report pipeline-waiting, which the watchdog
    # treats as "check again next tick", not as a stall episode.
    worst = max((n for n in beats
                 if stale(n) and beats[n]['state'] != 'idle'
                 and beats[n]['state'] not in _WAITING_STATES),
                key=lambda n: beats[n]['age_s'], default=None)
    if worst is None:
        return (PIPELINE_WAITING, 'unknown',
                'every stale stage is parked waiting on another; no '
                'culpable stage has crossed its own deadline yet')
    return ('{}-stalled'.format(worst), worst,
            'stage {!r} silent for {}s in state {!r}'.format(
                worst, beats[worst]['age_s'], beats[worst]['state']))


class StallDiagnosis(dict):
    """The report attached to trace events, diagnostics, and
    :class:`PipelineStallError`: classification + stage + detail + the
    last-beat table + probe snapshots + an all-thread stack dump."""

    @classmethod
    def capture(cls, registry, classification, stage, detail,
                beats=None, probes=None):
        """``beats``/``probes`` accept the snapshots that already drove the
        classification — probes can be expensive (rpc liveness sweeps), so
        the diagnosis must not pay for them twice (and must report exactly
        the evidence the classifier saw, not a second, possibly different,
        sample)."""
        return cls(classification=classification, stage=stage, detail=detail,
                   beats=beats if beats is not None else registry.beat_table(),
                   probes=(probes if probes is not None
                           else registry.probe_snapshot()),
                   stacks=dump_all_stacks(),
                   captured_at=time.time())

    def summary(self):
        """The diagnosis minus the (large) stack dump — what rides in
        ``stats`` / ``diagnostics`` without bloating them."""
        return {k: v for k, v in self.items() if k != 'stacks'}

    def format(self):
        lines = ['pipeline stall: {} (stage {!r}): {}'.format(
            self['classification'], self['stage'], self['detail'])]
        lines.append('last beats: {}'.format(
            {n: '{}s/{}'.format(b['age_s'], b['state'])
             for n, b in sorted(self['beats'].items())}))
        if self['probes']:
            lines.append('probes: {}'.format(self['probes']))
        lines.append('--- all-thread stack dump ---')
        lines.append(self['stacks'])
        return '\n'.join(lines)


class Watchdog(object):
    """Supervisor thread over a :class:`HeartbeatRegistry`.

    Ticks at a fraction of the tightest stage deadline. On a stall it
    classifies, records + traces the diagnosis, and runs the soft
    recoveries registered for that classification; a stall that persists
    past ``escalation * deadline`` (and is not in :data:`SOFT_ONLY`)
    becomes a hard :class:`PipelineStallError` handed to ``on_hard_stall``
    — which delivers it into the consumer's queue so the training loop
    raises a diagnosed error instead of hanging.
    """

    def __init__(self, registry, on_hard_stall=None, tracer=None,
                 escalation=DEFAULT_ESCALATION_FACTOR, poll_interval_s=None,
                 name='pst-watchdog', flight_recorder=None):
        from petastorm_tpu import metrics
        self._registry = registry
        self._on_hard_stall = on_hard_stall
        from petastorm_tpu.trace import resolve
        self._tracer = resolve(tracer)
        #: Optional petastorm_tpu.flight_recorder.FlightRecorder: sampled
        #: every check pass, dumped on hard escalation so the stall's trace
        #: ring + metric history survive the process.
        self._flight_recorder = flight_recorder
        self._m_stalls = metrics.counter(
            'pst_watchdog_stalls_total',
            'Stall episodes detected, by classification',
            labelnames=('classification',))
        self._m_soft = metrics.counter(
            'pst_watchdog_soft_recoveries_total',
            'Stall episodes where a soft recovery action ran')
        self._m_hard = metrics.counter(
            'pst_watchdog_hard_stalls_total',
            'Stalls escalated to PipelineStallError, by classification',
            labelnames=('classification',))
        self._escalation = max(1.0, float(escalation))
        self._poll_interval_s = poll_interval_s
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=name)
        self._lock = threading.Lock()
        # Current stall episode: (stage, classification, started_at,
        # hard_fired). A fresh beat on the stage ends the episode.
        self._episode = None
        self.stalls_detected = 0
        self.soft_recoveries = 0
        self.hard_stalls = 0
        self.last_diagnosis = None

    def start(self):
        self._thread.start()
        return self

    def stop(self, join_timeout_s=5):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=join_timeout_s)

    @property
    def alive(self):
        return self._thread.is_alive()

    @property
    def episode_active(self):
        """True while a stall episode is in progress (detected and not yet
        recovered). The autotuner (``autotune.py``) pauses on this — knob
        changes mid-recovery would blur the diagnosis and can mask the
        stall the watchdog is escalating."""
        return self._episode is not None

    def _interval(self):
        if self._poll_interval_s is not None:
            return self._poll_interval_s
        # Four checks per tightest deadline, clamped to something humane.
        return min(max(self._registry.min_timeout() / 4.0, 0.02), 5.0)

    def _loop(self):
        while not self._stop.wait(self._interval()):
            try:
                self.check()
            except Exception:  # noqa: BLE001 - the dog must not die of a bug
                logger.exception('watchdog check failed')

    def check(self, now=None):
        """One supervision pass (also called directly by tests)."""
        now = now if now is not None else time.monotonic()
        if self._flight_recorder is not None:
            try:
                self._flight_recorder.sample()
            except Exception:  # noqa: BLE001 - recording must not kill the dog
                logger.debug('flight recorder sample failed', exc_info=True)
        stalled = self._registry.stalled(now)
        if not stalled:
            self._episode = None
            return None
        beats = self._registry.beat_table(now)
        probes = self._registry.probe_snapshot()
        classification, stage, detail = classify_stall(beats, probes)
        if classification == PIPELINE_WAITING:
            self._episode = None
            return None
        episode = self._episode
        if episode is None or episode[0] != stage or episode[1] != classification:
            # New stall episode: diagnose, trace, soft-recover.
            diagnosis = StallDiagnosis.capture(
                self._registry, classification, stage, detail,
                beats=beats, probes=probes)
            with self._lock:
                self.stalls_detected += 1
                self.last_diagnosis = diagnosis
            self._m_stalls.labels(classification).inc()
            self._tracer.instant('stall:{}'.format(classification),
                                 cat='watchdog')
            logger.warning('pipeline stall detected: %s (stage %r): %s',
                           classification, stage, detail)
            acted = False
            for fn in self._registry.recoveries_for(classification):
                try:
                    acted = bool(fn(diagnosis)) or acted
                except Exception:  # noqa: BLE001
                    logger.exception('soft recovery for %s failed',
                                     classification)
            if acted:
                with self._lock:
                    self.soft_recoveries += 1
                self._m_soft.inc()
                self._tracer.instant('stall-recovery:{}'.format(classification),
                                     cat='watchdog')
            self._episode = (stage, classification, now, False)
            return diagnosis
        # Ongoing episode: escalate once past escalation * deadline.
        _, _, started_at, hard_fired = episode
        deadline = self._registry.timeout_for(stage)
        hb_entry = beats.get(stage)
        if hb_entry is not None and hb_entry['stall_timeout_s'] is not None:
            deadline = hb_entry['stall_timeout_s']
        if (not hard_fired and classification not in SOFT_ONLY
                and now - started_at >= self._escalation * deadline):
            diagnosis = StallDiagnosis.capture(
                self._registry, classification, stage, detail,
                beats=beats, probes=probes)
            with self._lock:
                self.hard_stalls += 1
                self.last_diagnosis = diagnosis
            self._episode = (stage, classification, started_at, True)
            self._m_hard.labels(classification).inc()
            self._tracer.instant('stall-hard:{}'.format(classification),
                                 cat='watchdog')
            if self._flight_recorder is not None:
                # Dump BEFORE delivering the error: the post-mortem must
                # exist even if the consumer's teardown kills the process,
                # and the dump path rides the diagnosis into the error text.
                try:
                    dump_path = self._flight_recorder.dump(
                        diagnosis, reason=classification)
                    if dump_path is not None:
                        diagnosis['flight_dump'] = dump_path
                except Exception:  # noqa: BLE001 - best-effort by contract
                    logger.exception('flight recorder dump failed')
            error = PipelineStallError(diagnosis.format(),
                                       diagnosis=diagnosis)
            logger.error('pipeline stall escalated to hard error: %s '
                         '(stage %r)', classification, stage)
            if self._on_hard_stall is not None:
                try:
                    self._on_hard_stall(error)
                except Exception:  # noqa: BLE001
                    logger.exception('hard-stall delivery failed')
            return diagnosis
        return None

    def stats(self):
        with self._lock:
            last = self.last_diagnosis
            out = {'stalls_detected': self.stalls_detected,
                   'soft_recoveries': self.soft_recoveries,
                   'hard_stalls': self.hard_stalls,
                   'episode_active': self.episode_active,
                   'last_stall': last.summary() if last is not None else None}
        if self._flight_recorder is not None:
            out['flight_dumps'] = list(self._flight_recorder.dumps)
        return out


class HealthMonitor(object):
    """Registry + watchdog pair with one owner (a Reader or a JaxLoader).

    ``attach_health(registry)`` protocols let a loader share its registry
    with the reader underneath it, so one watchdog supervises the whole
    pipeline; a reader used standalone owns its own monitor.
    """

    def __init__(self, stall_timeouts=None, on_hard_stall=None, tracer=None,
                 escalation=DEFAULT_ESCALATION_FACTOR, poll_interval_s=None,
                 flight_recorder=None):
        self.registry = HeartbeatRegistry(stall_timeouts)
        if flight_recorder is None:
            # Env-armed stall flight recorder (PETASTORM_TPU_FLIGHT_RECORDER
            # = a directory): every supervised pipeline then dumps its
            # trace ring + metrics on a hard stall with no code change.
            from petastorm_tpu import flight_recorder as flight_mod
            flight_recorder = flight_mod.maybe_from_env(tracer=tracer)
        self.flight_recorder = flight_recorder
        self.watchdog = Watchdog(self.registry, on_hard_stall=on_hard_stall,
                                 tracer=tracer, escalation=escalation,
                                 poll_interval_s=poll_interval_s,
                                 flight_recorder=flight_recorder)

    def start(self):
        self.watchdog.start()
        return self

    def stop(self):
        self.watchdog.stop()

    def stats(self):
        out = self.watchdog.stats()
        out['beats'] = self.registry.beat_table()
        return out
