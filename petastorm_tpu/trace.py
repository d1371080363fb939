"""Input-pipeline tracing: one span stream from the reader to the consumer.

Every interval the pipeline clocks is clocked HERE, once: a :class:`Span`
reads ``time.perf_counter_ns()`` (and, on the pool's worker threads, the
thread's own CPU clock) on entry and exit, and closing it (a) appends one
record to the tracer's bounded ring, (b) adds its *self* seconds (its
duration less what spans nested inside it on the same thread covered) to the
running total that ``loader.stats`` / ``worker_stage_timings`` report, and
(c) observes the site's ``pst_*_seconds`` histogram. No call site keeps a
``perf_counter()`` pair or an ``observe()`` of its own.

Records are plain tuples:

* a span is ``(name, layer, start_ns, dur_ns, cpu_ns, tid, id, cause)``:
  ``start_ns`` on ``time.perf_counter_ns()`` (absolute: the clock a training
  loop pins to a device trace), ``cpu_ns`` the thread's CPU time inside the
  span for the layers in :data:`CPU_LAYERS` (the pool's worker threads) and
  ``None`` for the others, ``id`` the row-group key
  (``'<piece>:<drop-partition>'``) for reader and decode spans and the
  loader's batch sequence number from collate onwards, ``cause`` what
  produced it (``reader.cache_get`` says hit or miss, ``dispatch.stage``
  the transfer tiers, ``consumer.deliver`` when its batch was staged);
* an instant is a span record with ``dur_ns`` ``None``; in those layers its
  ``cpu_ns`` slot holds the thread's CPU clock at that moment, so successive
  instants of one thread say how much CPU it burnt between them, inside
  spans or not;
* a counter is ``(name, layer, t_ns, value)``.

**On by default.** :func:`get_global_tracer` returns a process-wide ring of
:data:`DEFAULT_RING_EVENTS` records (about 7 MB when full: minutes of a
training run, oldest dropped first) unless :func:`set_global_tracer` installed
another tracer, and every pipeline object built with ``tracer=None`` records
there. ``set_global_tracer(NullTracer())`` is the one way to switch it off
(totals and histograms keep working; nothing is recorded). A watchdog stall
dump therefore holds the last seconds of spans though nobody armed anything.

The chrome://tracing / Perfetto export, the per-process JSONL sidecars
(``PETASTORM_TPU_TRACE_DIR``), :meth:`Tracer.merge_process_files` and
:meth:`Tracer.summary` are all derived from those tuples.

Usage::

    os.environ['PETASTORM_TPU_TRACE_DIR'] = '/tmp/pst-trace'  # before reader
    tracer = Tracer()
    with make_tensor_reader(url, reader_pool_type='process') as reader:
        with JaxLoader(reader, 1024, tracer=tracer) as loader:
            for batch in loader: ...
    tracer.merge_process_files()
    tracer.export_chrome_trace('/tmp/input_pipeline.json')

Pure stdlib (jax is touched only by :func:`watch_jax_compiles`, which the
loader calls), thread-safe, bounded (drops oldest beyond ``max_events``;
sidecars stop at ``spill_max_events`` lines).
"""

import glob
import json
import logging
import os
import threading
import time
import uuid
from collections import deque

logger = logging.getLogger(__name__)

#: Directory that arms per-process sidecar spill for every Tracer built
#: while it is set (inherited by spawned worker processes). The default
#: ring never spills: sidecars are for tracers somebody built.
TRACE_DIR_ENV = 'PETASTORM_TPU_TRACE_DIR'

#: Bound of the default process-wide ring, in records. A full ring measures
#: about 7 MB (216 bytes a span with its numbers). The two benchmark cells
#: write 60 and 160 records a second on the chip and hold 2,000 and 7,600
#: when a traced run's metrics are read (PERF.md); a streamed four-chip cell
#: should write about 600 a second, 50 s of which fit.
DEFAULT_RING_EVENTS = 32768

#: How many train steps (:class:`StepProgram`) a tracer keeps for
#: :meth:`Tracer.op_scopes`: the newest, as the ring keeps its newest
#: records. A kept step keeps its ``jax.jit`` object and what that compiled.
MAX_STEP_PROGRAMS = 4

_SIDECAR_GLOB = 'trace-*.jsonl'
_HEADER_KEY = '__pst_trace_sidecar__'

#: The layers whose spans and instants read ``time.thread_time_ns()``: the
#: pool's worker threads, whose idle CPU a benchmark metric names. The
#: loader's own threads do not: on a TPU host one read measured 6 µs (a
#: span with it 15 µs, without 2.5, PERF.md), it holds the interpreter lock
#: the collate and dispatch threads need, and that clock ticks in 10 ms
#: steps, so a span of microseconds would read 0 anyway.
CPU_LAYERS = ('reader', 'decode')

_perf_ns = time.perf_counter_ns
_cpu_ns = time.thread_time_ns
_tid = threading.get_ident
# The innermost open span of each thread: a closing span hands its duration
# to the one it was opened inside, which is how self time is known without
# subtracting totals from one another.
_open = threading.local()
# Several threads' spans may feed one total (the per-device streams' fences),
# and a reset may race them: adds and resets take this lock.
_totals_lock = threading.Lock()


def reset_totals(totals):
    """Zero every key of a mapping that closing spans add to."""
    with _totals_lock:
        for key in totals:
            totals[key] = 0.0


class Span(object):
    """One clocked interval; use as a context manager. After it closed,
    ``dur_ns``, ``cpu_ns`` (``None`` outside :data:`CPU_LAYERS`) and
    ``self_ns`` (duration less nested spans of the same thread) are
    readable, and ``id`` / ``cause`` may be set any time before it closes."""

    __slots__ = ('_sink', 'name', 'layer', 'id', 'cause', '_hist', '_total',
                 '_self_total', '_parent', '_child_ns', 'start_ns', 'cpu0_ns',
                 'dur_ns', 'cpu_ns')

    def __init__(self, sink, name, layer, id, cause, hist, total,
                 self_total):
        self._sink = sink
        self.name = name
        self.layer = layer
        self.id = id
        self.cause = cause
        self._hist = hist
        self._total = total
        self._self_total = self_total
        self._child_ns = 0

    def __enter__(self):
        self._parent = getattr(_open, 'span', None)
        _open.span = self
        self.cpu0_ns = _cpu_ns() if self.layer in CPU_LAYERS else None
        self.start_ns = _perf_ns()
        return self

    def __exit__(self, *exc):
        self.dur_ns = dur = _perf_ns() - self.start_ns
        self.cpu_ns = (None if self.cpu0_ns is None
                       else _cpu_ns() - self.cpu0_ns)
        parent = _open.span = self._parent
        if parent is not None:
            parent._child_ns += dur
        if self._total is not None or self._self_total is not None:
            with _totals_lock:
                if self._total is not None:
                    totals, key = self._total
                    totals[key] = totals.get(key, 0.0) + dur / 1e9
                if self._self_total is not None:
                    totals, key = self._self_total
                    totals[key] = totals.get(key, 0.0) + self.self_ns / 1e9
        if self._hist is not None:
            self._hist.observe(dur / 1e9)
        if self._sink is not None:
            self._sink._record((self.name, self.layer, self.start_ns, dur,
                                self.cpu_ns, _tid(), self.id, self.cause))
        return False

    @property
    def self_ns(self):
        return max(0, self.dur_ns - self._child_ns)


class Tracer(object):
    """Thread-safe span recorder with Chrome trace-event export.

    :param max_events: in-memory ring bound (oldest dropped past it).
    :param spill_dir: directory for this process's JSONL sidecar file.
        ``None`` (default) consults ``PETASTORM_TPU_TRACE_DIR``; ``False``
        disables spill even when the env var is set.
    :param role: human label for this process's track in merged timelines
        (``'main'`` for the default in-process tracer; worker bootstraps
        pass ``'worker-<id>'``).
    :param spill_max_events: sidecar line bound (defaults to
        ``max_events``); past it events keep landing in memory but the
        file stops growing (a truncation marker records the drop count).
    """

    def __init__(self, max_events=100000, spill_dir=None, role=None,
                 spill_max_events=None):
        # deque(maxlen=...): O(1) drop-oldest, and append is atomic under
        # the GIL, so recording takes no lock unless a sidecar is armed.
        self._events = deque(maxlen=max_events)
        self._lock = threading.Lock()
        self._t0_ns = _perf_ns()
        # Wall-clock anchor of t0: what lets merge align sidecars recorded
        # by other processes (perf_counter is process-local) onto one
        # timeline. Same-host clocks, so the alignment is ~exact.
        self._wall0 = time.time()
        self._pid = os.getpid()
        self.role = role or 'main'
        if spill_dir is None:
            spill_dir = os.environ.get(TRACE_DIR_ENV) or None
        elif spill_dir is False:
            spill_dir = None
        self._spill_dir = spill_dir
        self._spill_file = None
        self._spill_path = None
        self._spill_count = 0
        self._spill_dropped = 0
        self._spill_failed = False
        self._spill_max = (int(spill_max_events)
                           if spill_max_events is not None else max_events)
        self._merged = []            # events folded in from sidecar files
        self._roles = {}             # pid -> role (merged sidecar headers)
        # The newest train steps that compiled a program
        # (:class:`StepProgram`, each with its signatures and tables), and
        # how many programs each function's name has named so far
        self._steps = deque(maxlen=MAX_STEP_PROGRAMS)
        self._programs_named = {}

    # -- recording ---------------------------------------------------------

    def span(self, name, cat='pipeline', id=None, cause=None, hist=None,
             total=None, self_total=None):
        """A :class:`Span` of layer ``cat``. On close ``total=(mapping,
        key)`` gets the span's seconds added, ``self_total`` its self
        seconds, and ``hist`` (a metrics histogram) observes its
        duration."""
        return Span(self, name, cat, id, cause, hist, total, self_total)

    def instant(self, name, cat='pipeline', args=None, id=None):
        """A zero-duration marker. ``args`` (JSON-safe) renders in the
        trace viewer's detail pane — the autotuner attaches each decision's
        knob changes, ``consumer.deliver`` the time its batch was staged.
        In :data:`CPU_LAYERS` the record's ``cpu_ns`` slot holds the
        thread's CPU clock."""
        self._record((name, cat, _perf_ns(), None,
                      _cpu_ns() if cat in CPU_LAYERS else None, _tid(), id,
                      args))

    def counter(self, name, value, cat='pipeline'):
        """A counter sample (chrome trace 'C' event: a filled area chart)
        next to the spans it explains: arena-pool occupancy, the in-flight
        transfer window, a wait's empty wake-ups."""
        self._record((name, cat, _perf_ns(), value))

    def note_program(self, step, function, leaves, nbytes):
        """A :class:`StepProgram` compiled a program for a signature it had
        not met: name it (``train_step``, then ``train_step#2``...), write
        one ``step.program`` instant (cat ``step``; args: the function, the
        program's name, the signature's leaves and bytes) and keep the step
        for :meth:`op_scopes`. The instant's time says when the step was
        traced anew: before a timed window it is a warm-up, inside one it is
        the retrace behind that window's ``jax.compile`` span
        (``docs/troubleshoot.rst``), and a reader of several programs takes
        the newest before its window's end as the one the window ran
        (``perfbench/scope_reduce.py``). The newest
        :data:`MAX_STEP_PROGRAMS` steps are kept, as the ring keeps its
        newest records: a step has to outlive its caller's last reference to
        be asked about after a run, and a process that builds steps in a
        loop holds no more than these."""
        with self._lock:
            n = self._programs_named.get(function, 0) + 1
            self._programs_named[function] = n
            if not any(known is step for known in self._steps):
                self._steps.append(step)
        program = function if n == 1 else '{}#{}'.format(function, n)
        self.instant('step.program', cat='step', args={
            'function': function, 'program': program, 'leaves': leaves,
            'bytes': nbytes})
        return program

    def op_scopes(self):
        """``{program: {'module': name, 'instructions': {instruction name:
        {'opcode', 'result', 'part', 'pass', 'path', 'parts_fused'}}}}`` for
        the train steps this process compiled (one entry a distinct
        signature of :func:`petastorm_tpu.models.train.make_train_step`'s
        callable, named as its ``step.program`` instant names it): which
        part of the model and which pass (``forward``, ``recompute``,
        ``backward``, ``update``) each instruction of the compiled step
        belongs to, by the scope its ``op_name`` carries
        (``petastorm_tpu.models.scopes``: the parts and their rules). A
        device trace names its events by the same instruction names, so
        this is what puts a ``jax.profiler`` capture down to the model's
        parts (``docs/troubleshoot.rst``).

        Computed here, on demand and once a program, from
        ``jitted.lower(*signature).compile().as_text()``: jax answers that
        from the caches the step's own call filled (no compilation, no
        ``jax.compile`` span), and nothing is computed before somebody
        asks. ``parts_fused`` is ``None`` but for a fusion; a container
        (``scopes.CONTAINERS``) keeps its opcode so that a reader can leave
        it out and count what it runs once."""
        with self._lock:
            steps = list(self._steps)
        out = {}
        for step in steps:
            out.update(step.op_scopes())
        return out

    def _record(self, record):
        self._events.append(record)
        if self._spill_dir is not None:
            with self._lock:
                self._spill(self._chrome(record))

    def records(self):
        """A snapshot of the ring's raw tuples, oldest first (see the module
        docstring for the two shapes)."""
        while True:
            try:
                return list(self._events)
            except RuntimeError:     # appended to while copying: again
                continue

    def _chrome(self, record):
        """One raw record as a Chrome trace event (``ts``/``dur`` in
        microseconds since this tracer was built)."""
        if len(record) == 4:
            name, layer, t_ns, value = record
            return {'name': name, 'cat': layer, 'ph': 'C',
                    'ts': (t_ns - self._t0_ns) / 1e3, 'pid': self._pid,
                    'tid': 0, 'args': {name: value}}
        name, layer, start_ns, dur_ns, cpu_ns, tid, id, cause = record
        event = {'name': name, 'cat': layer, 'ph': 'X',
                 'ts': (start_ns - self._t0_ns) / 1e3, 'pid': self._pid,
                 'tid': tid}
        if dur_ns is None:
            event.update(ph='i', s='t')
            args = dict(cause) if isinstance(cause, dict) else {}
        else:
            event['dur'] = dur_ns / 1e3
            args = {} if cpu_ns is None else {'cpu_us': cpu_ns / 1e3}
            if cause is not None:
                args['cause'] = cause
        if id is not None:
            args['id'] = id
        if args:
            event['args'] = args
        return event

    # -- sidecar spill -----------------------------------------------------

    def _spill(self, event):
        """Append one event line to the sidecar (lock held). Line-buffered
        so a killed process leaves whole lines plus at most one torn tail;
        bounded so a long run cannot fill the disk."""
        if self._spill_failed:
            return
        if self._spill_file is None and not self._open_spill():
            return
        if self._spill_count >= self._spill_max:
            if self._spill_dropped == 0:
                try:
                    self._spill_file.write(json.dumps(
                        {'name': 'trace-spill-truncated', 'cat': 'trace',
                         'ph': 'i', 's': 't',
                         'ts': event.get('ts', 0.0),
                         'pid': self._pid,
                         'tid': threading.get_ident()}) + '\n')
                except OSError:
                    self._spill_failed = True
            self._spill_dropped += 1
            return
        try:
            self._spill_file.write(json.dumps(event) + '\n')
            self._spill_count += 1
        except (OSError, TypeError, ValueError):
            # Disk gone or an un-JSONable args payload: tracing is
            # advisory — never let it take the pipeline down.
            logger.warning('trace sidecar write failed; disabling spill',
                           exc_info=True)
            self._spill_failed = True

    def _open_spill(self):
        try:
            os.makedirs(self._spill_dir, exist_ok=True)
            path = os.path.join(self._spill_dir, 'trace-{}-{}.jsonl'.format(
                self._pid, uuid.uuid4().hex[:8]))
            # buffering=1: one flush per line — crash-tolerant (complete
            # lines survive a SIGKILL) at row-group event granularity.
            self._spill_file = open(path, 'w', buffering=1)
            self._spill_path = path
            self._spill_file.write(json.dumps(
                {_HEADER_KEY: 1, 'pid': self._pid, 'role': self.role,
                 'wall0': self._wall0}) + '\n')
            return True
        except OSError:
            logger.warning('cannot open trace sidecar in %r; disabling spill',
                           self._spill_dir, exc_info=True)
            self._spill_failed = True
            return False

    @property
    def spill_path(self):
        """This tracer's sidecar file (``None`` when spill is off or no
        event has been recorded yet)."""
        with self._lock:
            return self._spill_path

    def close(self):
        """Flush + close the sidecar file (worker bootstraps call this on
        shutdown; safe to call repeatedly, and spill-less tracers no-op)."""
        with self._lock:
            f, self._spill_file = self._spill_file, None
        if f is not None:
            try:
                f.flush()
                f.close()
            except OSError:  # pragma: no cover - disk already gone
                pass

    # -- merge -------------------------------------------------------------

    @property
    def wall0(self):
        """Wall-clock anchor of this tracer's t0 (the merge timebase)."""
        return self._wall0

    def merge_process_files(self, spill_dir=None, since_wall0=None):
        """Fold every sidecar file under ``spill_dir`` (default: this
        tracer's spill dir, else ``PETASTORM_TPU_TRACE_DIR``) into this
        tracer's timeline. Each file's events are shifted by its
        wall-clock anchor so worker tracks align with local spans; this
        tracer's own sidecar is skipped (its events are already in
        memory). Torn/corrupt lines (a worker killed mid-write) are
        skipped, not fatal. Returns the number of files merged.

        The directory is NOT run-scoped: sidecars from an earlier run
        left in the same directory merge too. Use a fresh directory per
        run (``tempfile.mkdtemp``), or pass ``since_wall0`` (e.g. this
        tracer's :attr:`wall0`, captured before the pipeline was built)
        to skip sidecar files whose anchor predates the run."""
        directory = spill_dir or self._spill_dir \
            or os.environ.get(TRACE_DIR_ENV)
        if not directory:
            raise ValueError('no spill directory: pass spill_dir or set '
                             '{}'.format(TRACE_DIR_ENV))
        own = self.spill_path
        merged_files = 0
        for path in sorted(glob.glob(os.path.join(directory, _SIDECAR_GLOB))):
            if own is not None and os.path.abspath(path) == os.path.abspath(own):
                continue
            header, events = read_sidecar_file(path)
            if header is None and not events:
                continue
            if since_wall0 is not None and header is not None \
                    and header.get('wall0', since_wall0) < since_wall0:
                continue        # a previous run's leftover sidecar
            offset_us = 0.0
            pid = None
            if header is not None:
                pid = header.get('pid')
                offset_us = (header.get('wall0', self._wall0)
                             - self._wall0) * 1e6
                if pid is not None and header.get('role'):
                    self._roles[pid] = header['role']
            adjusted = []
            for event in events:
                event = dict(event)
                event['ts'] = event.get('ts', 0.0) + offset_us
                if 'pid' not in event and pid is not None:
                    event['pid'] = pid
                adjusted.append(event)
            with self._lock:
                self._merged.extend(adjusted)
            merged_files += 1
        return merged_files

    # -- inspection / export -----------------------------------------------

    @property
    def events(self):
        """Chrome trace events: this tracer's records, then merged ones."""
        with self._lock:
            merged = list(self._merged)
        return [self._chrome(r) for r in self.records()] + merged

    def summary(self):
        """Per-span-name latency digest — the quick-look view that makes a
        trace useful without opening Perfetto::

            {name: {'count': n, 'total_s': t, 'p50_s': m, 'p99_s': p}}
        """
        durations = {}
        for e in self.events:
            if e.get('ph') == 'X':
                durations.setdefault(e['name'], []).append(
                    e.get('dur', 0.0) / 1e6)
        out = {}
        for name, values in sorted(durations.items()):
            values.sort()
            out[name] = {'count': len(values),
                         'total_s': round(sum(values), 4),
                         'p50_s': round(_percentile(values, 0.50), 6),
                         'p99_s': round(_percentile(values, 0.99), 6)}
        return out

    def export_chrome_trace(self, path):
        """Write the Chrome trace-event JSON (open in chrome://tracing).

        Atomic (tmp file + rename): a watchdog dumping a trace while the
        process crashes — or two dumps racing — can never leave a torn
        JSON at ``path``. Distinct pids get ``process_name`` metadata so
        merged multi-process timelines render labeled tracks."""
        events = self.events
        roles = dict(self._roles)
        roles.setdefault(self._pid, self.role)
        metadata = []
        for pid in sorted({e.get('pid') for e in events if 'pid' in e}):
            metadata.append({
                'name': 'process_name', 'ph': 'M', 'pid': pid,
                'args': {'name': '{} (pid {})'.format(
                    roles.get(pid, 'process'), pid)}})
        # pid alone is not unique enough: two threads exporting to the
        # same path (periodic export racing a watchdog dump) must not
        # share — and truncate — one tmp file.
        tmp = '{}.tmp.{}.{}'.format(path, os.getpid(), uuid.uuid4().hex[:8])
        with open(tmp, 'w') as f:
            json.dump({'traceEvents': metadata + events,
                       'displayTimeUnit': 'ms'}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path


def _percentile(sorted_values, q):
    """Nearest-rank percentile of an ascending list (empty -> 0)."""
    if not sorted_values:
        return 0.0
    index = min(len(sorted_values) - 1,
                max(0, int(round(q * (len(sorted_values) - 1)))))
    return sorted_values[index]


def read_sidecar_file(path):
    """``(header_or_None, [events])`` from one sidecar JSONL file.

    Torn trailing lines and corrupt lines (a worker SIGKILLed mid-write)
    are skipped — the file stays readable even if its writer died."""
    header = None
    events = []
    try:
        with open(path, 'r') as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    record = json.loads(line)
                except ValueError:
                    continue        # torn/corrupt line: skip, keep reading
                if not isinstance(record, dict):
                    continue
                if record.get(_HEADER_KEY):
                    header = record
                else:
                    events.append(record)
    except OSError:
        logger.warning('cannot read trace sidecar %r', path, exc_info=True)
    return header, events


def install_worker_tracer(role=None):
    """Worker-bootstrap hook: when ``PETASTORM_TPU_TRACE_DIR`` is set
    (inherited from the parent through the spawn environment), build a
    spilling tracer, install it as this process's global tracer, and
    return it (the bootstrap closes it on shutdown). With no directory
    set nobody could read this process's ring, so recording is switched
    off (the chunk's ``timings`` are still clocked) and ``None`` returned."""
    if not os.environ.get(TRACE_DIR_ENV):
        set_global_tracer(NullTracer())
        return None
    tracer = Tracer(role=role or 'worker-{}'.format(os.getpid()))
    set_global_tracer(tracer)
    return tracer


_global_tracer = None
_default_ring = None


def set_global_tracer(tracer):
    """Install the process-wide tracer: what :func:`get_global_tracer`
    returns, hence what every pipeline object built with ``tracer=None``
    and every worker-side site records to. ``NullTracer()`` switches
    recording off; ``None`` goes back to the default ring. Returns the
    previous setting (``None`` when the default ring was in use)."""
    global _global_tracer
    previous = _global_tracer
    _global_tracer = tracer
    return previous


def get_global_tracer():
    """The tracer installed by :func:`set_global_tracer`, else the
    process-wide default ring (:data:`DEFAULT_RING_EVENTS` records, never
    spilled), built on first use."""
    global _default_ring
    if _global_tracer is not None:
        return _global_tracer
    if _default_ring is None:
        _default_ring = Tracer(max_events=DEFAULT_RING_EVENTS,
                               spill_dir=False)
    return _default_ring


def resolve(tracer):
    """``tracer`` itself, or the global one for ``None``: what every
    ``tracer=None`` constructor argument means."""
    return get_global_tracer() if tracer is None else tracer


# -- compilations ----------------------------------------------------------------

# Of a compilation's stages only this one is recorded: tracing fires once a
# traced sub-function (thousands of records for one train step, enough to
# push a run's set-up out of the ring) and says nothing the last stage does
# not.
_JAX_BACKEND_COMPILE = '/jax/core/compile/backend_compile_duration'
_jax_watched = False


def _on_jax_duration(event, duration_secs, **kwargs):
    if event == _JAX_BACKEND_COMPILE:
        # The listener fires as the stage ends: it began that long ago.
        dur_ns = int(duration_secs * 1e9)
        get_global_tracer()._record(
            ('jax.compile', 'step', _perf_ns() - dur_ns, dur_ns, 0, _tid(),
             'backend_compile', kwargs.get('fun_name')))


def watch_jax_compiles():
    """Register, once a process, a ``jax.monitoring`` listener that writes
    a ``jax.compile`` span (``id``: ``backend_compile``; ``cause``: the
    function's name) to the global tracer for every program handed to the
    backend. That stage covers the persistent cache's lookup, so a program
    met for the first time shows even when the cache had it: in a training
    loop's timed window there should be none."""
    global _jax_watched
    if not _jax_watched:
        _jax_watched = True
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_jax_duration)


# -- the step's programs: what :meth:`Tracer.op_scopes` hands out -------------------


def _abstract(x):
    """What ``jit.lower`` needs of one argument and no more: never the array
    (a train step donates its state)."""
    shape, dtype = getattr(x, 'shape', None), getattr(x, 'dtype', None)
    if shape is None or dtype is None:
        return x
    import jax
    # An array nobody placed lowers with no sharding, as its call did.
    sharding = getattr(x, 'sharding', None) if getattr(
        x, 'committed', False) else None
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding,
                                weak_type=bool(getattr(x, 'weak_type', False)))


class StepProgram(object):
    """A train step's ``jax.jit`` object behind one Python frame: calls and
    attributes (``.lower``, ``.trace``, ...) go through, and when a call
    grew the jit's cache (a first call, a new signature) the call's abstract
    signature is kept here, beside the jit object it belongs to, and the
    global tracer is told (:meth:`Tracer.note_program`). :meth:`op_scopes`
    lowers the signatures again when somebody asks."""

    def __init__(self, jitted):
        self._jitted = jitted
        self._name = getattr(jitted, '__name__', 'step')
        # jax's own count of the programs a jit object holds (private, and
        # all there is that costs a call an integer compare); a jax without
        # it gets its first call's signature alone.
        self._cache_size = getattr(jitted, '_cache_size', lambda: 1)
        self._compiled = 0
        self._signatures = {}       # treedef and leaves -> program's name
        self._tables = {}           # program's name -> [signature, table]
        self.__wrapped__ = jitted

    def __call__(self, *args, **kwargs):
        out = self._jitted(*args, **kwargs)
        compiled = self._cache_size()
        if compiled != self._compiled:
            self._compiled = compiled
            self._note(args, kwargs)
        return out

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def _note(self, args, kwargs):
        import jax
        leaves = jax.tree_util.tree_leaves((args, kwargs))
        # A call under another trace is that trace's program, not one of
        # its own.
        if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
            return
        signature = jax.tree_util.tree_map(_abstract, (args, kwargs))
        flat, treedef = jax.tree_util.tree_flatten(signature)
        key = (treedef, tuple(flat))
        if key in self._signatures:
            return
        program = get_global_tracer().note_program(
            self, self._name, len(leaves),
            sum(getattr(leaf, 'nbytes', 0) for leaf in leaves))
        if program is not None:         # None: recording is switched off
            self._signatures[key] = program
            self._tables[program] = [signature, None]

    def op_scopes(self):
        """``{program: table}`` of this step's programs
        (:meth:`Tracer.op_scopes`), each parsed once."""
        import jax
        from petastorm_tpu.models.scopes import parse_hlo_scopes
        for known in self._tables.values():
            if known[1] is not None:
                continue
            if jax.config.jax_compilation_cache_dir and not \
                    jax.config.jax_compilation_cache_include_metadata_in_key:
                logger.warning(
                    'op_scopes: the persistent compilation cache is on and '
                    'does not key a program by its metadata: a step that '
                    'came back from it carries the scopes of the build that '
                    'cached it (petastorm_tpu.utils.enable_compile_cache '
                    'sets the key)')
            args, kwargs = known[0]
            known[1] = parse_hlo_scopes(self._jitted.lower(
                *args, **kwargs).compile().as_text())
        return {program: known[1] for program, known in self._tables.items()}


class _NullSpan(object):
    id = cause = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class NullTracer(Tracer):
    """Records nothing. A span that feeds a total or a histogram is still
    clocked (``loader.stats`` must not depend on tracing being on); one
    that feeds neither costs nothing."""

    _SPAN = _NullSpan()

    def __init__(self):
        super().__init__(max_events=0, spill_dir=False, role='off')

    def span(self, name, cat='pipeline', id=None, cause=None, hist=None,
             total=None, self_total=None):
        if hist is None and total is None and self_total is None:
            return self._SPAN
        return Span(None, name, cat, id, cause, hist, total, self_total)

    def instant(self, *args, **kwargs):
        pass

    counter = _record = note_program = instant

    def op_scopes(self):
        return None
