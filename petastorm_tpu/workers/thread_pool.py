"""In-process thread pool with ventilator feed and bounded results queue.

Parity: reference ``petastorm/workers_pool/thread_pool.py`` — per-worker
threads polling the ventilation queue (``thread_pool.py:61``), bounded
results queue with stop-aware put (``:200-214``), end-of-data detection
(queue empty AND all ventilated items processed AND ventilator completed,
``:155-160``), worker exceptions re-raised in the consumer (``:68-73``,
``:169-172``), and optional per-thread cProfile (``:48-49``, ``:190-198``).
"""

import pstats
import queue
import threading
from collections import deque

from petastorm_tpu.membudget import approx_nbytes, get_governor
from petastorm_tpu.trace import get_global_tracer
from petastorm_tpu.utils import drain_queue
from petastorm_tpu.workers import (EmptyResultError, RowGroupQuarantined,
                                   TimeoutWaitingForResultError,
                                   VentilatedItemProcessedMessage,
                                   deliver_quarantine, quarantine_record_for)

_DEFAULT_RESULTS_QUEUE_SIZE = 50
#: Ventilation-queue bound when no ventilator declares a window (manual
#: ventilate() callers): far above any real in-flight cap, but no longer
#: the one genuinely unbounded cross-thread channel in the package —
#: start() re-sizes it down to the ventilator's actual window.
_DEFAULT_VENTILATION_QUEUE_SIZE = 1024
_VENTILATION_POLL_TIMEOUT_S = 0.001
_RESULTS_POLL_TIMEOUT_S = 0.01
#: Ventilation-queue headroom over the worker count after a live resize
#: (mirrors the reader's workers + extra in-flight convention).
_RESIZE_VENT_SLACK = 4


class _WorkerTerminationRequested(Exception):
    pass


class WorkerThread(threading.Thread):
    def __init__(self, pool, worker, profiling_enabled=False):
        super().__init__(daemon=True,
                         name='pst-pool-worker-{}'.format(worker.worker_id))
        self._pool = pool
        self._worker = worker
        self._profiling_enabled = profiling_enabled
        self.profile = None

    def run(self):
        if self._profiling_enabled:
            import cProfile
            self.profile = cProfile.Profile()
            try:
                self.profile.enable()
            except ValueError:
                # Python 3.12 allows one active profiler per thread; another
                # tool (e.g. an outer profiler on a reused thread) wins —
                # degrade to unprofiled rather than kill the worker.
                self.profile = None
        try:
            self._worker.initialize()
            while True:
                item = self._take()
                if item is None:
                    return
                args, kwargs = item
                try:
                    self._worker.process(*args, **kwargs)
                    with get_global_tracer().span('reader.publish', 'reader'):
                        self._pool._put_result(
                            VentilatedItemProcessedMessage())
                except _WorkerTerminationRequested:
                    return
                except Exception as e:  # noqa: BLE001 - surfaces to consumer
                    record = quarantine_record_for(self._worker, e, args, kwargs)
                    self._pool._put_result(record if record is not None else e)
        except _WorkerTerminationRequested:
            return
        finally:
            if self._profiling_enabled and self.profile is not None:
                self.profile.disable()
            self._worker.shutdown()

    def _take(self):
        """The next ventilated item, or ``None`` once the pool stops or this
        worker retires. One ``reader.take`` span from entering the wait to
        leaving it, however many times the poll woke empty: the wake-ups
        are counted here and written once (``reader.vent_polls``), or ten
        idle workers would put 10,000 records a second into the ring. The
        instant before it carries this thread's CPU clock, so CPU burnt
        under no span shows too."""
        pool = self._pool
        tracer = get_global_tracer()
        tracer.instant('reader.thread_cpu', 'reader')
        polls = 0
        with tracer.span('reader.take', 'reader'):
            # Retire check sits BETWEEN items only: a worker that has
            # already popped a ventilated item always processes it, so
            # a shrinking resize() can never drop work on the floor.
            while not pool._stop_event.is_set() \
                    and not pool._should_retire(self):
                try:
                    item = pool._ventilator_queue.get(
                        timeout=_VENTILATION_POLL_TIMEOUT_S)
                    break
                except queue.Empty:
                    polls += 1
            else:
                item = None
        if polls:
            tracer.counter('reader.vent_polls', polls, 'reader')
        return item


class ThreadPool(object):
    def __init__(self, workers_count, results_queue_size=_DEFAULT_RESULTS_QUEUE_SIZE,
                 profiling_enabled=False):
        self._workers_count = workers_count
        self._results_queue = queue.Queue(maxsize=results_queue_size)
        self._ventilator_queue = queue.Queue(
            maxsize=_DEFAULT_VENTILATION_QUEUE_SIZE)
        self._stop_event = threading.Event()
        self._workers = []
        self._retired_workers = []
        self._ventilator = None
        self._profiling_enabled = profiling_enabled
        self._ventilated_unprocessed = 0
        self._count_lock = threading.Lock()
        # Live-resize state (autotune.py): the target count may differ from
        # len(_workers) while retire requests are pending.
        self._resize_lock = threading.Lock()
        self._retire_requests = 0
        self._next_worker_id = workers_count
        self._worker_class = None
        self._worker_args = None
        # Consumer-local drain buffer: get_results() moves every already-
        # ready result here under ONE queue-mutex acquisition instead of
        # paying a lock round trip per pop (warm from a cache, a chunk
        # costs little but its pop). Touched only by the consumer thread.
        self._pending_results = deque()
        #: Ventilator backpressure watermark: when set, the ventilator
        #: stops feeding new row-groups while the results queue holds this
        #: many items (bounding peak queue depth / decoded-block memory
        #: instead of racing ahead of a slow consumer). ``None`` = off.
        self.results_watermark = None
        self._results_peak = 0
        #: Set by the Reader when ``error_budget`` is enabled; receives
        #: RowGroupQuarantined records (and raises when the budget is spent).
        self.quarantine_sink = None
        #: Optional health.Heartbeat (set by ``Reader.attach_health``).
        self.health_heartbeat = None
        #: EMA of one published result's bytes (written by worker threads,
        #: racy float rebinds tolerated — it feeds an *estimate*): the
        #: memory governor's results-queue accounting is depth x this.
        self.result_nbytes_ema = 0.0
        #: Optional ``decode_budget.PoolShare`` (set by the Reader): this
        #: pool's registered stake in the process-wide native decode-
        #: thread budget. ``resize()`` re-divides it so every worker's
        #: next decode call sees the new fair share.
        self.decode_share = None

    @property
    def workers_count(self):
        return self._workers_count

    def start(self, worker_class, worker_args=None, ventilator=None):
        if self._workers:
            raise RuntimeError('ThreadPool already started')
        self._worker_class = worker_class
        self._worker_args = worker_args
        for worker_id in range(self._workers_count):
            self._spawn_worker(worker_id)
        self._ventilator = ventilator
        if ventilator is not None:
            # Size the ventilation queue from the ventilator's in-flight
            # window: the feeder caps outstanding items (queued + being
            # processed) at the window, so the queue can never legitimately
            # hold more — a tight bound that makes queued decode work a
            # *visible*, bounded quantity instead of an open-ended pile.
            # Rebuilt here (before ventilator.start(), so it is empty):
            # the window isn't known at construction. set_max_in_flight may
            # later raise the cap past this bound — ventilate()'s
            # stop-aware put then briefly backpressures the feeder instead
            # of deadlocking shutdown.
            window = getattr(ventilator, '_max_ventilation_queue_size', None)
            if window:
                self._ventilator_queue = queue.Queue(maxsize=max(1, int(window)))
            ventilator._ventilate_fn = self.ventilate
            if getattr(ventilator, 'backpressure_fn', None) is None:
                ventilator.backpressure_fn = self._results_backpressure
            ventilator.start()

    def _spawn_worker(self, worker_id):
        worker = self._worker_class(worker_id, self._put_result,
                                    self._worker_args)
        thread = WorkerThread(self, worker, self._profiling_enabled)
        with self._count_lock:
            self._workers.append(thread)
        thread.start()

    def resize(self, n):
        """Grow or shrink the live worker count to ``n`` (autotune hookup).

        Growing spawns fresh workers immediately; shrinking posts retire
        requests that workers honor **between** items — each request
        retires exactly one worker, and a worker that already popped work
        always finishes it first, so no ventilated item is ever lost or
        double-processed. Returns the new target count."""
        n = int(n)
        if n < 1:
            raise ValueError('workers_count must be >= 1, got {}'.format(n))
        with self._resize_lock:
            if self._worker_class is None:
                raise RuntimeError('ThreadPool.resize() requires a started pool')
            if self._stop_event.is_set():
                return self._workers_count
            with self._count_lock:
                delta = n - self._workers_count
                if delta == 0:
                    return n
                if delta < 0:
                    self._retire_requests += -delta
                    self._workers_count = n
                    if self.decode_share is not None:
                        # Shrinks widen the survivors' fair share on
                        # their next decode call.
                        self.decode_share.resize(n)
                    return n
                # Growing: outstanding retire requests are cancelled first —
                # resurrecting a not-yet-retired worker is cheaper than a
                # retire/spawn churn pair.
                cancelled = min(self._retire_requests, delta)
                self._retire_requests -= cancelled
                spawn = delta - cancelled
                self._workers_count = n
                worker_id = self._next_worker_id
                self._next_worker_id += spawn
            for i in range(spawn):
                self._spawn_worker(worker_id + i)
            # Grow the ventilation-queue bound with the pool: the reader's
            # resize hook raises the ventilator's in-flight cap to track
            # the worker count, and a queue still sized for the old window
            # would quietly re-backpressure the feeder to the old width.
            vent_queue = self._ventilator_queue
            with vent_queue.mutex:
                if vent_queue.maxsize and n + _RESIZE_VENT_SLACK > vent_queue.maxsize:
                    vent_queue.maxsize = n + _RESIZE_VENT_SLACK
                    vent_queue.not_full.notify_all()
            if self.decode_share is not None:
                # Re-divide the process decode-thread budget: N workers
                # each took total//old_n native threads per batch call;
                # the next call fair-shares against the new count.
                self.decode_share.resize(n)
            return n

    def _should_retire(self, thread):
        """Exactly-once retire claim (called by worker threads between
        items): consumes one pending retire request, moving the thread to
        the retired list so join() still reaps it."""
        if self._retire_requests <= 0:   # lock-free fast path: this check
            return False                 # runs every ventilation poll
        with self._count_lock:
            if self._retire_requests <= 0:
                return False
            self._retire_requests -= 1
            try:
                self._workers.remove(thread)
            except ValueError:  # pragma: no cover - stop/retire race
                pass
            self._retired_workers.append(thread)
            return True

    def _results_backpressure(self):
        """Ventilator saturation signal. ``None`` while no watermark is set
        (the signal is unarmed: the ventilator keeps its plain bursty
        feeding); with a watermark, True while undelivered results sit
        at/over it. Counts the consumer's drain buffer too — the bulk pop
        moves the whole queue there, and a watermark blind to it would
        release the moment the consumer took one result, while the full
        backlog still sits in memory."""
        watermark = self.results_watermark
        if watermark is None:
            return None
        return (self._results_queue.qsize()
                + len(self._pending_results)) >= watermark

    def ventilate(self, *args, **kwargs):
        with self._count_lock:
            self._ventilated_unprocessed += 1
        # Stop-aware bounded put (mirrors _put_result): the ventilation
        # queue is bounded now, and the feeder thread must never wedge
        # stop()/join() by blocking into a pool that is shutting down. An
        # item dropped at stop time must also retract its in-flight count
        # — _all_done() requires the counter to reach zero, and a leaked
        # +1 would spin a concurrently-stopping consumer forever.
        while True:
            if self._stop_event.is_set():
                with self._count_lock:
                    self._ventilated_unprocessed -= 1
                return
            try:
                self._ventilator_queue.put((args, kwargs),
                                           timeout=_RESULTS_POLL_TIMEOUT_S)
                return
            except queue.Full:
                continue

    def _put_result(self, data):
        # Stop-aware bounded put (parity: thread_pool.py:200-214): never block
        # forever on a full queue if the pool is being stopped.
        from petastorm_tpu.faults import maybe_inject
        maybe_inject('queue-stall')
        if not isinstance(data, VentilatedItemProcessedMessage):
            # Weighed only while a governor is armed: the size walk is
            # cheap but non-zero, and pipelines that never opt in must not
            # pay it per published chunk.
            if get_governor().armed:
                self.result_nbytes_ema += 0.25 * (approx_nbytes(data)
                                                  - self.result_nbytes_ema)
        retries = 0
        while True:
            if self._stop_event.is_set():
                raise _WorkerTerminationRequested()
            try:
                self._results_queue.put(data, timeout=_RESULTS_POLL_TIMEOUT_S)
            except queue.Full:
                retries += 1
                continue
            if retries:     # written once, by the put that got through
                get_global_tracer().counter('reader.publish_retries',
                                            retries, 'reader')
            depth = (self._results_queue.qsize()
                     + len(self._pending_results))
            if depth > self._results_peak:   # racy double-check is fine: a
                with self._count_lock:       # lost update costs one sample
                    if depth > self._results_peak:
                        self._results_peak = depth
            return

    def inject_consumer_error(self, exc):
        """Watchdog delivery path: surface ``exc`` to a consumer parked in
        :meth:`get_results` (whose default timeout is unbounded). Unlike a
        worker exception, an injected error does NOT stop/join the pool —
        the very point is that a worker may be wedged and unjoinable; the
        caller owns teardown."""
        self._injected_error = exc

    _injected_error = None

    def _pop_result(self):
        """One result off the consumer-local drain buffer, refilled from
        the results queue in bulk: a single mutex acquisition moves a
        batch of already-ready items over (vs one lock round trip per
        pop), and producers blocked on the bounded put wake immediately
        for the freed capacity. The batch is capped at a quarter of the
        queue's capacity: every drained slot is capacity the workers
        refill, so an uncapped drain would let undelivered results reach
        ~2x the configured queue bound — the cap keeps the overshoot
        small while still amortizing the mutex. Raises ``queue.Empty`` on
        a dry poll."""
        if self._pending_results:
            return self._pending_results.popleft()
        result = self._results_queue.get(timeout=_RESULTS_POLL_TIMEOUT_S)
        drain_queue(self._results_queue, self._pending_results,
                    self._results_queue.maxsize // 4)
        return result

    def get_results(self, timeout=None):
        import time
        deadline = time.monotonic() + timeout if timeout is not None else None
        while True:
            if (self._injected_error is not None
                    and not self._pending_results
                    and self._results_queue.empty()):
                # Still no results: the diagnosed stall stands. (With
                # results available the pipeline recovered — deliver them
                # and drop the stale injection below.)
                error, self._injected_error = self._injected_error, None
                raise error
            if self.health_heartbeat is not None:
                self.health_heartbeat.beat('poll')
            try:
                result = self._pop_result()
            except queue.Empty:
                if self._all_done():
                    raise EmptyResultError()
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutWaitingForResultError()
                continue
            if isinstance(result, VentilatedItemProcessedMessage):
                with self._count_lock:
                    self._ventilated_unprocessed -= 1
                if self._ventilator is not None:
                    self._ventilator.processed_item()
                continue
            if isinstance(result, RowGroupQuarantined):
                # Quarantine counts as item-processed (the row-group is
                # skipped, not retried); the sink enforces the budget.
                with self._count_lock:
                    self._ventilated_unprocessed -= 1
                if self._ventilator is not None:
                    self._ventilator.processed_item()
                try:
                    deliver_quarantine(self, result)
                except Exception:
                    self.stop()
                    self.join()
                    raise
                continue
            if isinstance(result, Exception):
                self.stop()
                self.join()
                raise result
            self._injected_error = None   # results flow again: recovered
            return result

    def _all_done(self):
        # Order matters: observe `completed` FIRST. After it is set no further
        # ventilation can occur, so the subsequent counter/queue reads cannot
        # miss in-flight items (they only drain monotonically).
        ventilator_done = self._ventilator is None or self._ventilator.completed()
        if not ventilator_done:
            return False
        with self._count_lock:
            nothing_in_flight = self._ventilated_unprocessed == 0
        return (nothing_in_flight and not self._pending_results
                and self._results_queue.empty() and self._ventilator_queue.empty())

    def stop(self):
        if self._ventilator is not None:
            self._ventilator.stop()
        self._stop_event.set()

    def join(self):
        # The resize lock orders this snapshot after any in-flight
        # resize(): a grow that passed its stop check concurrently with
        # stop()/join() finishes spawning first, so its workers are in the
        # snapshot and get reaped — join() must never leave a thread
        # running against a store the owner is about to close.
        with self._resize_lock:
            with self._count_lock:
                threads = list(self._workers) + list(self._retired_workers)
        for thread in threads:
            thread.join()
        if self._profiling_enabled:
            self._print_profiles()
        self._workers = []
        self._retired_workers = []

    def _print_profiles(self):
        # A worker that never got ventilated work has an empty profile, which
        # pstats.Stats() rejects with TypeError — skip those.
        profiles = [t.profile for t in self._workers + self._retired_workers
                    if t.profile is not None and t.profile.getstats()]
        if not profiles:
            return
        stats = None
        for profile in profiles:
            if stats is None:
                stats = pstats.Stats(profile)
            else:
                stats.add(profile)
        if stats is not None:
            stats.sort_stats('cumulative').print_stats(30)

    @property
    def diagnostics(self):
        with self._count_lock:
            live = sum(1 for t in self._workers if t.is_alive())
        return {'output_queue_size': (self._results_queue.qsize()
                                      + len(self._pending_results)),
                'ventilation_queue_size': self._ventilator_queue.qsize(),
                'ventilated_unprocessed': self._ventilated_unprocessed,
                'workers_count': self._workers_count,
                'live_worker_threads': live,
                'results_queue_peak': self._results_peak,
                'results_watermark': self.results_watermark}

    @property
    def results_qsize(self):
        return self._results_queue.qsize() + len(self._pending_results)

    @property
    def results_capacity(self):
        return self._results_queue.maxsize

    def results_nbytes(self):
        """Estimated decoded bytes parked in the results queue (+ the
        consumer's drain buffer): depth x the published-result size EMA —
        the memory governor's ``results-queue`` accounting hook."""
        return int(self.results_qsize * self.result_nbytes_ema)
