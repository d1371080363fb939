"""Single-threaded synchronous pool: work happens inside ``get_results()``.

Parity: reference ``petastorm/workers_pool/dummy_pool.py`` — used for
debugging, deterministic tests, and profiler-friendly in-main-thread
execution (``dummy_pool.py:24-25``).
"""

import threading
from collections import deque

from petastorm_tpu.workers import (EmptyResultError, RowGroupQuarantined,
                                   VentilatedItemProcessedMessage,
                                   deliver_quarantine, quarantine_record_for)


class DummyPool(object):
    #: Readers build the ventilator with ``inline=True`` for this pool: work
    #: happens on the consumer thread, so a feeder thread (and its GIL
    #: ping-pong with the consumer) would be pure overhead. ``get_results``
    #: pumps the ventilator itself.
    inline_ventilation = True

    def __init__(self, workers_count=None):
        self._results = deque()
        self._ventilated = deque()
        self._worker = None
        self._ventilator = None
        self._stopped = False
        # Serializes item processing (consumer thread) against worker
        # shutdown (often another thread, e.g. JaxLoader.stop() while its
        # staging thread is mid-decode): closing parquet file handles under
        # an in-flight read segfaults inside pyarrow.
        self._work_lock = threading.Lock()
        self._shutdown_done = False
        #: Set by the Reader when ``error_budget`` is enabled.
        self.quarantine_sink = None

    def start(self, worker_class, worker_args=None, ventilator=None):
        self._worker = worker_class(0, self._results.append, worker_args)
        self._worker.initialize()
        self._ventilator = ventilator
        if ventilator is not None:
            ventilator._ventilate_fn = self.ventilate
            ventilator.start()

    def ventilate(self, *args, **kwargs):
        self._ventilated.append((args, kwargs))

    def get_results(self):
        while True:
            if self._stopped and not self._results:
                # Stop requested from another thread: don't start decoding
                # further items whose file handles are about to be closed.
                raise EmptyResultError()
            while self._results:
                result = self._results.popleft()
                if isinstance(result, VentilatedItemProcessedMessage):
                    if self._ventilator is not None:
                        self._ventilator.processed_item()
                    continue
                if isinstance(result, RowGroupQuarantined):
                    if self._ventilator is not None:
                        self._ventilator.processed_item()
                    deliver_quarantine(self, result)
                    continue
                if isinstance(result, Exception):
                    raise result
                return result
            if not self._ventilated:
                if self._ventilator is None:
                    raise EmptyResultError()
                if getattr(self._ventilator, 'inline', False):
                    # Everything runs on this thread: pump the ventilator
                    # directly instead of waiting on a feeder thread.
                    if not self._ventilator.pump() and not self._ventilated:
                        if self._ventilator.completed() or self._stopped:
                            raise EmptyResultError()
                        raise RuntimeError(
                            'inline ventilator stalled: nothing ventilated, '
                            'nothing queued, not completed')
                # Read `completed` BEFORE re-checking the deque: once completed
                # is observed no further ventilation can occur, so a still-empty
                # deque really means end of data (no lost-item race).
                elif self._ventilator.completed():
                    if not self._ventilated and not self._results:
                        raise EmptyResultError()
                else:
                    continue
            if not self._ventilated:
                continue
            args, kwargs = self._ventilated.popleft()
            try:
                with self._work_lock:
                    if self._shutdown_done:
                        raise EmptyResultError()
                    self._worker.process(*args, **kwargs)
                self._results.append(VentilatedItemProcessedMessage())
            except EmptyResultError:
                raise
            except Exception as e:  # noqa: BLE001 - parity: exceptions surface to consumer
                record = quarantine_record_for(self._worker, e, args, kwargs)
                self._results.append(record if record is not None else e)

    def stop(self):
        self._stopped = True
        if self._ventilator is not None:
            self._ventilator.stop()
        # Worker shutdown (closes parquet handles) waits for any in-flight
        # process() call on the consuming thread — see _work_lock.
        self._shutdown_worker()

    def _shutdown_worker(self):
        if self._worker is None:
            return
        with self._work_lock:
            if not self._shutdown_done:
                self._shutdown_done = True
                self._worker.shutdown()

    def join(self):
        self._shutdown_worker()

    @property
    def diagnostics(self):
        return {'output_queue_size': len(self._results),
                'ventilation_queue_size': len(self._ventilated)}

    @property
    def results_qsize(self):
        return len(self._results)
