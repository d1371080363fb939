"""Ventilator: backpressure-controlled work feeder.

Parity: reference ``petastorm/workers_pool/ventilator.py`` —
``Ventilator`` ABC (``:26-52``) and ``ConcurrentVentilator`` (``:55-166``):
runs on its own daemon thread, caps in-flight items at
``max_ventilation_queue_size``, optionally reshuffles item order every epoch,
``iterations=None`` means infinite epochs, and exposes the
``processed_item()`` / ``completed()`` / ``reset()`` protocol.

TPU-first improvement: shuffling is **seeded and reproducible**
(``random_seed``), unlike the reference's unseeded ``random.shuffle``
(``ventilator.py:143-144``) — determinism across pod hosts matters for
synchronized input pipelines (SURVEY.md §7 "Determinism across hosts").

Deterministic mode (``deterministic=`` dict, armed by ``Reader`` when built
with ``deterministic=True``) goes further: the stateful ``random.Random``
epoch shuffle is replaced by the counter-based Feistel permutation of
``petastorm_tpu.determinism`` keyed by ``(seed, epoch)`` — epoch order is a
pure function of scalars, so any process recomputes it and resume
*fast-forwards* to a cursor position instead of replaying RNG history. Each
fed item additionally carries a ``pst_det`` tag (host-local ``seq`` for the
consumer-side resequencer, absolute ``epoch`` and global ``pos`` for the
stream cursor), and ``cur_shard``/``shard_count`` is applied here as a
stride over the *global* order — the reshard-invariance mechanism (see the
``determinism`` module docstring).
"""

import hashlib
import random
import threading

from petastorm_tpu import determinism


class Ventilator(object):
    def __init__(self, ventilate_fn):
        self._ventilate_fn = ventilate_fn

    def start(self):
        raise NotImplementedError

    def processed_item(self):
        raise NotImplementedError

    def completed(self):
        raise NotImplementedError

    def reset(self):
        raise NotImplementedError

    def stop(self):
        raise NotImplementedError


class ConcurrentVentilator(Ventilator):
    def __init__(self, ventilate_fn, items_to_ventilate,
                 iterations=1, randomize_item_order=False,
                 random_seed=None,
                 max_ventilation_queue_size=None,
                 ventilation_interval=0.01,
                 inline=False,
                 backpressure_fn=None,
                 deterministic=None):
        """
        :param ventilate_fn: called with ``**item`` for each ventilated item.
        :param items_to_ventilate: list of dicts of kwargs.
        :param iterations: number of epochs; ``None`` = infinite.
        :param randomize_item_order: reshuffle before each epoch.
        :param random_seed: seed for reproducible shuffling (``None`` = os random).
        :param max_ventilation_queue_size: cap on unprocessed in-flight items;
            defaults to ``len(items_to_ventilate)``.
        :param backpressure_fn: optional saturation signal ``() -> None |
            bool``: ``None`` = unarmed (plain bursty feeding), ``True`` =
            hold ventilation even below the in-flight cap, ``False`` =
            armed but clear — feeding proceeds *paced* (one item per
            ``ventilation_interval`` or per ``processed_item()`` ack), so
            the signal gets to see each fed item's results land before the
            next feed; an unpaced burst would fill the whole in-flight
            window before any watermark could react. The worker pools wire
            this to a results-queue watermark so a saturated downstream
            stops new row-groups from being fed (bounding decoded-block
            memory and tail latency). Assignable after construction.
        :param deterministic: ``None`` (default, classic seeded shuffle) or
            a dict ``{'seed', 'cur_shard', 'shard_count', 'start_epoch',
            'start_pos'}`` arming seed-stable deterministic feeding: epoch
            order comes from the counter-based Feistel permutation
            (``determinism.epoch_order``), sharding is a stride over the
            global order, ``start_epoch``/``start_pos`` fast-forward to a
            resume cursor, and every fed item gains a ``pst_det`` tag
            (``seq``/``epoch``/``pos``) the workers echo on published
            chunks for the consumer-side resequencer.
        :param inline: no ventilation thread — the consumer drives
            ventilation by calling :meth:`pump` (synchronous pools). A
            ventilator thread next to an inline pool is pure overhead: the
            feeder thread and the consumer only hand the GIL back and
            forth (no chip record bears on it; the pool does its work on
            the consumer's thread either way).
        """
        if iterations is not None and iterations <= 0:
            raise ValueError('iterations must be positive or None, got {}'.format(iterations))
        super().__init__(ventilate_fn)
        self._items_to_ventilate = list(items_to_ventilate)
        self._iterations = iterations
        self._iterations_remaining = iterations
        self._randomize_item_order = randomize_item_order
        self._rng = random.Random(random_seed)
        self._max_ventilation_queue_size = (max_ventilation_queue_size
                                            if max_ventilation_queue_size is not None
                                            else len(self._items_to_ventilate))
        self._ventilation_interval = ventilation_interval
        self.inline = inline
        self.backpressure_fn = backpressure_fn

        # Deterministic mode (petastorm_tpu.determinism): epoch order is
        # the counter-based Feistel permutation, sharding is a stride over
        # the global order, and every fed item carries a pst_det tag.
        self._det = dict(deterministic) if deterministic is not None else None
        self._det_epoch = 0          # absolute epoch being fed (1-based)
        self._det_order = None       # epoch_order(...) of the current epoch
        self._det_positions = None   # this shard's global positions
        self._det_epoch_base = 0     # resume base of the current epoch
        self._det_phase = 0          # round-robin offset from earlier epochs
        self._det_seq = 0            # host-local seq (resequencer ordering)

        self._current_item_to_ventilate = 0
        self._in_flight = 0
        self._in_flight_lock = threading.Lock()
        # Batch provenance (petastorm_tpu.lineage): which epoch is being
        # fed and a digest of THIS epoch's item order — what pins "what
        # the shuffle chose" into ledgered batch records. epochs_started
        # counts feed epochs (1-based once start() ran).
        self.epochs_started = 0
        self._epoch_order_digest = None
        self._ventilation_thread = None
        self._started = False
        self._stop_event = threading.Event()
        self._wakeup = threading.Event()
        self._completed_flag = threading.Event()
        #: Optional :class:`petastorm_tpu.health.Heartbeat` (set by
        #: ``Reader.attach_health``): beaten every feeder-loop iteration so
        #: the watchdog can prove the ventilation thread itself is alive
        #: (state 'ventilating' / 'backpressure' / 'idle' once done).
        self.heartbeat = None
        #: Optional observer ``(item_dict) -> None`` called just before an
        #: item is fed to the pool — i.e. in exact dispatch order,
        #: ``max_ventilation_queue_size`` items ahead of the workers. The
        #: reader wires the NVMe chunk store's madvise/WILLNEED readahead
        #: here so the next scheduled row-group's extents are page-cache
        #: resident before a worker touches them. Must be cheap and must
        #: not raise (exceptions are swallowed: advice, not work).
        self.on_ventilate = None

    def start(self):
        if self._started:
            raise RuntimeError('Ventilator already started')
        self._started = True
        if not self._items_to_ventilate or (self._iterations is not None and self._iterations == 0):
            self._completed_flag.set()
            return
        if self._det is not None:
            if not self._det_start():
                # The resume cursor already sits past the final epoch.
                self._completed_flag.set()
                return
        elif self._randomize_item_order:
            self._rng.shuffle(self._items_to_ventilate)
        self._on_epoch_order()
        if self.inline:
            return
        self._ventilation_thread = threading.Thread(target=self._ventilate, daemon=True,
                                                    name='pst-ventilator')
        self._ventilation_thread.start()

    def _det_start(self):
        """Position the deterministic feed at the resume cursor. False
        when the cursor's epoch already exhausted a finite iteration
        budget (nothing left to feed)."""
        det = self._det
        start_epoch = max(1, int(det.get('start_epoch') or 1))
        if self._iterations is not None:
            self._iterations_remaining = self._iterations - (start_epoch - 1)
            if self._iterations_remaining <= 0:
                return False
        self._det_seq = 0
        self._det_epoch_setup(start_epoch, int(det.get('start_pos') or 0),
                              phase=0)
        return True

    def _det_epoch_setup(self, epoch, base, phase):
        """Fix one epoch's deterministic feed plan: the full permuted
        order (recomputed from scalars — O(items), comparable to the
        classic mode's Fisher-Yates shuffle) and this shard's stride
        positions over it. ``phase`` carries the round-robin offset
        accumulated by earlier epochs (see ``determinism.shard_positions``)
        so host assignment stays continuous across epoch rolls."""
        det = self._det
        n = len(self._items_to_ventilate)
        self._det_epoch = epoch
        self._det_epoch_base = base
        self._det_phase = phase
        self._det_order = determinism.epoch_order(
            n, det.get('seed'), epoch, shuffle=det.get('shuffle', True))
        self._det_positions = determinism.shard_positions(
            n, base, det.get('cur_shard') or 0, det.get('shard_count') or 1,
            phase=phase)

    def _epoch_items(self):
        """How many items this feeder ventilates in the current epoch."""
        return (len(self._det_positions) if self._det is not None
                else len(self._items_to_ventilate))

    def _next_item(self):
        """The next item to feed (advancing the epoch position). In
        deterministic mode the canonical item is resolved through the
        epoch permutation and tagged with its ``pst_det`` identity."""
        i = self._current_item_to_ventilate
        self._current_item_to_ventilate += 1
        if self._det is None:
            return self._items_to_ventilate[i]
        pos = self._det_positions[i]
        item = dict(self._items_to_ventilate[self._det_order[pos]])
        item['pst_det'] = {'seq': self._det_seq,
                           'epoch': self._det_epoch,
                           'pos': pos}
        self._det_seq += 1
        return item

    def _advance_epoch(self):
        """At the end of an item list, roll to the next epoch (reshuffling)
        or mark completion. Returns False when all iterations are done.
        A ``while`` (not ``if``): a deterministic shard whose stride got
        no positions in the resume epoch (cursor near the epoch's end)
        rolls straight through to the next epoch."""
        while self._current_item_to_ventilate >= self._epoch_items():
            if self._iterations_remaining is not None:
                self._iterations_remaining -= 1
                if self._iterations_remaining <= 0:
                    self._completed_flag.set()
                    return False
            self._current_item_to_ventilate = 0
            if self._det is not None:
                # Advance the stride phase by the positions ALL hosts fed
                # in the finished epoch, keeping the global round-robin
                # continuous across the roll (an epoch length that is not
                # a multiple of shard_count would otherwise desync hosts).
                n = len(self._items_to_ventilate)
                shard_count = self._det.get('shard_count') or 1
                phase = (self._det_phase
                         + n - self._det_epoch_base) % shard_count
                self._det_epoch_setup(self._det_epoch + 1, 0, phase)
            elif self._randomize_item_order:
                self._rng.shuffle(self._items_to_ventilate)
            self._on_epoch_order()
        return True

    def _on_epoch_order(self):
        """A new epoch's item order is fixed: bump the epoch counter and
        invalidate the order-digest memo. The digest itself (by each
        item's JSON-safe identity keys — what lets the provenance ledger
        prove two runs claiming the same seed fed identically) is O(items)
        and only ever read by lineage probes, so it is computed lazily on
        first probe rather than stalling every epoch roll for pipelines
        that never arm lineage."""
        if self._det is not None:
            # Deterministic epochs are absolute (resume fast-forwards past
            # prior sessions' epochs without replaying them).
            self.epochs_started = self._det_epoch
        else:
            self.epochs_started += 1
        self._epoch_order_digest = None

    def lineage_state(self):
        """``{'epoch', 'order_digest', 'position'}`` — the live shuffle
        state stamped into provenance records (advisory near epoch rolls:
        a multi-worker pool interleaves chunks across the boundary, and a
        roll may invalidate the memo mid-probe)."""
        epoch = self.epochs_started
        memo = self._epoch_order_digest
        if memo is None or memo[0] != epoch:
            if self._det is not None:
                # The fed order is the epoch permutation, not the list
                # order — digest what actually feeds, so two hosts of one
                # deterministic job (and a resumed session) agree.
                value = determinism.order_digest(self._items_to_ventilate,
                                                 self._det_order)
            else:
                digest = hashlib.md5()
                for index, item in enumerate(self._items_to_ventilate):
                    identity = (item.get('piece_index', index),
                                item.get('shuffle_row_drop_partition')) \
                        if isinstance(item, dict) else index
                    digest.update(repr(identity).encode())
                value = digest.hexdigest()[:12]
            memo = (epoch, value)
            self._epoch_order_digest = memo
        return {'epoch': epoch,
                'order_digest': memo[1],
                'position': self._current_item_to_ventilate}

    def _backpressured(self):
        """Tri-state sample of the saturation signal: ``None`` = no signal
        armed (no fn, fn says unarmed, or fn died), ``False`` = armed but
        clear, ``True`` = hold ventilation. Armed-but-clear still matters:
        it selects paced feeding (see ``_ventilate``)."""
        fn = self.backpressure_fn
        if fn is None:
            return None
        try:
            value = fn()
        except Exception:  # noqa: BLE001 - a dying probe must not stop feeding
            return None
        return None if value is None else bool(value)

    def pump(self):
        """Inline mode: ventilate items up to the backpressure cap from the
        CALLING thread. Returns the number of items ventilated."""
        assert self.inline, 'pump() is for inline ventilators'
        pumped = 0
        while (not self._stop_event.is_set()
               and not self._completed_flag.is_set()):
            if self.heartbeat is not None:
                self.heartbeat.beat('ventilating')
            if self._in_flight >= self._max_ventilation_queue_size:
                break
            if self._backpressured():
                break
            if not self._advance_epoch():
                break
            item = self._next_item()
            self._in_flight += 1   # single-threaded: no lock needed
            self._observe(item)
            self._ventilate_fn(**item)
            pumped += 1
        return pumped

    def _observe(self, item):
        observer = self.on_ventilate
        if observer is not None:
            try:
                observer(item)
            except Exception:  # noqa: BLE001 - advisory hook must not stop feeding
                pass

    def _ventilate(self):
        while not self._stop_event.is_set():
            heartbeat = self.heartbeat
            if not self._advance_epoch():
                if heartbeat is not None:
                    heartbeat.beat('idle')   # all epochs fed: quiet != stalled
                return
            with self._in_flight_lock:
                below_cap = self._in_flight < self._max_ventilation_queue_size
            backpressure = self._backpressured() if below_cap else None
            if below_cap and not backpressure:
                if heartbeat is not None:
                    heartbeat.beat('ventilating')
                item = self._next_item()
                with self._in_flight_lock:
                    self._in_flight += 1
                self._observe(item)
                self._ventilate_fn(**item)
                if backpressure is not None:
                    # Paced feeding while a saturation signal is ARMED
                    # (even when currently clear): the just-fed item's
                    # results haven't landed yet, so an unpaced loop would
                    # fill the whole in-flight window before the signal
                    # could react — a cap-sized result burst the watermark
                    # exists to prevent. One item per interval, or per
                    # consumer ack (processed_item() sets the wakeup),
                    # whichever comes sooner.
                    self._wakeup.clear()
                    self._wakeup.wait(self._ventilation_interval)
            else:
                if heartbeat is not None:
                    heartbeat.beat('backpressure')
                self._wakeup.wait(self._ventilation_interval)
                self._wakeup.clear()

    def processed_item(self):
        with self._in_flight_lock:
            self._in_flight = max(0, self._in_flight - 1)
        self._wakeup.set()

    def set_max_in_flight(self, n):
        """Retarget the in-flight cap at runtime (autotune hookup: the cap
        tracks the resized worker count). A raised cap wakes a parked
        feeder immediately; a lowered one simply stops new ventilation
        until in-flight items drain below it."""
        self._max_ventilation_queue_size = max(1, int(n))
        self._wakeup.set()

    def completed(self):
        return self._completed_flag.is_set()

    def reset(self):
        """Restart ventilation for another round of `iterations` epochs.

        Parity: reference ``ventilator.py:118-134`` (used by ``Reader.reset()``).
        """
        if self._ventilation_thread is not None:
            if self._completed_flag.is_set():
                # Completed but possibly still in final teardown — wait it out
                # rather than spuriously refusing the reset.
                self._ventilation_thread.join()
            elif self._ventilation_thread.is_alive():
                raise RuntimeError('Cannot reset a ventilator that is still ventilating')
        elif self._started and self.inline and not self._completed_flag.is_set():
            raise RuntimeError('Cannot reset a ventilator that is still ventilating')
        self._ventilation_thread = None
        self._started = False
        self._iterations_remaining = self._iterations
        self._current_item_to_ventilate = 0
        if self._det is not None:
            # A reset is a fresh round: the resume cursor was consumed by
            # the first start. Re-applying it here would replay only the
            # prior session's tail (and nothing at all for a cursor
            # normalized past the final epoch) instead of `iterations`
            # full epochs, unlike a default-mode reset.
            self._det['start_epoch'] = 1
            self._det['start_pos'] = 0
        with self._in_flight_lock:
            self._in_flight = 0
        self._completed_flag.clear()
        self._stop_event.clear()
        self.start()

    def stop(self):
        self._stop_event.set()
        self._wakeup.set()
        if self._ventilation_thread is not None:
            self._ventilation_thread.join()
            self._ventilation_thread = None
