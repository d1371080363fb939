"""Host memory governor: unified byte accounting + cgroup-aware pressure
ladder + OOM-proof graceful degradation.

The pipeline owns ~10 independent byte-holding pools — host arenas, the
loader prefetch queue, the staging in-flight window, the NVMe chunk
store's write-behind queue and open mmaps, the lineage writer queue, the
shuffling buffer, the deterministic resequencer's reorder buffer,
``MemoryCache``, the data-service snapshot ring — each bounded in
*items*, none (before this module) bounded in *bytes*, with no shared
budget. Every failure mode of PRs 1/3/10 is recoverable except the one
that actually kills production trainers: the kernel OOM killer, which
SIGKILLs the process with zero diagnosis (the blind spot the tf.data
service autoscaling literature calls out, arXiv:2210.14826; MinatoLoader
frames the same host-memory-vs-throughput tradeoff, arXiv:2509.10712).

:class:`MemoryGovernor` closes the gap:

* every byte-holding subsystem registers an **accountable pool** — a
  ``(name, nbytes_fn, degrade_fn, shed_fn, advisory_fn)`` handle — at
  construction (registration is a dict insert; unarmed it costs nothing);
* the **budget** resolves from ``PETASTORM_TPU_HOST_MEM_BUDGET`` (bytes,
  ``k``/``m``/``g``/``t`` suffixes, or ``auto``), else auto-detects the
  cgroup v2 ``memory.max`` / v1 ``limit_in_bytes`` container limit minus
  headroom, else falls back to a fraction of ``MemTotal``;
* a sampler thread (``pst-mem-governor``, registered in the leak-guard
  registry) sums the pools each tick and walks the **pressure ladder**:

  ========== ============== ==================================================
  state      trigger        actions
  ========== ============== ==================================================
  ok         < 70% budget   none
  advisory   >= 70%         autotuner biases knobs down (one ``mem-shrink``
                            step per cooldown: prefetch / inflight /
                            arena-depth / workers / watermark); chunk-store
                            spill paused
  degrade    >= 85%         per-tick degrade hooks: evict ``MemoryCache``,
                            close LRU chunk-store mmaps, shed lineage ledger
                            records (counted, never silent), halve the
                            shuffling buffer (non-deterministic pipelines
                            only)
  shed       >= 92%         pace ventilation (tight results watermark),
                            data-service servers refuse **new** consumers
                            with the PR-10 typed refusal
  breach     >= 100%        flight-recorder dump ranking pools by bytes, then
                            a typed :class:`~petastorm_tpu.errors.
                            HostMemoryExceededError` delivered to the
                            consumer — the process dies WITH a diagnosis,
                            before the kernel kills it without one
  ========== ============== ==================================================

* the watchdog (``health.py``) classifies stalls under pressure as
  ``memory-pressure`` (soft-only: the governor owns the hard path);
* the ``mem-pressure`` fault site (``faults.py``) inflates a registered
  pool's reported bytes (``match=`` targets a pool by substring,
  ``bytes=`` sets the inflation) so every ladder rung is chaos-testable
  deterministically without allocating a single real gigabyte;
* metrics: ``pst_mem_budget_bytes``, ``pst_mem_accounted_bytes{pool}``,
  ``pst_mem_pressure_state``, ``pst_mem_degrade_actions_total{action}``,
  ``pst_mem_breaches_total``.

Degradation preserves determinism: in ``deterministic=True`` mode the
ladder only shrinks knobs the resequencer/cursor machinery already
tolerates (queue depths, pool sizes, cache contents — never item order),
so a pressured run's chunk stream stays bit-identical to an unpressured
one; order-affecting hooks (shuffle-buffer halving) are simply not
registered by deterministic pipelines.

The governor is **process-wide** (one budget per process — that is what
the kernel enforces) and **refcount-armed**: every Reader/JaxLoader built
while ``PETASTORM_TPU_HOST_MEM_BUDGET`` is set arms it, teardown of the
last one stops the sampler thread. Pools register regardless of arming,
so ``probe()``/``stats()`` always have the inventory.
"""

import contextlib
import logging
import os
import sys
import tempfile
import threading
import time
from collections import deque

logger = logging.getLogger(__name__)

ENV_VAR = 'PETASTORM_TPU_HOST_MEM_BUDGET'

# Ladder states, least to most severe. Levels are the metric encoding
# (pst_mem_pressure_state) and the comparison order.
STATE_OK = 'ok'
STATE_ADVISORY = 'advisory'
STATE_DEGRADE = 'degrade'
STATE_SHED = 'shed'
STATE_BREACH = 'breach'
STATES = (STATE_OK, STATE_ADVISORY, STATE_DEGRADE, STATE_SHED, STATE_BREACH)
STATE_LEVELS = {name: level for level, name in enumerate(STATES)}

#: Headroom subtracted from a detected container limit: the budget guards
#: the pools this package owns, while the rest of the process (python,
#: jax, XLA buffers, code) needs room of its own under the same limit.
DEFAULT_HEADROOM_FRAC = 0.1
MIN_HEADROOM_BYTES = 256 << 20

#: No cgroup limit at all (bare host): budget = this fraction of MemTotal.
DEFAULT_HOST_FRAC = 0.8

_BYTE_SUFFIXES = {'k': 1 << 10, 'm': 1 << 20, 'g': 1 << 30, 't': 1 << 40}

#: cgroup v1/v2 report "no limit" as a value near 2**63; anything this
#: large is unlimited, not a budget.
_CGROUP_UNLIMITED = 1 << 60


def parse_bytes(text):
    """``'512m'``/``'2g'``/``'1073741824'`` -> bytes; None for empty or
    the ``auto`` keyword (caller then auto-detects). Raises ValueError on
    garbage — a typo'd budget must fail the run that set it, not silently
    disarm the governor."""
    text = (text or '').strip().lower()
    if not text or text == 'auto':
        return None
    mult = 1
    if text[-1] in _BYTE_SUFFIXES:
        mult = _BYTE_SUFFIXES[text[-1]]
        text = text[:-1]
    value = int(float(text) * mult)
    if value <= 0:
        raise ValueError('memory budget must be positive, got {!r}'.format(value))
    return value


def cgroup_memory_limit(cgroup_root='/sys/fs/cgroup'):
    """The container memory limit in bytes, or None (no cgroup / no
    limit). Tries cgroup v2 (``memory.max`` — unified hierarchy mounts
    the controller at the root for the common container case) then v1
    (``memory/memory.limit_in_bytes``)."""
    for rel in ('memory.max', os.path.join('memory', 'memory.limit_in_bytes')):
        path = os.path.join(cgroup_root, rel)
        try:
            with open(path) as f:
                raw = f.read().strip()
        except OSError:
            continue
        if raw == 'max':      # v2's "no limit": try the next hierarchy
            continue
        try:
            value = int(raw)
        except ValueError:
            continue
        if 0 < value < _CGROUP_UNLIMITED:
            return value
    return None


def host_memory_total(meminfo_path='/proc/meminfo'):
    """MemTotal in bytes, or None off-linux."""
    try:
        with open(meminfo_path) as f:
            for line in f:
                if line.startswith('MemTotal:'):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        return None
    return None


def process_rss_bytes(statm_path='/proc/self/statm'):
    """Current resident set size in bytes, or None off-linux."""
    try:
        with open(statm_path) as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf('SC_PAGE_SIZE')
    except (OSError, ValueError, IndexError):
        return None


def peak_rss_bytes():
    """Lifetime peak RSS (``ru_maxrss``) in bytes. Kernel units differ:
    Linux reports kilobytes, macOS bytes."""
    import resource
    maxrss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return int(maxrss if sys.platform == 'darwin' else maxrss * 1024)


def resolve_budget(explicit=None, cgroup_root='/sys/fs/cgroup',
                   meminfo_path='/proc/meminfo'):
    """``(budget_bytes, source)`` for an explicit/env budget value.

    ``explicit`` (int, or a string per :func:`parse_bytes`) wins; else the
    environment variable; a value of ``auto`` (or an env var set to it)
    auto-detects: container cgroup limit minus headroom, else
    ``MemTotal * DEFAULT_HOST_FRAC``. Returns ``(None, None)`` only when
    nothing is configured at all (env unset and ``explicit`` None)."""
    source = None
    value = None
    if explicit is not None:
        value = explicit if isinstance(explicit, int) else parse_bytes(explicit)
        source = 'explicit'
    else:
        raw = os.environ.get(ENV_VAR, '')
        if not raw.strip():
            return None, None
        value = parse_bytes(raw)
        source = 'env'
    if value is not None:
        return value, source
    limit = cgroup_memory_limit(cgroup_root)
    if limit is not None:
        headroom = max(MIN_HEADROOM_BYTES, int(limit * DEFAULT_HEADROOM_FRAC))
        return max(1, limit - headroom), 'cgroup'
    total = host_memory_total(meminfo_path)
    if total is not None:
        return int(total * DEFAULT_HOST_FRAC), 'meminfo'
    # Last resort: a fraction-of-current-peak guess keeps the ladder armed
    # rather than silently off on exotic platforms.
    return max(1 << 30, peak_rss_bytes() * 4), 'rss-fraction'


def approx_nbytes(value, _depth=0):
    """Duck-typed byte estimate for pool contents: ``.nbytes`` arrays,
    dicts/lists/tuples of them, bytes-likes, scalars. Deliberately cheap
    and approximate — the governor needs ladder-rung accuracy, not
    allocator truth."""
    if value is None:
        return 0
    if _depth > 6:
        # Recursion guard: a deeper nest still weighs SOMETHING — a flat
        # getsizeof beats pretending the subtree is free (it feeds the
        # MemoryCache byte cap too, where 0 would let the cache outgrow
        # its configured limit).
        try:
            return sys.getsizeof(value)
        except TypeError:  # pragma: no cover - exotic object
            return 64
    nbytes = getattr(value, 'nbytes', None)
    if nbytes is not None:
        try:
            return int(nbytes)
        except (TypeError, ValueError):
            pass
    if isinstance(value, (bytes, bytearray, memoryview)):
        return len(value)   # buffer-dominated: payload IS the memory
    if isinstance(value, str):
        # getsizeof, not len: a python str's ~49-byte object header is
        # real resident memory, and wide-schema chunk dicts hold hundreds
        # of key strings per cached value (the MemoryCache byte-cap rule
        # this function inherited).
        return sys.getsizeof(value)
    if isinstance(value, dict):
        return sum(approx_nbytes(k, _depth + 1) + approx_nbytes(v, _depth + 1)
                   for k, v in value.items())
    if isinstance(value, (list, tuple)):
        if len(value) > 16:
            # Long row lists: sample EVENLY-SPACED elements and
            # extrapolate — the governor samples pools every tick, and
            # walking thousands of rows per tick would cost more than the
            # accuracy is worth. A stride (not the head) keeps the
            # estimate honest for data ordered by size (e.g. rows sorted
            # by text length), where head-sampling would systematically
            # under-count.
            stride = len(value) // 8
            picked = value[::stride][:8]
            sampled = sum(approx_nbytes(v, _depth + 1) for v in picked)
            return int(sampled * len(value) / len(picked))
        return sum(approx_nbytes(v, _depth + 1) for v in value)
    try:
        return sys.getsizeof(value)
    except TypeError:  # pragma: no cover - exotic object
        return 64


class GovernorConfig(object):
    """Ladder thresholds (fractions of the budget) and sampler pacing."""

    def __init__(self, interval_s=0.5, advisory_frac=0.70, degrade_frac=0.85,
                 shed_frac=0.92, breach_frac=1.0, transitions_log=256):
        if not (0 < advisory_frac <= degrade_frac <= shed_frac <= breach_frac):
            raise ValueError(
                'ladder thresholds must ascend: advisory {} <= degrade {} '
                '<= shed {} <= breach {}'.format(
                    advisory_frac, degrade_frac, shed_frac, breach_frac))
        self.interval_s = float(interval_s)
        self.advisory_frac = float(advisory_frac)
        self.degrade_frac = float(degrade_frac)
        self.shed_frac = float(shed_frac)
        self.breach_frac = float(breach_frac)
        self.transitions_log = int(transitions_log)

    def state_for(self, frac):
        if frac >= self.breach_frac:
            return STATE_BREACH
        if frac >= self.shed_frac:
            return STATE_SHED
        if frac >= self.degrade_frac:
            return STATE_DEGRADE
        if frac >= self.advisory_frac:
            return STATE_ADVISORY
        return STATE_OK


class PoolHandle(object):
    """One registered accountable pool.

    :param nbytes_fn: ``() -> int`` current bytes held. Must be cheap and
        thread-safe (runs on the governor thread).
    :param degrade_fn: optional ``() -> bool-ish``; called once per
        governor tick while the ladder sits at *degrade* or worse. Must be
        idempotent (evict, close, shed — all safe to repeat); a truthy
        return means "acted" and counts toward
        ``pst_mem_degrade_actions_total``.
    :param degrade_release_fn: optional ``() -> None`` called when the
        ladder drops back below *degrade* — owners whose degrade action is
        a standing mode (lineage record shedding) restore normal service
        here.
    :param shed_fn: optional ``(active: bool) -> None`` toggle, called on
        entering/leaving the *shed* rung.
    :param advisory_fn: optional ``(active: bool) -> None`` toggle, called
        on entering/leaving *advisory-or-worse*.

    Toggles must be **idempotent on re-assert**: a pool registered while
    an episode is already active gets the toggle fired at registration,
    and the same transition may fire it again on the sampler's next pass
    — a second ``True`` must not re-capture state a later ``False``
    restores.
    """

    __slots__ = ('name', 'nbytes_fn', 'degrade_fn', 'degrade_release_fn',
                 'shed_fn', 'advisory_fn', 'last_nbytes', '_governor')

    def __init__(self, governor, name, nbytes_fn, degrade_fn=None,
                 degrade_release_fn=None, shed_fn=None, advisory_fn=None):
        self.name = name
        self.nbytes_fn = nbytes_fn
        self.degrade_fn = degrade_fn
        self.degrade_release_fn = degrade_release_fn
        self.shed_fn = shed_fn
        self.advisory_fn = advisory_fn
        self.last_nbytes = 0
        self._governor = governor

    def close(self):
        """Unregister (idempotent). Owners call this at teardown so a dead
        pipeline's pools stop being sampled (and metric children retire)."""
        governor, self._governor = self._governor, None
        if governor is not None:
            governor._unregister(self)


class MemoryGovernor(object):
    """Process-wide pool registry + budget + pressure-ladder sampler.

    Normally reached through :func:`get_governor`; tests build their own
    and drive :meth:`check` directly with a synthetic clock."""

    def __init__(self, budget=None, config=None):
        from petastorm_tpu import metrics as metrics_mod
        from petastorm_tpu.analysis import sanitize
        self.config = config if config is not None else GovernorConfig()
        self._lock = sanitize.tracked_lock(
            'petastorm_tpu.membudget:MemoryGovernor._lock')
        self._pools = []
        self._breach_sinks = []
        self._budget = budget
        self._budget_source = 'explicit' if budget is not None else None
        self._arm_count = 0
        self._thread = None          # (Thread, its stop Event) while armed
        self._state = STATE_OK
        self._frac = 0.0
        self._accounted = 0
        self._last_pools = {}
        self._peak_frac = 0.0
        self._peak_level = 0
        self._peak_rss = 0
        self._breach_fired = False
        self.breaches = 0
        self.last_breach = None
        self._transitions = deque(maxlen=self.config.transitions_log)
        self._t0 = None
        self._degrade_actions = {}
        self._m_budget = metrics_mod.gauge(
            'pst_mem_budget_bytes',
            'Host memory budget the governor enforces (0 = unarmed)')
        self._m_accounted = metrics_mod.gauge(
            'pst_mem_accounted_bytes',
            'Bytes currently held, by accountable pool',
            labelnames=('pool',))
        self._m_state = metrics_mod.gauge(
            'pst_mem_pressure_state',
            'Pressure-ladder position (0 ok, 1 advisory, 2 degrade, '
            '3 shed, 4 breach)')
        self._m_actions = metrics_mod.counter(
            'pst_mem_degrade_actions_total',
            'Degradation actions the governor ran, by action',
            labelnames=('action',))
        self._m_breaches = metrics_mod.counter(
            'pst_mem_breaches_total',
            'Hard budget breaches (flight dump + HostMemoryExceededError)')

    # -- pool registry -----------------------------------------------------

    def register_pool(self, name, nbytes_fn, degrade_fn=None,
                      degrade_release_fn=None, shed_fn=None,
                      advisory_fn=None):
        """Register one accountable pool; returns its :class:`PoolHandle`
        (close it at owner teardown). Several handles may share a name
        (two readers in one process): accounting sums them."""
        handle = PoolHandle(self, name, nbytes_fn, degrade_fn=degrade_fn,
                            degrade_release_fn=degrade_release_fn,
                            shed_fn=shed_fn, advisory_fn=advisory_fn)
        with self._lock:
            self._pools.append(handle)
            shedding = STATE_LEVELS[self._state] >= STATE_LEVELS[STATE_SHED]
            advising = STATE_LEVELS[self._state] >= STATE_LEVELS[STATE_ADVISORY]
        # A pool registered mid-episode joins the episode's toggles.
        if advising:
            self._toggle(handle.advisory_fn, True, handle.name, 'advisory')
        if shedding:
            self._toggle(handle.shed_fn, True, handle.name, 'shed')
        return handle

    def _unregister(self, handle):
        with self._lock:
            try:
                self._pools.remove(handle)
            except ValueError:
                return
            survivors = {h.name for h in self._pools}
        if handle.name not in survivors:
            self._m_accounted.remove(handle.name)
            # Copy-and-rebind (atomic) rather than mutate: probe()/
            # pool_ranking() iterate the dict from other threads.
            last = dict(self._last_pools)
            last.pop(handle.name, None)
            self._last_pools = last

    def add_breach_sink(self, fn):
        """``fn(HostMemoryExceededError)`` called (governor thread) when
        the ladder breaches — pipelines deliver it into their consumer
        queue so the trainer raises a diagnosed error, never a SIGKILL."""
        with self._lock:
            self._breach_sinks.append(fn)
        return fn

    def remove_breach_sink(self, fn):
        with self._lock:
            try:
                self._breach_sinks.remove(fn)
            except ValueError:
                pass

    # -- arming ------------------------------------------------------------

    @property
    def armed(self):
        return self._arm_count > 0 and self._budget is not None

    @property
    def budget(self):
        return self._budget

    def arm(self, budget=None):
        """Refcounted arm: resolve the budget (on first arm, or when an
        explicit one is passed) and start the sampler thread. Returns True
        when armed. Pair every arm with one :meth:`release`.

        A malformed budget value raises ``ValueError`` — the run that set
        the typo fails loudly; a governor that silently stayed unarmed
        would hand the next OOM back to the kernel, the exact outcome
        arming exists to prevent."""
        with self._lock:
            # Re-resolve on every FRESH arming epoch (owner count 0 -> 1),
            # not just the first ever: an env value changed between
            # pipelines — including a typo'd one, which must raise — takes
            # effect instead of a stale first-resolution silently winning.
            if budget is not None or self._budget is None \
                    or self._arm_count == 0:
                resolved, source = resolve_budget(explicit=budget)
                if resolved is not None:
                    self._budget = resolved
                    self._budget_source = source
                elif self._budget is None:
                    return False
            self._arm_count += 1
            thread = None
            if self._thread is None:
                # Each sampler owns its own stop event: a stale thread
                # still draining a previous release's stop must not be
                # resurrected (or its shared event un-set) by a racing
                # re-arm — the new sampler is simply a new thread.
                stop = threading.Event()
                thread = threading.Thread(
                    target=self._loop, args=(stop,), daemon=True,
                    name='pst-mem-governor')
                self._thread = (thread, stop)
        if thread is not None:
            thread.start()
        self._m_budget.set(self._budget)
        logger.info('memory governor armed: budget %d bytes (%s)',
                    self._budget, self._budget_source)
        return True

    def release(self):
        """Drop one arm reference; the sampler stops when the last owner
        releases (the leak-guard sweep requires the thread to die with its
        owners)."""
        with self._lock:
            self._arm_count = max(0, self._arm_count - 1)
            entry = None
            last = self._arm_count == 0
            if last:
                # Claim the thread UNDER the lock: a concurrent arm() then
                # sees None and starts a fresh sampler instead of adopting
                # the one this release is about to stop.
                entry, self._thread = self._thread, None
        if entry is not None:
            thread, stop = entry
            stop.set()
            if thread.is_alive():
                thread.join(timeout=5)
        if last:
            self._reset_ladder()
            # Honor the gauges' documented '0 = unarmed' semantics: with
            # the sampler gone nothing else would ever reset them, and a
            # scrape after teardown must not alert on a dead pipeline.
            self._m_budget.set(0)
            self._m_state.set(0)

    def _reset_ladder(self):
        """Return the ladder to ``ok`` when the last owner releases: a
        parked degrade/shed state with no sampler would (a) leave
        surviving pools' advisory/shed toggles engaged forever (a spill
        paused with nobody to unpause it) and (b) keep the watchdog's
        ``memory`` probe soft-classifying every later genuine stall as
        memory pressure. Runs the normal recede path so release hooks
        fire."""
        previous = self._state
        if previous == STATE_OK:
            return
        self._state = STATE_OK
        self._frac = 0.0
        self._breach_fired = False
        with self._lock:
            self._transitions.append({'t': (round(time.monotonic() - self._t0,
                                                  3)
                                            if self._t0 is not None else 0.0),
                                      'state': STATE_OK,
                                      'frac': 0.0,
                                      'accounted': self._accounted,
                                      'reason': 'disarmed'})
        logger.info('memory governor disarmed at %r: ladder reset to ok',
                    previous)
        self._apply_rung(STATE_OK, previous, {})

    def _loop(self, stop):
        while not stop.wait(self.config.interval_s):
            try:
                self.check()
            except Exception:  # noqa: BLE001 - the governor must not die of a bug
                logger.exception('memory governor check failed')

    # -- the ladder --------------------------------------------------------

    def pressure_level(self):
        """Current ladder level as an int (0 ok .. 4 breach); 0 while
        unarmed. The autotuner's memory bias consults this every tick."""
        if not self.armed:
            return 0
        return STATE_LEVELS[self._state]

    def _sample_pools(self):
        """{name: bytes} summed over handles, with the ``mem-pressure``
        fault site's deterministic inflation applied per pool."""
        from petastorm_tpu import faults
        injector = faults.get_injector()
        spec = injector.spec('mem-pressure')
        with self._lock:
            handles = list(self._pools)
        sampled = {}
        for handle in handles:
            try:
                nbytes = int(handle.nbytes_fn() or 0)
            except Exception:  # noqa: BLE001 - a dying pool must not kill the tick
                logger.debug('pool %s nbytes_fn failed', handle.name,
                             exc_info=True)
                nbytes = handle.last_nbytes
            # The fallback cache holds the UNINFLATED sample — inflation
            # is applied after, or a dying pool under an active fault
            # would compound the inflation every tick (N, 2N, 3N, ...)
            # and walk a deterministically-parked rung into a breach.
            handle.last_nbytes = nbytes
            sampled[handle.name] = sampled.get(handle.name, 0) + nbytes
        if spec is not None:
            # Inflation is per POOL NAME, not per handle: same-named
            # pools (two readers in one process) sum their real bytes,
            # but a per-handle inflation would double the injected
            # pressure and park a chaos drill on the wrong rung.
            inflate = spec.inflate_bytes
            if inflate is None:
                # Unspecified inflation = a full budget's worth: the
                # site then guarantees a breach whatever the budget.
                inflate = self._budget or 0
            for name in list(sampled):
                if injector.selected('mem-pressure', name):
                    sampled[name] += int(inflate)
        return sampled

    def check(self, now=None):
        """One governor pass (the sampler thread's tick; tests call it
        directly). Samples every pool, walks the ladder, runs the rung's
        actions. Returns the resulting state."""
        now = now if now is not None else time.monotonic()
        if self._t0 is None:
            self._t0 = now
        pools = self._sample_pools()
        accounted = sum(pools.values())
        budget = self._budget
        frac = (accounted / budget) if budget else 0.0
        state = self.config.state_for(frac) if self.armed else STATE_OK
        previous = self._state
        self._accounted = accounted
        self._frac = frac
        self._last_pools = pools
        rss = process_rss_bytes()
        if rss:
            self._peak_rss = max(self._peak_rss, rss)
        for name, nbytes in pools.items():
            self._m_accounted.labels(name).set(nbytes)
        self._m_state.set(STATE_LEVELS[state])
        if frac > self._peak_frac:
            self._peak_frac = frac
        if STATE_LEVELS[state] > self._peak_level:
            self._peak_level = STATE_LEVELS[state]
        if state != previous:
            self._state = state
            with self._lock:   # stats()/breach copy while we append
                self._transitions.append({'t': round(now - self._t0, 3),
                                          'state': state,
                                          'frac': round(frac, 4),
                                          'accounted': accounted})
            logger.log(
                logging.WARNING if STATE_LEVELS[state] > STATE_LEVELS[previous]
                else logging.INFO,
                'memory pressure %s -> %s: %d of %s budget bytes (%.0f%%)',
                previous, state, accounted, budget, 100 * frac)
            from petastorm_tpu.trace import get_global_tracer
            get_global_tracer().instant('mem-pressure:{}'.format(state),
                                        cat='membudget')
        self._apply_rung(state, previous, pools)
        return state

    def _toggle(self, fn, active, pool_name, rung):
        if fn is None:
            return
        try:
            fn(active)
            if active:
                self._count_action('{}:{}'.format(rung, pool_name))
        except Exception:  # noqa: BLE001 - one pool's hook must not stop the rest
            logger.exception('%s toggle for pool %s failed', rung, pool_name)

    def _count_action(self, action):
        self._m_actions.labels(action).inc()
        with self._lock:
            self._degrade_actions[action] = \
                self._degrade_actions.get(action, 0) + 1

    def _apply_rung(self, state, previous, pools):
        level, prev_level = STATE_LEVELS[state], STATE_LEVELS[previous]
        advisory, shed = STATE_LEVELS[STATE_ADVISORY], STATE_LEVELS[STATE_SHED]
        degrade = STATE_LEVELS[STATE_DEGRADE]
        with self._lock:
            handles = list(self._pools)
        # Advisory / shed are toggles (entering and leaving the band).
        if (level >= advisory) != (prev_level >= advisory):
            for handle in handles:
                self._toggle(handle.advisory_fn, level >= advisory,
                             handle.name, 'advisory')
        if (level >= shed) != (prev_level >= shed):
            for handle in handles:
                self._toggle(handle.shed_fn, level >= shed,
                             handle.name, 'shed')
        # Degrade hooks run every tick while the rung holds: the actions
        # are idempotent frees and memory may keep climbing between ticks.
        if level >= degrade:
            for handle in handles:
                if handle.degrade_fn is None:
                    continue
                try:
                    acted = handle.degrade_fn()
                except Exception:  # noqa: BLE001
                    logger.exception('degrade hook for pool %s failed',
                                     handle.name)
                    continue
                if acted:
                    self._count_action('degrade:{}'.format(handle.name))
        elif prev_level >= degrade:
            # Dropping below the band: standing degrade modes (lineage
            # record shedding) return to normal service.
            for handle in handles:
                if handle.degrade_release_fn is None:
                    continue
                try:
                    handle.degrade_release_fn()
                except Exception:  # noqa: BLE001
                    logger.exception('degrade release for pool %s failed',
                                     handle.name)
        if level >= STATE_LEVELS[STATE_BREACH]:
            if not self._breach_fired:
                self._breach_fired = True
                self._fire_breach(pools)
        else:
            self._breach_fired = False

    # -- breach ------------------------------------------------------------

    def pool_ranking(self):
        """Pools by bytes, biggest first — the flight dump's headline."""
        return sorted(({'pool': name, 'nbytes': nbytes}
                       for name, nbytes in self._last_pools.items()),
                      key=lambda entry: entry['nbytes'], reverse=True)

    def _fire_breach(self, pools):
        from petastorm_tpu.errors import HostMemoryExceededError
        self.breaches += 1
        self._m_breaches.inc()
        ranking = self.pool_ranking()
        with self._lock:
            transitions = list(self._transitions)
        diagnosis = {'budget_bytes': self._budget,
                     'budget_source': self._budget_source,
                     'accounted_bytes': self._accounted,
                     'frac': round(self._frac, 4),
                     'rss_bytes': process_rss_bytes(),
                     'peak_rss_bytes': self._peak_rss,
                     'pool_ranking': ranking,
                     'transitions': transitions}
        dump_path = self._dump_flight(diagnosis)
        top = ranking[0] if ranking else {'pool': 'none', 'nbytes': 0}
        message = (
            'host memory budget breached: {} accounted bytes of {} budget '
            '({:.0%}); top pool {!r} holds {} bytes. Flight dump: {}. '
            'Raising before the kernel OOM killer does it without a '
            'diagnosis.'.format(self._accounted, self._budget, self._frac,
                                top['pool'], top['nbytes'],
                                dump_path or '<unavailable>'))
        error = HostMemoryExceededError(message, budget=self._budget,
                                        accounted=self._accounted,
                                        ranking=ranking,
                                        flight_dump=dump_path)
        self.last_breach = error
        logger.error('%s', message)
        with self._lock:
            sinks = list(self._breach_sinks)
        for sink in sinks:
            try:
                sink(error)
            except Exception:  # noqa: BLE001 - delivery is best-effort per sink
                logger.exception('memory breach delivery failed')

    def _dump_flight(self, diagnosis):
        """Best-effort flight-recorder dump (trace ring + metrics +
        per-pool ranking). Uses the env-armed recorder directory when set,
        the shared tempdir otherwise — a breach post-mortem must exist
        even on a pipeline that never armed the stall recorder."""
        try:
            from petastorm_tpu import flight_recorder as flight_mod
            from petastorm_tpu.trace import get_global_tracer
            base_dir = os.environ.get(flight_mod.ENV_VAR, '').strip() \
                or tempfile.gettempdir()
            recorder = flight_mod.FlightRecorder(base_dir,
                                                 tracer=get_global_tracer())
            return recorder.dump(diagnosis, reason='mem-breach')
        except Exception:  # noqa: BLE001 - a failed dump must not mask the breach
            logger.exception('memory breach flight dump failed')
            return None

    # -- observability -----------------------------------------------------

    def probe(self):
        """The watchdog's ``memory`` probe: last sample, no re-walk."""
        return {'state': self._state,
                'level': STATE_LEVELS[self._state],
                'armed': self.armed,
                'frac': round(self._frac, 4),
                'budget_bytes': self._budget,
                'accounted_bytes': self._accounted,
                'pools': dict(self._last_pools)}

    def stats(self):
        """The ``stats`` surface: budget provenance, ladder peaks,
        per-action degrade counts, transition history."""
        with self._lock:
            actions = dict(self._degrade_actions)
            transitions = list(self._transitions)
        return {'armed': self.armed,
                'budget_bytes': self._budget,
                'budget_source': self._budget_source,
                'state': self._state,
                'frac': round(self._frac, 4),
                'accounted_bytes': self._accounted,
                'peak_frac': round(self._peak_frac, 4),
                'peak_state': STATES[self._peak_level],
                'peak_rss_bytes': self._peak_rss,
                'pools': dict(self._last_pools),
                'degrade_actions': actions,
                'breaches': self.breaches,
                'transitions': transitions}


# --------------------------------------------------------------------------
# process-wide default governor
# --------------------------------------------------------------------------

_governor = None
_governor_lock = threading.Lock()


def get_governor():
    """The process-wide governor every subsystem registers with."""
    global _governor
    if _governor is None:
        with _governor_lock:
            if _governor is None:
                _governor = MemoryGovernor()
    return _governor


def set_governor(governor):
    """Swap the process-wide governor (tests isolate ladders this way);
    returns the previous one. Pools registered on the old governor keep
    reporting there — swap before building pipelines."""
    global _governor
    with _governor_lock:
        previous = _governor
        _governor = governor
        return previous


def register_pool(name, nbytes_fn, degrade_fn=None, degrade_release_fn=None,
                  shed_fn=None, advisory_fn=None):
    """Register an accountable pool on the process-wide governor."""
    return get_governor().register_pool(name, nbytes_fn,
                                        degrade_fn=degrade_fn,
                                        degrade_release_fn=degrade_release_fn,
                                        shed_fn=shed_fn,
                                        advisory_fn=advisory_fn)


@contextlib.contextmanager
def transient_pool(name, nbytes_fn, degrade_fn=None, shed_fn=None,
                   advisory_fn=None):
    """Register an accountable pool for the duration of a ``with``
    block — the bounded-lifetime version of :func:`register_pool` for
    phases that hold real bytes but outlive no scope (a warm-joining
    lookup replica buffering peer chunk blobs, a transcode pass holding
    a batch in flight). Guarantees the handle closes on the way out, so
    an aborted phase can never leave a dangling pool inflating the
    governor's accounting forever."""
    handle = register_pool(name, nbytes_fn, degrade_fn=degrade_fn,
                           shed_fn=shed_fn, advisory_fn=advisory_fn)
    try:
        yield handle
    finally:
        handle.close()


def validate_env_budget():
    """Parse-check ``PETASTORM_TPU_HOST_MEM_BUDGET`` without arming;
    raises ``ValueError`` on a malformed value. Reader/JaxLoader call
    this FIRST in ``__init__`` so a typo'd budget fails before any
    pipeline thread starts or process-wide registration happens —
    raising from the tail arm would strand started threads with no
    teardown path."""
    raw = os.environ.get(ENV_VAR, '')
    if raw.strip():
        parse_bytes(raw)


def maybe_arm_from_env():
    """Arm the process-wide governor when ``PETASTORM_TPU_HOST_MEM_BUDGET``
    is set (Reader/JaxLoader construction calls this). Returns True when
    this call took an arm reference — the caller must then pair it with
    ``get_governor().release()`` at teardown."""
    if not os.environ.get(ENV_VAR, '').strip():
        return False
    return get_governor().arm()
