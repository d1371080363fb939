"""From the program's span ring to numbers: clip a list of records to a
window, self time by what nested spans cover, a thread's CPU inside spans
and outside them, quantiles. Plain lists in, numbers out, no jax, so it is
checked on a recorded list (``tests/data/small.spans.json``) without a chip.

The records are ``petastorm_tpu.trace``'s: a span is ``(name, layer,
start_ns, dur_ns, cpu_ns, tid, id, cause)`` on ``time.perf_counter_ns()``
(``cpu_ns`` ``None`` outside the worker threads' layers), an instant the
same with ``dur_ns`` ``None`` and the thread's CPU clock in the ``cpu_ns``
slot, a counter ``(name, layer, t_ns, value)``.
"""

NAME, LAYER, START, DUR, CPU, TID, ID, CAUSE = range(8)


def ring_records():
    """The records of the program's process-wide ring, or ``None`` where the
    program has no such ring (a commit before it, or one switched off)."""
    try:
        from petastorm_tpu.trace import get_global_tracer
    except ImportError:
        return None
    records = getattr(get_global_tracer(), 'records', None)
    return records() if records is not None else None


def window_of(ctx, records=None):
    """What the ring holds of the run's counted window, or ``None`` where it
    does not hold all of it: a ring that wrapped inside the window has
    dropped part of what a share or a mean is taken over, and a number too
    low is worse than none. ``ctx['begin']['t']`` and ``ctx['end']['t']`` are
    ``time.perf_counter()`` at the window's two ends: the records' clock."""
    if records is None:
        records = ring_records()
    if not records:
        return None
    window = clip(records, int(ctx['begin']['t'] * 1e9),
                  int(ctx['end']['t'] * 1e9))
    return window if window['covered'] else None


def clip(records, t0_ns, t1_ns):
    """``{'spans', 'instants', 'counters', 't0_ns', 't1_ns', 'covered'}``:
    spans cut to the window with their CPU in proportion, instants and
    counters inside it. ``covered`` says that nothing of the window was
    dropped from a full ring: records are written as they close, so what a
    ring dropped closed before its first record did, and that one closed
    before the window opened."""
    spans, instants, counters = [], [], []
    first = tuple(records[0]) if records else None
    for r in records:
        r = tuple(r)
        at = r[2]
        if len(r) == 4:
            if t0_ns <= at <= t1_ns:
                counters.append(r)
        elif r[DUR] is None:
            if t0_ns <= at <= t1_ns:
                instants.append(r)
        else:
            lo, hi = max(at, t0_ns), min(at + r[DUR], t1_ns)
            if hi > lo or (r[DUR] == 0 and t0_ns <= at <= t1_ns):
                share = (hi - lo) / r[DUR] if r[DUR] else 1.0
                spans.append(r[:START] + (lo, hi - lo, r[CPU] and int(
                    r[CPU] * share)) + r[TID:])
    return {'spans': spans, 'instants': instants, 'counters': counters,
            't0_ns': t0_ns, 't1_ns': t1_ns,
            'covered': first is not None and first[START] + (
                (len(first) == 8 and first[DUR]) or 0) <= t0_ns}


def named(spans, *names):
    return [s for s in spans if s[NAME] in names]


def seconds(spans, *names):
    return sum(s[DUR] for s in named(spans, *names)) / 1e9


def nesting(spans):
    """``[(span, covered_ns, top_level)]``: for each span the nanoseconds
    that spans opened inside it on the same thread cover (its direct
    children: theirs are inside those), and whether it lies in no other."""
    out = []
    by_thread = {}
    for s in spans:
        by_thread.setdefault(s[TID], []).append(s)
    for thread in by_thread.values():
        # An enclosing span starts no later and ends no earlier.
        thread.sort(key=lambda s: (s[START], -s[DUR]))
        stack = []          # [span, end_ns, covered_ns]
        for s in thread:
            while stack and stack[-1][1] <= s[START]:
                done = stack.pop()
                out.append((done[0], done[2], not stack))
            if stack:
                stack[-1][2] += min(s[DUR], stack[-1][1] - s[START])
            stack.append([s, s[START] + s[DUR], 0])
        while stack:
            done = stack.pop()
            out.append((done[0], done[2], not stack))
    return out


def self_seconds(spans, name):
    """``(self seconds, count)`` of the spans called ``name``: duration less
    what their children cover."""
    mine = [(s, covered) for s, covered, _ in nesting(spans)
            if s[NAME] == name]
    return sum(max(0, s[DUR] - covered) for s, covered in mine) / 1e9, \
        len(mine)


def thread_cpu(window, mark, waits):
    """``{tid: {'total_ns', 'in_spans_ns', 'in_waits_ns'}}`` for every
    thread with two or more ``mark`` instants in the window: the CPU it
    burnt between its first and its last, how much of it under any span,
    and how much under the spans named in ``waits``. What is under no span
    is ``total_ns - in_spans_ns``."""
    marks = {}
    for i in window['instants']:
        if i[NAME] == mark and i[CPU] is not None:
            marks.setdefault(i[TID], []).append(i)
    out = {}
    for tid, seen in marks.items():
        if len(seen) < 2:
            continue
        seen.sort(key=lambda i: i[START])
        lo, hi = seen[0][START], seen[-1][START]
        inside = clip([s for s in window['spans'] if s[TID] == tid],
                      lo, hi)['spans']
        out[tid] = {
            'total_ns': seen[-1][CPU] - seen[0][CPU],
            'in_spans_ns': sum(s[CPU] or 0 for s, _, top in nesting(inside)
                               if top),
            'in_waits_ns': sum(s[CPU] or 0 for s in named(inside, *waits))}
    return out


def quantile(values, q):
    """The q-quantile by linear interpolation (numpy's default)."""
    values = sorted(values)
    if not values:
        return None
    pos = (len(values) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(values) - 1)
    return values[lo] + (values[hi] - values[lo]) * (pos - lo)


def residency_ms(window, name='consumer.deliver', key='staged_ns'):
    """Milliseconds between a batch's staging and its delivery, for every
    delivery in the window that says when its batch was staged."""
    return [(i[START] - i[CAUSE][key]) / 1e6 for i in window['instants']
            if i[NAME] == name and isinstance(i[CAUSE], dict)
            and i[CAUSE].get(key) is not None]


def lead_batches(window, name='consumer.deliver'):
    """The loader's lead over the training loop in batches: the median time
    a delivered batch had been staged, over the median time between two
    deliveries. A step that gets faster leaves it alone; a loader that falls
    behind takes it to 0. ``None`` under two deliveries."""
    taken = sorted(i[START] for i in window['instants'] if i[NAME] == name)
    waited = residency_ms(window, name)
    if len(taken) < 2 or not waited:
        return None
    between = quantile([b - a for a, b in zip(taken, taken[1:])], 0.5) / 1e6
    return quantile(waited, 0.5) / between if between else None
