"""From a profiler trace to numbers: device busy and idle time, time per
operation, a kernel's time, and each idle gap charged to the host span that
covered it: once to the training loop's three spans, and once more, thread by
thread, to the program's own spans (``trace['stages']``), so that a gap names
the pipeline stage the host was in. Works on plain event lists, so it is
checked on a recorded trace (``tests/data``) without a chip;
:func:`load_xplane` turns the profiler's ``.xplane.pb`` into those lists with
nothing but jax.

An event is ``(name, start_ns, duration_ns)``.
"""

import bisect
import glob
import os
import re

HOST_SPANS = ('next_batch', 'dispatch_step', 'await_step')
# Lines of a device plane that hold the operations themselves. Other lines
# ("Steps", "XLA Modules", "XLA TraceMe") cover the same time again.
OPS_LINE = 'XLA Ops'


_OP = re.compile(r'^%?([^ =]+) = \(?([a-z0-9]+)\[([0-9,]*)\]')


def short_name(name):
    """``%fusion.20 = (f32[768,50257]{...}, ...) fusion(...)`` becomes
    ``fusion.20_f32_768_50257_``: the operation and its first result's type,
    which is what tells two fusions apart in a breakdown."""
    m = _OP.match(name)
    if not m:
        return name[:80]
    dims = m.group(3).replace(',', '_')
    return '{}_{}_{}_'.format(m.group(1), m.group(2), dims) if dims else \
        '{}_{}_'.format(m.group(1), m.group(2))


def load_xplane(path):
    """``{'devices': {plane name: [event, ...]}, 'host': {span: [event]}}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, {name: [] for name in HOST_SPANS}
    for plane in data.planes:
        if plane.name.startswith('/device:TPU:'):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (short_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events)
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host:
                        host[e.name].append(
                            (e.name, int(e.start_ns), int(e.duration_ns)))
    return {'devices': devices, 'host': host}


def _shift(last_step_done_ns, devices):
    """What the host's clock lacks to the trace's: the host saw the last
    step done at ``last_step_done_ns``, which is when the last device
    operation ended, to the tenth of a millisecond the call takes to return."""
    ends = [s + d for events in devices.values() for _, s, d in events]
    if not ends:
        raise RuntimeError('no device operation in the trace')
    return max(ends) - last_step_done_ns


def spans_on_the_trace_clock(spans, last_step_done_ns, devices):
    """The loop's spans, taken by the host's clock as ``(name, start_ns,
    duration_ns)``, moved onto the trace's clock."""
    shift = _shift(last_step_done_ns, devices)
    host = {name: [] for name in HOST_SPANS}
    for name, start, duration in spans:
        host[name].append((name, start + shift, duration))
    return host


def stages_on_the_trace_clock(spans, last_step_done_ns, devices):
    """The program's own spans, ``(thread, name, start_ns, duration_ns)`` on
    the same host clock (``petastorm_tpu.trace`` records on
    ``time.perf_counter_ns()``, as the loop's spans are taken), moved by the
    same shift: ``{thread: [event, ...]}``."""
    shift = _shift(last_step_done_ns, devices)
    threads = {}
    for thread, name, start, duration in spans:
        threads.setdefault(thread, []).append((name, start + shift, duration))
    return threads


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not found:
        raise RuntimeError('the profiler wrote no xplane under ' + trace_dir)
    return found[-1]


def _merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(events, window):
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def _innermost(events):
    """One thread's nested spans as disjoint ``(start, end, name)`` pieces,
    each moment under the innermost span open at it: a ``collate.batch``
    that waits for the reader is ``collate.reader_wait`` while it waits."""
    out, stack, cursor = [], [], 0          # stack of (name, end)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            top, end = stack.pop()
            if end > cursor:
                out.append((cursor, end, top))
                cursor = end
        if stack and start > cursor:
            out.append((cursor, start, stack[-1][0]))
        cursor = max(cursor, start) if stack else start
        stack.append((name, start + dur))
    while stack:
        top, end = stack.pop()
        if end > cursor:
            out.append((cursor, end, top))
            cursor = end
    return out


def stage_intervals(threads, window):
    """``{span name: [[start, end], ...]}``: for each of the program's span
    names the moments of the window at which some thread was in it (and in
    no span nested inside it), sorted and disjoint. Ten pool workers that
    all sit in ``reader.take`` are one stage waiting, not ten."""
    by_name = {}
    for events in threads.values():
        for start, end, name in _innermost(_clip(events, window)):
            by_name.setdefault(name, []).append((start, end))
    return {name: _merge(ivs) for name, ivs in by_name.items()}


def _overlap(a, b):
    """Nanoseconds that two sorted lists of disjoint intervals share."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def whole_steps_window(host):
    """The traced window: from the end of the first ``await_step`` to the end
    of the last, so that the bubble the profiler's own start leaves and the
    drain at its stop stay outside. ``None`` with fewer than three."""
    awaits = sorted(host.get('await_step', ()), key=lambda e: e[1])
    if len(awaits) < 3:
        return None
    return (awaits[0][1] + awaits[0][2], awaits[-1][1] + awaits[-1][2])


def reduce_trace(trace, window=None, top=10):
    """Busy seconds (union of operation intervals, averaged over the chips),
    the window's seconds, seconds per operation name and idle gaps by host
    span: by the loop's (``idle_gaps``) and, where the trace carries the
    program's spans by thread (``trace['stages']``), by those
    (``stage_gaps``; a gap is charged to every stage some thread was in, so
    these do not add up to the idle time; ``None`` without them). Raises
    where no operation ran on a device in the window."""
    if window is None:
        window = whole_steps_window(trace['host'])
    if window is None:
        starts = [e[1] for ev in trace['devices'].values() for e in ev]
        ends = [e[1] + e[2] for ev in trace['devices'].values() for e in ev]
        if not starts:
            raise RuntimeError('no device operation in the trace')
        window = (min(starts), max(ends))
    n = len(trace['devices'])
    busy_ns, per_op, gaps = 0, {}, {}
    # The loop's spans follow one another on one thread: sorted, disjoint.
    spans = sorted((s, s + d, name) for name in HOST_SPANS
                   for _, s, d in _clip(trace['host'].get(name, ()), window))
    span_ends = [e for _, e, _ in spans]
    stages = None if trace.get('stages') is None else stage_intervals(
        trace['stages'], window)
    stage_gaps = {}
    for events in trace['devices'].values():
        events = _clip(events, window)
        merged = _merge((s, s + d) for _, s, d in events)
        busy_ns += sum(e - s for s, e in merged)
        for name, _, dur in events:
            per_op[name] = per_op.get(name, 0) + dur
        edges = [window[0]] + [t for iv in merged for t in iv] + [window[1]]
        idle = [gap for gap in zip(edges[0::2], edges[1::2]) if gap[1] > gap[0]]
        for name, intervals in (stages or {}).items():
            stage_gaps[name] = stage_gaps.get(name, 0) + _overlap(
                idle, intervals)
        for gap_start, gap_end in idle:
            left = gap_end - gap_start
            k = bisect.bisect_right(span_ends, gap_start)
            while k < len(spans) and spans[k][0] < gap_end:
                s, e, name = spans[k]
                cover = min(e, gap_end) - max(s, gap_start)
                if cover > 0:
                    gaps[name] = gaps.get(name, 0) + cover
                    left -= cover
                k += 1
            if left > 0:
                gaps['outside_loop_spans'] = gaps.get(
                    'outside_loop_spans', 0) + left
    if n == 0 or busy_ns == 0:
        raise RuntimeError('no device operation ran in the traced window')

    def ranked(table):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / n / 1e9] for name, ns in rows]

    steps = sum(1 for _, s, d in trace['host'].get('await_step', ())
                if window[0] < s + d <= window[1])
    return {'busy_s': busy_ns / n / 1e9,
            'window_s': (window[1] - window[0]) / 1e9,
            'chips': n,
            'steps': steps,
            'per_op_s': {k: v / n / 1e9 for k, v in per_op.items()},
            'device_ops': ranked(per_op),
            'idle_gaps': ranked(gaps),
            'stage_gaps': None if stages is None else ranked(
                {k: v for k, v in stage_gaps.items() if v > 0})}


def kernel_seconds(reduced, pattern):
    """Seconds a chip spent in operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [s for name, s in reduced['per_op_s'].items() if rx.search(name)]
    return sum(hits) if hits else None


def roofline_share(flops, nbytes, seconds, peak):
    """Least time the chip could take over the time it took, in percent, and
    which bound applies."""
    compute = flops / peak['bf16_flops_per_s']
    memory = nbytes / peak['hbm_bytes_per_s']
    bound = 'compute' if compute >= memory else 'memory'
    return 100.0 * max(compute, memory) / seconds, bound
