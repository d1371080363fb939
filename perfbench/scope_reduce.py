"""From the traced window's time per operation to the step's time by the
model's own parts: the program says which part and pass every instruction of
its compiled step belongs to (``petastorm_tpu.trace.Tracer.op_scopes``, from
the scopes the instructions carry), a device trace names its events by the
same instruction names, and this joins the two. Plain dicts in, numbers out,
no jax, so it is checked on a recorded pair (``tests/data/gpt2s.*.json``)
without a chip.

``per_op_s`` is ``trace_reduce.reduce_trace``'s: seconds a chip spent in each
operation of the window, keyed by ``trace_reduce.short_name`` of the event
(``fusion.850_f32_3584_16384_``: the instruction's name, then its first
result's type; an event ``short_name`` could not read keeps its first 80
characters, ``%slice-start.6 = ((f32[...``).
"""

import re
import time

from perfbench import span_reduce

OUTSIDE = 'outside_step'
PASSES = ('forward', 'recompute', 'backward', 'update', '-')
LARGEST = 10        # operations listed of each kind that no part claims

_RAW = re.compile(r'^%?([^ =]+) = ')
_TYPE = re.compile(r'^[a-z][a-z0-9]*_(?:[0-9]+_)*$')


def tables():
    """The program's ``op_scopes()``, or ``None`` where it has none (a commit
    before it, or the ring switched off)."""
    try:
        from petastorm_tpu.trace import get_global_tracer
    except ImportError:
        return None
    op_scopes = getattr(get_global_tracer(), 'op_scopes', None)
    return op_scopes() if op_scopes is not None else None


def programs_announced(records, t1_ns):
    """The programs that the ring's ``step.program`` instants name up to
    ``t1_ns``, as ``[(program, t_ns)]`` with the newest first: a step that was
    traced anew holds several, and the newest before a window's end is the
    one the window's last steps ran."""
    found = [(r[span_reduce.START], r[span_reduce.CAUSE]['program'])
             for r in records or ()
             if len(r) == 8 and r[span_reduce.NAME] == 'step.program'
             and r[span_reduce.DUR] is None and r[span_reduce.START] <= t1_ns]
    return [(program, at) for at, program in sorted(found, reverse=True)]


def _type_key(result):
    """``f32[768,50257]`` as ``short_name`` spells it: ``f32_768_50257_``."""
    kind, _, dims = result.rstrip(']').partition('[')
    return kind + '_' + ''.join(d + '_' for d in dims.split(',') if d)


def instruction_of(key, instructions):
    """The name of the instruction an event came from, or ``None``: the part
    of ``key`` before its first result's type that ``instructions`` knows
    (``fusion.8_f32_4_`` is ``fusion.8`` and never ``fusion.85``; a name may
    hold underscores itself, ``compare_select_fusion.3``), and whose first
    result is the event's where the table says it (another program's
    ``fusion.8`` is not the step's)."""
    raw = _RAW.match(key)
    if raw:
        return raw.group(1) if raw.group(1) in instructions else None
    at = len(key) - 1
    while True:             # from the right: the longest name first
        at = key.rfind('_', 0, at)
        if at <= 0:
            return None
        name, rest = key[:at], key[at + 1:]
        known = instructions.get(name) if _TYPE.match(rest) else None
        if known is not None:
            result = known.get('result')
            return name if result is None or _type_key(result) == rest \
                else None


def reduce_scopes(per_op_s, program_tables, steps):
    """Milliseconds a step by ``(part, pass)`` of the window's operations:

    ``{'parts': {(part, pass): ms}, 'pallas': {(part, pass): ms of the part's
    Pallas custom calls, which 'parts' holds too}, 'total_ms' (every event
    but the containers), 'containers_ms' (left out), 'matched_ms',
    'mixed_ms' (fusions over more than one part), 'mixed' ({'body+norm':
    ms}: which parts they span; each is charged whole to its own scope's
    part) and 'mixed_parts' ({part: ms of it that sits in such fusions}),
    'events', 'matched', 'largest': {'other' | 'unscoped' | 'outside_step':
    [(ms, key, path)]}}``.

    Where two programs hold an instruction of one name, the first table of
    ``program_tables`` says what it is. The opcodes whose events cover what
    they run (``petastorm_tpu.models.scopes.CONTAINERS``: ``per_op_s``
    holds a ``cond`` and its branch's operations) are left out.

    An event no table knows (the harness's checksum, the program file's
    ``prepare``) is ``('outside_step', '-')``; an instruction with no scope
    has the pass ``'-'``. ``None`` without tables."""
    if program_tables is None:
        return None
    from petastorm_tpu.models.scopes import CONTAINERS
    instructions = {}
    for table in program_tables.values():
        for name, entry in table['instructions'].items():
            instructions.setdefault(name, entry)
    parts, pallas, mixed, mixed_parts = {}, {}, {}, {}
    largest = {'other': [], 'unscoped': [], OUTSIDE: []}
    containers = matched_s = total_s = 0.0
    matched = 0
    for key, seconds in per_op_s.items():
        name = instruction_of(key, instructions)
        entry = instructions[name] if name is not None else None
        if entry is not None and entry['opcode'] in CONTAINERS:
            containers += seconds
            continue
        total_s += seconds
        if entry is None:
            where, path = (OUTSIDE, '-'), ''
        else:
            matched += 1
            matched_s += seconds
            where, path = (entry['part'], entry['pass'] or '-'), entry['path']
            if entry['opcode'] == 'custom-call' \
                    and path.endswith('pallas_call'):
                pallas[where] = pallas.get(where, 0.0) + seconds
            if len(entry.get('parts_fused') or ()) > 1:
                spans = '+'.join(entry['parts_fused'])
                mixed[spans] = mixed.get(spans, 0.0) + seconds
                mixed_parts[where[0]] = mixed_parts.get(where[0], 0.0) \
                    + seconds
        parts[where] = parts.get(where, 0.0) + seconds
        if where[0] in largest:
            largest[where[0]].append((seconds, key, path))

    def ms(seconds):
        return 1e3 * seconds / steps

    return {'parts': {k: ms(v) for k, v in parts.items()},
            'pallas': {k: ms(v) for k, v in pallas.items()},
            'total_ms': ms(total_s), 'containers_ms': ms(containers),
            'matched_ms': ms(matched_s), 'mixed_ms': ms(sum(mixed.values())),
            'mixed': {k: ms(v) for k, v in mixed.items()},
            'mixed_parts': {k: ms(v) for k, v in mixed_parts.items()},
            'events': len(per_op_s), 'matched': matched,
            'largest': {which: [(ms(s), key, path) for s, key, path
                                in sorted(rows, reverse=True)[:LARGEST]]
                        for which, rows in largest.items()}}


def ms_of(reduced, parts=None, passes=None, table='parts'):
    """The milliseconds a step of ``reduced[table]`` in these parts and
    passes (``None``: every one)."""
    return sum((v for (part, which), v in reduced[table].items()
                if (parts is None or part in parts)
                and (passes is None or which in passes)), 0.0)


def lines(reduced, busy_ms=None):
    """The table as lines of text: part by pass, ms a step and the share of
    the summed time, what of the part its Pallas calls took and what of it
    sits in fusions that hold other parts' instructions too (``mixed``), then
    those fusions by the parts they span and what the join left over."""
    total = reduced['total_ms'] or 1.0
    names = sorted({part for part, _ in reduced['parts']},
                   key=lambda part: (-ms_of(reduced, (part,)), part))
    out = ['step parts: {} of {} events matched, {:.3f} of {:.3f} ms a step '
           '({:.2f} %){}; containers left out {:.3f} ms; fusions over more '
           'than one part {:.3f} ms ({:.1f} %)'.format(
               reduced['matched'], reduced['events'], reduced['matched_ms'],
               reduced['total_ms'], 100.0 * reduced['matched_ms'] / total,
               '' if busy_ms is None else ', busy {:.3f}'.format(busy_ms),
               reduced['containers_ms'], reduced['mixed_ms'],
               100.0 * reduced['mixed_ms'] / total),
           '{:<13}'.format('part') + ''.join(
               '{:>10}'.format(which) for which in PASSES)
           + '{:>10}{:>8}{:>10}{:>10}'.format('ms', '%', 'pallas', 'mixed')]
    for part in names:
        row = [reduced['parts'].get((part, which), 0.0) for which in PASSES]
        out.append('{:<13}'.format(part) + ''.join(
            '{:>10.3f}'.format(v) if v else '{:>10}'.format('.') for v in row)
            + '{:>10.3f}{:>8.2f}{:>10.3f}{:>10.3f}'.format(
                sum(row), 100.0 * sum(row) / total,
                ms_of(reduced, (part,), table='pallas'),
                reduced['mixed_parts'].get(part, 0.0)))
    for spans, value in sorted(reduced['mixed'].items(),
                               key=lambda kv: (-kv[1], kv[0]))[:6]:
        out.append('fusions over {}: {:.3f} ms'.format(spans, value))
    for which, rows in sorted(reduced['largest'].items()):
        for value, key, path in rows:
            out.append('largest {}: {:.3f} ms {} {}'.format(
                which, value, key, path))
    return out


def step_parts(ctx):
    """The reduction of this run's traced window, made once a run (the first
    metric that asks computes it, prints it through the harness's ``say`` and
    leaves it in ``ctx``). ``None`` without a trace or without a table."""
    if 'step_parts' in ctx:
        return ctx['step_parts']
    reduced = None
    t = ctx['trace']
    if t is not None:
        t0 = time.perf_counter()
        program_tables = tables()
        took = time.perf_counter() - t0
        if program_tables is not None:
            from perfbench.harness import say
            # The ring says which programs there are and when each came: the
            # window ran the newest before its end, so that one's table comes
            # first; one that came inside the window is a retrace to report.
            t0_ns, t1_ns = [ctx[end]['t'] * 1e9 if end in ctx else None
                            for end in ('begin', 'end')]
            announced = programs_announced(
                span_reduce.ring_records(),
                float('inf') if t1_ns is None else t1_ns)
            first = [program for program, _ in announced
                     if program in program_tables]
            program_tables = {program: program_tables[program]
                              for program in first + list(program_tables)}
            say('op_scopes(): {:.3f} s, {} program(s), {} instructions; '
                'step.program instants: {}'.format(
                    took, len(program_tables),
                    sum(len(table['instructions'])
                        for table in program_tables.values()),
                    ', '.join(program for program, _ in announced) or 'none'))
            for program, at in announced:
                if t0_ns is not None and at >= t0_ns:
                    say('step.program: {} was traced {:.3f} s into the '
                        'window: its steps ran two programs'.format(
                            program, (at - t0_ns) / 1e9))
            reduced = reduce_scopes(t['per_op_s'], program_tables, t['steps'])
            for line in lines(reduced, 1e3 * t['busy_s'] / t['steps']):
                say(line)
    ctx['step_parts'] = reduced
    return reduced


def read(ctx, parts=None, passes=None, less_pallas=False):
    """What a ``step.*_ms_per_step`` metric reads: ``None`` where the program
    has no table, else a number (0 where nothing ran in these parts)."""
    reduced = step_parts(ctx)
    if reduced is None:
        return None
    value = ms_of(reduced, parts, passes)
    if less_pallas:
        value -= ms_of(reduced, parts, passes, table='pallas')
    return value
