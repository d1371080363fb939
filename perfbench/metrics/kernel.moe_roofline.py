"""The least time the chip could take for the operations and bytes of the
held experts' grouped products (from the shapes: forward, recomputed forward
and both gradients) over the time the Pallas kernels took. The pairs counted
are the pairs routed: the program's ``moe.expert_load.e<slot>`` counters
(running totals of the step's ``expert_load``, summed over the expert layers)
read at the window's two ends, over the steps between them, so a seed whose
router sends the held experts more or fewer pairs than the expected 256 an
expert moves count and time together. What the empty tiles of the dropless
capacity and the padding of a group to whole tiles cost is in the time alone,
so they show as a lower share."""

from perfbench import span_reduce


def pairs_per_step(ctx):
    window = span_reduce.window_of(ctx)
    if not window:
        return None
    totals = {}
    for name, _, _, value in window['counters']:
        if name.startswith('moe.expert_load.e'):
            totals.setdefault(name, []).append(value)
    steps = min([len(values) - 1 for values in totals.values()] or [0])
    if steps < 1:
        return None
    return sum(values[steps] - values[0] for values in totals.values()) / steps


def read(ctx):
    t = ctx['trace']
    kernels = getattr(ctx['ref'], 'kernels', None)
    if t is None or kernels is None or ctx['peak'] is None:
        return None
    rows = ctx['batch'] // ctx['chips']
    if not kernels(ctx['cfg'], rows).get('moe'):
        return None
    pairs = pairs_per_step(ctx)
    if pairs is None:
        return None
    k = kernels(ctx['cfg'], rows, moe_pairs_per_step=pairs)['moe']
    s = ctx['trace_reduce'].kernel_seconds(t, k['match'])
    if s is None:
        return None
    share, _ = ctx['trace_reduce'].roofline_share(
        k['flops'], k['bytes'], s / t['steps'], ctx['peak'])
    return share
