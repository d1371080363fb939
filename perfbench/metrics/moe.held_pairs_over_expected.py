"""The pairs the held experts were sent over the window, over what uniform
routing would send them (the held share of ``T * top_k`` an expert layer and
row): the program's ``moe.expert_load.e<slot>`` counters (running totals of
the step's ``expert_load``, summed over the expert layers) read at the
window's two ends, over the steps between them. 1 is the held experts' share;
under a selection by groups a small held share need not get it."""

from perfbench import span_reduce


def read(ctx):
    ref = ctx['ref']
    expected = getattr(ref, 'expected_pairs_per_row', None)
    window = span_reduce.window_of(ctx)
    if expected is None or not window:
        return None
    totals = {}
    for name, _, _, value in window['counters']:
        if name.startswith('moe.expert_load.e'):
            totals.setdefault(name, []).append(value)
    steps = min([len(values) - 1 for values in totals.values()] or [0])
    if steps < 1:
        return None
    layers = sum('moe' in kind for kind in ref.layer_kinds(ctx['cfg']))
    sent = sum(values[steps] - values[0] for values in totals.values()) / steps
    return sent / (layers * expected(ctx['cfg']) * ctx['batch'] // ctx['chips'])
