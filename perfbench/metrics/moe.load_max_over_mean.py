"""The busiest held expert's pairs over the mean held expert's, over the
window: the program's ``moe.expert_load.e<slot>`` counters (running totals of
the step's ``expert_load``, summed over the expert layers) read at the
window's two ends. 1 is even routing; the grouped products' time follows the
busiest expert's tiles."""

from perfbench import span_reduce


def read(ctx):
    window = span_reduce.window_of(ctx)
    if not window:
        return None
    totals = {}
    for name, _, _, value in window['counters']:
        if name.startswith('moe.expert_load.e'):
            totals.setdefault(name, []).append(value)
    loads = [values[-1] - values[0] for values in totals.values()
             if len(values) > 1]
    if not loads or not sum(loads):
        return None
    return max(loads) * len(loads) / sum(loads)
