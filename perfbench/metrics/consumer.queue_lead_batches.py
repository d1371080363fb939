"""The loader's lead over the training loop, in batches: how long the
window's batches had been staged when the loop took them (median), over
the time between two takes (median). About ``prefetch`` + 1 where the step
sets the pace, 0 where the loader does."""

from perfbench import span_reduce


def read(ctx):
    window = span_reduce.window_of(ctx)
    return span_reduce.lead_batches(window) if window else None
