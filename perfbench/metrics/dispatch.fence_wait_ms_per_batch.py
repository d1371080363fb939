"""Seconds a batch that the dispatch side spent blocked on the oldest
transfer in flight (``dispatch.fence``: the engine's window and the
per-device streams')."""

from perfbench import span_reduce


def read(ctx):
    window = span_reduce.window_of(ctx)
    batches = len(span_reduce.named(window['spans'], 'dispatch.stage')) \
        if window else 0
    return 1e3 * span_reduce.seconds(window['spans'], 'dispatch.fence') \
        / batches if batches else None
