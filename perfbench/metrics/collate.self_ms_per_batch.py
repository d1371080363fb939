"""Mean self time of the window's ``collate.batch`` spans: their duration
less what their ``collate.reader_wait`` and ``collate.arena_wait`` children
cover."""

from perfbench import span_reduce


def read(ctx):
    window = span_reduce.window_of(ctx)
    self_s, count = span_reduce.self_seconds(
        window['spans'], 'collate.batch') if window else (0.0, 0)
    return 1e3 * self_s / count if count else None
