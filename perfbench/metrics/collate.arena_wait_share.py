"""Share of the window the assembler spent blocked on the arena pool
(``collate.arena_wait``)."""

from perfbench import span_reduce


def read(ctx):
    window = span_reduce.window_of(ctx)
    if not window or not span_reduce.named(window['spans'], 'collate.batch'):
        return None
    return 100.0 * span_reduce.seconds(window['spans'], 'collate.arena_wait') \
        / ((window['t1_ns'] - window['t0_ns']) / 1e9)
