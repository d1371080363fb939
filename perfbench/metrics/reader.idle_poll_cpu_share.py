"""CPU the pool's worker threads burnt waiting (inside ``reader.take`` and
``reader.publish``) or under no span at all, over all their CPU in the window:
what the host pays for workers that have nothing to read or decode."""

from perfbench import span_reduce


def read(ctx):
    window = span_reduce.window_of(ctx)
    threads = window and span_reduce.thread_cpu(
        window, 'reader.thread_cpu', ('reader.take', 'reader.publish'))
    total = sum(t['total_ns'] for t in threads.values()) if threads else 0
    if not total:
        return None
    idle = sum(t['in_waits_ns'] + t['total_ns'] - t['in_spans_ns']
               for t in threads.values())
    return 100.0 * idle / total
