"""Device time a chip spends in a step's collective operations, as the trace
names them on the chip's line of operations (``all-reduce``, ``all-gather``,
``reduce-scatter``, ``collective-permute``, ``all-to-all``; an asynchronous
one is its ``-start`` and its ``-done``, the time the core is held by either,
and what runs between the two is compute that hides it). Nothing to read
where the step has none: one chip."""


def read(ctx):
    t = ctx['trace']
    if t is None:
        return None
    s = ctx['trace_reduce'].kernel_seconds(
        t, r'^(all-reduce|all-gather|reduce-scatter|collective-permute|'
           r'all-to-all)(-start|-done)?[._]')
    return None if s is None else 1e3 * s / t['steps']
