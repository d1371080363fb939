"""Share of the traced window's summed operation time that the program's table
(``Tracer.op_scopes``) puts in a part of the model: everything but ``other``
(a scope no rule knows), ``unscoped`` (the compiler's own copies and prefetch
waits) and ``outside_step`` (events of no instruction of the step: a join
that broke shows here, as a low share)."""

from perfbench import scope_reduce


def read(ctx):
    reduced = scope_reduce.step_parts(ctx)
    if reduced is None or not reduced['total_ms']:
        return None
    left = scope_reduce.ms_of(reduced, ('other', 'unscoped',
                                        scope_reduce.OUTSIDE))
    return 100.0 * (1.0 - left / reduced['total_ms'])
