"""Device time a step of the recomputed forward (instructions under
``rematted_computation``), every part (``Tracer.op_scopes``)."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.read(ctx, passes=('recompute',))
