"""Device time a step of ``embed``, ``head`` (final norm, head, next-token
heads) and ``loss``, every pass (``Tracer.op_scopes``)."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.read(ctx, ('embed', 'head', 'loss'))
