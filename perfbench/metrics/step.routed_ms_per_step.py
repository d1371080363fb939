"""Device time a step of the part ``ffn.routed``: router, selection, the
dispatch plan, the row gathers, the grouped products, ``token_sums`` and the
dense fallback, every pass (``Tracer.op_scopes``)."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.read(ctx, ('ffn.routed',))
