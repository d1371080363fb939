"""Device time a step of the feed-forward sub-layers: ``ffn.dense``,
``ffn.routed`` and ``ffn.shared``, every pass (``Tracer.op_scopes``)."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.read(ctx, ('ffn.dense', 'ffn.routed', 'ffn.shared'))
