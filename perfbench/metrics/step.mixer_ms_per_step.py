"""Device time a step of the part ``mixer`` (attention, latent attention,
``gdn``, ``kda``: norms, projections, convolutions, gates, kernels), every
pass, by the program's table of its compiled step (``Tracer.op_scopes``)."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.read(ctx, ('mixer',))
