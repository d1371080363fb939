"""Device time a step of the part ``optimizer``: the gradients' casts, the
update and its application (``Tracer.op_scopes``). Listed for the cells whose
leaves update in fusions of their own; where the compiler fuses a weight
gradient with its update the fusion counts where its own scope says, the
gradient's part, and ``step.mixed_fusions_ms_per_step`` holds it."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.read(ctx, ('optimizer',))
