"""Device time a step in fusions whose fused instructions belong to more than
one part of the model (a weight gradient with its AdamW update, a convolution
with its batch norm): each is counted whole under its own scope's part, so
this is how much of the other ``step.*_ms_per_step`` numbers is shared with
a second part (``Tracer.op_scopes``' ``parts_fused``; the log's table says
which parts, and how much of each)."""

from perfbench import scope_reduce


def read(ctx):
    reduced = scope_reduce.step_parts(ctx)
    return None if reduced is None else reduced['mixed_ms']
