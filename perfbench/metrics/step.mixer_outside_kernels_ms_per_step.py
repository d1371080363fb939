"""``step.mixer_ms_per_step`` less the part's Pallas custom calls: what the
mixers run as XLA operations around their kernels."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.read(ctx, ('mixer',), less_pallas=True)
