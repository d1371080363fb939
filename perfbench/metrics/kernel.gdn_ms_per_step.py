"""Device time of the gated delta rule's Pallas kernels (the pass over
chunks, forward, recomputed forward and backward) in a step. The rule's
batched transform runs as XLA fusions under other names and is not in it."""


def read(ctx):
    t = ctx['trace']
    kernels = getattr(ctx['ref'], 'kernels', None)
    k = kernels and kernels(ctx['cfg'], ctx['batch'] // ctx['chips']).get('gdn')
    if t is None or not k:
        return None
    s = ctx['trace_reduce'].kernel_seconds(t, k['match'])
    if s is None:
        return None
    return 1e3 * s / t['steps']
