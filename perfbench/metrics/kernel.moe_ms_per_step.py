"""Device time of the held experts' grouped products (the Pallas kernels of
``ops.grouped_matmul``: forward, recomputed forward and the two gradients of
every expert layer) in a step. Routing, the gathers to and from the rows and
the shared expert run as XLA operations under other names and are not in it."""


def read(ctx):
    t = ctx['trace']
    kernels = getattr(ctx['ref'], 'kernels', None)
    k = kernels and kernels(ctx['cfg'], ctx['batch'] // ctx['chips']).get('moe')
    if t is None or not k:
        return None
    s = ctx['trace_reduce'].kernel_seconds(t, k['match'])
    if s is None:
        return None
    return 1e3 * s / t['steps']
