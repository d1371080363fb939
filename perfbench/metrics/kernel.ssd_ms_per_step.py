"""Device time of the Mamba-2 state-space rule's Pallas kernels (the whole
chunked rule: forward, recomputed forward and backward) in a step: the
trace's events named ``ssd*``. A reference with no ``ssd`` kernel, or a trace
without such events (a program before the kernels), gives nothing."""


def read(ctx):
    t = ctx['trace']
    kernels = getattr(ctx['ref'], 'kernels', None)
    k = kernels and kernels(ctx['cfg'],
                            ctx['batch'] // ctx['chips']).get('ssd')
    if t is None or not k:
        return None
    s = ctx['trace_reduce'].kernel_seconds(t, k['match'])
    if s is None:
        return None
    return 1e3 * s / t['steps']
