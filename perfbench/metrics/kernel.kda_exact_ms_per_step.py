"""Device time of Kimi delta attention's Pallas kernels on the rule's exact
path (the whole chunked rule with a decay of no bound: forward, recomputed
forward and backward) in a step: the trace's events named ``kda_exact*``. A
reference with no ``kda_exact`` kernel, or a trace without such events (a
program before the exact path), gives nothing."""


def read(ctx):
    t = ctx['trace']
    kernels = getattr(ctx['ref'], 'kernels', None)
    k = kernels and kernels(ctx['cfg'],
                            ctx['batch'] // ctx['chips']).get('kda_exact')
    if t is None or not k:
        return None
    s = ctx['trace_reduce'].kernel_seconds(t, k['match'])
    if s is None:
        return None
    return 1e3 * s / t['steps']
