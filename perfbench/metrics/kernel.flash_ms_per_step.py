"""Device time of the flash-attention kernels (forward and backward) in a step."""


def read(ctx):
    t = ctx['trace']
    k = ctx['ref'].kernels(ctx['cfg'], ctx['batch'] // ctx['chips']).get('flash')
    if t is None or k is None:
        return None
    s = ctx['trace_reduce'].kernel_seconds(t, k['match'])
    if s is None:
        return None
    return 1e3 * s / t['steps']
