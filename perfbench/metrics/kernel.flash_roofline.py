"""The least time the chip could take for the flash kernels' operations and
bytes (from the shapes) over the time they took; compute-bound at these
shapes."""


def read(ctx):
    t = ctx['trace']
    k = ctx['ref'].kernels(ctx['cfg'], ctx['batch'] // ctx['chips']).get('flash')
    if t is None or k is None or ctx['peak'] is None:
        return None
    s = ctx['trace_reduce'].kernel_seconds(t, k['match'])
    if s is None:
        return None
    steps = t['steps']
    share, _ = ctx['trace_reduce'].roofline_share(k['flops'], k['bytes'], s / steps, ctx['peak'])
    return share
