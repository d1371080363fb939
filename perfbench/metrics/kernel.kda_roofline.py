"""The least time the chip could take for the operations and bytes of Kimi
delta attention's chunked rule (from the shapes, forward and in reverse once
each) over the time its Pallas kernels took, the recomputed forward included;
memory-bound at these shapes. Count and time cover the same work: the events
named ``kda*`` hold the whole rule, the decayed sub-block products and the
unit-lower inverse with it."""


def read(ctx):
    t = ctx['trace']
    kernels = getattr(ctx['ref'], 'kernels', None)
    k = kernels and kernels(ctx['cfg'], ctx['batch'] // ctx['chips']).get('kda')
    if t is None or not k or ctx['peak'] is None:
        return None
    s = ctx['trace_reduce'].kernel_seconds(t, k['match'])
    if s is None:
        return None
    share, _ = ctx['trace_reduce'].roofline_share(
        k['flops'], k['bytes'], s / t['steps'], ctx['peak'])
    return share
