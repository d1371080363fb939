"""The least time the chip could take for the operations and bytes of the
Mamba-2 state-space rule (from the shapes, forward and in reverse once each)
over the time its Pallas kernels took, the recomputed forward included. Count
and time cover the same work: the events named ``ssd*`` hold the whole rule,
the decays, ``C B^T`` and the state's reads and writes with it."""


def read(ctx):
    t = ctx['trace']
    kernels = getattr(ctx['ref'], 'kernels', None)
    k = kernels and kernels(ctx['cfg'],
                            ctx['batch'] // ctx['chips']).get('ssd')
    if t is None or not k or ctx['peak'] is None:
        return None
    s = ctx['trace_reduce'].kernel_seconds(t, k['match'])
    if s is None:
        return None
    share, _ = ctx['trace_reduce'].roofline_share(
        k['flops'], k['bytes'], s / t['steps'], ctx['peak'])
    return share
