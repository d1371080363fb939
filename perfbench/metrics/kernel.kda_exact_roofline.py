"""The least time the chip could take for the operations and bytes of Kimi
delta attention's rule on its exact path (from the shapes, forward and in
reverse once each: the products before each sub-block, the sub-blocks' own
pairs one by one, the inverse and the products with the state) over the time
its Pallas kernels took, the recomputed forward included; memory-bound at
these shapes. Count and time cover the same work: the events named
``kda_exact*`` hold the whole rule."""


def read(ctx):
    t = ctx['trace']
    kernels = getattr(ctx['ref'], 'kernels', None)
    k = kernels and kernels(ctx['cfg'],
                            ctx['batch'] // ctx['chips']).get('kda_exact')
    if t is None or not k or ctx['peak'] is None:
        return None
    s = ctx['trace_reduce'].kernel_seconds(t, k['match'])
    if s is None:
        return None
    share, _ = ctx['trace_reduce'].roofline_share(
        k['flops'], k['bytes'], s / t['steps'], ctx['peak'])
    return share
