"""memory_stats()['peak_bytes_reserved'] on the fullest chip: what the
allocator had to reserve at the worst moment, the programs' scratch with it."""


def read(ctx):
    return ctx['memory_peak_bytes'] / 1e9 if ctx['memory_peak_bytes'] else None
