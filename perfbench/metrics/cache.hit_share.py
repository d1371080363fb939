"""Chunks the cache served over chunks asked for in the window; a reader with
no cache misses every chunk."""


def read(ctx):
    hits = ctx['end']['cache_hits'] - ctx['begin']['cache_hits']
    misses = ctx['end']['cache_misses'] - ctx['begin']['cache_misses']
    return 100.0 * hits / (hits + misses) if hits + misses else None
