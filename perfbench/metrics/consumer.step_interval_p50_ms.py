"""Median time between consecutive step completions: the steady statistic
beside the tail."""


def read(ctx):
    return 1e3 * ctx['percentile'](ctx['intervals'], 0.5)
