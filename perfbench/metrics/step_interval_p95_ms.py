"""95th percentile of the time between consecutive step completions, over every
step of the window."""


def read(ctx):
    return 1e3 * ctx['percentile'](ctx['intervals'], 0.95)
