"""Peak resident set of the process (getrusage): spreads by a tenth and more
between runs, so it bounds nothing."""


def read(ctx):
    return ctx['end']['rss_peak_mb']
