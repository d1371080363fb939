"""Seconds in JaxLoader._stage (issuing the transfers) for a batch."""


def read(ctx):
    return 1e3 * (ctx['end']['stats'].get('stage_dispatch_s', 0.0) - ctx['begin']['stats'].get('stage_dispatch_s', 0.0)) / ctx['steps']
