"""Busy seconds of the assemble thread (paused while it pulls from the reader)
for a batch."""


def read(ctx):
    return 1e3 * (ctx['end']['stats'].get('assemble_s', 0.0) - ctx['begin']['stats'].get('assemble_s', 0.0)) / ctx['steps']
