"""Device busy time for a step in the traced window."""


def read(ctx):
    t = ctx['trace']
    if t is None:
        return None
    return 1e3 * t['busy_s'] / t['steps']
