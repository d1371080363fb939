"""Share of the host's staging work that ran while a transfer was in flight;
only the per-device streams report it."""


def read(ctx):
    return ctx['end']['stats'].get('h2d_overlap_frac')
