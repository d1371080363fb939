"""Share of the window the assemble thread spent blocked pulling from the
reader: high means read and decode set the pace, near 0 that collate does."""


def read(ctx):
    return 100.0 * (ctx['end']['stats'].get('reader_wait_s', 0.0) - ctx['begin']['stats'].get('reader_wait_s', 0.0)) / ctx['window_s']
