"""Share of the window the training loop spent blocked in JaxLoader.__next__:
the queue wait only, beside device.idle_share and never instead of it."""


def read(ctx):
    return (ctx['end']['stats'].get('wait_s', 0.0) - ctx['begin']['stats'].get('wait_s', 0.0)) / ctx['window_s']
