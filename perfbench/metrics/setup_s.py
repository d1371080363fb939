"""Process start to the window opening: store, weights, compilation, check
steps, cache fill, warm-up."""


def read(ctx):
    return ctx['setup_s']
