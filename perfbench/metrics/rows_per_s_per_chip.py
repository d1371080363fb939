"""All rows of all steps completed in the window, over the whole window, over
the chips."""


def read(ctx):
    return ctx['rate']
