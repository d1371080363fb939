"""Loader construction to the first batch in hand (set-up, cold reader)."""


def read(ctx):
    return ctx['first_batch_s']
