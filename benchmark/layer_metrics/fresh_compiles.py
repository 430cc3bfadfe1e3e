"""Compiles during set-up that the persistent cache did not answer."""


def read(ctx):
    c = ctx["compile"]
    return c["compiles"] - c["cache_hits"]
