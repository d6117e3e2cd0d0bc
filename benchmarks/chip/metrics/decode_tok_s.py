"""Output tokens returned in the window over the window's seconds."""
from stats import rate


def read(ctx):
    return rate(ctx.served, ctx.window[0], ctx.window[1])
