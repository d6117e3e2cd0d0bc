"""Process start to the window's start: imports, device start, weights,
engine, compilation (or the compile cache) and warm-up."""


def read(ctx):
    return ctx.setup_s
