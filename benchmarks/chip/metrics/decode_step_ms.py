"""Device time of the engine's jitted decode program per call."""


def read(ctx):
    t, n = ctx.trace.time_matching("decode_step", modules=True)
    return 1e3 * t / n if n else None
