"""Device time of the engine's jitted prefill-chunk program per call."""


def read(ctx):
    t, n = ctx.trace.time_matching("prefill_chunk", modules=True)
    return 1e3 * t / n if n else None
