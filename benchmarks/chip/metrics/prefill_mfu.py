"""Model FLOPs of the prompt chunks in the traced window per second of
the window, over the chip's peak: the whole prefill's share of the peak."""
from costs import model


def read(ctx):
    if not ctx.log.chunks or ctx.trace is None:
        return None
    flops = sum(model.prefill_chunk(ctx.cfg, o, n) for o, n in ctx.log.chunks)
    return 100.0 * flops / ctx.trace.window_s / ctx.peak_flops
