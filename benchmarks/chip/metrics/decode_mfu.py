"""Model FLOPs of the decode steps in the traced window (dense layers, LM
head, the sparse attention the method does) per second of the window,
over the chip's peak: the whole decode's share of the peak."""
from costs import model


def read(ctx):
    steps = [s for s in ctx.log.decode_steps if s]
    if not steps or ctx.trace is None:
        return None
    flops = sum(model.decode_step(ctx.cfg, lens) for lens in steps)
    return 100.0 * flops / ctx.trace.window_s / ctx.peak_flops
