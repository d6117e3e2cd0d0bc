"""Model FLOPs of the decode steps in the traced window (projections,
FFNs, LM head, the sparse and window attention the method does) per
second of the window, over the chip's peak: the whole decode's share of
the peak.  None, with a note, where a layer kind has no count."""
from costs import model


def read(ctx):
    steps = [s for s in ctx.log.decode_steps if s]
    if not steps or ctx.trace is None:
        return None
    flops = [model.decode_step(ctx.cfg, lens) for lens in steps]
    if None in flops:
        ctx.notes["decode_mfu"] = "no FLOP count for layer kinds " + ", ".join(
            model.uncounted(ctx.cfg))
        return None
    return 100.0 * sum(flops) / ctx.trace.window_s / ctx.peak_flops
