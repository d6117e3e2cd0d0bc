"""Model FLOPs of the prompt chunks in the traced window per second of
the window, over the chip's peak: the whole prefill's share of the peak.
None, with a note, where a layer kind has no count."""
from costs import model


def read(ctx):
    if not ctx.log.chunks or ctx.trace is None:
        return None
    flops = [model.prefill_chunk(ctx.cfg, o, n) for o, n in ctx.log.chunks]
    if None in flops:
        ctx.notes["prefill_mfu"] = "no FLOP count for layer kinds " + ", ".join(
            model.uncounted(ctx.cfg))
        return None
    return 100.0 * sum(flops) / ctx.trace.window_s / ctx.peak_flops
