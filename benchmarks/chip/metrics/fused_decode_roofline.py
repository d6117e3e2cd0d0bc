"""Fused decode kernel: least time the chip could take for the work it
did (max of FLOPs / peak FLOP/s and bytes / peak bandwidth, summed over
calls, one a global-attention layer a step) over the kernel's summed
device time."""
from costs import fused_decode
from model import sparse_layers


def read(ctx):
    t, n = ctx.trace.time_matching(fused_decode.KERNEL)
    steps = [s for s in ctx.log.decode_steps if s]
    if not n or not steps:
        return None
    pf, pb = ctx.peak_flops, ctx.peak_bytes
    least = flops_bound = 0.0
    calls = 0
    for lens in steps:
        for i in range(len(sparse_layers(ctx.cfg))):
            f, b = fused_decode.call(ctx.cfg, i, lens)
            least += max(f / pf, b / pb)
            flops_bound += f / pf
            calls += 1
    ctx.notes["fused_decode_bound"] = (
        "compute" if flops_bound > 0.5 * least else "memory")
    return 100.0 * (least / calls) * n / t
