"""Sparse prefill kernel: least time for the work it did (max of FLOPs /
peak and bytes / bandwidth, summed over calls, one a global-attention
layer a chunk) over its device time."""
from costs import sparse_prefill
from model import sparse_layers


def read(ctx):
    t, n = ctx.trace.time_matching(sparse_prefill.KERNEL)
    chunks = ctx.log.chunks
    if not n or not chunks:
        return None
    pf, pb = ctx.peak_flops, ctx.peak_bytes
    least = flops_bound = 0.0
    calls = 0
    for offset, size in chunks:
        for i in range(len(sparse_layers(ctx.cfg))):
            f, b = sparse_prefill.call(ctx.cfg, i, offset, size)
            least += max(f / pf, b / pb)
            flops_bound += f / pf
            calls += 1
    ctx.notes["sparse_prefill_bound"] = (
        "compute" if flops_bound > 0.5 * least else "memory")
    return 100.0 * (least / calls) * n / t
