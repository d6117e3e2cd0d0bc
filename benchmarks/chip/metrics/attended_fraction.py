"""Share of the live context that decode attention reads: KV pages the
selection sent to attention (the engine's device-side sparsity counters,
summed over heads, global-attention layers and slots) times the page
size, over the live tokens of the same steps times heads and those
layers.  Read in the counting pass after the window, as the counters are
a program of their own."""
from model import sparse_layers

NEEDS = ("telemetry",)


def read(ctx):
    log = ctx.counted
    if log is None or log.telemetry_pages is None:
        return None
    steps = [s for s in log.decode_steps if s]
    if not steps:
        return None
    cfg = ctx.cfg
    live = sum(sum(s) for s in steps) * cfg.n_kv_heads * len(sparse_layers(cfg))
    return 100.0 * log.telemetry_pages * cfg.sparse.page_size / live
