"""Work of one fused sparse decode call (one global layer, the whole batch).

Counted from the shapes and the configuration, never from the kernel, so
the count stays the same whatever implements the work.  Per live slot of
context length ``L`` and per KV head of block size ``b``:

- scoring: ``ceil(L / b)`` rank-key rows of width ``2 * head_dim`` against
  the group's ``g`` queries (a multiply and an add each), reading the 4-bit
  codes and that head's f32 scale and zero;
- attention: ``min(L, budget)`` selected tokens, QK and PV for ``g``
  queries, reading their K and V in the cache dtype;
- the slot's queries in and outputs out, in the activation dtype.
"""
import numpy as np

#: the kernel's name as it appears among the trace's device ops
KERNEL = "absparse_fused_decode"


def call(cfg, sparse_layer: int, live_lengths, elem_bytes: int = 2):
    """-> (flops, bytes) of one call over slots of these live lengths, on
    the ``sparse_layer``-th global-attention layer (``model.sparse_layers``),
    which reads that row of the block-size assignment."""
    sp = cfg.sparse
    hd, n_q, n_kv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    g = n_q // n_kv
    dp = 2 * hd                               # Quest rank key: [max, min]
    code_bytes = dp * 4 // 8 if sp.quant.startswith("int4") else dp
    L = np.asarray(live_lengths, np.int64)[:, None]            # [slots, 1]
    b = np.asarray(sp.layer_block_sizes(sparse_layer, n_kv), np.int64)[None]
    rows = -(-L // b)                                          # ceil
    sel = np.minimum(L, sp.token_budget)
    flops = 2 * rows * dp * g + 4 * g * sel * hd
    byts = rows * code_bytes + 2 * dp * 4 + 2 * sel * hd * elem_bytes
    return (int(flops.sum()),
            int(byts.sum()) + 2 * len(live_lengths) * n_q * hd * elem_bytes)
