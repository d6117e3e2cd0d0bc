"""Work of one sliding-window attention layer (kind ``local_attn``).

Counted from the shapes and the configuration.  A query at position ``p``
attends exactly the keys at ``(p - W, p]``, its own included (``W`` the
configuration's ``local_window``): ``min(p + 1, W)`` keys, QK and PV for
each of the ``n_q`` query heads, reading their K and V in the cache dtype;
the queries in and the outputs out in the activation dtype.
"""


def decode(cfg, live_lengths, elem_bytes: int = 2):
    """-> (flops, bytes) of one layer's decode over slots of these live
    lengths (the new token's key included)."""
    hd, n_q, n_kv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    keys = [min(int(L), cfg.local_window) for L in live_lengths]
    flops = 4 * n_q * hd * sum(keys)
    byts = 2 * n_kv * hd * elem_bytes * sum(keys)
    return flops, byts + 2 * len(keys) * n_q * hd * elem_bytes


def prefill(cfg, offset: int, n: int, elem_bytes: int = 2):
    """-> (flops, bytes) of one layer on prompt tokens [offset, offset + n)."""
    hd, n_q, n_kv, W = (cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads,
                        cfg.local_window)
    keys = sum(min(p + 1, W) for p in range(offset, offset + n))
    read = offset + n - max(0, offset + 1 - W)     # keys the chunk touches
    return (4 * n_q * hd * keys,
            2 * n_kv * hd * elem_bytes * read + 2 * n * n_q * hd * elem_bytes)
