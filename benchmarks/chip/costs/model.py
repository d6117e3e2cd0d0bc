"""Model FLOPs of the dense decoder: what one token needs, for MFU.

Matrix products count two FLOPs per multiply-add; the LM head counts for
every decoded token and is left out of prefill (once per prompt, under
0.1% of a prompt's work); attention counts the sparse work the method does
(the kernels' own cost functions), not dense attention over the context.
"""
from costs import fused_decode, sparse_prefill


def dense_per_token(cfg) -> int:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    per_layer = (d * cfg.n_heads * hd * 2 + 2 * d * cfg.n_kv_heads * hd
                 + 3 * d * cfg.d_ff)
    return 2 * per_layer * cfg.n_layers


def head(cfg) -> int:
    return 2 * cfg.d_model * cfg.vocab_size


def decode_step(cfg, live_lengths) -> int:
    """FLOPs of one decode step over slots of these live lengths."""
    attn = sum(fused_decode.call(cfg, l, live_lengths)[0]
               for l in range(cfg.n_layers))
    return len(live_lengths) * (dense_per_token(cfg) + head(cfg)) + attn


def prefill_chunk(cfg, offset: int, n: int) -> int:
    """FLOPs of one prompt chunk (without the LM head: see ``head``)."""
    attn = sum(sparse_prefill.call(cfg, l, offset, n)[0]
               for l in range(cfg.n_layers))
    return n * dense_per_token(cfg) + attn
