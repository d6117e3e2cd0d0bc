"""Model FLOPs of the decoder, counted layer by layer from each layer's
kind: what one token needs, for MFU.

Matrix products count two FLOPs per multiply-add.  Every attention layer
(``attn``, ``local_attn``) has its projections; every layer's FFN is dense
(``3 d d_ff``) or routed (the router, ``d E``, and the ``experts_per_token``
experts a token goes to, ``3 d d_ff`` each: the model's work, not the
capacity padding).  The LM head counts for every decoded token and is left
out of prefill (once per prompt, under 0.1% of a prompt's work).  Attention
over the context counts the work the method does: the sparse kernels' own
cost functions on the global layers (``model.sparse_layers``) and exact
window attention on the window layers, not dense attention.  A layer kind
with no count here (``rglru``, ``rwkv``) makes the step's count ``None``:
never a partial number.
"""
from costs import fused_decode, sparse_prefill, window_attention
from model import sparse_layers

COUNTED = ("attn", "local_attn")


def uncounted(cfg) -> tuple:
    """The layer kinds of ``cfg`` that have no count here."""
    return tuple(sorted(set(cfg.layer_kinds) - set(COUNTED)))


def dense_per_token(cfg):
    """FLOPs of one token that do not depend on the context: every layer's
    projections and FFN.  ``None`` where a layer kind has no count."""
    if uncounted(cfg):
        return None
    d, hd = cfg.d_model, cfg.resolved_head_dim
    proj = d * cfg.n_heads * hd * 2 + 2 * d * cfg.n_kv_heads * hd
    if cfg.moe is None:
        ffn = 3 * d * cfg.d_ff
    else:
        ffn = d * cfg.moe.n_experts + cfg.moe.experts_per_token * 3 * d * cfg.d_ff
    return 2 * (proj + ffn) * cfg.n_layers


def head(cfg) -> int:
    return 2 * cfg.d_model * cfg.vocab_size


def decode_step(cfg, live_lengths):
    """FLOPs of one decode step over slots of these live lengths."""
    per_token = dense_per_token(cfg)
    if per_token is None:
        return None
    attn = sum(fused_decode.call(cfg, i, live_lengths)[0]
               for i in range(len(sparse_layers(cfg))))
    n_window = cfg.layer_kinds.count("local_attn")
    if n_window:
        attn += n_window * window_attention.decode(cfg, live_lengths)[0]
    return len(live_lengths) * (per_token + head(cfg)) + attn


def prefill_chunk(cfg, offset: int, n: int):
    """FLOPs of one prompt chunk (without the LM head: see ``head``)."""
    per_token = dense_per_token(cfg)
    if per_token is None:
        return None
    attn = sum(sparse_prefill.call(cfg, i, offset, n)[0]
               for i in range(len(sparse_layers(cfg))))
    n_window = cfg.layer_kinds.count("local_attn")
    if n_window:
        attn += n_window * window_attention.prefill(cfg, offset, n)[0]
    return n * per_token + attn
