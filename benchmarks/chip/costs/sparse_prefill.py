"""Work of one sparse prefill call (one global layer, one chunk of one prompt).

Counted from the shapes and the configuration, never from the kernel.  The
chunk's tokens form query blocks of ``prefill_block_q``; per query block
(ending at ``q_end``, with ``nq`` live queries) and KV head of block size
``b``, the method attends the forced blocks (the sink block and the blocks
reaching into the ``local_pages`` pages before the query block, its own
included) and scores every other causal block, keeping the
``ceil(K_h * prefill_topk_scale)`` best:

- scoring: each candidate row (width ``2 * head_dim``, 4-bit codes plus a
  per-row f32 scale and zero) against the block's ``g * nq`` queries;
- attention: QK and PV over the attended tokens, reading their K and V;
- the block's queries in and outputs out.
"""
import math

KERNEL = "absparse_sparse_prefill"


def _blocks(q_start: int, q_end: int, b: int, sp):
    causal = q_end // b + 1
    lo = max(0, q_start - sp.local_pages * sp.page_size) // b
    forced = len({0} | set(range(lo, q_end // b + 1)))
    return causal, forced


def call(cfg, sparse_layer: int, offset: int, n: int, elem_bytes: int = 2):
    """-> (flops, bytes) of one call on tokens [offset, offset + n), on the
    ``sparse_layer``-th global-attention layer (``model.sparse_layers``),
    which reads that row of the block-size assignment."""
    sp = cfg.sparse
    hd, n_q, n_kv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    g = n_q // n_kv
    dp = 2 * hd
    code_bytes = dp * 4 // 8 if sp.quant.startswith("int4") else dp
    bq = sp.prefill_block_q
    flops = byts = 0
    for qs in range(offset, offset + n, bq):
        q_end = min(qs + bq, offset + n) - 1
        nq = q_end - qs + 1
        for b in sp.layer_block_sizes(sparse_layer, n_kv):
            k_h = sp.token_budget // b
            k_sel = math.ceil(k_h * sp.prefill_topk_scale)
            causal, forced = _blocks(qs, q_end, b, sp)
            cand = causal - forced
            attended = min(q_end + 1, (forced + min(k_sel, cand)) * b)
            flops += 2 * cand * dp * g * nq + 4 * g * nq * attended * hd
            byts += cand * (code_bytes + 8) + 2 * attended * hd * elem_bytes
        byts += 2 * n_q * nq * hd * elem_bytes
    return flops, byts
