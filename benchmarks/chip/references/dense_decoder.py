"""Plain reference: a dense decoder (RMSNorm, RoPE, GQA attention with an
optional QKV bias, SwiGLU MLP, untied LM head) with AB-Sparse attention,
in ``jax.numpy`` and float32 at ``Precision.HIGHEST``.

It imports nothing of the program.  It follows the published layer
equations of the configuration's source and the AB-Sparse method as the
configuration states it:

- every KV head ``h`` splits the context into blocks of ``b_h`` tokens
  (the committed per-(layer, head) assignment);
- a block's rank key is the per-channel [max, min] of its keys (Quest);
  a query's block score is ``sum_d max(q_d * kmax_d, q_d * kmin_d)`` on
  the dequantized 4-bit asymmetric rank key, max-pooled over the query
  heads of the KV group;
- prompt tokens (chunked sparse prefill) are taken in query blocks of
  ``prefill_block_q``: each block attends the sink block, the blocks
  reaching into the ``local_pages`` pages before it and its own, plus the
  ``ceil(K_h * prefill_topk_scale)`` best-scored remaining blocks, scored
  on rank keys quantized per block row (one scale and zero over the row);
- decoded tokens each attend the sink block, the blocks reaching into the
  last ``local_pages`` pages, and the best-scored blocks up to
  ``K_h = budget / b_h`` blocks in all, scored on rank keys quantized per
  (head, channel) with the scale and zero fixed when the prompt completed
  (over the cache as it then stood: the prompt, zeros after it);
- attention over the selected tokens is exact softmax attention.

Where the program keeps its KV cache in bfloat16, the reference keeps
float32; that, and the order of sums, is all that separates the two.

``lower`` replaces float32 with float8 (e4m3) on both operands of every
matrix product: the control, the precision a later change might be
tempted to serve at.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG, POS = -1e30, 1e30


def _cast(a, lower: bool):
    a = a.astype(jnp.float32)
    if lower:
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return a


def _mm(x, w, lower, spec="nd,df->nf"):
    return jnp.einsum(spec, _cast(x, lower), _cast(w, lower), precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x [N, H, D]; rotate the two halves (GPT-NeoX / Hugging Face order)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rank_rows(k, bsz, cands, nb_max):
    """k [S, H, D] -> Quest rank keys [H, nb_max, 2D] at each head's block
    size (rows past the head's block count are zero)."""
    S, H, D = k.shape
    out = jnp.zeros((H, nb_max, 2 * D), jnp.float32)
    for c in cands:
        kb = k.reshape(S // c, c, H, D)
        rk = jnp.concatenate([kb.max(1), kb.min(1)], -1)        # [S/c, H, 2D]
        rk = jnp.pad(jnp.moveaxis(rk, 1, 0), ((0, 0), (0, nb_max - S // c), (0, 0)))
        out = jnp.where((bsz == c)[:, None, None], rk, out)
    return out


def _q4(x, lo, hi):
    """4-bit asymmetric round trip under (min, max) statistics."""
    scale = jnp.maximum((hi - lo) / 15.0, 1e-8)
    return jnp.clip(jnp.round((x - lo) / scale), 0, 15) * scale + lo


def _quest_query(q):
    return jnp.concatenate([jnp.maximum(q, 0.0), jnp.minimum(q, 0.0)], -1)


def _topk_mask(scores, k_h, kmax):
    """scores [..., H, M] -> bool mask of the ``k_h[h]`` best blocks with a
    score above NEG; among equal scores the lower block index ranks first
    (``lax.top_k`` order)."""
    vals = jax.lax.top_k(scores, kmax)[0]                        # [..., H, kmax]
    kth = jnp.take_along_axis(
        vals, jnp.broadcast_to(k_h[:, None] - 1, vals.shape[:-1] + (1,)), -1)
    above = scores > kth
    tied = scores == kth
    room = k_h[:, None] - above.sum(-1, keepdims=True)
    take = above | (tied & (jnp.cumsum(tied, -1) <= room))
    return take & (scores > NEG / 2)


def _to_tokens(sel, bsz, cands, S):
    """Block mask [..., H, M] (block j of head h = tokens [j*b_h, (j+1)*b_h))
    -> token mask [..., H, S]."""
    out = jnp.zeros(sel.shape[:-1] + (S,), jnp.bool_)
    for c in cands:
        tok = jnp.repeat(sel[..., : S // c], c, axis=-1)
        out = jnp.where((bsz == c)[:, None], tok, out)
    return out


@partial(jax.jit, static_argnames=("meta", "lower"))
def _layer(p, x, n_prompt, n_live, bsz, meta, lower):
    (eps, theta, n_q, n_kv, hd, page, sink, local, block_q, budget,
     prefill_scale, cands, group, bias) = meta
    S = x.shape[0]
    g = n_q // n_kv
    nb_max = S // min(cands)
    pos = jnp.arange(S)

    h = _rms(x, p["norm1"].astype(jnp.float32), eps)
    q = _mm(h, p["wq"], lower)
    k = _mm(h, p["wk"], lower)
    v = _mm(h, p["wv"], lower)
    if bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _rope(q.reshape(S, n_q, hd), pos, theta)
    k = _rope(k.reshape(S, n_kv, hd), pos, theta)
    v = v.reshape(S, n_kv, hd)
    if lower:
        q, k, v = _cast(q, True), _cast(k, True), _cast(v, True)

    live = pos < n_live
    k_live = jnp.where(live[:, None, None], k, 0.0)
    v_live = jnp.where(live[:, None, None], v, 0.0)
    k_prompt = jnp.where((pos < n_prompt)[:, None, None], k, 0.0)

    nb_h = S // bsz                                              # [H]
    k_h = jnp.minimum(budget // bsz, nb_h)
    k_pre = jnp.clip(jnp.ceil(k_h * prefill_scale).astype(jnp.int32), 1, nb_h)
    kmax = int(min(nb_max, math.ceil(budget / min(cands) * max(prefill_scale, 1.0))))
    j = jnp.arange(nb_max)
    real = j[None] < nb_h[:, None]                               # [H, M]
    starts = j[None] * bsz[:, None]                              # [H, M]

    rows = _rank_rows(k_live, bsz, cands, nb_max)                # [H, M, 2D]
    # prefill scoring rows: one (min, max) per block row
    pre_rows = _q4(rows, rows.min(-1, keepdims=True), rows.max(-1, keepdims=True))
    # decode rows: per-(head, channel) params frozen at prompt completion
    prompt_rows = _rank_rows(k_prompt, bsz, cands, nb_max)
    lo = jnp.where(real[..., None], prompt_rows, POS).min(1, keepdims=True)
    hi = jnp.where(real[..., None], prompt_rows, NEG).max(1, keepdims=True)
    dec_rows = _q4(rows, lo, hi)

    G = group

    def attend(i):
        qs = i * G
        qpos = qs + jnp.arange(G)
        qg = jax.lax.dynamic_slice_in_dim(q, qs, G)              # [G, n_q, D]
        rq = _quest_query(qg).reshape(G, n_kv, g, 2 * hd)
        s_pre = jnp.einsum("qhgd,hmd->qhgm", _cast(rq, lower),
                           _cast(pre_rows, lower), precision=HI).max(2)
        s_dec = jnp.einsum("qhgd,hmd->qhgm", _cast(rq, lower),
                           _cast(dec_rows, lower), precision=HI).max(2)

        # prompt queries: one selection per query block
        nqb = G // block_q
        in_prompt = (qpos < n_prompt).reshape(nqb, block_q)
        s_pre = jnp.where(in_prompt[..., None, None],
                          s_pre.reshape(nqb, block_q, n_kv, nb_max), NEG).max(1)
        qb_start = qs + jnp.arange(nqb) * block_q                # [nqb]
        q_end = jnp.minimum(qb_start + block_q, n_prompt) - 1
        causal = real[None] & (starts[None] <= q_end[:, None, None])
        forced = causal & ((starts[None] < sink * page) | (
            starts[None] + bsz[None, :, None]
            > (qb_start - local * page)[:, None, None]))
        cand = causal & ~forced
        scored = _topk_mask(jnp.where(cand, s_pre, NEG), k_pre, kmax)
        sel_pre = jnp.repeat(forced | scored, block_q, axis=0)   # [G, H, M]

        # decoded tokens: one selection per token
        n_v = (qpos + 1)[:, None, None]                          # live length
        valid = real[None] & (starts[None] < n_v)
        pin = valid & ((starts[None] < jnp.minimum(sink * page, n_v)) | (
            starts[None] + bsz[None, :, None] > n_v - local * page))
        s_dec = jnp.where(pin, POS, jnp.where(valid, s_dec, NEG))
        sel_dec = _topk_mask(s_dec, k_h, kmax)

        sel = jnp.where((qpos >= n_prompt)[:, None, None], sel_dec, sel_pre)
        tok = _to_tokens(sel, bsz, cands, S)
        ok = tok & (pos[None, None] <= qpos[:, None, None]) & live[None, None]
        logits = jnp.einsum("qhgd,khd->qhgk", _cast(qg.reshape(G, n_kv, g, hd), lower),
                            _cast(k_live, lower), precision=HI) / math.sqrt(hd)
        logits = jnp.where(ok[:, :, None], logits, NEG)
        probs = jax.nn.softmax(logits, -1)
        out = jnp.einsum("qhgk,khd->qhgd", probs, _cast(v_live, lower),
                         precision=HI)
        return out.reshape(G, n_q * hd)

    attn = jax.lax.map(attend, jnp.arange(S // G)).reshape(S, n_q * hd)
    x = x + _mm(attn, p["wo"], lower)
    h = _rms(x, p["norm2"].astype(jnp.float32), eps)
    mlp = jax.nn.silu(_mm(h, p["gate"], lower)) * _mm(h, p["up"], lower)
    return x + _mm(mlp, p["down"], lower)


def weights(params, cfg) -> dict:
    """The seeded weights (the program's parameter layout, one stacked
    position ``pos0``) as the per-layer dicts ``logits`` takes."""
    cyc = params["cycles"]["pos0"]
    attn, ffn = cyc["attn"], cyc["ffn"]
    layers = []
    for l in range(cfg.n_layers):
        p = {
            "norm1": cyc["norm1"]["scale"][l], "norm2": cyc["norm2"]["scale"][l],
            "wq": attn["wq"]["w"][l], "wk": attn["wk"]["w"][l],
            "wv": attn["wv"]["w"][l], "wo": attn["wo"]["w"][l],
            "gate": ffn["gate"]["w"][l], "up": ffn["up"]["w"][l],
            "down": ffn["down"]["w"][l],
        }
        if cfg.qkv_bias:
            p.update(bq=attn["wq"]["b"][l], bk=attn["wk"]["b"][l],
                     bv=attn["wv"]["b"][l])
        layers.append(p)
    return {"embed": params["embed"], "final_norm": params["final_norm"]["scale"],
            "lm_head": params["lm_head"], "layers": layers}


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, w, eps, lower):
    return _mm(_rms(x, norm.astype(jnp.float32), eps), w, lower)


def logits(weights, spec: dict, layer_block_sizes: Sequence[Sequence[int]],
           tokens: np.ndarray, n_prompt: int, positions: np.ndarray,
           max_context: int, lower: bool = False,
           row_block: int = 512) -> np.ndarray:
    """Float32 logits at ``positions`` of ``tokens`` (prompt, then served
    tokens fed back), computed layer by layer over a ``max_context``-token
    frame.  ``weights`` is a dict with ``embed`` [V, d], ``final_norm``
    [d], ``lm_head`` [d, V] and ``layers``: a list of per-layer dicts
    (``norm1 norm2 wq wk wv wo gate up down`` and, with a bias, ``bq bk
    bv``), in any float dtype."""
    sp = spec["sparse"]
    n_q, n_kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["head_dim"]
    cands = tuple(sorted(sp["candidate_block_sizes"]))
    budget = sp["token_budget"]
    for row in layer_block_sizes:       # the layout rounds the budget to
        l = math.lcm(*row)              # a multiple of every block size
        assert budget % l == 0, (budget, row)
    S = max_context
    group = next(g for g in (256, 128, 64) if S % g == 0)   # queries a step
    assert group % sp["prefill_block_q"] == 0
    meta = (spec["rms_norm_eps"], spec["rope_theta"], n_q, n_kv, hd,
            sp["page_size"], sp["sink_pages"], sp["local_pages"],
            sp["prefill_block_q"], budget, sp["prefill_topk_scale"], cands,
            group, bool(spec["qkv_bias"]))
    n_live = len(tokens)
    ids = np.zeros(S, np.int32)
    ids[:n_live] = tokens
    x = jnp.asarray(weights["embed"])[jnp.asarray(ids)].astype(jnp.float32)
    for p, row in zip(weights["layers"], layer_block_sizes):
        x = _layer(p, x, jnp.int32(n_prompt), jnp.int32(n_live),
                   jnp.asarray(row, jnp.int32), meta, lower)
    n = len(positions)
    padded = np.zeros(-(-n // row_block) * row_block, np.int32)
    padded[:n] = positions
    out = [np.asarray(_head(x[jnp.asarray(padded[i:i + row_block])],
                            weights["final_norm"], weights["lm_head"],
                            spec["rms_norm_eps"], lower))
           for i in range(0, len(padded), row_block)]
    return np.concatenate(out)[:n]
