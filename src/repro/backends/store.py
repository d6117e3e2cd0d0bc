"""Scan-safe centroid-store construction and incremental maintenance.

These run INSIDE the model's layer scan, where per-head block sizes are
traced array values (per-layer heterogeneous layouts ride the scan as
:class:`repro.core.stacked.LayoutArrays`).  Rank keys are therefore built at
every candidate block size from page-granular pooled statistics and each
flat store row selects its head's size — fully vectorized, static shapes.

Shared by every registered backend so prefill and decode-append emit
byte-identical stores regardless of which backend executes estimation /
attention (backend parity of page tables depends on this).  All
quantization math comes from :mod:`repro.core.quantization`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import SparseConfig
from repro.core.centroids import padded_rank_key_width
from repro.core.quantization import (
    affine_params_from_minmax,
    encode_affine,
    pack_split_half,
    store_bits,
    store_symmetric,
)
from repro.core.stacked import as_arrays

BIG = 1e30


def _merge_page_stats(pmax, pmin, pmean, group: int, method: str, Dp: int):
    """Page-granular (max, min, mean) stats -> rank keys at block size
    ``group * page_size``, padded on the channel axis to Dp."""
    B, n_kv, n_pages, hd = pmax.shape
    nb = n_pages // group
    mmax = pmax.reshape(B, n_kv, nb, group, hd).max(3)
    mmin = pmin.reshape(B, n_kv, nb, group, hd).min(3)
    mmean = pmean.reshape(B, n_kv, nb, group, hd).mean(3)
    if method == "mean":
        rk = mmean
    elif method == "quest":
        rk = jnp.concatenate([mmax, mmin], axis=-1)
    else:  # arkvale approximated from page stats: center + half-diagonal
        center = 0.5 * (mmax + mmin)
        radius = 0.5 * jnp.linalg.norm(mmax - mmin, axis=-1)
        rk = jnp.concatenate([center, radius[..., None]], axis=-1)
    pad = Dp - rk.shape[-1]
    if pad:
        rk = jnp.pad(rk, ((0, 0),) * (rk.ndim - 1) + ((0, pad),))
    # pad the block axis to the max candidate count (= n_pages)
    return jnp.pad(rk, ((0, 0), (0, 0), (0, n_pages - nb), (0, 0)))


def _selected_rank_keys(k_cache: jax.Array, layout, sparse: SparseConfig):
    """Paged/dense K cache -> per-head rank keys at each head's (possibly
    traced) block size: ``(sel [B, n_kv, n_pages, Dp], nb_h [n_kv])`` where
    the first ``nb_h[h]`` rows of head ``h`` are its rank keys."""
    la = as_arrays(layout)
    method = sparse.centroid_method
    page = sparse.page_size
    if k_cache.ndim == 4:
        B, n_kv, S_max, hd = k_cache.shape
        k_cache = k_cache.reshape(B, n_kv, S_max // page, page, hd)
    B, n_kv, n_pages, _, hd = k_cache.shape
    S_max = n_pages * page
    Dp = padded_rank_key_width(hd, method)
    cands = sparse.candidate_block_sizes

    pages = k_cache.astype(jnp.float32)
    pmax = pages.max(axis=3)
    pmin = pages.min(axis=3)
    pmean = pages.mean(axis=3)

    merged = jnp.stack(
        [_merge_page_stats(pmax, pmin, pmean, c // page, method, Dp)
         for c in cands]
    )                                                   # [C, B, n_kv, nP, Dp]
    bsz = la.block_sizes                                # [n_kv] (maybe traced)
    sel = jnp.zeros_like(merged[0])
    nb_h = jnp.zeros((n_kv,), jnp.int32)
    for ci, c in enumerate(cands):
        hit = (bsz == c)
        sel = jnp.where(hit[None, :, None, None], merged[ci], sel)
        nb_h = jnp.where(hit, S_max // c, nb_h)
    return sel, nb_h


def build_store_codes(
    k_cache: jax.Array,
    layout,
    offsets: jax.Array,
    sparse: SparseConfig,
    quant: Optional[str] = None,
    sel_nb=None,
):
    """k_cache — paged ``[B, n_kv, n_pages, page, hd]`` (the decode cache's
    native layout) or dense ``[B, n_kv, S_max, hd]`` — ->
    :class:`CentroidStore` for ONE layer in the flattened layout (scan-safe;
    ``layout`` is LayoutArrays).  ``sel_nb`` accepts a precomputed
    :func:`_selected_rank_keys` result so callers that also build the
    prefill scoring segment pay for the page-stats merge once."""
    from repro.backends.base import CentroidStore

    la = as_arrays(layout)
    quant = sparse.quant if quant is None else quant
    bits = store_bits(quant)
    symmetric = store_symmetric(quant)
    if bits not in (0, 4, 8):
        raise ValueError(
            f"centroid store supports none/int8/int4 schemes, got {quant!r}"
        )
    method = sparse.centroid_method
    page = sparse.page_size
    if k_cache.ndim == 4:
        B, n_kv, S_max, hd = k_cache.shape
        k_cache = k_cache.reshape(B, n_kv, S_max // page, page, hd)
    B, n_kv, n_pages, _, hd = k_cache.shape
    Dp = padded_rank_key_width(hd, method)
    rows_total = la.total_rows
    if sel_nb is None:
        sel_nb = _selected_rank_keys(k_cache, la, sparse)
    sel, nb_h = sel_nb
    # sel: per head, the first nb_h[h] rows are that head's rank keys.

    # per-head affine params over valid blocks only
    blk_valid = (
        jnp.arange(n_pages)[None, :] < nb_h[:, None]
    )[None, :, :, None]                                 # [1, n_kv, nP, 1]
    if bits == 0:
        scale = jnp.ones((B, n_kv, Dp), jnp.float32)
        zero = jnp.zeros((B, n_kv, Dp), jnp.float32)
    else:
        xmin = jnp.where(blk_valid, sel, BIG).min(axis=2)
        xmax = jnp.where(blk_valid, sel, -BIG).max(axis=2)
        scale, zero = affine_params_from_minmax(xmin, xmax, bits, symmetric)

    # flat rows: row r -> (head = row_head[r], local block j = r - offset)
    row_head = jnp.repeat(
        la.tile_head, la.tile_rows, total_repeat_length=rows_total
    )                                                   # [rows]
    row_off = offsets[row_head]                         # [rows]
    row_j = jnp.arange(rows_total, dtype=jnp.int32) - row_off
    row_j = jnp.clip(row_j, 0, n_pages - 1)
    rk_rows = sel[:, row_head, row_j]                   # [B, rows, Dp]

    if bits == 0:
        codes = rk_rows
    else:
        s_rows = scale[:, row_head]                     # [B, rows, Dp]
        z_rows = zero[:, row_head]
        codes = encode_affine(rk_rows, s_rows, z_rows, bits, symmetric)
        if bits == 4:
            codes = pack_split_half(codes)
    return CentroidStore(codes, scale, zero, bits, symmetric)


def _encode_score_rows(rk_rows: jax.Array, bits: int, symmetric: bool):
    """Rank-key rows ``[..., Dp]`` -> per-ROW affine codes.

    The prefill scoring segment quantizes each block row with its own scalar
    (scale, zero) over the channel axis — unlike the decode store's
    per-(head, channel) params, a row's bytes depend ONLY on that block's
    keys, which is what makes chunked sparse prefill token-identical to the
    single-shot build (a completed block encodes the same bytes whenever it
    is encoded).  ``bits == 0`` returns identity params (concrete arrays,
    never None — callers DMA / cache them unconditionally)."""
    if bits == 0:
        shp = rk_rows.shape[:-1] + (1,)
        return (
            rk_rows.astype(jnp.float32),
            jnp.ones(shp, jnp.float32),
            jnp.zeros(shp, jnp.float32),
        )
    xmin = rk_rows.min(axis=-1, keepdims=True)
    xmax = rk_rows.max(axis=-1, keepdims=True)
    scale, zero = affine_params_from_minmax(xmin, xmax, bits, symmetric)
    codes = encode_affine(rk_rows, scale, zero, bits, symmetric)
    if bits == 4:
        codes = pack_split_half(codes)
    return codes, scale, zero


def build_score_rows(
    k_cache: jax.Array,
    layout,
    offsets: jax.Array,
    sparse: SparseConfig,
    quant: Optional[str] = None,
    sel_nb=None,
):
    """Full-sequence prefill scoring segment (scan-safe).

    -> ``(codes [B, rows, Cw], scale [B, rows, 1], zero [B, rows, 1])`` in
    the flattened ragged row layout (identity params when unquantized).
    Rows of blocks beyond the live context are built from
    whatever is in the cache — they are never scored (the kernel only scores
    blocks fully behind a query block's local window).  ``sel_nb`` accepts
    a precomputed :func:`_selected_rank_keys` result (see
    :func:`build_store_codes`)."""
    la = as_arrays(layout)
    quant = sparse.quant if quant is None else quant
    bits = store_bits(quant)
    symmetric = store_symmetric(quant)
    if sel_nb is None:
        sel_nb = _selected_rank_keys(k_cache, la, sparse)
    sel, _ = sel_nb                                     # [B, n_kv, nP, Dp]
    n_pages = sel.shape[2]
    rows_total = la.total_rows
    row_head = jnp.repeat(
        la.tile_head, la.tile_rows, total_repeat_length=rows_total
    )
    row_off = offsets[row_head]
    row_j = jnp.clip(
        jnp.arange(rows_total, dtype=jnp.int32) - row_off, 0, n_pages - 1
    )
    rk_rows = sel[:, row_head, row_j]                   # [B, rows, Dp]
    return _encode_score_rows(rk_rows, bits, symmetric)


def refresh_score_rows(
    codes: jax.Array,                  # [B, rows, Cw]
    scale: Optional[jax.Array],        # [B, rows, 1]
    zero: Optional[jax.Array],
    k_cache: jax.Array,                # paged [B, n_kv, n_pages, page, hd]
    layout,
    offsets: jax.Array,
    chunk_start: jax.Array,            # scalar: first token of the chunk
    chunk_end: jax.Array,              # scalar: one past the chunk's last token
    sparse: SparseConfig,
    window: int,                       # static token window, multiple of Bmax
    bits: Optional[int] = None,
    symmetric: Optional[bool] = None,
    layer=None,
    slot=None,
):
    """Incremental prefill-scoring update: re-encode the rows of every block
    COMPLETED by the chunk ``[chunk_start, chunk_end)`` from a static-size
    K window, leaving all other rows untouched.  Blocks still partial at
    ``chunk_end`` keep their stale bytes — they are not scoreable until a
    later chunk completes them (and that chunk's window covers them).
    With ``layer`` and ``slot`` given, ``k_cache`` is the paged pool of
    every layer and slot ``[L, Bp, n_kv, n_pages, page, hd]`` and the
    window is read from rows ``slot ..`` of layer ``layer`` where they
    lie."""
    la = as_arrays(layout)
    bits = store_bits(sparse.quant) if bits is None else bits
    symmetric = store_symmetric(sparse.quant) if symmetric is None else symmetric
    page = sparse.page_size
    B = codes.shape[0]
    if layer is None:
        k_cache, layer, slot = k_cache[None], 0, 0
    n_kv, n_pages, _, hd = k_cache.shape[2:]
    S_max = n_pages * page
    bmax = sparse.max_block_size
    assert window % bmax == 0 and window <= S_max, (window, bmax, S_max)

    # Bmax-aligned window covering every block ending in (start, end]: such
    # blocks span [start + 1 - bmax, end], so a window of
    # ``chunk + 2 * bmax`` tokens anchored one (aligned) bmax before the
    # chunk start always contains them.
    assert window >= bmax  # caller sizes it as chunk_len + 2 * bmax
    w0 = jnp.clip((chunk_start - bmax) // bmax * bmax, 0, S_max - window)
    win = jax.lax.dynamic_slice(
        k_cache, (layer, slot, 0, w0 // page, 0, 0),
        (1, B, n_kv, window // page, page, hd),
    )[0]
    sel_win, _ = _selected_rank_keys(win, la, sparse)   # [B, n_kv, nW, Dp]
    new_codes, new_scale, new_zero = _encode_score_rows(
        sel_win, bits, symmetric
    )                                                   # [B, n_kv, nW, ...]

    n_win = window // page                              # max window rows/head
    bsz = la.block_sizes                                # [n_kv]
    i = jnp.arange(n_win, dtype=jnp.int32)[None, :]     # [1, nW]
    jg = w0 // bsz[:, None] + i                         # global block index
    end_tok = (jg + 1) * bsz[:, None]
    upd = (
        (i < window // bsz[:, None])
        & (end_tok > chunk_start)
        & (end_tok <= chunk_end)
    )
    rows_idx = jnp.where(
        upd, offsets[:, None] + jg, la.total_rows       # OOB -> dropped
    ).reshape(-1)                                       # [n_kv * nW]
    bidx = jnp.arange(B)[:, None]
    flat = lambda a: a.reshape(B, n_kv * n_win, a.shape[-1])
    codes = codes.at[bidx, rows_idx[None]].set(flat(new_codes))
    if bits:
        scale = scale.at[bidx, rows_idx[None]].set(flat(new_scale))
        zero = zero.at[bidx, rows_idx[None]].set(flat(new_zero))
    return codes, scale, zero


def refresh_tail_codes(
    store,
    k_cache: jax.Array,
    layout,
    offsets: jax.Array,
    seq_len: jax.Array,
    sparse: SparseConfig,
    layer=None,
) -> jax.Array:
    """Recompute + requantize the rank-key row of the block containing the
    newest token, for every head (vectorized, static shapes) -> new codes.

    The max-candidate-sized window containing the token is pooled at each
    candidate size; the row for each head is selected by its (possibly
    layer-dynamic) block size.  Positions beyond ``seq_len`` are neutralized
    (-inf/+inf for max/min, zero-weight for mean).  With ``layer`` given,
    ``k_cache`` ``[L, B, n_kv, nP, page, hd]`` and the store's codes are
    stacked over layers: the window is read from, and the row written into,
    layer ``layer`` where it lies, and the stacked codes come back.
    """
    la = as_arrays(layout)
    codes, scale, zero = store.codes, store.scale, store.zero
    method = sparse.centroid_method
    page = sparse.page_size
    if layer is None:
        if k_cache.ndim == 4:
            B, n_kv, S_max, hd = k_cache.shape
            k_cache = k_cache.reshape(B, n_kv, S_max // page, page, hd)
        k_cache, codes = k_cache[None], codes[None]
    _, B, n_kv, n_pages, _, hd = k_cache.shape
    lyr = 0 if layer is None else layer
    Dp = padded_rank_key_width(hd, method)
    Wmax = max(sparse.candidate_block_sizes)
    w0 = (seq_len // Wmax) * Wmax                        # [B]

    # gather the window [B, n_kv, Wmax, hd] — Wmax is page-aligned, so the
    # slice runs over whole pages of the paged cache.  One dynamic slice a
    # slot (not a vmapped gather), so the pool keeps the layout it has
    # everywhere else.
    wp = Wmax // page
    win = jnp.concatenate([
        jax.lax.dynamic_slice(
            k_cache, (lyr, b, 0, w0[b] // page, 0, 0),
            (1, 1, n_kv, wp, page, hd),
        )
        for b in range(B)
    ]).reshape(B, n_kv, Wmax, hd)
    pos = w0[:, None] + jnp.arange(Wmax)[None]           # [B, Wmax]
    ok = (pos <= seq_len[:, None])[:, None, :, None]     # include new tok
    winf = win.astype(jnp.float32)

    def pooled(c):
        n = Wmax // c
        wm = winf.reshape(B, n_kv, n, c, hd)
        okm = ok.reshape(B, 1, n, c, 1)
        mx = jnp.where(okm, wm, -BIG).max(3)
        mn = jnp.where(okm, wm, BIG).min(3)
        cnt = jnp.maximum(okm.sum(3), 1)
        mean = jnp.where(okm, wm, 0.0).sum(3) / cnt
        # slot containing the new token
        slot = (seq_len % Wmax) // c                     # [B]
        take = lambda a: jnp.take_along_axis(
            a, slot[:, None, None, None], axis=2
        )[:, :, 0]
        mx, mn, mean = take(mx), take(mn), take(mean)    # [B, n_kv, hd]
        if method == "mean":
            rk = mean
        elif method == "quest":
            rk = jnp.concatenate([mx, mn], axis=-1)
        else:
            center = 0.5 * (mx + mn)
            radius = 0.5 * jnp.linalg.norm(mx - mn, axis=-1)
            rk = jnp.concatenate([center, radius[..., None]], axis=-1)
        pad = Dp - rk.shape[-1]
        if pad:
            rk = jnp.pad(rk, ((0, 0), (0, 0), (0, pad)))
        return rk                                        # [B, n_kv, Dp]

    cands = sparse.candidate_block_sizes
    rks = jnp.stack([pooled(c) for c in cands])          # [C, B, n_kv, Dp]
    bsz = la.block_sizes                                 # [n_kv]
    sel = jnp.zeros_like(rks[0])
    for ci, c in enumerate(cands):
        sel = jnp.where((bsz == c)[None, :, None], rks[ci], sel)

    # requantize with the frozen per-head affine params
    if store.bits == 0:
        new_codes = sel
    else:
        qv = encode_affine(sel, scale, zero, store.bits, store.symmetric)
        new_codes = pack_split_half(qv) if store.bits == 4 else qv

    rows = offsets[None, :] + (seq_len[:, None] // bsz[None, :])  # [B, n_kv]
    bidx = jnp.arange(B)[:, None]
    codes = codes.at[lyr, bidx, rows].set(new_codes)
    return codes[0] if layer is None else codes
