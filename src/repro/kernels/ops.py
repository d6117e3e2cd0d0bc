"""Public jit'd wrappers around the Pallas kernels.

These are the execute-stage primitives consumed by the ``"pallas"``
attention backend (:mod:`repro.backends.pallas`).  Off-TPU every kernel
runs in ``interpret=True`` mode — the kernel body executes as jnp for
correctness validation.  On TPU the serving path's kernels (fused decode,
sparse prefill, staged estimation and paged attention) compile to Mosaic:
``tests/test_tpu_compile.py`` compiles them for v5e and ``chip_smoke.py``
runs them there.  ``flash_attention``, ``topk_threshold`` and the pooling
kernel are off that path and have not been compiled for a chip.

Store construction and orchestration live in :mod:`repro.backends`; the
unified :class:`repro.backends.CentroidStore` byte layout (flattened ragged
rank keys, INT4 split-half packed, per-(sequence, head, channel)
scale/zero) is exactly what the estimation kernel DMAs.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.ragged import RaggedLayout
from repro.core.sparse_attention import as_paged
from repro.kernels import (
    centroid_score,
    flash_attention as fa,
    paged_attention as pa,
    topk_threshold as tk,
)

NEG_INF = -1e30


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Kernel 1: estimation
# ---------------------------------------------------------------------------


def centroid_scores(
    rq: jax.Array,
    store,                      # repro.backends.CentroidStore (duck-typed)
    layout,
    n_kv: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """rank queries [B, n_q, Dp] + store -> padded 2-D scores
    [B, n_kv, max_blocks] (-inf pads), ready for selection."""
    from repro.core.stacked import as_arrays

    if interpret is None:
        interpret = default_interpret()
    la = as_arrays(layout)

    if store.bits == 0:
        flat = centroid_score.centroid_scores_f32(
            rq, store.codes, n_kv, la.tile_head, la.tile_rows,
            interpret=interpret,
        )
    else:
        flat = centroid_score.centroid_scores_quantized(
            rq, store.codes, store.scale, store.zero,
            la.tile_head, la.tile_rows, store.symmetric, store.bits,
            interpret=interpret,
        )
    return flat_to_padded(flat, la)


def flat_to_padded(flat: jax.Array, layout) -> jax.Array:
    """[B, total_rows] -> [B, n_heads, max_blocks] with -inf pads.

    ``scatter_rows``/``pad_mask`` are precomputed static layout arrays
    (:class:`repro.core.ragged.RaggedLayout` cached properties) consumed
    directly as gather indices — one batched ``take`` per call instead of
    re-materializing a ``[B, H, max_blocks]`` broadcast index tensor every
    decode step."""
    from repro.core.stacked import as_arrays

    la = as_arrays(layout)
    rows, mask = la.scatter_rows, la.pad_mask                 # [H, M]
    picked = jnp.take(flat, rows, axis=1)                     # [B, H, M]
    return jnp.where(mask[None], picked, NEG_INF)


# ---------------------------------------------------------------------------
# Kernel 2: top-k
# ---------------------------------------------------------------------------


def topk_threshold(
    scores: jax.Array,
    layout,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    from repro.core.stacked import as_arrays

    if interpret is None:
        interpret = default_interpret()
    la = as_arrays(layout)
    k_arr = jnp.minimum(
        la.token_budget // la.block_sizes, la.context_len // la.block_sizes
    ).astype(jnp.int32)
    return tk.topk_threshold(scores, k_arr, interpret=interpret)


# ---------------------------------------------------------------------------
# Kernel 3: paged attention
# ---------------------------------------------------------------------------


def paged_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    page_table: jax.Array,
    page_valid: jax.Array,
    page_size: int,
    seq_len: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """q [B, n_q, D]; k/v either a pre-paged ``[B, n_kv, n_pages, page, D]``
    view (the decode cache's native layout — no per-call reshape) or dense
    ``[B, n_kv, S, D]`` (reshaped here once for offline callers)."""
    if interpret is None:
        interpret = default_interpret()
    k_pages, v_pages = as_paged(k, page_size), as_paged(v, page_size)
    B = k_pages.shape[0]
    if seq_len is None:
        S = k_pages.shape[2] * page_size
        seq_len = jnp.full((B,), S, jnp.int32)
    else:
        seq_len = jnp.broadcast_to(jnp.asarray(seq_len, jnp.int32), (B,))
    return pa.paged_attention(
        q, k_pages, v_pages, page_table, page_valid, seq_len, page_size,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
def _dma_window(layout, la, max_pages_per_block, kernel: str) -> int:
    """Static DMA window (pages) of a sparse kernel's inner loop: the
    caller's bound reconciled with the layout's own maximum wherever that is
    statically known — a window smaller than the largest assigned block
    would silently truncate its attention span.  Inside a layer scan the
    layout arrays are traced and the caller must size the window."""
    if isinstance(layout, RaggedLayout):
        return max(max_pages_per_block or 0, max(layout.pages_per_block))
    import numpy as np

    try:
        layout_max = int(np.max(jax.device_get(la.pages_per_block)))
    except jax.errors.ConcretizationTypeError:
        if max_pages_per_block is None:
            raise ValueError(
                f"{kernel} needs a static max_pages_per_block when the layout "
                "arrays are traced (e.g. inside a layer scan); pass it "
                "explicitly"
            ) from None
        return max_pages_per_block
    return max(max_pages_per_block or 0, layout_max)


# ---------------------------------------------------------------------------
# Fused decode: kernels 1+2+3 in one launch
# ---------------------------------------------------------------------------


def stacked_operands(k, v, codes, page_size: int, layer=None):
    """The sparse kernels' operand form: KV pools
    ``[L, B, n_kv, nP, page, D]`` and store codes ``[L, B, rows, Cw]``
    stacked over layers, plus the layer to read.  With ``layer=None`` the
    inputs are one layer's (KV paged or dense 4-D) and gain a unit layer
    axis, a bitcast.  ``codes`` may be None (sparse prefill reads none)."""
    if layer is None:
        return (as_paged(k, page_size)[None], as_paged(v, page_size)[None],
                None if codes is None else codes[None], 0)
    return k, v, codes, layer


def fused_decode(
    q: jax.Array,               # [B, n_q, D]
    rq: jax.Array,              # [B, n_q, Dp] rank queries
    k: jax.Array,               # paged [B, n_kv, nP, page, D] or dense 4-D
    v: jax.Array,
    store,                      # repro.backends.CentroidStore (duck-typed)
    layout,                     # RaggedLayout or LayoutArrays
    sink_pages: int = 1,
    local_pages: int = 4,
    seq_len: Optional[jax.Array] = None,
    max_pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
    layer=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-launch AB-Sparse decode (estimation -> top-k -> attention).

    ``max_pages_per_block`` is the static DMA window (pages) of the fused
    inner loop; it defaults to the layout's own maximum, which requires the
    layout arrays to be concrete — inside a layer scan pass it explicitly
    (e.g. from ``SparseConfig.candidate_block_sizes``).  With ``layer``
    given, ``k``/``v`` are the paged pools of every layer
    ``[L, B, n_kv, nP, page, D]`` and ``store.codes`` is ``[L, B, rows, Cw]``
    (``scale``/``zero`` are that layer's): the kernel reads layer ``layer``
    where it lies.
    -> (out [B, n_q, D], page_table [B, H, P_sel], page_valid [B, H, P_sel]).
    """
    from repro.core.stacked import as_arrays
    from repro.kernels import fused_decode as fd

    if interpret is None:
        interpret = default_interpret()
    la = as_arrays(layout)
    kp, vp, codes, layer = stacked_operands(
        k, v, store.codes, la.page_size, layer
    )
    B = q.shape[0]
    if seq_len is None:
        seq_len = jnp.full((B,), la.context_len, jnp.int32)
    else:
        seq_len = jnp.broadcast_to(jnp.asarray(seq_len, jnp.int32), (B,))
    max_pages_per_block = _dma_window(
        layout, la, max_pages_per_block, "fused_decode"
    )
    Dp = rq.shape[-1]
    if store.bits == 0:
        scale = jnp.ones((B, la.n_heads, Dp), jnp.float32)
        zero = jnp.zeros((B, la.n_heads, Dp), jnp.float32)
    else:
        scale, zero = store.scale, store.zero
    return fd.fused_decode(
        q, rq, kp, vp, codes, scale, zero,
        la.row_offsets, la.n_blocks, la.top_k,
        la.block_sizes, la.pages_per_block, seq_len, layer,
        page_size=la.page_size,
        ppb_max=max_pages_per_block,
        bits=store.bits,
        symmetric=store.symmetric,
        sink_pages=sink_pages,
        local_pages=local_pages,
        seg=la.max_blocks,
        p_sel=la.selected_pages,
        tile_rows=la.tile_rows,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Sparse prefill: query-block sparse flash attention
# ---------------------------------------------------------------------------


def _prefill_query_blocks(
    q, rq, n_kv, la, block_q, topk_scale, n_valid, chunk_offset
):
    """Shared preamble of the sparse-prefill kernel AND its jnp oracle:
    query-block padding/reshape, the prefill-scaled per-head K, live-length
    broadcast, and the chunk's query-block base index.  One definition so
    the two entry points cannot drift apart."""
    B, Hq, Sq, _ = q.shape
    g = Hq // n_kv
    nQB = -(-Sq // block_q)
    pad = nQB * block_q - Sq
    if n_valid is None:
        n_valid = jnp.asarray(chunk_offset + Sq, jnp.int32)
    n_valid = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,))
    qb0 = jnp.asarray(chunk_offset, jnp.int32).reshape(-1)[:1] // block_q

    def to_blocks(x):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        x = x.reshape(B, n_kv, g, nQB, block_q, x.shape[-1])
        return jnp.moveaxis(x, 3, 2)       # [B, n_kv, nQB, g, BQ, .]

    k_sel = jnp.clip(
        jnp.ceil(la.top_k.astype(jnp.float32) * topk_scale).astype(jnp.int32),
        1, la.n_blocks,
    )
    q6 = to_blocks(q)
    rq6 = to_blocks(rq.astype(jnp.float32))
    return q6, rq6, k_sel, n_valid, qb0, nQB


def _from_blocks(out6, Sq):
    B, n_kv, nQB, g, bq, D = out6.shape
    out = jnp.moveaxis(out6, 2, 3).reshape(B, n_kv * g, nQB * bq, D)
    return out[:, :, :Sq]


def sparse_prefill_reference(
    q: jax.Array,               # [B, Hq, Sq, D]
    rq: jax.Array,              # [B, Hq, Sq, Dp] per-token rank queries
    k: jax.Array,               # paged [B, n_kv, nP, page, D] or dense 4-D
    v: jax.Array,
    score_store,                # duck-typed: codes/scale/zero/bits/symmetric
    layout,
    sink_pages: int = 1,
    local_pages: int = 4,
    block_q: int = 64,
    topk_scale: float = 1.0,
    n_valid: Optional[jax.Array] = None,
    chunk_offset=0,
) -> Tuple[jax.Array, jax.Array]:
    """Pure-jnp selection-exact oracle of :func:`sparse_prefill` — same
    signature shape, same shared preamble, dense masked attention."""
    from repro.core.stacked import as_arrays
    from repro.kernels import ref

    la = as_arrays(layout)
    kp = as_paged(k, la.page_size)
    vp = as_paged(v, la.page_size)
    Sq = q.shape[2]
    q6, rq6, k_sel, n_valid, qb0, _ = _prefill_query_blocks(
        q, rq, kp.shape[1], la, block_q, topk_scale, n_valid, chunk_offset
    )
    rank_rows = ref.dequant_score_rows(
        score_store.codes, score_store.scale, score_store.zero,
        score_store.bits, score_store.symmetric,
    )
    out6, n_att = ref.sparse_prefill_ref(
        q6, rq6, kp, vp, rank_rows, la, k_sel, n_valid, qb0[0], block_q,
        sink_pages, local_pages,
    )
    return _from_blocks(out6, Sq), n_att


def sparse_prefill(
    q: jax.Array,               # [B, Hq, Sq, D]
    rq: jax.Array,              # [B, Hq, Sq, Dp] per-token rank queries
    k: jax.Array,               # paged [B, n_kv, nP, page, D] or dense 4-D
    v: jax.Array,
    score_store,                # duck-typed: codes/scale/zero/bits/symmetric
    layout,                     # RaggedLayout or LayoutArrays
    sink_pages: int = 1,
    local_pages: int = 4,
    block_q: int = 64,
    topk_scale: float = 1.0,
    n_valid: Optional[jax.Array] = None,
    chunk_offset=0,             # absolute pos of q[..., 0, :]; block_q-aligned
    max_pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
    layer=None,
    slot=None,
) -> Tuple[jax.Array, jax.Array]:
    """Single-launch query-block sparse prefill over the ragged layout.

    ``score_store`` holds the running prefill scoring segment (per-ROW
    affine codes from :func:`repro.backends.store.build_score_rows`).
    ``chunk_offset``/``n_valid`` replay later chunks of a chunked prefill
    through the identical kernel (`n_valid` defaults to
    ``chunk_offset + Sq``, the live length after this chunk).  With
    ``layer`` and ``slot`` given, ``k``/``v`` are the paged pools of every
    layer and slot ``[L, Bp, n_kv, nP, page, D]`` and query row ``b`` reads
    pool row ``slot + b`` of layer ``layer`` where it lies.
    -> (out [B, Hq, Sq, D], n_attended [B, n_kv, nQB]).
    """
    from repro.core.stacked import as_arrays
    from repro.kernels import ref, sparse_prefill as sp

    if interpret is None:
        interpret = default_interpret()
    la = as_arrays(layout)
    kp, vp, _, layer = stacked_operands(k, v, None, la.page_size, layer)
    Sq = q.shape[2]
    q6, rq6, k_sel, n_valid, qb0, _ = _prefill_query_blocks(
        q, rq, kp.shape[2], la, block_q, topk_scale, n_valid, chunk_offset
    )
    max_pages_per_block = _dma_window(
        layout, la, max_pages_per_block, "sparse_prefill"
    )
    # the score segment's per-ROW affine params sit on the sublane axis,
    # where a kernel cannot slice them; dequantizing here (the oracle's own
    # function) hands the kernel the exact rank rows the oracle scores.
    rank_rows = ref.dequant_score_rows(
        score_store.codes, score_store.scale, score_store.zero,
        score_store.bits, score_store.symmetric,
    )
    out6, nsel = sp.sparse_prefill(
        q6, rq6, kp, vp, rank_rows,
        la.row_offsets, la.n_blocks, k_sel,
        la.block_sizes, la.pages_per_block, n_valid, qb0,
        layer, 0 if slot is None else slot,
        page_size=la.page_size,
        ppb_max=max_pages_per_block,
        block_q=block_q,
        sink_pages=sink_pages,
        local_pages=local_pages,
        seg=la.max_blocks,
        tile_rows=la.tile_rows,
        interpret=interpret,
    )
    return _from_blocks(out6, Sq), nsel


# ---------------------------------------------------------------------------
# Flash attention (prefill)
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    return fa.flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
