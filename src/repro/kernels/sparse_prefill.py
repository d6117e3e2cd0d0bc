"""Query-block sparse flash prefill: variable-block-size AB-Sparse applied
to the prefill phase in ONE Pallas launch per layer.

Per ``(batch, kv-head, query-block)`` grid cell the kernel:

1. **Scores** the head's running centroid segment: its rank rows are DMA'd
   from the flattened ragged segment (dequantized by the caller with the
   oracle's own :func:`repro.kernels.ref.dequant_score_rows` — the per-ROW
   affine params sit on the sublane axis, where a kernel cannot slice
   them) and hit the MXU against the query block's rank queries; the block
   score is the max over the block's (live) queries and the GQA group.
2. **Selects** the union of
   - *forced* blocks — sink blocks plus every block overlapping the query
     block's local window / causal diagonal (these are never scored, so a
     block whose keys are still being written can never influence
     selection — the property that makes chunked prefill token-identical
     to single-shot), and
   - the top ``ceil(K_h * prefill_topk_scale)`` *scored* blocks among the
     causally-valid blocks fully behind the local window, via the same
     exact k-th-value threshold (tie order == ``lax.top_k``'s set) as the
     fused decode kernel.
   Early query blocks have no scoreable candidates and therefore stay
   EXACT (every causal block is forced).
3. **Attends** flash-style over only the selected blocks, walked in index
   order as in :mod:`repro.kernels.fused_decode`: double-buffered page DMA,
   per-token causal masking inside the diagonal blocks, running (m, l, acc)
   softmax state in registers.

Raggedness rides the same scalar-prefetched grid descriptor as decode
(per-head flat-row offsets, block counts, block sizes, pages-per-block), so
heterogeneous per-head block sizes share one launch.  A scalar ``qb0``
offsets the query-block index, which is how chunked prefill replays later
chunks through the identical kernel.

Interpret mode validates the numerics on CPU; ``tests/test_tpu_compile.py``
compiles the kernel for a v5e chip, and ``chip_smoke.py`` runs it there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_threshold import next_selected, topk_row_mask

NEG_INF = -1e30
POS_INF = 1e30


def _sparse_prefill_kernel(
    # -- scalar prefetch: ragged grid descriptor + live length + chunk base
    row_off_ref,               # [H] int32 flat-row offset of the head segment
    n_blocks_ref,              # [H] int32 real blocks per head
    k_sel_ref,                 # [H] int32 prefill-scaled K per head
    bsz_ref,                   # [H] int32 block size (tokens)
    ppb_ref,                   # [H] int32 pages per block
    n_valid_ref,               # [B] int32 live tokens (queries AND keys)
    qb0_ref,                   # [1] int32 absolute index of query block 0
    layer_ref,                 # [1] int32 layer of the stacked pools
    slot_ref,                  # [1] int32 pool row of query row 0
    # -- array inputs
    rows_ref,                  # [B, R, Dp] f32 rank rows (HBM/ANY)
    rq_ref,                    # [1, 1, 1, g, BQ, Dp] rank queries
    q_ref,                     # [1, 1, 1, g, BQ, D]
    k_ref,                     # [L, Bp, H, n_pages, ps, D] pools (HBM/ANY)
    v_ref,                     # [L, Bp, H, n_pages, ps, D]
    # -- outputs
    o_ref,                     # [1, 1, 1, g, BQ, D]
    nsel_ref,                  # [B, H, nQB] int32 blocks attended (SMEM)
    # -- scratch
    rows_scr,                  # VMEM [SEG, Dp]
    kbuf, vbuf,                # VMEM [2, ppb_max, ps, D] double buffers
    csem,                      # DMA sem (rank rows)
    sem,                       # DMA sems [2, 2] (k/v double buffer)
    *,
    seg: int, block_q: int, row_align: int,
    page_size: int, ppb_max: int, n_pages: int, total_rows: int,
    sink_pages: int, local_pages: int, scale_qk: float,
):
    b = pl.program_id(0)
    h = pl.program_id(1)
    qb = pl.program_id(2)
    row_off = row_off_ref[h]
    nblk = n_blocks_ref[h]
    k_sel = k_sel_ref[h]
    bsz = bsz_ref[h]
    ppb = ppb_ref[h]
    nv = n_valid_ref[b]
    lyr = layer_ref[0]
    row = slot_ref[0] + b                              # this query row's pool row
    q_start = (qb0_ref[0] + qb) * block_q
    q_end = jnp.minimum(q_start + block_q, nv) - 1     # last live query pos

    # ---- phase 1: score the head's centroid segment ------------------------
    start = pl.multiple_of(jnp.minimum(row_off, total_rows - seg), row_align)
    adj = row_off - start
    cdma = pltpu.make_async_copy(
        rows_ref.at[b, pl.ds(start, seg)], rows_scr, csem
    )
    cdma.start()
    cdma.wait()
    g, BQ, Dp = rq_ref.shape[3:]
    rq = rq_ref[0, 0, 0].reshape(g * BQ, Dp)           # [gBQ, Dp]
    qpos = q_start + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (g * BQ, 1), 0), BQ
    )                                                  # [gBQ, 1] absolute pos
    s_all = jax.lax.dot_general(
        rq, rows_scr[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                  # [gBQ, SEG]
    s_all = jnp.where(qpos < nv, s_all, NEG_INF)
    s = jnp.max(s_all, axis=0, keepdims=True)          # [1, SEG]

    # ---- phase 2: forced union + exact top-K over scored candidates --------
    jloc = jax.lax.broadcasted_iota(jnp.int32, (1, seg), 1) - adj
    starts_tok = jloc * bsz
    in_seg = (jloc >= 0) & (jloc < nblk)
    causal = in_seg & (starts_tok <= q_end) & (starts_tok < nv)
    forced = causal & (starts_tok < sink_pages * page_size)
    lo = q_start - local_pages * page_size
    forced = forced | (causal & (starts_tok + bsz > lo))
    cand = causal & jnp.logical_not(forced)
    s_m = jnp.where(cand, s, NEG_INF)
    # drop -inf "candidates" (dead query blocks / fewer candidates than K)
    scored = topk_row_mask(s_m, k_sel) & cand & (s_m > NEG_INF / 2)
    # fully-dead query blocks (chunk padding past nv) select nothing: their
    # outputs are discarded, so attending their forced blocks would only
    # burn DMA and overstate the attended-block count (parity: the
    # reference oracle masks identically).
    selected = (forced | scored) & (q_start < nv)
    n_live = jnp.sum(selected.astype(jnp.int32))
    nsel_ref[b, h, qb] = n_live

    # ---- phase 3: flash attention over the selected blocks -----------------
    # selected blocks are walked in index order (see fused_decode)
    q = q_ref[0, 0, 0].reshape(g * BQ, -1).astype(jnp.float32)      # [gBQ, D]
    D = q.shape[-1]
    W = ppb_max * page_size
    tok_ids = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)

    def page_start(p):
        return jnp.clip((p - adj) * ppb, 0, n_pages - ppb_max)

    def kv_dma(slot, p):
        pg = page_start(p)
        return (
            pltpu.make_async_copy(
                k_ref.at[lyr, row, h, pl.ds(pg, ppb_max)], kbuf.at[slot],
                sem.at[slot, 0],
            ),
            pltpu.make_async_copy(
                v_ref.at[lyr, row, h, pl.ds(pg, ppb_max)], vbuf.at[slot],
                sem.at[slot, 1],
            ),
        )

    # n_live == 0 is reachable (any fully-dead trailing query block — they
    # select no blocks at all): the loop below then never runs, so starting
    # the warm-up DMA unconditionally would leak un-awaited semaphore
    # signals into the next grid cell on real hardware.
    p0 = next_selected(selected, -1)

    @pl.when(n_live > 0)
    def _warmup():
        dk0, dv0 = kv_dma(0, p0)
        dk0.start()
        dv0.start()

    def body(i, carry):
        m, l, acc, p = carry
        slot = i % 2
        p_next = next_selected(selected, p)

        @pl.when(i + 1 < n_live)
        def _prefetch_next():
            dk, dv = kv_dma(1 - slot, p_next)
            dk.start()
            dv.start()

        dk, dv = kv_dma(slot, p)
        dk.wait()
        dv.wait()
        kf = kbuf[slot].reshape(W, D).astype(jnp.float32)
        vf = vbuf[slot].reshape(W, D).astype(jnp.float32)

        t0 = (p - adj) * bsz
        pos = page_start(p) * page_size + tok_ids      # [1, W]
        ok_k = (pos >= t0) & (pos < t0 + bsz) & (pos < nv)
        logits = jax.lax.dot_general(
            q, kf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale_qk                                   # [gBQ, W]
        ok = ok_k & (pos <= qpos)
        logits = jnp.where(ok, logits, NEG_INF)

        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        pr = jnp.exp(logits - m_new)
        # fully-masked rows (no visible key in this block) contribute
        # nothing: their p row is exp(NEG_INF - m) == 0 once any real key
        # has been seen; before that m == NEG_INF and p == exp(0) == 1 for
        # masked lanes, so zero those rows explicitly.
        pr = jnp.where(ok, pr, 0.0)
        l_new = l * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            pr, vf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new, p_next

    carry = (
        jnp.full((g * BQ, 1), NEG_INF, jnp.float32),
        jnp.zeros((g * BQ, 1), jnp.float32),
        jnp.zeros((g * BQ, D), jnp.float32),
        p0,
    )
    _, l, acc, _ = jax.lax.fori_loop(0, n_live, body, carry)
    out = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    o_ref[0, 0, 0] = out.reshape(g, BQ, D)


@functools.partial(
    jax.jit,
    static_argnames=(
        "page_size", "ppb_max", "block_q", "sink_pages", "local_pages",
        "seg", "tile_rows", "interpret",
    ),
)
def sparse_prefill(
    q: jax.Array,              # [B, n_kv, nQB, g, BQ, D]
    rq: jax.Array,             # [B, n_kv, nQB, g, BQ, Dp] rank queries
    k_pages: jax.Array,        # [L, Bp, n_kv, n_pages, page, D] stacked pools
    v_pages: jax.Array,        # [L, Bp, n_kv, n_pages, page, D]
    rank_rows: jax.Array,      # [B, total_rows, Dp] f32 dequantized rows
    row_off: jax.Array,        # [H] int32 descriptor arrays ----------------
    n_blocks: jax.Array,       # [H] int32
    k_sel: jax.Array,          # [H] int32 prefill-scaled top-K
    bsz: jax.Array,            # [H] int32
    ppb: jax.Array,            # [H] int32
    n_valid: jax.Array,        # [B] int32
    qb0: jax.Array,            # [1] int32
    layer: jax.Array,          # int32 scalar: the layer the call reads
    slot: jax.Array,           # int32 scalar: the pool row of query row 0
    *,
    page_size: int,
    ppb_max: int,
    block_q: int,
    sink_pages: int,
    local_pages: int,
    seg: int,
    tile_rows: int,
    interpret: bool = False,
):
    """-> (out [B, n_kv, nQB, g, BQ, D], n_attended [B, n_kv, nQB] int32).

    One launch covers every (sequence, kv head, query block) cell of the
    ragged grid; the attended block SET per cell is forced-union-top-K and
    identical whether the query blocks arrive in one shot (``qb0 == 0``)
    or chunk by chunk (``qb0 == chunk_offset // block_q``).  The KV pools
    arrive stacked over layers and slots, as the prefill chunk's layer scan
    carries them; query row ``b`` reads pool row ``slot + b`` of layer
    ``layer`` in place, so no slice of a pool exists as a buffer.
    """
    B, n_kv, nQB, g, BQ, D = q.shape
    n_pages = k_pages.shape[3]
    Dp = rq.shape[-1]
    total_rows = rank_rows.shape[1]

    kernel = functools.partial(
        _sparse_prefill_kernel,
        seg=seg,
        block_q=block_q,
        row_align=tile_rows,
        page_size=page_size,
        ppb_max=ppb_max,
        n_pages=n_pages,
        total_rows=total_rows,
        sink_pages=sink_pages,
        local_pages=local_pages,
        scale_qk=1.0 / float(np.sqrt(D)),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(B, n_kv, nQB),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),      # rank rows
            pl.BlockSpec(
                (1, 1, 1, g, BQ, Dp), lambda b, h, qb, *_: (b, h, qb, 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, 1, g, BQ, D), lambda b, h, qb, *_: (b, h, qb, 0, 0, 0)
            ),
            pl.BlockSpec(memory_space=pl.ANY),      # k pages
            pl.BlockSpec(memory_space=pl.ANY),      # v pages
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, 1, g, BQ, D), lambda b, h, qb, *_: (b, h, qb, 0, 0, 0)
            ),
            pl.BlockSpec(memory_space=pltpu.SMEM),     # whole counter array
        ],
        scratch_shapes=[
            pltpu.VMEM((seg, Dp), jnp.float32),
            pltpu.VMEM((2, ppb_max, page_size, D), k_pages.dtype),
            pltpu.VMEM((2, ppb_max, page_size, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out, nsel = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, nQB, g, BQ, D), q.dtype),
            jax.ShapeDtypeStruct((B, n_kv, nQB), jnp.int32),
        ],
        name="absparse_sparse_prefill",
        interpret=interpret,
    )(
        row_off.astype(jnp.int32),
        n_blocks.astype(jnp.int32),
        k_sel.astype(jnp.int32),
        bsz.astype(jnp.int32),
        ppb.astype(jnp.int32),
        n_valid.astype(jnp.int32),
        qb0.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.asarray(slot, jnp.int32).reshape(1),
        rank_rows.astype(jnp.float32),
        rq,
        q,
        k_pages,
        v_pages,
    )
    return out, nsel
