"""Fused variable-block-size decode: estimation -> selection -> paged
attention in ONE Pallas launch (the staged pipeline's three kernels plus the
padded-score scatter, collapsed).

Per ``(batch, kv-head)`` grid cell the kernel:

1. **Scores** the head's quantized centroid segment in-register: the packed
   INT8/INT4 codes are DMA'd straight from the flattened ragged store (Dp/2
   bytes per centroid for INT4), dequantized in VREGs with the per-(head,
   channel) affine params, and hit the MXU against the GQA rank-query group
   (a lane-major ``[1, SEG]`` score row).  Neither a dequantized store nor
   the padded ``[B, n_kv, max_blocks]`` score tensor is ever materialized
   in HBM.
2. **Selects** the head's top ``K_h`` blocks in-register via the exact
   k-th-value threshold (32-step binary search over the sortable-integer
   encoding of f32 — :func:`repro.kernels.topk_threshold.topk_row_mask`),
   with the staged path's causal masking and sink/local pinning applied to
   the scores first.  Tie handling (index order) reproduces ``lax.top_k``'s
   selected SET exactly, so the fused and staged paths attend over
   identical tokens.
3. **Attends** flash-style over ONLY the selected blocks, walking them in
   index order (each next block is one masked min over the selection row,
   so DMA addresses stay scalars and the selection is never compacted): a
   double-buffered DMA loop streams each block's pages from the paged KV
   pool in HBM into VMEM while the previous block is on the MXU; the
   running (m, l, acc) softmax state lives in registers.

Raggedness rides a precomputed grid descriptor — per-head flat-row offsets,
real block counts, ``K_h``, block sizes and pages-per-block — delivered via
scalar prefetch (``RaggedLayout.row_offsets_arr`` & co., stacked per layer
in :class:`repro.core.stacked.LayoutArrays`), so heterogeneous head groups
share one launch instead of one per distinct block size.

Interpret mode validates the numerics on CPU; ``tests/test_tpu_compile.py``
compiles the kernel for a v5e chip at llama3.2-3b widths, and
``chip_smoke.py`` runs it there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.centroid_score import dequant_rows, group_scores
from repro.kernels.topk_threshold import next_selected, topk_row_mask

NEG_INF = -1e30
POS_INF = 1e30


def _fused_decode_kernel(
    # -- scalar prefetch: the ragged grid descriptor + live lengths
    row_off_ref,               # [H] int32 flat-row offset of the head segment
    n_blocks_ref,              # [H] int32 real blocks per head
    k_sel_ref,                 # [H] int32 K_h per head
    bsz_ref,                   # [H] int32 block size (tokens)
    ppb_ref,                   # [H] int32 pages per block
    seq_len_ref,               # [B] int32
    layer_ref,                 # [1] int32 layer of the stacked pools
    # -- array inputs
    codes_ref,                 # [L, B, R, Cw] store codes (HBM/ANY)
    scale_ref,                 # [1, 1, 1, Dp] f32
    zero_ref,                  # [1, 1, 1, Dp] f32
    rq_ref,                    # [1, 1, g, Dp] f32 rank queries
    q_ref,                     # [1, 1, g, D]
    k_ref,                     # [L, B, H, n_pages, ps, D] paged pools (HBM/ANY)
    v_ref,                     # [L, B, H, n_pages, ps, D] (HBM/ANY)
    # -- outputs
    o_ref,                     # [1, 1, g, D]
    tbl_ref,                   # [1, 1, 1, P_sel] int32
    vld_ref,                   # [1, 1, 1, P_sel] int32
    # -- scratch
    codes_scr,                 # VMEM [SEG, Cw]
    kbuf, vbuf,                # VMEM [2, ppb_max, ps, D] double buffers
    csem,                      # DMA sem (codes)
    sem,                       # DMA sems [2, 2] (k/v double buffer)
    *,
    bits: int, symmetric: bool, seg: int, p_sel: int,
    page_size: int, ppb_max: int, n_pages: int, total_rows: int,
    sink_pages: int, local_pages: int, scale_qk: float, row_align: int,
):
    b = pl.program_id(0)
    h = pl.program_id(1)
    row_off = row_off_ref[h]
    nblk = n_blocks_ref[h]
    k_sel = k_sel_ref[h]
    bsz = bsz_ref[h]
    ppb = ppb_ref[h]
    sl = seq_len_ref[b]
    lyr = layer_ref[0]

    # ---- phase 1: score the head's centroid segment ------------------------
    # SEG-row window (static size) with a dynamic start; when the segment is
    # shorter than SEG the window is clamped left, and rows before the
    # segment (adj) belong to the previous head and are masked below.
    start = pl.multiple_of(jnp.minimum(row_off, total_rows - seg), row_align)
    adj = row_off - start
    cdma = pltpu.make_async_copy(
        codes_ref.at[lyr, b, pl.ds(start, seg)], codes_scr, csem
    )
    cdma.start()
    cdma.wait()
    rk = dequant_rows(
        codes_scr[...], scale_ref[0, 0], zero_ref[0, 0], bits, symmetric
    )                                                      # [SEG, Dp]
    s = group_scores(rq_ref[0, 0], rk)                     # [1, SEG]

    jloc = jax.lax.broadcasted_iota(jnp.int32, (1, seg), 1) - adj
    starts_tok = jloc * bsz
    in_seg = (jloc >= 0) & (jloc < nblk)
    valid = in_seg & (starts_tok < sl)
    s = jnp.where(valid, s, NEG_INF)
    # sink / local pinning — same semantics as mask_and_pin_scores
    if sink_pages > 0:
        pin = in_seg & (starts_tok < jnp.minimum(sink_pages * page_size, sl))
        s = jnp.where(pin, POS_INF, s)
    if local_pages > 0:
        lo = jnp.maximum(sl - local_pages * page_size, 0)
        pin = valid & (starts_tok + bsz > lo)
        s = jnp.where(pin, POS_INF, s)

    # ---- phase 2: exact top-K_h selection in-register ----------------------
    selected = topk_row_mask(s, k_sel)                     # [1, SEG], K_h set

    # ---- phase 3: flash attention over the selected blocks -----------------
    # Slot i attends the (i+1)-th selected block in index order; the walk
    # finds each next block with one masked min, so the selection is never
    # compacted and the DMA addresses stay scalars.
    q = q_ref[0, 0].astype(jnp.float32)                    # [g, D]
    g, D = q.shape
    W = ppb_max * page_size
    pg_ids = jax.lax.broadcasted_iota(jnp.int32, (1, p_sel), 1)
    tok_ids = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)

    def page_start(p):
        # clamped so a full ppb_max-page window stays in bounds
        return jnp.clip((p - adj) * ppb, 0, n_pages - ppb_max)

    def kv_dma(slot, p):
        pg = page_start(p)
        return (
            pltpu.make_async_copy(
                k_ref.at[lyr, b, h, pl.ds(pg, ppb_max)], kbuf.at[slot],
                sem.at[slot, 0],
            ),
            pltpu.make_async_copy(
                v_ref.at[lyr, b, h, pl.ds(pg, ppb_max)], vbuf.at[slot],
                sem.at[slot, 1],
            ),
        )

    # warm-up: first block's pages in flight before the loop (K_h >= 1)
    p0 = next_selected(selected, -1)
    dk0, dv0 = kv_dma(0, p0)
    dk0.start()
    dv0.start()

    def body(i, carry):
        m, l, acc, p, tbl, vld = carry
        slot = i % 2
        p_next = next_selected(selected, p)

        @pl.when(i + 1 < k_sel)
        def _prefetch_next():
            dk, dv = kv_dma(1 - slot, p_next)
            dk.start()
            dv.start()

        dk, dv = kv_dma(slot, p)
        dk.wait()
        dv.wait()
        kf = kbuf[slot].reshape(W, D).astype(jnp.float32)
        vf = vbuf[slot].reshape(W, D).astype(jnp.float32)

        blk = p - adj
        t0 = blk * bsz
        pos = page_start(p) * page_size + tok_ids          # [1, W]
        ok = (pos >= t0) & (pos < t0 + bsz) & (pos < sl)
        logits = jax.lax.dot_general(
            q, kf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale_qk                                       # [g, W]
        logits = jnp.where(ok, logits, NEG_INF)

        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        alpha = jnp.exp(m - m_new)
        pr = jnp.exp(logits - m_new)
        l_new = l * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            pr, vf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # page table (parity instrumentation / staged interop): this
        # slot's pages are table entries [i*ppb, (i+1)*ppb)
        within = pg_ids - i * ppb
        mine = (within >= 0) & (within < ppb)
        tbl = jnp.where(
            mine, jnp.clip(blk * ppb + within, 0, n_pages - 1), tbl
        )
        live = (blk >= 0) & (blk < nblk) & (t0 < sl)
        vld = jnp.where(mine, live.astype(jnp.int32), vld)
        return m_new, l_new, acc_new, p_next, tbl, vld

    carry = (
        jnp.full((g, 1), NEG_INF, jnp.float32),
        jnp.zeros((g, 1), jnp.float32),
        jnp.zeros((g, D), jnp.float32),
        p0,
        jnp.zeros((1, p_sel), jnp.int32),
        jnp.zeros((1, p_sel), jnp.int32),
    )
    _, l, acc, _, tbl, vld = jax.lax.fori_loop(0, k_sel, body, carry)
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    tbl_ref[0, 0] = tbl
    vld_ref[0, 0] = vld


@functools.partial(
    jax.jit,
    static_argnames=(
        "page_size", "ppb_max", "bits", "symmetric",
        "sink_pages", "local_pages", "seg", "p_sel", "tile_rows", "interpret",
    ),
)
def fused_decode(
    q: jax.Array,              # [B, n_q, D]
    rq: jax.Array,             # [B, n_q, Dp] rank queries
    k_pages: jax.Array,        # [L, B, n_kv, n_pages, page, D] stacked pools
    v_pages: jax.Array,        # [L, B, n_kv, n_pages, page, D]
    codes: jax.Array,          # [L, B, total_rows, Cw] stacked store codes
    scale: jax.Array,          # [B, n_kv, Dp] f32 (layer ``layer``'s)
    zero: jax.Array,           # [B, n_kv, Dp] f32
    row_off: jax.Array,        # [H] int32 descriptor arrays ----------------
    n_blocks: jax.Array,       # [H] int32
    top_k: jax.Array,          # [H] int32
    bsz: jax.Array,            # [H] int32
    ppb: jax.Array,            # [H] int32
    seq_len: jax.Array,        # [B] int32
    layer: jax.Array,          # int32 scalar: the layer the call reads
    *,
    page_size: int,
    ppb_max: int,
    bits: int,
    symmetric: bool,
    sink_pages: int,
    local_pages: int,
    seg: int,
    p_sel: int,
    tile_rows: int,
    interpret: bool = False,
):
    """-> (out [B, n_q, D], page_table [B, H, P_sel] i32, page_valid bool).

    One launch covers every (sequence, kv head) cell of the ragged grid;
    the selected SET of blocks per head is identical to the staged
    estimation -> ``lax.top_k`` -> expansion pipeline.  The KV pools and
    store codes arrive stacked over layers, as the decode step's layer
    scan carries them, and the DMAs address layer ``layer`` in place: no
    layer slice exists as a buffer.
    """
    B, n_q, D = q.shape
    n_kv = k_pages.shape[2]
    n_pages = k_pages.shape[3]
    g = n_q // n_kv
    Dp = rq.shape[-1]
    total_rows = codes.shape[2]
    rq4 = rq.astype(jnp.float32).reshape(B, n_kv, g, Dp)
    q4 = q.reshape(B, n_kv, g, D)

    kernel = functools.partial(
        _fused_decode_kernel,
        bits=bits,
        symmetric=symmetric,
        seg=seg,
        p_sel=p_sel,
        page_size=page_size,
        ppb_max=ppb_max,
        n_pages=n_pages,
        total_rows=total_rows,
        sink_pages=sink_pages,
        local_pages=local_pages,
        scale_qk=1.0 / float(np.sqrt(D)),
        row_align=tile_rows,
    )
    # unit axes keep every VMEM block's last two dims equal to the array's
    # (the TPU (8, 128) block rule) for the per-head params and the table.
    param_spec = pl.BlockSpec((1, 1, 1, Dp), lambda b, h, *_: (b, h, 0, 0))
    row_spec = pl.BlockSpec((1, 1, 1, p_sel), lambda b, h, *_: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(B, n_kv),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),          # codes
            param_spec,
            param_spec,
            pl.BlockSpec((1, 1, g, Dp), lambda b, h, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, g, D), lambda b, h, *_: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),          # k pages
            pl.BlockSpec(memory_space=pl.ANY),          # v pages
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, D), lambda b, h, *_: (b, h, 0, 0)),
            row_spec,
            row_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((seg, codes.shape[-1]), codes.dtype),
            pltpu.VMEM((2, ppb_max, page_size, D), k_pages.dtype),
            pltpu.VMEM((2, ppb_max, page_size, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out, table, valid = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, g, D), q.dtype),
            jax.ShapeDtypeStruct((B, n_kv, 1, p_sel), jnp.int32),
            jax.ShapeDtypeStruct((B, n_kv, 1, p_sel), jnp.int32),
        ],
        name="absparse_fused_decode",
        interpret=interpret,
    )(
        row_off.astype(jnp.int32),
        n_blocks.astype(jnp.int32),
        top_k.astype(jnp.int32),
        bsz.astype(jnp.int32),
        ppb.astype(jnp.int32),
        seq_len.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        codes,
        scale.astype(jnp.float32).reshape(B, n_kv, 1, Dp),
        zero.astype(jnp.float32).reshape(B, n_kv, 1, Dp),
        rq4,
        q4,
        k_pages,
        v_pages,
    )
    table = table.reshape(B, n_kv, p_sel)
    valid = valid.reshape(B, n_kv, p_sel)
    return out.reshape(B, n_q, D), table, valid > 0
