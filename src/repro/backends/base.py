"""The unified attention-backend API: one plan/execute interface.

AB-Sparse is an algorithm-system co-design; this module is the seam between
the algorithm (block-size plans, budgets, rank-key stores) and the systems
that execute it (pure-jnp reference, Pallas kernels, dense oracle).

Three pieces:

- :class:`AttentionPlan` — everything static about sparse attention for one
  ``(model_cfg, context_len)`` pair: per-layer :class:`RaggedLayout`s, the
  token budget, the rank-key width.  Built once (``build_plan`` is cached)
  and reused by the model, the serving engine, the dry-run and benchmarks,
  instead of each caller re-deriving layouts by hand.

- :class:`CentroidStore` — the ONE flattened ragged rank-key store shared by
  every backend (replaces the old reference ``CentroidStore`` / kernel
  ``KernelCentroidStore`` split).  Quantization math lives in
  :mod:`repro.core.quantization`; the byte layout (INT4 split-half packed,
  per-(sequence, head, channel) affine params) is exactly what the Pallas
  estimation kernel DMAs, and the reference path dequantizes the same bytes.

- :class:`AttentionBackend` — the execute protocol
  (``build_store / append / scores / attend / decode``) with a registry.
  ``SparseConfig.backend`` names a registered backend: ``"dense"`` (full
  -attention oracle), ``"reference"`` (pure jnp), ``"pallas"`` (interpret on
  CPU, Mosaic on TPU).  Adding a backend == one module + one
  ``register_backend`` call.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from functools import cached_property
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, SparseConfig
from repro.core.centroids import padded_rank_key_width, rank_query
from repro.core.quantization import (
    affine_params_from_minmax,
    decode_affine,
    encode_affine,
    pack_split_half,
    store_bits,
    store_symmetric,
    unpack_split_half,
)
from repro.core.ragged import RaggedLayout, layout_for
from repro.core.selection import (
    rank_blocks,
    select_page_table,
    selection_telemetry,
)
from repro.core.stacked import LayoutArrays, as_arrays, stack_layouts


# ---------------------------------------------------------------------------
# Unified centroid store
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class CentroidStore:
    """Flattened ragged rank-key store in the canonical byte layout.

    ``codes``: ``[B, total_rows, Dp]`` f32 when ``bits == 0``;
    ``[B, total_rows, Dp]`` uint8 for INT8; ``[B, total_rows, Dp//2]`` uint8
    (split-half packed) for INT4.  Row segments per kv head follow the
    layout's prefix-sum offsets.  ``scale``/``zero``: ``[B, n_kv, Dp]`` f32
    per-(sequence, head, channel) affine params (unused when ``bits == 0``).
    """

    codes: jax.Array
    scale: Optional[jax.Array]
    zero: Optional[jax.Array]
    bits: int            # 0 (f32), 4, or 8
    symmetric: bool = False

    def tree_flatten(self):
        return (self.codes, self.scale, self.zero), (self.bits, self.symmetric)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def bytes_per_row(self) -> int:
        if self.bits == 0:
            return self.codes.shape[-1] * 4
        return self.codes.shape[-1]

    @property
    def nbytes(self) -> int:
        """Total scoring-segment footprint (codes + affine params) — the
        part of the cache the hierarchical KV memory keeps permanently
        HBM-resident, vs the full KV pages it migrates."""
        n = self.codes.size * self.codes.dtype.itemsize
        for arr in (self.scale, self.zero):
            if arr is not None:
                n += arr.size * arr.dtype.itemsize
        return n

    def dequantize(self, layout) -> jax.Array:
        """-> ``[B, total_rows, Dp]`` f32 rank keys (reference-path view of
        the same bytes the Pallas kernel dequantizes in-register)."""
        if self.bits == 0:
            return self.codes.astype(jnp.float32)
        la = as_arrays(layout)
        codes = (
            unpack_split_half(self.codes) if self.bits == 4 else self.codes
        )
        row_head = jnp.repeat(
            la.tile_head, la.tile_rows, total_repeat_length=self.codes.shape[1]
        )                                                     # [rows]
        B = codes.shape[0]
        idx = jnp.broadcast_to(row_head[None, :, None], (B,) + row_head.shape + (1,))
        s = jnp.take_along_axis(self.scale, idx, axis=1)      # [B, rows, Dp]
        z = jnp.take_along_axis(self.zero, idx, axis=1)
        return decode_affine(codes, s, z, self.bits, self.symmetric)

    @classmethod
    def quantize_heads(
        cls,
        per_head_rank_keys: Sequence[jax.Array],   # n_kv x [B, nb_h, Dp]
        layout: RaggedLayout,
        quant: Optional[str],
    ) -> "CentroidStore":
        """Per-head rank keys -> flattened (optionally quantized) store.

        The single quantization path every backend's offline store build
        funnels through: per-(sequence, head, channel) affine params reduced
        over the block-row axis, INT4 split-half packed.
        """
        bits = store_bits(quant)
        symmetric = store_symmetric(quant)
        if bits not in (0, 4, 8):
            raise ValueError(
                f"centroid store supports none/int8/int4 schemes, got {quant!r}"
            )
        if bits == 0:
            segs = []
            for h, rk in enumerate(per_head_rank_keys):
                pad = layout.padded_n_blocks[h] - rk.shape[1]
                segs.append(jnp.pad(rk, ((0, 0), (0, pad), (0, 0))))
            flat = jnp.concatenate(segs, axis=1).astype(jnp.float32)
            return cls(flat, None, None, 0, False)

        code_segs, scales, zeros = [], [], []
        for h, rk in enumerate(per_head_rank_keys):
            rk = rk.astype(jnp.float32)                       # [B, nb, Dp]
            xmin = jnp.min(rk, axis=1, keepdims=True)
            xmax = jnp.max(rk, axis=1, keepdims=True)
            scale, zero = affine_params_from_minmax(xmin, xmax, bits, symmetric)
            codes = encode_affine(rk, scale, zero, bits, symmetric)
            pad = layout.padded_n_blocks[h] - codes.shape[1]
            code_segs.append(jnp.pad(codes, ((0, 0), (0, pad), (0, 0))))
            scales.append(scale[:, 0])                        # [B, Dp]
            zeros.append(zero[:, 0])
        codes = jnp.concatenate(code_segs, axis=1)            # [B, rows, Dp]
        if bits == 4:
            codes = pack_split_half(codes)                    # [B, rows, Dp//2]
        return cls(
            codes,
            jnp.stack(scales, axis=1),                        # [B, n_kv, Dp]
            jnp.stack(zeros, axis=1),
            bits,
            symmetric,
        )


# ---------------------------------------------------------------------------
# Attention plan
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AttentionPlan:
    """Static sparse-attention plan for one ``(model_cfg, context_len)``.

    Hashable and cached (:func:`build_plan`): the layouts, stacked layout
    arrays and prefix offsets are derived once and shared by the cache
    allocator, prefill, decode, the serving engine and the dry-run.
    """

    backend: str
    sparse: SparseConfig
    n_layers: int
    n_kv_heads: int
    head_dim: int
    context_len: int
    #: False when sparse attention is disabled / pointless at this context
    #: (the model then runs every backend's dense fallback).
    active: bool
    layouts: Tuple[RaggedLayout, ...] = ()

    @property
    def token_budget(self) -> int:
        return self.layouts[0].token_budget if self.layouts else 0

    @property
    def rank_key_width(self) -> int:
        """Padded rank-key width Dp (the store's channel dimension)."""
        return padded_rank_key_width(self.head_dim, self.sparse.centroid_method)

    def layout(self, layer: int) -> RaggedLayout:
        return self.layouts[layer]

    @cached_property
    def stacked(self) -> LayoutArrays:
        """All layer layouts as one ``[L, ...]`` array stack (scan-ready).

        Host numpy children: this property is cached on the shared
        (lru-cached) plan and its first access may occur under a trace, so
        jnp constants here would leak tracers into every later consumer.
        """
        return stack_layouts(list(self.layouts))

    @cached_property
    def offsets(self) -> np.ndarray:
        """[n_layers, n_kv_heads] int32 flat-row offset of each head segment
        (host numpy — see :attr:`stacked` for why)."""
        offs = np.zeros((self.n_layers, self.n_kv_heads), np.int32)
        for l, lay in enumerate(self.layouts):
            offs[l] = lay.offsets[:-1]
        return offs

    def get_backend(self) -> "AttentionBackend":
        return get_backend(self.backend)


@functools.lru_cache(maxsize=128)
def build_plan(model_cfg: ModelConfig, context_len: int) -> AttentionPlan:
    """The one place layouts are derived from a model config + context."""
    sp = model_cfg.sparse
    active = (
        sp.enabled
        and not model_cfg.is_attention_free
        and context_len >= 2 * sp.budget_for(context_len)
    )
    layouts: Tuple[RaggedLayout, ...] = ()
    if active:
        budget = sp.budget_for(context_len)
        layouts = tuple(
            layout_for(
                sp.layer_block_sizes(l, model_cfg.n_kv_heads),
                context_len,
                sp.page_size,
                budget,
            )
            for l in range(model_cfg.n_layers)
        )
    return AttentionPlan(
        backend=sp.backend,
        sparse=sp,
        n_layers=model_cfg.n_layers,
        n_kv_heads=model_cfg.n_kv_heads,
        head_dim=model_cfg.resolved_head_dim,
        context_len=context_len,
        active=active,
        layouts=layouts,
    )


# ---------------------------------------------------------------------------
# Backend protocol
# ---------------------------------------------------------------------------


class AttentionBackend:
    """plan/execute protocol.  Subclasses implement the pooling, estimation
    and attention stages; store quantization and the decode orchestration
    are shared so all backends emit byte-identical stores and page tables.
    """

    name: str = "?"

    # -- store construction --------------------------------------------------

    def _pool_rank_keys(
        self, keys: jax.Array, layout: RaggedLayout, method: str
    ) -> List[jax.Array]:
        """keys [B, n_kv, S, D] -> per-head rank keys (n_kv x [B, nb_h, Dp])."""
        raise NotImplementedError

    def build_store(
        self,
        keys: jax.Array,
        layout: RaggedLayout,
        method: str = "quest",
        quant: Optional[str] = "int4_asym",
    ) -> CentroidStore:
        """Offline store build from a dense key cache (benchmarks, tests,
        one-shot prefill at a static layout).  Defaults to the paper's
        deployed INT4-asym scheme, matching the pre-unification builders."""
        per_head = self._pool_rank_keys(keys, layout, method)
        return CentroidStore.quantize_heads(per_head, layout, quant)

    def prefill_store(
        self,
        k_cache: jax.Array,
        layout,                               # LayoutArrays (scan-safe)
        offsets: jax.Array,
        sparse: SparseConfig,
        quant: Optional[str] = None,
    ) -> CentroidStore:
        """Scan-safe in-model store build (dynamic per-head block sizes).

        Shared across backends so prefill emits identical bytes whatever
        executes decode — a prerequisite for backend-parity page tables.
        """
        from repro.backends.store import build_store_codes

        return build_store_codes(k_cache, layout, offsets, sparse, quant)

    def append(
        self,
        store: CentroidStore,
        k_cache: jax.Array,
        layout,                               # LayoutArrays
        offsets: jax.Array,
        seq_len: jax.Array,
        sparse: SparseConfig,
    ) -> CentroidStore:
        """Incremental decode-time update: refresh the rank-key row of the
        block containing the newest token (frozen affine params)."""
        from repro.backends.store import refresh_tail_codes

        codes = refresh_tail_codes(
            store, k_cache, layout, offsets, seq_len, sparse
        )
        return CentroidStore(
            codes, store.scale, store.zero, store.bits, store.symmetric
        )

    def prefill_score_rows(
        self,
        k_cache: jax.Array,
        layout,                               # LayoutArrays (scan-safe)
        offsets: jax.Array,
        sparse: SparseConfig,
        quant: Optional[str] = None,
        sel_nb=None,
    ) -> "CentroidStore":
        """Full-sequence prefill scoring segment (per-ROW affine codes —
        a row's bytes depend only on its own block's keys, the invariant
        chunked sparse prefill relies on).  Shared across backends."""
        from repro.backends.store import build_score_rows

        codes, scale, zero = build_score_rows(
            k_cache, layout, offsets, sparse, quant, sel_nb=sel_nb
        )
        q = sparse.quant if quant is None else quant
        return CentroidStore(codes, scale, zero, store_bits(q), store_symmetric(q))

    def prefill_stores(
        self,
        k_cache: jax.Array,
        layout,
        offsets: jax.Array,
        sparse: SparseConfig,
        quant: Optional[str] = None,
    ) -> Tuple["CentroidStore", "CentroidStore"]:
        """(decode store, prefill scoring segment) from ONE page-stats pass
        over the K cache — sparse prefill needs both per layer."""
        from repro.backends.store import _selected_rank_keys, build_store_codes

        from repro.core.stacked import as_arrays

        la = as_arrays(layout)
        sel_nb = _selected_rank_keys(k_cache, la, sparse)
        store = build_store_codes(
            k_cache, la, offsets, sparse, quant, sel_nb=sel_nb
        )
        score = self.prefill_score_rows(
            k_cache, la, offsets, sparse, quant, sel_nb=sel_nb
        )
        return store, score

    def refresh_score_rows(
        self,
        score_store: "CentroidStore",
        k_cache: jax.Array,
        layout,
        offsets: jax.Array,
        chunk_start: jax.Array,
        chunk_end: jax.Array,
        sparse: SparseConfig,
        window: int,
    ) -> "CentroidStore":
        """Incremental scoring-segment update: re-encode the rows of blocks
        completed by ``[chunk_start, chunk_end)`` (chunked prefill)."""
        from repro.backends.store import refresh_score_rows

        codes, scale, zero = refresh_score_rows(
            score_store.codes, score_store.scale, score_store.zero,
            k_cache, layout, offsets, chunk_start, chunk_end, sparse, window,
            bits=score_store.bits, symmetric=score_store.symmetric,
        )
        return CentroidStore(
            codes, scale, zero, score_store.bits, score_store.symmetric
        )

    # -- execute stages ------------------------------------------------------

    def scores(
        self, rank_q: jax.Array, store: CentroidStore, layout, n_kv: int
    ) -> jax.Array:
        """rank queries [B, n_q, Dp] + store -> block scores
        [B, n_kv, max_blocks] (-inf pads)."""
        raise NotImplementedError

    def attend(
        self,
        q: jax.Array,
        k: jax.Array,
        v: jax.Array,
        page_table: jax.Array,
        page_valid: jax.Array,
        page_size: int,
        seq_len: Optional[jax.Array] = None,
    ) -> jax.Array:
        raise NotImplementedError

    def prefill_attention(
        self,
        q: jax.Array,                         # [B, Hq, Sq, D]
        k: jax.Array,                         # paged [B, n_kv, nP, page, D]
        v: jax.Array,
        score_store: Optional[CentroidStore],  # per-row prefill segment
        layout,
        sparse: SparseConfig,
        n_valid: Optional[jax.Array] = None,  # [B] live tokens after chunk
        chunk_offset=0,                       # abs pos of q[..., 0, :]
        max_pages_per_block: Optional[int] = None,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Query-block sparse prefill attention: each query block attends
        forced (sink + local/diagonal) blocks plus its top-scored blocks.
        Default implementation is the pure-jnp selection-exact oracle
        (:func:`repro.kernels.ops.sparse_prefill_reference` — same shared
        preamble as the kernel entry point); the Pallas backend overrides
        with the fused kernel.  ``chunk_offset`` must be a multiple of
        ``sparse.prefill_block_q`` (chunked replay).
        -> (out [B, Hq, Sq, D], n_attended [B, n_kv, nQB])."""
        from repro.kernels import ops

        rq = rank_query(q, sparse.centroid_method, q.shape[-1])
        return ops.sparse_prefill_reference(
            q, rq, k, v, score_store, layout,
            sink_pages=sparse.sink_pages,
            local_pages=sparse.local_pages,
            block_q=sparse.prefill_block_q,
            topk_scale=sparse.prefill_topk_scale,
            n_valid=n_valid,
            chunk_offset=chunk_offset,
        )

    def decode(
        self,
        q: jax.Array,
        k: jax.Array,
        v: jax.Array,
        store: CentroidStore,
        layout,
        sparse: SparseConfig,
        seq_len: Optional[jax.Array] = None,
        collect_tel: bool = False,
    ) -> Tuple[jax.Array, ...]:
        """Full AB-Sparse decode step: estimation -> adaptive top-k ->
        paged attention.  q [B, n_q, D]; k/v paged
        ``[B, n_kv, n_pages, page, D]`` (the cache's native layout) or
        dense ``[B, n_kv, S, D]`` ->
        (out [B, n_q, D], page_table [B, H, P_sel], pages [B] int32), where
        ``pages`` counts the KV pages attention read per slot, summed over
        heads (the valid entries of the page table).

        With ``collect_tel=True`` the return gains a fourth element: per-slot
        sparsity counters ``[B, 4]`` (:func:`selection_telemetry`) derived
        from the SAME estimation scores the selection just ranked — no
        second pass over the store, so telemetry costs only a top-k over the
        (small) block-score tensor."""
        la = as_arrays(layout)
        n_kv = k.shape[1]
        rq = rank_query(q, sparse.centroid_method, q.shape[-1])
        scores = self.scores(rq, store, la, n_kv)
        ranked = rank_blocks(
            scores, la, seq_len, sparse.sink_pages, sparse.local_pages
        )
        page_table, page_valid = select_page_table(
            scores,
            la,
            seq_len=seq_len,
            sink_pages=sparse.sink_pages,
            local_pages=sparse.local_pages,
            ranked=ranked,
        )
        out = self.attend(
            q, k, v, page_table, page_valid, la.page_size, seq_len
        )
        pages = page_valid.sum(axis=(1, 2), dtype=jnp.int32)
        if collect_tel:
            tel = selection_telemetry(
                scores, la, seq_len=seq_len,
                sink_pages=sparse.sink_pages,
                local_pages=sparse.local_pages,
                ranked=ranked,
            )
            return out, page_table, pages, tel
        return out, page_table, pages


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, AttentionBackend] = {}


def register_backend(backend: AttentionBackend) -> AttentionBackend:
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> AttentionBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown attention backend {name!r}; "
            f"available: {available_backends()}"
        ) from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))
