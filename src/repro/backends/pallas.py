"""Pallas kernel backend: interpret mode off-TPU, Mosaic kernels on TPU.

Wraps the kernels in :mod:`repro.kernels` behind the backend protocol.
Store quantization is inherited from the shared path (so page tables match
the reference backend bit-for-bit); only the pooling / estimation /
attention compute runs in Pallas.

Two decode modes, selected by ``SparseConfig.fused_decode``:

- **staged** (default): three launches per layer — estimation kernel,
  XLA top-k + page-table expansion, paged-attention kernel.  This is the
  parity oracle and the fallback.
- **fused**: ONE ragged-grid launch per layer
  (:mod:`repro.kernels.fused_decode`) that scores the quantized store,
  selects, and attends without materializing the padded score tensor or
  the page table between stages.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends.base import AttentionBackend, CentroidStore
from repro.core.centroids import rank_query
from repro.core.ragged import RaggedLayout
from repro.core.selection import selection_telemetry


class PallasBackend(AttentionBackend):
    name = "pallas"

    def __init__(self, interpret: Optional[bool] = None):
        #: None -> auto (interpret everywhere but TPU), resolved per call;
        #: a chip run pins False so nothing falls back to the interpreter.
        self.interpret = interpret

    def _interp(self) -> bool:
        from repro.kernels import ops

        return ops.default_interpret() if self.interpret is None else self.interpret

    def _pool_rank_keys(
        self, keys: jax.Array, layout: RaggedLayout, method: str
    ) -> List[jax.Array]:
        from repro.kernels import block_centroid

        S = keys.shape[2]
        # heads partitioned by assigned block size (static): one pooling
        # kernel launch per distinct size.
        groups = {}
        for h, b in enumerate(layout.block_sizes):
            groups.setdefault(b, []).append(h)
        per_head: List[Optional[jax.Array]] = [None] * layout.n_heads
        for bsz, heads in sorted(groups.items()):
            sub = keys[:, np.asarray(heads)]                 # [B, Hg, S, D]
            pooled = block_centroid.pool_rank_keys(
                sub, bsz, method, chunk=min(1024, S), interpret=self._interp()
            )                                                # [B, Hg, nb, Dp]
            for i, h in enumerate(heads):
                per_head[h] = pooled[:, i]
        return per_head

    def scores(self, rank_q, store: CentroidStore, layout, n_kv):
        from repro.kernels import ops

        # named_scope tags the ragged launches so jax.profiler / Perfetto
        # device traces attribute kernel time to the AB-Sparse stages.
        with jax.named_scope("absparse.estimation"):
            return ops.centroid_scores(
                rank_q, store, layout, n_kv, interpret=self._interp()
            )

    def attend(self, q, k, v, page_table, page_valid, page_size, seq_len=None):
        from repro.kernels import ops

        with jax.named_scope("absparse.paged_attention"):
            return ops.paged_attention(
                q, k, v, page_table, page_valid, page_size, seq_len,
                interpret=self._interp(),
            )

    def prefill_attention(
        self, q, k, v, score_store, layout, sparse,
        n_valid=None, chunk_offset=0,
        max_pages_per_block=None, layer=None, slot=None,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Query-block sparse flash prefill in ONE Pallas launch
        (:mod:`repro.kernels.sparse_prefill`); the base-class jnp oracle
        remains the parity reference.  Under an active sharding context the
        launch is shard_map'd over the ``(data, model)`` mesh
        (:mod:`repro.distributed.kernel_partition`).  With ``layer`` and
        ``slot`` given, the launch reads the stacked pools in place (see
        :meth:`AttentionBackend.prefill_attention`)."""
        from repro.distributed import kernel_partition

        rq = rank_query(q, sparse.centroid_method, q.shape[-1])
        with jax.named_scope("absparse.sparse_prefill"):
            return kernel_partition.sparse_prefill(
                q, rq, k, v, score_store, layout,
                sink_pages=sparse.sink_pages,
                local_pages=sparse.local_pages,
                block_q=sparse.prefill_block_q,
                topk_scale=sparse.prefill_topk_scale,
                n_valid=n_valid,
                chunk_offset=chunk_offset,
                max_pages_per_block=max_pages_per_block
                or sparse.max_block_size // sparse.page_size,
                interpret=self._interp(), layer=layer, slot=slot,
            )

    def decode(
        self, q, k, v, store, layout, sparse, seq_len=None, collect_tel=False,
        layer=None,
    ) -> Tuple[jax.Array, ...]:
        """Fused single-launch decode when ``sparse.fused_decode`` is set;
        otherwise the shared staged pipeline (the parity oracle).  Under an
        active sharding context the fused launch is shard_map'd over the
        ``(data, model)`` mesh (:mod:`repro.distributed.kernel_partition`).
        With ``layer`` given, the fused launch reads the stacked pools in
        place (see :meth:`AttentionBackend.decode`)."""
        if not sparse.fused_decode:
            return super().decode(
                q, k, v, store, layout, sparse, seq_len,
                collect_tel=collect_tel, layer=layer,
            )
        from repro.distributed import kernel_partition

        rq = rank_query(q, sparse.centroid_method, q.shape[-1])
        with jax.named_scope("absparse.fused_decode"):
            out, table, valid = kernel_partition.fused_decode(
                q, rq, k, v, store, layout,
                sink_pages=sparse.sink_pages,
                local_pages=sparse.local_pages,
                seq_len=seq_len,
                max_pages_per_block=sparse.max_block_size // sparse.page_size,
                interpret=self._interp(), layer=layer,
            )
        pages = valid.sum(axis=(1, 2), dtype=jnp.int32)
        if collect_tel:
            # the fused kernel keeps scores in-register; re-run the (cheap)
            # estimation stage to derive counters from the identical score
            # tensor — this is what makes fused/staged counter parity exact.
            scores = self.scores(
                rq, store.at_layer(layer), layout, table.shape[1]
            )
            tel = selection_telemetry(
                scores, layout, seq_len=seq_len,
                sink_pages=sparse.sink_pages,
                local_pages=sparse.local_pages,
            )
            return out, table, pages, tel
        return out, table, pages
