"""Full-attention oracle backend.

Keeps the exact cache/store structure of the sparse backends (store build
and append are inherited no-op-compatible) but attends over the ENTIRE live
context, ignoring estimation and selection.  This is the paper's
Full Attention baseline, addressable through the same plan/execute API so
benchmarks and parity tests swap it in with one config string.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.backends.base import at_layer, at_rows
from repro.backends.reference import ReferenceBackend
from repro.core.stacked import as_arrays
from repro.core.sparse_attention import as_dense, dense_decode_attention


class DenseBackend(ReferenceBackend):
    name = "dense"

    def append(self, store, k_cache, layout, offsets, seq_len, sparse,
               layer=None):
        # centroids are never read on the dense path; skip the tail refresh.
        return store

    def decode(
        self, q, k, v, store, layout, sparse, seq_len=None, collect_tel=False,
        layer=None,
    ) -> Tuple[jax.Array, ...]:
        k, v = at_layer(k, layer), at_layer(v, layer)
        out = dense_decode_attention(q, as_dense(k), as_dense(v), seq_len=seq_len)
        # full attention reads every live page of every KV head
        la = as_arrays(layout)
        live = la.context_len if seq_len is None else seq_len
        pages = jnp.broadcast_to(
            k.shape[1] * -(-jnp.asarray(live, jnp.int32) // la.page_size),
            (q.shape[0],),
        )
        if collect_tel:           # no selection on the dense path
            return out, None, pages, None
        return out, None, pages

    def prefill_attention(
        self, q, k, v, score_store, layout, sparse,
        n_valid=None, chunk_offset=0,
        max_pages_per_block=None, layer=None, slot=None,
    ):
        """Full-attention prefill oracle: every query attends its whole
        causal prefix; selection is ignored.  This is what the sparse
        prefill parity suite compares against at generous budgets."""
        k = at_rows(k, layer, slot, q.shape[0])
        v = at_rows(v, layer, slot, q.shape[0])
        kd = as_dense(k).astype(jnp.float32)
        vd = as_dense(v).astype(jnp.float32)
        B, Hq, Sq, D = q.shape
        n_kv = kd.shape[1]
        g = Hq // n_kv
        S = kd.shape[2]
        if n_valid is None:
            n_valid = jnp.asarray(chunk_offset + Sq, jnp.int32)
        n_valid = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,))
        qpos = jnp.asarray(chunk_offset, jnp.int32) + jnp.arange(Sq)
        qf = q.reshape(B, n_kv, g, Sq, D).astype(jnp.float32)
        logits = jnp.einsum("bhgqd,bhsd->bhgqs", qf, kd) / jnp.sqrt(
            jnp.float32(D)
        )
        pos = jnp.arange(S, dtype=jnp.int32)
        ok = (
            (pos[None, None, :] <= qpos[None, :, None])
            & (pos[None, None, :] < n_valid[:, None, None])
        )[:, None, None]                                 # [B,1,1,Sq,S]
        logits = jnp.where(ok, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqs,bhsd->bhgqd", probs, vd)
        return out.reshape(B, Hq, Sq, D).astype(q.dtype), None
