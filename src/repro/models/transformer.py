"""The unified Transformer covering all assigned architectures.

One composable model: dense GQA / MoE FFN / RG-LRU hybrid / RWKV6 / modality
-stub prefixes, driven entirely by :class:`repro.config.ModelConfig`.

Layer execution uses ``lax.scan`` over *pattern cycles* (params stacked along
the cycle axis) so 96-layer models lower to small HLO; remainder layers (when
``n_layers % len(pattern) != 0``) run unscanned.  Per-layer heterogeneous
AB-Sparse layouts ride the scan as stacked arrays (:mod:`repro.core.stacked`).

Three entry points per model:
  forward_train  full causal pass -> final hidden (loss via chunked CE)
  prefill        builds the KV cache + quantized centroid store
  decode_step    one token; AB-Sparse estimation -> top-k -> paged attention
                 on attention layers when enabled, O(1) state for
                 recurrent/SSM layers.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.backends import AttentionPlan, CentroidStore, build_plan, get_backend
from repro.config import ModelConfig
from repro.core.centroids import rank_query
from repro.core.quantization import store_bits, store_symmetric
from repro.core.ragged import RaggedLayout
from repro.core.selection import selected_page_masks
from repro.core.sparse_attention import dense_decode_attention
from repro.distributed.sharding import constrain
from repro.models import layers, moe as moe_mod, rglru, rwkv6

Cache = Dict[str, Any]

def _attn_chunk(S: int, target: int = 512) -> int:
    """Largest chunk <= target that divides S (prefix-extended sequences
    like 4096+256 patches are not powers of two)."""
    c = min(target, S)
    while S % c:
        c -= 1
    return c



def _split_like(key, n):
    return list(jax.random.split(key, n))


@dataclass(frozen=True)
class _Plan:
    """Static execution plan derived from the config."""

    pattern: Tuple[str, ...]
    n_cycles: int
    n_rest: int          # remainder layers (prefix of pattern)

    @property
    def rest_kinds(self) -> Tuple[str, ...]:
        return self.pattern[: self.n_rest]


class Transformer:
    def __init__(self, cfg: ModelConfig, context_len: Optional[int] = None):
        self.cfg = cfg
        pattern = cfg.layer_pattern
        self.plan = _Plan(
            pattern=pattern,
            n_cycles=cfg.n_layers // len(pattern),
            n_rest=cfg.n_layers % len(pattern),
        )
        self.dtype = jnp.dtype(cfg.dtype)
        self._context_len = context_len
        #: attention backend resolved once through the registry; every
        #: sparse-path stage (store build / append / scores / attend) routes
        #: through it.
        self.backend = get_backend(cfg.sparse.backend)
        if cfg.sparse.enabled:
            assert pattern == ("attn",), (
                "AB-Sparse decode currently assumes a homogeneous global-"
                "attention stack (see DESIGN.md §Arch-applicability)"
            )

    # ------------------------------------------------------------------ init

    def _init_layer(self, key, kind: str) -> Dict:
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        p: Dict[str, Any] = {
            "norm1": layers.init_rmsnorm(cfg.d_model, self.dtype),
            "norm2": layers.init_rmsnorm(cfg.d_model, self.dtype),
        }
        if kind in ("attn", "local_attn"):
            p["attn"] = layers.init_attention(k1, cfg)
        elif kind == "rglru":
            p["rec"] = rglru.init_rglru(k1, cfg)
        elif kind == "rwkv":
            p["tmix"] = rwkv6.init_rwkv(k1, cfg)
        else:
            raise ValueError(kind)
        if cfg.moe is not None:
            p["ffn"] = moe_mod.init_moe(k2, cfg)
        else:
            p["ffn"] = layers.init_mlp(
                k2, cfg.d_model, cfg.d_ff, cfg.activation, self.dtype
            )
        return p

    def init(self, key) -> Dict:
        cfg = self.cfg
        ke, kh, kl = jax.random.split(key, 3)
        params: Dict[str, Any] = {
            "embed": layers.truncated_normal_init(
                ke, (cfg.vocab_size, cfg.d_model), 0.02, self.dtype
            ),
            "final_norm": layers.init_rmsnorm(cfg.d_model, self.dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = layers.truncated_normal_init(
                kh, (cfg.d_model, cfg.vocab_size),
                cfg.d_model**-0.5, self.dtype,
            )

        # stacked cycle params: vmap init over the cycle axis
        pat = self.plan.pattern
        cyc_keys = jax.random.split(kl, max(self.plan.n_cycles, 1))

        def init_cycle(k):
            ks = jax.random.split(k, len(pat))
            return {
                f"pos{i}": self._init_layer(ks[i], kind)
                for i, kind in enumerate(pat)
            }

        if self.plan.n_cycles > 0:
            params["cycles"] = jax.vmap(init_cycle)(jnp.stack(cyc_keys))
        if self.plan.n_rest:
            kr = jax.random.fold_in(kl, 10_007)
            rest_keys = jax.random.split(kr, self.plan.n_rest)
            params["rest"] = [
                self._init_layer(rest_keys[i], kind)
                for i, kind in enumerate(self.plan.rest_kinds)
            ]
        return params

    # -------------------------------------------------------------- layouts

    def attention_plan(self, context_len: int) -> AttentionPlan:
        """The cached static plan (layouts / budget / rank-key width) for
        this model at ``context_len`` — the single derivation point."""
        return build_plan(self.cfg, context_len)

    def sparse_layouts(self, context_len: int) -> Optional[List[RaggedLayout]]:
        plan = self.attention_plan(context_len)
        return list(plan.layouts) if plan.active else None

    def use_sparse(self, context_len: int) -> bool:
        return self.attention_plan(context_len).active

    # -------------------------------------------------------------- embedding

    def embed_inputs(
        self,
        params,
        tokens: jax.Array,                   # [B, S]
        prefix_emb: Optional[jax.Array],     # [B, P, d] or None
    ) -> jax.Array:
        cfg = self.cfg
        x = params["embed"][tokens]          # [B, S, d]
        if cfg.family in ("vlm", "audio") and prefix_emb is not None:
            x = jnp.concatenate([prefix_emb.astype(x.dtype), x], axis=1)
        if cfg.name.startswith("musicgen"):
            # sinusoidal additive positions (MusicGen uses absolute pos emb)
            pos = jnp.arange(x.shape[1])
            x = x + layers.sinusoidal_embedding(pos, cfg.d_model)[None].astype(
                x.dtype
            )
        return constrain(x, "batch", None, "embed")

    def unembed(self, params, h: jax.Array) -> jax.Array:
        if self.cfg.tie_embeddings:
            w = params["embed"].T
        else:
            w = params["lm_head"]
        logits = jnp.einsum("...d,dv->...v", h, w)
        return logits

    # ----------------------------------------------------------- train pass

    def _layer_train(self, p, kind: str, x, positions, aux_sum):
        cfg = self.cfg
        h = layers.rms_norm(p["norm1"], x, cfg.norm_eps)
        if kind in ("attn", "local_attn"):
            q, k, v = layers.qkv_project(p["attn"], h, cfg, positions)
            window = cfg.local_window if kind == "local_attn" else None
            attn = layers.chunked_causal_attention(
                jnp.moveaxis(q, 1, 2),
                jnp.moveaxis(k, 1, 2),
                jnp.moveaxis(v, 1, 2),
                chunk=_attn_chunk(x.shape[1]),
                window=window,
            )
            h = layers.out_project(p["attn"], jnp.moveaxis(attn, 1, 2), cfg)
        elif kind == "rglru":
            h = rglru.rglru_block(p["rec"], h, cfg)
        elif kind == "rwkv":
            h = rwkv6.rwkv_time_mix(p["tmix"], h, cfg)
        x = x + h
        h = layers.rms_norm(p["norm2"], x, cfg.norm_eps)
        if cfg.moe is not None:
            h, aux = moe_mod.moe_ffn(p["ffn"], h, cfg)
            aux_sum = aux_sum + aux
        else:
            h = layers.mlp(p["ffn"], h, cfg.activation)
        return x + h, aux_sum

    def forward_train(
        self,
        params,
        tokens: jax.Array,
        prefix_emb: Optional[jax.Array] = None,
        remat: str = "none",
    ) -> Tuple[jax.Array, jax.Array]:
        """-> (final hidden [B, S_tot, d], moe aux loss scalar)."""
        cfg = self.cfg
        x = self.embed_inputs(params, tokens, prefix_emb)
        S_tot = x.shape[1]
        positions = jnp.arange(S_tot)[None, :]
        pat = self.plan.pattern

        def cycle_fn(carry, cyc_params):
            from repro.distributed.params import (
                cast_cotangent,
                shard_param_cotangents,
            )

            x, aux = carry
            cyc_params = shard_param_cotangents(cyc_params)
            x = cast_cotangent(x, self.dtype)
            for i, kind in enumerate(pat):
                x, aux = self._layer_train(
                    cyc_params[f"pos{i}"], kind, x, positions, aux
                )
            return (x, aux), None

        if remat == "full":
            cycle_fn = jax.checkpoint(cycle_fn)
        elif remat == "dots":
            cycle_fn = jax.checkpoint(
                cycle_fn,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )

        aux0 = jnp.zeros((), jnp.float32)
        if self.plan.n_cycles > 0:
            (x, aux), _ = jax.lax.scan(cycle_fn, (x, aux0), params["cycles"])
        else:
            aux = aux0
        for i, kind in enumerate(self.plan.rest_kinds):
            x, aux = self._layer_train(params["rest"][i], kind, x, positions, aux)
        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        return x, aux

    def loss(
        self,
        params,
        tokens: jax.Array,            # [B, S]
        prefix_emb: Optional[jax.Array] = None,
        remat: str = "none",
        label_chunk: int = 2048,
    ) -> jax.Array:
        """Next-token CE over the token region (prefix positions excluded),
        computed in sequence chunks so [B, S, vocab] never materializes.

        Chunking trades the logits buffer against one (tied-)embedding
        gradient all-reduce PER CHUNK in the backward pass — with pure-FSDP
        batch sharding the per-device logits are small, so fewer, larger
        chunks win (§Perf iteration 2.5)."""
        cfg = self.cfg
        h, aux = self.forward_train(params, tokens, prefix_emb, remat)
        P = h.shape[1] - tokens.shape[1]
        h_tok = h[:, P:, :]
        inputs = h_tok[:, :-1]
        targets = tokens[:, 1:]

        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        B, Sm1, d = inputs.shape
        label_chunk = min(label_chunk, Sm1)
        n_chunks = Sm1 // label_chunk
        rem = Sm1 - n_chunks * label_chunk

        def chunk_loss(h_c, t_c):
            logits = jnp.einsum("bsd,dv->bsv", h_c, w).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
            return jnp.sum(logz - gold)

        total = jnp.zeros((), jnp.float32)
        if n_chunks:
            hc = inputs[:, : n_chunks * label_chunk].reshape(
                B, n_chunks, label_chunk, d
            )
            tc = targets[:, : n_chunks * label_chunk].reshape(
                B, n_chunks, label_chunk
            )

            def body(tot, xs):
                h_c, t_c = xs
                return tot + chunk_loss(h_c, t_c), None

            total, _ = jax.lax.scan(
                body,
                total,
                (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(tc, 1, 0)),
            )
        if rem:
            total = total + chunk_loss(inputs[:, -rem:], targets[:, -rem:])
        ce = total / (B * Sm1)
        if cfg.moe is not None:
            ce = ce + cfg.moe.router_aux_weight * aux / cfg.n_layers
        return ce

    # ----------------------------------------------------------------- cache

    def init_cache(
        self, batch: int, max_context: int, quant: Optional[str] = None
    ) -> Cache:
        """Allocate the decode cache (KV pools / recurrent states / centroid
        store) for ``batch`` sequences of up to ``max_context`` tokens."""
        cfg = self.cfg
        quant = cfg.sparse.quant if quant is None else quant
        hd = cfg.resolved_head_dim
        pat = self.plan.pattern
        nc = self.plan.n_cycles
        cache: Cache = {"seq_len": jnp.zeros((batch,), jnp.int32)}

        aplan = self.attention_plan(max_context)
        sparse = aplan.active
        if sparse:
            # private copies: the engine donates the cache to its jit'd steps,
            # and donating the plan's own (LRU-cached, shared) descriptor
            # buffers would invalidate them for every other plan consumer.
            cache["_layouts"] = jax.tree.map(jnp.array, aplan.stacked)
            cache["_offsets"] = jnp.array(aplan.offsets)

        def per_pos(i, kind):
            entry = {}
            if kind == "attn":
                if sparse:
                    # the sparse decode path holds the KV cache in its paged
                    # [.., n_pages, page, hd] form — reshaped ONCE here at
                    # allocation instead of on every paged-attention call.
                    ps = cfg.sparse.page_size
                    entry["k"] = jnp.zeros(
                        (nc, batch, cfg.n_kv_heads, max_context // ps, ps, hd),
                        self.dtype,
                    )
                else:
                    entry["k"] = jnp.zeros(
                        (nc, batch, cfg.n_kv_heads, max_context, hd), self.dtype
                    )
                entry["v"] = jnp.zeros_like(entry["k"])
                if sparse:
                    stk = cache["_layouts"]
                    Dp = aplan.rank_key_width
                    bits = store_bits(quant)
                    if bits == 4:
                        entry["codes"] = jnp.zeros(
                            (nc, batch, stk.total_rows, Dp // 2), jnp.uint8
                        )
                    elif bits == 8:
                        entry["codes"] = jnp.zeros(
                            (nc, batch, stk.total_rows, Dp), jnp.uint8
                        )
                    else:
                        entry["codes"] = jnp.zeros(
                            (nc, batch, stk.total_rows, Dp), jnp.float32
                        )
                    entry["scale"] = jnp.ones(
                        (nc, batch, cfg.n_kv_heads, Dp), jnp.float32
                    )
                    entry["zero"] = jnp.zeros_like(entry["scale"])
                    if cfg.sparse.sparse_prefill:
                        # running prefill scoring segment (per-ROW affine):
                        # chunked prefill carries it across chunks so later
                        # chunks can score earlier blocks.
                        cw = Dp // 2 if bits == 4 else Dp
                        cdt = jnp.uint8 if bits else jnp.float32
                        entry["pcodes"] = jnp.zeros(
                            (nc, batch, stk.total_rows, cw), cdt
                        )
                        entry["pscale"] = jnp.ones(
                            (nc, batch, stk.total_rows, 1), jnp.float32
                        )
                        entry["pzero"] = jnp.zeros_like(entry["pscale"])
            elif kind == "local_attn":
                W = min(cfg.local_window, max_context)
                entry["k"] = jnp.zeros(
                    (nc, batch, cfg.n_kv_heads, W, hd), self.dtype
                )
                entry["v"] = jnp.zeros_like(entry["k"])
            elif kind == "rglru":
                h0, c0 = rglru.init_state(cfg, batch)
                entry["h"] = jnp.zeros((nc,) + h0.shape, h0.dtype)
                entry["conv"] = jnp.zeros((nc,) + c0.shape, c0.dtype)
            elif kind == "rwkv":
                S0, xp0 = rwkv6.init_state(cfg, batch)
                entry["S"] = jnp.zeros((nc,) + S0.shape, S0.dtype)
                entry["xprev"] = jnp.zeros((nc,) + xp0.shape, xp0.dtype)
            return entry

        for i, kind in enumerate(pat):
            cache[f"pos{i}"] = per_pos(i, kind)
        if self.plan.n_rest:
            cache["rest"] = []
            for i, kind in enumerate(self.plan.rest_kinds):
                e = per_pos(i, kind)
                cache["rest"].append(jax.tree.map(lambda a: a[0], e))
        return cache

    # --------------------------------------------------------------- prefill

    def prefill(
        self,
        params,
        tokens: jax.Array,                    # [B, S]
        prefix_emb: Optional[jax.Array] = None,
        max_context: Optional[int] = None,
        quant: Optional[str] = None,
    ) -> Tuple[jax.Array, Cache]:
        """Process the full prompt; build KV cache + centroid store.
        -> (last-token logits [B, vocab], cache)."""
        cfg = self.cfg
        x = self.embed_inputs(params, tokens, prefix_emb)
        B, S_tot, _ = x.shape
        if max_context is None:
            max_context = S_tot
        cache = self.init_cache(B, max_context, quant=quant)
        positions = jnp.arange(S_tot)[None, :]
        pat = self.plan.pattern
        sparse = self.use_sparse(max_context)
        quant = cfg.sparse.quant if quant is None else quant
        # static DMA window of the sparse prefill launch (a Python int, so
        # the layer scan sees a compile-time constant).
        sp_ppb_max = cfg.sparse.max_block_size // cfg.sparse.page_size

        def run_layer(p, kind, x, entry, layer_layout, layer_offs):
            cfgl = self.cfg
            h = layers.rms_norm(p["norm1"], x, cfgl.norm_eps)
            new_entry = dict(entry)
            if kind in ("attn", "local_attn"):
                q, k, v = layers.qkv_project(p["attn"], h, cfgl, positions)
                window = cfgl.local_window if kind == "local_attn" else None
                use_sp = sparse and cfgl.sparse.sparse_prefill and kind == "attn"
                kk = jnp.moveaxis(k, 1, 2)      # [B, n_kv, S, hd]
                vv = jnp.moveaxis(v, 1, 2)
                score_store = None
                if kind == "attn":
                    pad = max_context - S_tot
                    kk = jnp.pad(kk, ((0, 0), (0, 0), (0, pad), (0, 0)))
                    vv = jnp.pad(vv, ((0, 0), (0, 0), (0, pad), (0, 0)))
                    if sparse:
                        # cache holds the paged view; reshaped once here.
                        ps = cfgl.sparse.page_size
                        kk = kk.reshape(
                            B, cfgl.n_kv_heads, max_context // ps, ps,
                            cfgl.resolved_head_dim,
                        )
                        vv = vv.reshape(kk.shape)
                        if use_sp:
                            # decode store + scoring segment share one
                            # page-stats pass over the K cache.
                            store, score_store = self.backend.prefill_stores(
                                kk, layer_layout, layer_offs,
                                cfgl.sparse, quant=quant,
                            )
                            new_entry["pcodes"] = score_store.codes
                            new_entry["pscale"] = score_store.scale
                            new_entry["pzero"] = score_store.zero
                        else:
                            store = self.backend.prefill_store(
                                kk, layer_layout, layer_offs,
                                cfgl.sparse, quant=quant,
                            )
                        new_entry["codes"] = store.codes
                        new_entry["scale"] = store.scale
                        new_entry["zero"] = store.zero
                    new_entry["k"] = kk
                    new_entry["v"] = vv
                if use_sp:
                    # query-block sparse flash prefill over the ragged layout
                    attn_o, _ = self.backend.prefill_attention(
                        jnp.moveaxis(q, 1, 2), kk, vv, score_store,
                        layer_layout, cfgl.sparse,
                        n_valid=jnp.full((B,), S_tot, jnp.int32),
                        max_pages_per_block=sp_ppb_max,
                    )
                    h = layers.out_project(
                        p["attn"], jnp.moveaxis(attn_o, 1, 2), cfgl
                    )
                else:
                    attn = layers.chunked_causal_attention(
                        jnp.moveaxis(q, 1, 2),
                        jnp.moveaxis(k, 1, 2),
                        jnp.moveaxis(v, 1, 2),
                        chunk=_attn_chunk(S_tot),
                        window=window,
                    )
                    h = layers.out_project(
                        p["attn"], jnp.moveaxis(attn, 1, 2), cfgl
                    )
                if kind == "local_attn":
                    # ring-buffer fill: last min(W, S) tokens at slot pos % W
                    W = entry["k"].shape[-2]
                    L = min(W, S_tot)
                    tail_pos = jnp.arange(S_tot - L, S_tot)
                    slots = tail_pos % W
                    new_entry["k"] = entry["k"].at[:, :, slots].set(
                        kk[:, :, -L:]
                    )
                    new_entry["v"] = entry["v"].at[:, :, slots].set(
                        vv[:, :, -L:]
                    )
            elif kind == "rglru":
                h = rglru.rglru_block(p["rec"], h, cfgl)
                # rebuild the final state by a short decode replay of the
                # last CONV_K tokens is avoided: recompute states directly.
                new_entry["h"], new_entry["conv"] = self._rglru_final_state(
                    p["rec"], layers.rms_norm(p["norm1"], x, cfgl.norm_eps)
                )
            elif kind == "rwkv":
                h = rwkv6.rwkv_time_mix(p["tmix"], h, cfgl)
                new_entry["S"], new_entry["xprev"] = self._rwkv_final_state(
                    p["tmix"], layers.rms_norm(p["norm1"], x, cfgl.norm_eps)
                )
            x = x + h
            h = layers.rms_norm(p["norm2"], x, cfgl.norm_eps)
            if cfgl.moe is not None:
                h, _ = moe_mod.moe_ffn(p["ffn"], h, cfgl)
            else:
                h = layers.mlp(p["ffn"], h, cfgl.activation)
            return x + h, new_entry

        stk = cache.get("_layouts")
        all_offs = cache.get("_offsets")

        def cycle_fn(x, xs):
            cyc_params, cyc_cache, cyc_idx = xs
            new_cache = {}
            for i, kind in enumerate(pat):
                is_sparse_attn = stk is not None and kind == "attn"
                lay = stk.layer(cyc_idx) if is_sparse_attn else None
                offs = all_offs[cyc_idx] if is_sparse_attn else None
                x, new_cache[f"pos{i}"] = run_layer(
                    cyc_params[f"pos{i}"], kind, x, cyc_cache[f"pos{i}"], lay, offs
                )
            return x, new_cache

        if self.plan.n_cycles > 0:
            cyc_cache_in = {
                f"pos{i}": cache[f"pos{i}"] for i in range(len(pat))
            }
            x, new_cyc = jax.lax.scan(
                cycle_fn,
                x,
                (params["cycles"], cyc_cache_in, jnp.arange(self.plan.n_cycles)),
            )
            for i in range(len(pat)):
                cache[f"pos{i}"] = new_cyc[f"pos{i}"]
        for i, kind in enumerate(self.plan.rest_kinds):
            lay_idx = self.plan.n_cycles * len(pat) + i
            is_sparse_attn = stk is not None and kind == "attn"
            lay = stk.layer(lay_idx) if is_sparse_attn else None
            offs = all_offs[lay_idx] if is_sparse_attn else None
            x, cache["rest"][i] = run_layer(
                params["rest"][i], kind, x, cache["rest"][i], lay, offs
            )

        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self.unembed(params, x[:, -1])
        cache["seq_len"] = jnp.full((B,), S_tot, jnp.int32)
        return logits, cache

    # ------------------------------------------------------- chunked prefill

    def supports_chunked_prefill(self) -> bool:
        """Chunked prefill (and therefore prefix-cache KV reuse) currently
        targets the homogeneous global-attention stack — the only pattern
        the AB-Sparse decode path admits anyway."""
        return self.plan.pattern == ("attn",) and self.plan.n_rest == 0

    def prefill_chunk(
        self,
        params,
        cache: Cache,
        slot,                          # scalar int32: batch slot to fill
        tokens: jax.Array,             # [C] int32, first n_valid are real
        offset,                        # scalar int32: position of tokens[0]
        n_valid,                       # scalar int32: real tokens in buffer
    ) -> Tuple[jax.Array, Cache]:
        """Process one prompt chunk of a single batch slot in place.

        Writes the chunk's KV into rows ``[offset, offset + n_valid)`` of
        the slot's cache and attends each chunk query to the already-written
        prefix plus the causal part of the chunk — so a prompt can be
        prefilled across many engine ticks, interleaved with decode steps
        for the rest of the batch.  Padding rows (``>= n_valid``) produce
        out-of-bounds scatter indices and are dropped; chunk buffers keep a
        single compiled shape.  Centroid-store rows are NOT maintained here:
        call :meth:`refresh_slot_store` once after the final chunk.

        When ``SparseConfig.sparse_prefill`` is on, the chunk instead runs
        the query-block sparse prefill path: the slot's RUNNING scoring
        segment (``pcodes``/``pscale``/``pzero``) is refreshed with the
        blocks this chunk completes, then each query block attends its
        forced + top-scored KV blocks.  ``offset`` must then be a multiple
        of ``SparseConfig.prefill_block_q`` (the serving scheduler aligns
        chunk boundaries automatically), which makes the chunked run
        token-identical to single-shot sparse prefill.

        -> ``(logits [vocab] at the last valid position, cache)``.
        Chunk boundaries don't change per-position numerics: dense chunks
        reduce over the full cache row axis, and sparse chunks score only
        blocks fully behind the query block's local window (always complete
        by the time they are scored) — so a prefix installed from the cache
        + suffix chunks reproduces a monolithic run bit-for-bit (the
        prefix-sharing acceptance property).
        """
        assert self.supports_chunked_prefill()
        cfg = self.cfg
        C = tokens.shape[0]
        x = params["embed"][tokens][None]                 # [1, C, d]
        rel = jnp.arange(C)
        positions = (offset + rel)[None]                  # [1, C]
        valid = rel < n_valid
        paged = cache["pos0"]["k"].ndim == 6              # sparse-active cache
        ps = cfg.sparse.page_size
        if paged:
            S_max = cache["pos0"]["k"].shape[3] * ps
        else:
            S_max = cache["pos0"]["k"].shape[3]
        # invalid rows scatter out of bounds -> dropped (JAX semantics).
        write_pos = jnp.where(valid, offset + rel, S_max)
        stk = cache.get("_layouts")
        all_offs = cache.get("_offsets")
        use_sp = cfg.sparse.sparse_prefill and "pcodes" in cache["pos0"]
        # prefill sparsity counter (repro.obs): the engine plants
        # "_prefill_blocks" [n_layers] uint32 and every sparse chunk adds the
        # (query block, key block) pairs its kernel attended, per layer — a
        # running total, so the host reads it whenever it next syncs.
        count_blocks = use_sp and "_prefill_blocks" in cache
        if use_sp:
            sp_ppb_max = cfg.sparse.max_block_size // cfg.sparse.page_size
            bmax = cfg.sparse.max_block_size
            sp_window = min(-(-(C + 2 * bmax) // bmax) * bmax, S_max)
            sp_bits = store_bits(cfg.sparse.quant)
            sp_sym = store_symmetric(cfg.sparse.quant)

        def run_layer(p, x, entry, lay, offs):
            h = layers.rms_norm(p["norm1"], x, cfg.norm_eps)
            q, k, v = layers.qkv_project(p["attn"], h, cfg, positions)
            new_entry = dict(entry)
            # mixed scalar/array advanced indices around the head slice put
            # the broadcast (chunk) axis first: updates are [C, n_kv, hd].
            if paged:
                k_cache = entry["k"].at[
                    slot, :, write_pos // ps, write_pos % ps
                ].set(k[0].astype(entry["k"].dtype))
                v_cache = entry["v"].at[
                    slot, :, write_pos // ps, write_pos % ps
                ].set(v[0].astype(entry["v"].dtype))
            else:
                k_cache = entry["k"].at[slot, :, write_pos].set(
                    k[0].astype(entry["k"].dtype)
                )
                v_cache = entry["v"].at[slot, :, write_pos].set(
                    v[0].astype(entry["v"].dtype)
                )
            new_entry["k"] = k_cache
            new_entry["v"] = v_cache
            if use_sp:
                # sparse chunk: refresh the slot's running scoring segment
                # with the blocks this chunk completes, then query-block
                # sparse attention over the slot's paged KV.
                kslot = k_cache[slot][None]               # [1, n_kv, nP, ps, hd]
                vslot = v_cache[slot][None]
                sstore = CentroidStore(
                    entry["pcodes"][slot][None],
                    entry["pscale"][slot][None],
                    entry["pzero"][slot][None],
                    sp_bits, sp_sym,
                )
                sstore = self.backend.refresh_score_rows(
                    sstore, kslot, lay, offs,
                    offset, offset + n_valid, cfg.sparse, sp_window,
                )
                new_entry["pcodes"] = entry["pcodes"].at[slot].set(
                    sstore.codes[0]
                )
                new_entry["pscale"] = entry["pscale"].at[slot].set(
                    sstore.scale[0]
                )
                new_entry["pzero"] = entry["pzero"].at[slot].set(
                    sstore.zero[0]
                )
                attn_o, n_att = self.backend.prefill_attention(
                    jnp.moveaxis(q, 1, 2), kslot, vslot, sstore,
                    lay, cfg.sparse,
                    n_valid=offset + n_valid, chunk_offset=offset,
                    max_pages_per_block=sp_ppb_max,
                )
                if count_blocks:
                    new_entry["_blocksq"] = jnp.sum(n_att).astype(jnp.uint32)
                h = layers.out_project(
                    p["attn"], jnp.moveaxis(attn_o, 1, 2), cfg
                )
            else:
                # masked dense attention over the slot's rows: prefix +
                # causal chunk.  Rows beyond offset+i are masked, so stale
                # garbage past the live span never contributes.
                kf = k_cache[slot].reshape(
                    cfg.n_kv_heads, S_max, -1
                ).astype(jnp.float32)                     # [n_kv, S, hd]
                vf = v_cache[slot].reshape(
                    cfg.n_kv_heads, S_max, -1
                ).astype(jnp.float32)
                g = cfg.n_heads // cfg.n_kv_heads
                hd = cfg.resolved_head_dim
                qf = jnp.moveaxis(q, 1, 2)[0].reshape(
                    cfg.n_kv_heads, g, C, hd
                ).astype(jnp.float32)
                logits = jnp.einsum("hgcd,hsd->hgcs", qf, kf) / jnp.sqrt(
                    jnp.float32(hd)
                )
                mask = jnp.arange(S_max)[None, :] <= (offset + rel)[:, None]
                logits = jnp.where(mask[None, None], logits, -1e30)
                probs = jax.nn.softmax(logits, axis=-1)
                attn = jnp.einsum("hgcs,hsd->hgcd", probs, vf)
                attn = attn.reshape(cfg.n_heads, C, hd).astype(x.dtype)
                h = layers.out_project(
                    p["attn"], jnp.moveaxis(attn, 0, 1)[None], cfg
                )
            x = x + h
            h = layers.rms_norm(p["norm2"], x, cfg.norm_eps)
            if cfg.moe is not None:
                h, _ = moe_mod.moe_ffn(p["ffn"], h, cfg)
            else:
                h = layers.mlp(p["ffn"], h, cfg.activation)
            return x + h, new_entry

        def cycle_fn(x, xs):
            cyc_params, cyc_cache, cyc_idx = xs
            lay = stk.layer(cyc_idx) if (use_sp and stk is not None) else None
            offs = all_offs[cyc_idx] if (use_sp and all_offs is not None) else None
            x, new_entry = run_layer(
                cyc_params["pos0"], x, cyc_cache["pos0"], lay, offs
            )
            return x, {"pos0": new_entry}

        cache = dict(cache)
        if self.plan.n_cycles > 0:
            x, new_cyc = jax.lax.scan(
                cycle_fn,
                x,
                (
                    params["cycles"],
                    {"pos0": cache["pos0"]},
                    jnp.arange(self.plan.n_cycles),
                ),
            )
            entry = new_cyc["pos0"]
            if count_blocks:                              # [n_cycles] uint32
                cache["_prefill_blocks"] = (
                    cache["_prefill_blocks"] + entry.pop("_blocksq")
                )
            cache["pos0"] = entry
        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        h_last = jnp.take(x[0], n_valid - 1, axis=0)      # last valid row
        logits = self.unembed(params, h_last)
        return logits, cache

    def refresh_slot_store(self, cache: Cache, slot) -> Cache:
        """Rebuild one slot's centroid-store rows from its K cache.

        Chunked prefill writes K incrementally without maintaining the
        store; this derives codes/scale/zero for the whole slot in one pass
        once the prompt is complete (same ``prefill_store`` builder as
        monolithic prefill, so the bytes are identical)."""
        stk = cache.get("_layouts")
        if stk is None:
            return cache
        cfg = self.cfg
        offs_all = cache["_offsets"]
        entry = cache["pos0"]
        k_slot = entry["k"][:, slot]                      # [nc, n_kv, nP, ps, hd]

        def one(carry, xs):
            k_cyc, idx = xs
            store = self.backend.prefill_store(
                k_cyc[None], stk.layer(idx), offs_all[idx],
                cfg.sparse, quant=cfg.sparse.quant,
            )
            return carry, (store.codes[0], store.scale[0], store.zero[0])

        _, (codes, scale, zero) = jax.lax.scan(
            one, None, (k_slot, jnp.arange(self.plan.n_cycles))
        )
        entry = dict(entry)
        entry["codes"] = entry["codes"].at[:, slot].set(codes)
        entry["scale"] = entry["scale"].at[:, slot].set(scale)
        entry["zero"] = entry["zero"].at[:, slot].set(zero)
        cache = dict(cache)
        cache["pos0"] = entry
        return cache

    def refresh_slot_score_rows(self, cache: Cache, slot) -> Cache:
        """Rebuild one slot's PREFILL scoring segment from its K cache.

        Used after a prefix-cache install: the installed span's KV entered
        the cache without running ``prefill_chunk``, so its score rows must
        be derived here before later chunks can score those blocks.  Rows of
        blocks beyond the installed span are recomputed from zero keys and
        overwritten when their blocks complete — they are never scored
        before that."""
        stk = cache.get("_layouts")
        entry = cache["pos0"]
        if stk is None or "pcodes" not in entry:
            return cache
        cfg = self.cfg
        offs_all = cache["_offsets"]
        k_slot = entry["k"][:, slot]                      # [nc, n_kv, nP, ps, hd]

        def one(carry, xs):
            k_cyc, idx = xs
            st = self.backend.prefill_score_rows(
                k_cyc[None], stk.layer(idx), offs_all[idx], cfg.sparse,
            )
            return carry, (st.codes[0], st.scale[0], st.zero[0])

        _, (codes, scale, zero) = jax.lax.scan(
            one, None, (k_slot, jnp.arange(self.plan.n_cycles))
        )
        entry = dict(entry)
        entry["pcodes"] = entry["pcodes"].at[:, slot].set(codes)
        entry["pscale"] = entry["pscale"].at[:, slot].set(scale)
        entry["pzero"] = entry["pzero"].at[:, slot].set(zero)
        cache = dict(cache)
        cache["pos0"] = entry
        return cache

    def _rglru_final_state(self, p, h_in):
        """Final (h, conv-tail) after a full-sequence pass (for decode)."""
        gate = jax.nn.gelu(layers.dense(p["in_gelu"], h_in), approximate=True)
        u = layers.dense(p["in_rec"], h_in)
        uc = rglru._conv_full(p, u)
        r = jax.nn.sigmoid(layers.dense(p["w_a"], uc).astype(jnp.float32))
        i = jax.nn.sigmoid(layers.dense(p["w_x"], uc).astype(jnp.float32))
        a = rglru._decay(p, r)
        b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-9)) * (i * uc.astype(jnp.float32))

        def combine(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, b1 * a2 + b2

        _, hs = jax.lax.associative_scan(combine, (a, b), axis=1)
        conv_tail = u[:, -(rglru.CONV_K - 1):, :]
        return hs[:, -1], conv_tail

    def _rwkv_final_state(self, p, h_in):
        B, T, d = h_in.shape
        H = d // self.cfg.rwkv_head_dim
        N = self.cfg.rwkv_head_dim
        S0 = jnp.zeros((B, H, N, N), jnp.float32)

        def body(carry, xt):
            S, xp = carry
            S_new, _ = rwkv6._step(p, self.cfg, S, xt, xp)
            return (S_new, xt), None

        (S, xprev), _ = jax.lax.scan(
            body, (S0, jnp.zeros((B, d), h_in.dtype)), jnp.moveaxis(h_in, 1, 0)
        )
        return S, xprev

    # ------------------------------------------------------------ decode step

    def decode_step(
        self,
        params,
        cache: Cache,
        tokens: jax.Array,            # [B] next input token ids
    ) -> Tuple[jax.Array, Cache]:
        """One decode step for all sequences. -> (logits [B, vocab], cache)."""
        cfg = self.cfg
        B = tokens.shape[0]
        x = params["embed"][tokens][:, None, :]             # [B, 1, d]
        if cfg.name.startswith("musicgen"):
            pos0 = cache["seq_len"][:, None]
            x = x + jax.vmap(
                lambda p: layers.sinusoidal_embedding(p, cfg.d_model)
            )(pos0).astype(x.dtype)
        positions = cache["seq_len"][:, None]               # [B, 1]
        pat = self.plan.pattern
        stk = cache.get("_layouts")
        offsets = cache.get("_offsets")
        # opt-in selection emission for the tiered KV memory subsystem: the
        # engine plants "_sel_pages"/"_pre_pages" in the cache, and every
        # sparse attention layer reports its selected / margin-predicted
        # page masks (OR-reduced over layers below).
        collect = stk is not None and "_sel_pages" in cache
        # opt-in sparsity telemetry (repro.obs): the engine plants
        # "_telemetry" [n_layers, B, 4] and every sparse attention layer
        # reports [blocks, pages, forced, budget] per slot.
        collect_tel = stk is not None and "_telemetry" in cache
        # decode page counter (repro.obs): the engine plants "_decode_pages"
        # [n_layers, B] int32 and every sparse attention layer reports the
        # KV pages its attention read per slot (the backend's own count).
        count_pages = stk is not None and "_decode_pages" in cache

        def run_layer(p, kind, x, entry, lay, offs):
            h = layers.rms_norm(p["norm1"], x, cfg.norm_eps)
            new_entry = dict(entry)
            if kind == "attn":
                h, new_entry = self._attn_decode(
                    p["attn"], h, entry, lay, offs, positions,
                    collect=collect, collect_tel=collect_tel,
                    count_pages=count_pages,
                )
            elif kind == "local_attn":
                h, new_entry = self._local_attn_decode(
                    p["attn"], h, entry, positions
                )
            elif kind == "rglru":
                h, (new_entry["h"], new_entry["conv"]) = rglru.rglru_decode(
                    p["rec"], h, (entry["h"], entry["conv"]), cfg
                )
            elif kind == "rwkv":
                h, (new_entry["S"], new_entry["xprev"]) = rwkv6.rwkv_decode(
                    p["tmix"], h, (entry["S"], entry["xprev"]), cfg
                )
            x = x + h
            h = layers.rms_norm(p["norm2"], x, cfg.norm_eps)
            if cfg.moe is not None:
                h, _ = moe_mod.moe_ffn(p["ffn"], h, cfg, group_size=B)
            else:
                h = layers.mlp(p["ffn"], h, cfg.activation)
            return x + h, new_entry

        def cycle_fn(x, xs):
            cyc_params, cyc_cache, cyc_idx = xs
            new_cache = {}
            for i, kind in enumerate(pat):
                lay = stk.layer(cyc_idx) if (stk is not None and kind == "attn") else None
                offs = offsets[cyc_idx] if (offsets is not None and kind == "attn") else None
                x, new_cache[f"pos{i}"] = run_layer(
                    cyc_params[f"pos{i}"], kind, x, cyc_cache[f"pos{i}"], lay, offs
                )
            return x, new_cache

        if collect:
            sel_acc = jnp.zeros_like(cache["_sel_pages"])
            pre_acc = jnp.zeros_like(cache["_pre_pages"])
        if collect_tel:
            tel_acc = jnp.zeros_like(cache["_telemetry"])   # [L, B, 4]
        if count_pages:
            pages_acc = jnp.zeros_like(cache["_decode_pages"])   # [L, B]
        if self.plan.n_cycles > 0:
            cyc_cache_in = {f"pos{i}": cache[f"pos{i}"] for i in range(len(pat))}
            x, new_cyc = jax.lax.scan(
                cycle_fn,
                x,
                (params["cycles"], cyc_cache_in, jnp.arange(self.plan.n_cycles)),
            )
            for i, kind in enumerate(pat):
                entry = new_cyc[f"pos{i}"]
                if collect and kind == "attn":
                    sel_acc |= jnp.any(entry.pop("_selq"), axis=0)
                    pre_acc |= jnp.any(entry.pop("_preq"), axis=0)
                # layer index of cycle c, position i is c*len(pat)+i
                rows = jnp.arange(self.plan.n_cycles) * len(pat) + i
                if collect_tel and kind == "attn":
                    tel_acc = tel_acc.at[rows].set(entry.pop("_telq"))
                if count_pages and kind == "attn":
                    got = entry.pop("_pagesq")             # [n_cycles, B]
                    if len(pat) == 1 and not self.plan.rest_kinds:
                        pages_acc = got      # one layer a cycle: rows in order
                    else:
                        pages_acc = pages_acc.at[rows].set(got)
                cache[f"pos{i}"] = entry
        for i, kind in enumerate(self.plan.rest_kinds):
            lay_idx = self.plan.n_cycles * len(pat) + i
            lay = stk.layer(lay_idx) if (stk is not None and kind == "attn") else None
            offs = offsets[lay_idx] if (offsets is not None and kind == "attn") else None
            x, new_entry = run_layer(
                params["rest"][i], kind, x, cache["rest"][i], lay, offs
            )
            if collect and kind == "attn":
                sel_acc |= new_entry.pop("_selq")
                pre_acc |= new_entry.pop("_preq")
            if collect_tel and kind == "attn":
                tel_acc = tel_acc.at[lay_idx].set(new_entry.pop("_telq"))
            if count_pages and kind == "attn":
                pages_acc = pages_acc.at[lay_idx].set(new_entry.pop("_pagesq"))
            cache["rest"][i] = new_entry
        if collect:
            cache["_sel_pages"] = sel_acc
            cache["_pre_pages"] = pre_acc
        if collect_tel:
            cache["_telemetry"] = tel_acc
        if count_pages:
            cache["_decode_pages"] = pages_acc

        x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
        logits = self.unembed(params, x[:, 0])
        cache = dict(cache)
        cache["seq_len"] = cache["seq_len"] + 1
        return logits, cache

    # -- decode helpers ---------------------------------------------------

    def _attn_decode(self, p, h, entry, lay, offs, positions, collect=False,
                     collect_tel=False, count_pages=False):
        cfg = self.cfg
        B = h.shape[0]
        hd = cfg.resolved_head_dim
        q, k_new, v_new = layers.qkv_project(p, h, cfg, positions)
        q = q[:, 0]                                       # [B, Hq, hd]
        k_new = k_new[:, 0]                               # [B, n_kv, hd]
        v_new = v_new[:, 0]
        seq_len = positions[:, 0]                         # [B]

        # append KV at position seq_len (per sequence).  Keep every decode
        # tensor on the SAME sharding as the cache (batch x head_dim): the
        # baseline's unannotated fresh k/v made GSPMD bounce between
        # hd-sharded and kv-sharded layouts with full replication copies
        # per layer (the "involuntary full rematerialization" storm, §Perf).
        q = constrain(q, "batch", None, "head_dim")
        k_new = constrain(k_new, "batch", "kv_heads", "head_dim")
        v_new = constrain(v_new, "batch", "kv_heads", "head_dim")
        k_cache = entry["k"]     # dense [B, n_kv, S, hd] or paged [.., nP, ps, hd]
        v_cache = entry["v"]
        bidx = jnp.arange(B)
        if k_cache.ndim == 5:    # paged (sparse-active) cache
            ps = k_cache.shape[3]
            k_cache = k_cache.at[bidx, :, seq_len // ps, seq_len % ps].set(
                k_new.astype(k_cache.dtype)
            )
            v_cache = v_cache.at[bidx, :, seq_len // ps, seq_len % ps].set(
                v_new.astype(v_cache.dtype)
            )
            k_cache = constrain(
                k_cache, "batch", "kv_heads", "kv_pages", None, "head_dim"
            )
            v_cache = constrain(
                v_cache, "batch", "kv_heads", "kv_pages", None, "head_dim"
            )
        else:
            k_cache = k_cache.at[bidx, :, seq_len].set(k_new.astype(k_cache.dtype))
            v_cache = v_cache.at[bidx, :, seq_len].set(v_new.astype(v_cache.dtype))
            k_cache = constrain(k_cache, "batch", "kv_heads", "kv_pages", "head_dim")
            v_cache = constrain(v_cache, "batch", "kv_heads", "kv_pages", "head_dim")
        new_entry = dict(entry)
        new_entry["k"] = k_cache
        new_entry["v"] = v_cache
        live = seq_len + 1

        if lay is None:
            out = dense_decode_attention(q, k_cache, v_cache, seq_len=live)
            out = constrain(out, "batch", None, "head_dim")
            return layers.out_project(p, out[:, None], cfg), new_entry

        # --- AB-Sparse path: plan/execute through the attention backend ---
        quant = cfg.sparse.quant
        store = CentroidStore(
            entry["codes"], entry["scale"], entry["zero"],
            store_bits(quant), store_symmetric(quant),
        )
        # refresh the centroid row of the block containing the new token,
        # then estimation -> adaptive top-k -> paged attention.
        store = self.backend.append(
            store, k_cache, lay, offs, seq_len, cfg.sparse
        )
        new_entry["codes"] = store.codes
        # head-gather before the out projection: under a serving mesh the
        # kernel output arrives kv-head-sharded, and out_project must reduce
        # over the FULL head axis in single-device order for the sharded
        # path to stay token-identical (identity outside a context).
        if collect_tel:
            # sparsity counters piggyback on the estimation scores the
            # decode itself ranks (staged: same tensor; fused: an identical
            # recompute inside the backend) — no second pass over the store.
            out, _, pages, new_entry["_telq"] = self.backend.decode(
                q, k_cache, v_cache, store, lay, cfg.sparse, seq_len=live,
                collect_tel=True,
            )
        else:
            out, _, pages = self.backend.decode(
                q, k_cache, v_cache, store, lay, cfg.sparse, seq_len=live
            )
        if count_pages:
            new_entry["_pagesq"] = pages
        if collect:
            # re-run the (cheap) estimation stage against the post-append
            # store — identical scores to the ones backend.decode just
            # selected from, so the emitted mask is exactly the page set
            # the attention stage gathered, plus the margin prediction.
            sp = cfg.sparse
            rq = rank_query(q, sp.centroid_method, q.shape[-1])
            est = self.backend.scores(rq, store, lay, k_cache.shape[1])
            sel_mask, pre_mask = selected_page_masks(
                est, lay, seq_len=live,
                sink_pages=sp.sink_pages, local_pages=sp.local_pages,
                margin_blocks=sp.prefetch_margin_blocks,
                max_pages_per_block=sp.max_block_size // sp.page_size,
            )
            new_entry["_selq"] = sel_mask
            new_entry["_preq"] = pre_mask
        out = constrain(out, "batch", None, "head_dim")
        return layers.out_project(p, out[:, None], cfg), new_entry

    def _local_attn_decode(self, p, h, entry, positions):
        """Sliding-window decode with a ring-buffer KV cache."""
        cfg = self.cfg
        B = h.shape[0]
        q, k_new, v_new = layers.qkv_project(p, h, cfg, positions)
        q = q[:, 0]
        seq_len = positions[:, 0]
        k_cache, v_cache = entry["k"], entry["v"]           # [B, n_kv, W, hd]
        W = k_cache.shape[2]
        slot = seq_len % W
        bidx = jnp.arange(B)
        k_cache = k_cache.at[bidx, :, slot].set(
            k_new[:, 0].astype(k_cache.dtype)
        )
        v_cache = v_cache.at[bidx, :, slot].set(
            v_new[:, 0].astype(v_cache.dtype)
        )
        # a slot s holds position p = largest p <= seq_len with p % W == s;
        # valid iff that position is within the live window (seq_len-W, seq_len]
        pos_in_slot = seq_len[:, None] - (
            (seq_len[:, None] - jnp.arange(W)[None, :]) % W
        )
        valid = (pos_in_slot >= 0) & (pos_in_slot > seq_len[:, None] - W)
        out = self._masked_dense_decode(q, k_cache, v_cache, valid)
        new_entry = dict(entry)
        new_entry["k"] = k_cache
        new_entry["v"] = v_cache
        return layers.out_project(p, out[:, None], cfg), new_entry

    @staticmethod
    def _masked_dense_decode(q, k, v, valid):
        B, n_kv, W, D = k.shape
        g = q.shape[1] // n_kv
        qf = q.reshape(B, n_kv, g, D).astype(jnp.float32)
        logits = jnp.einsum("bhgd,bhsd->bhgs", qf, k.astype(jnp.float32))
        logits = logits / jnp.sqrt(jnp.float32(D))
        logits = jnp.where(valid[:, None, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgs,bhsd->bhgd", probs, v.astype(jnp.float32))
        return out.reshape(B, q.shape[1], D).astype(q.dtype)
