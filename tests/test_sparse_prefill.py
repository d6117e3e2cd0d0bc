"""Sparse-prefill parity suite.

Three layers of guarantees:

1. KERNEL vs selection-exact jnp oracle — identical attended block sets and
   outputs within flash-accumulation tolerance, across quant schemes,
   non-uniform per-head block sizes and causal edge cases.
2. CHUNKED vs SINGLE-SHOT — token-identical (bitwise logits) under ragged
   (query-block-aligned) chunk schedules, including the running scoring
   segment carried across chunks.
3. SPARSE vs DENSE oracle — early query blocks (every causal block forced)
   are exact; at a budget covering all blocks the whole prefill is exact.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import CentroidStore, PallasBackend, get_backend
from repro.config import SparseConfig
from repro.core.centroids import rank_query
from repro.core.ragged import layout_for
from repro.core.stacked import as_arrays
from repro.backends.store import build_score_rows, refresh_score_rows
from repro.kernels import ops, ref

pytestmark = pytest.mark.kernel

PALLAS = PallasBackend(interpret=True)
KEY = jax.random.PRNGKey(0)

B, N_KV, G, S, D = 2, 4, 2, 1024, 64
BQ = 64
NONUNIFORM = (16, 32, 64, 32)


def _qkv(seed=0):
    key = jax.random.fold_in(KEY, seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, N_KV * G, S, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, N_KV, S, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, N_KV, S, D))
    return q, k, v


def _paged(x, ps=16):
    return x.reshape(B, N_KV, S // ps, ps, x.shape[-1])


def _score_store(kp, lay, cfg, quant):
    la = as_arrays(lay)
    offs = jnp.asarray(lay.offsets[:-1], jnp.int32)
    codes, scale, zero = build_score_rows(kp, la, offs, cfg, quant=quant)
    from repro.core.quantization import store_bits, store_symmetric

    return CentroidStore(
        codes, scale, zero, store_bits(quant), store_symmetric(quant)
    )


def _kernel_and_ref(lay, cfg, quant, n_valid, seed=0):
    q, k, v = _qkv(seed)
    kp, vp = _paged(k), _paged(v)
    ss = _score_store(kp, lay, cfg, quant)
    out, nsel = ops.sparse_prefill(
        q, rank_query(q, cfg.centroid_method, D), kp, vp, ss, lay,
        sink_pages=cfg.sink_pages, local_pages=cfg.local_pages,
        block_q=BQ, topk_scale=cfg.prefill_topk_scale,
        n_valid=n_valid, interpret=True,
    )
    la = as_arrays(lay)
    rk_rows = ref.dequant_score_rows(
        ss.codes, ss.scale, ss.zero, ss.bits, ss.symmetric
    )
    rq6 = jnp.moveaxis(
        rank_query(q, cfg.centroid_method, D).reshape(
            B, N_KV, G, S // BQ, BQ, -1
        ), 3, 2,
    )
    q6 = jnp.moveaxis(q.reshape(B, N_KV, G, S // BQ, BQ, D), 3, 2)
    k_sel = jnp.clip(
        jnp.ceil(
            la.top_k.astype(jnp.float32) * cfg.prefill_topk_scale
        ).astype(jnp.int32),
        1, la.n_blocks,
    )
    oref, nref = ref.sparse_prefill_ref(
        q6, rq6, kp, vp, rk_rows, la, k_sel, n_valid, 0, BQ,
        cfg.sink_pages, cfg.local_pages,
    )
    oref = jnp.moveaxis(oref, 2, 3).reshape(B, N_KV * G, S, D)
    return out, nsel, oref, nref


def _valid_mask(n_valid, shape):
    m = np.arange(S)[None, None, :, None] < np.asarray(n_valid)[:, None, None, None]
    return np.broadcast_to(m, shape)


@pytest.mark.parametrize("quant", ["none", "int4_asym", "int8_asym"])
@pytest.mark.parametrize(
    "blocks", [NONUNIFORM, (32,) * N_KV], ids=["nonuniform", "uniform"]
)
def test_kernel_vs_oracle_quant_and_layout_sweep(quant, blocks):
    lay = layout_for(blocks, S, 16, 256)
    cfg = SparseConfig(token_budget=256, quant=quant, sparse_prefill=True)
    n_valid = jnp.array([S, 700], jnp.int32)
    out, nsel, oref, nref = _kernel_and_ref(lay, cfg, quant, n_valid)
    np.testing.assert_array_equal(np.asarray(nsel), np.asarray(nref))
    m = _valid_mask(n_valid, out.shape)
    np.testing.assert_allclose(
        np.asarray(out)[m], np.asarray(oref)[m], atol=2e-5
    )


@pytest.mark.parametrize(
    "nv", [(31, 100), (1, 1023), (512, 1024)], ids=["tiny", "edge", "half"]
)
def test_kernel_vs_oracle_ragged_live_lengths(nv):
    """Causal-mask edge cases: first query block, partially-live final
    query block, 1-token prompts, dead trailing cells."""
    lay = layout_for(NONUNIFORM, S, 16, 256)
    cfg = SparseConfig(token_budget=256, sparse_prefill=True)
    n_valid = jnp.array(nv, jnp.int32)
    out, nsel, oref, nref = _kernel_and_ref(lay, cfg, "int4_asym", n_valid, 3)
    np.testing.assert_array_equal(np.asarray(nsel), np.asarray(nref))
    m = _valid_mask(n_valid, out.shape)
    np.testing.assert_allclose(
        np.asarray(out)[m], np.asarray(oref)[m], atol=2e-5
    )


@pytest.mark.parametrize("sink,local", [(0, 0), (2, 8), (1, 4)])
def test_forced_blocks_and_early_exactness(sink, local):
    """Sink/local forcing survives selection, and query blocks whose causal
    prefix fits the forced-union-top-K budget match DENSE attention
    exactly (early blocks stay exact)."""
    lay = layout_for(NONUNIFORM, S, 16, 256)
    cfg = SparseConfig(
        token_budget=256, sparse_prefill=True,
        sink_pages=sink, local_pages=local,
    )
    q, k, v = _qkv(7)
    kp, vp = _paged(k), _paged(v)
    ss = _score_store(kp, lay, cfg, "int4_asym")
    n_valid = jnp.full((B,), S, jnp.int32)
    out, nsel = ops.sparse_prefill(
        q, rank_query(q, "quest", D), kp, vp, ss, lay,
        sink_pages=sink, local_pages=local, block_q=BQ,
        n_valid=n_valid, interpret=True,
    )
    dense = get_backend("dense")
    out_d, _ = dense.prefill_attention(
        q, kp, vp, None, lay, cfg, n_valid=n_valid
    )
    # block 0 of every head is causally complete at the first query block
    # (and sink+local force the whole prefix early on): compare the first
    # query block exactly against dense.
    np.testing.assert_allclose(
        np.asarray(out[:, :, :BQ]), np.asarray(out_d[:, :, :BQ]),
        atol=2e-5,
    )
    # forced sink block must always be attended by every live cell
    if sink > 0:
        assert int(np.min(np.asarray(nsel))) >= 1


def test_kernel_vs_oracle_scaled_budget():
    """prefill_topk_scale > 1 pushes k_sel past the decode budget
    ``max_top_k``: the jnp oracle must keep selecting (regression for the
    oracle capping top-k at the decode budget) and match the kernel."""
    lay = layout_for(NONUNIFORM, S, 16, 256)
    cfg = SparseConfig(
        token_budget=256, sparse_prefill=True, prefill_topk_scale=2.0
    )
    n_valid = jnp.full((B,), S, jnp.int32)
    out, nsel, oref, nref = _kernel_and_ref(lay, cfg, "int4_asym", n_valid, 17)
    np.testing.assert_array_equal(np.asarray(nsel), np.asarray(nref))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(oref), atol=2e-5
    )
    # the scaled budget must actually select more than the unscaled one
    cfg1 = dataclasses.replace(cfg, prefill_topk_scale=1.0)
    _, nsel1, _, _ = _kernel_and_ref(lay, cfg1, "int4_asym", n_valid, 17)
    assert int(np.sum(np.asarray(nsel))) > int(np.sum(np.asarray(nsel1)))


def test_dead_query_blocks_attend_nothing():
    """With sink/local forcing off, query blocks past n_valid have zero
    candidates and n_live == 0 — the kernel must not read KV at all there
    (regression for the warm-up DMA firing on empty cells)."""
    lay = layout_for(NONUNIFORM, S, 16, 256)
    cfg = SparseConfig(token_budget=256, sparse_prefill=True)
    q, k, v = _qkv(19)
    kp, vp = _paged(k), _paged(v)
    ss = _score_store(kp, lay, cfg, "int4_asym")
    n_valid = jnp.array([100, 40], jnp.int32)
    out, nsel = ops.sparse_prefill(
        q, rank_query(q, "quest", D), kp, vp, ss, lay,
        sink_pages=0, local_pages=0, block_q=BQ,
        n_valid=n_valid, interpret=True,
    )
    ns = np.asarray(nsel)
    # dead cells (whole query block beyond n_valid) attended zero blocks
    assert (ns[0, :, 2:] == 0).all() and (ns[1, :, 1:] == 0).all()
    assert np.isfinite(np.asarray(out)).all()


def test_generous_budget_matches_dense_everywhere():
    """With K_h covering every causal block, sparse prefill == dense."""
    lay = layout_for(NONUNIFORM, S, 16, 256)
    cfg = SparseConfig(
        token_budget=256, sparse_prefill=True,
        prefill_topk_scale=float(S) / 256.0,   # K_h -> all blocks
    )
    q, k, v = _qkv(11)
    kp, vp = _paged(k), _paged(v)
    ss = _score_store(kp, lay, cfg, "int4_asym")
    out, _ = ops.sparse_prefill(
        q, rank_query(q, "quest", D), kp, vp, ss, lay,
        block_q=BQ, topk_scale=cfg.prefill_topk_scale, interpret=True,
    )
    out_d, _ = get_backend("dense").prefill_attention(
        q, kp, vp, None, lay, cfg
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_d), atol=2e-5)


@pytest.mark.parametrize("quant", ["none", "int4_asym", "int8_asym"])
def test_chunked_token_identical_to_single_shot(quant):
    """Ragged (block_q-aligned) chunk schedule reproduces the single-shot
    kernel bitwise, with the scoring segment carried incrementally."""
    lay = layout_for(NONUNIFORM, S, 16, 256)
    cfg = SparseConfig(token_budget=256, quant=quant, sparse_prefill=True)
    la = as_arrays(lay)
    offs = jnp.asarray(lay.offsets[:-1], jnp.int32)
    q, k, v = _qkv(13)
    kp, vp = _paged(k), _paged(v)
    rq = rank_query(q, "quest", D)
    n_valid = jnp.array([S, 900], jnp.int32)

    ss = _score_store(kp, lay, cfg, quant)
    single, _ = ops.sparse_prefill(
        q, rq, kp, vp, ss, lay, block_q=BQ, n_valid=n_valid, interpret=True
    )

    from repro.core.quantization import store_bits, store_symmetric

    bits = store_bits(quant)
    shp = (B, la.total_rows, 1)
    codes = jnp.zeros_like(ss.codes)
    scale = jnp.ones(shp, jnp.float32)
    zero = jnp.zeros(shp, jnp.float32)
    bmax = 64
    outs = []
    schedule = ((0, 256), (256, 64), (320, 192), (512, 256), (768, 256))
    for off, n in schedule:
        window = n + 2 * bmax
        window = -(-window // bmax) * bmax
        codes, scale, zero = refresh_score_rows(
            codes, scale, zero, kp, la, offs,
            jnp.int32(off), jnp.int32(off + n), cfg, window=min(window, S),
            bits=bits, symmetric=store_symmetric(quant),
        )
        st = CentroidStore(codes, scale, zero, bits, store_symmetric(quant))
        o, _ = ops.sparse_prefill(
            q[:, :, off:off + n], rq[:, :, off:off + n], kp, vp, st, lay,
            block_q=BQ, n_valid=jnp.minimum(n_valid, off + n),
            chunk_offset=off, interpret=True,
        )
        outs.append(o)
    chunked = jnp.concatenate(outs, axis=2)
    m = _valid_mask(n_valid, single.shape)
    assert np.array_equal(np.asarray(chunked)[m], np.asarray(single)[m])


@pytest.mark.parametrize("layer,slot", [(0, 1), (2, 0)])
def test_kernel_reads_stacked_pools_at_layer_and_slot(layer, slot):
    """The prefill chunk hands the kernel every layer's and every slot's
    pools with the layer and slot to read: that call must equal today's
    batch-1 call on the slot's own slice, with layers and slots holding
    different KV (a wrong offset reads another layer's or slot's pages)."""
    L, chunk, off = 3, 128, 256
    lay = layout_for(NONUNIFORM, S, 16, 256)
    cfg = SparseConfig(token_budget=256)
    qkv = [_qkv(seed=40 + l) for l in range(L)]
    k_all = jnp.stack([_paged(k) for _, k, _ in qkv])   # [L, B, n_kv, nP, ..]
    v_all = jnp.stack([_paged(v) for _, _, v in qkv])
    kp, vp = k_all[layer, slot][None], v_all[layer, slot][None]
    q = qkv[0][0][slot:slot + 1, :, off:off + chunk]     # one slot's chunk
    rq = rank_query(q, cfg.centroid_method, D)
    ss = _score_store(kp, lay, cfg, "int4_asym")
    kw = dict(
        sink_pages=cfg.sink_pages, local_pages=cfg.local_pages, block_q=BQ,
        n_valid=off + chunk, chunk_offset=off, interpret=True,
    )
    got = ops.sparse_prefill(
        q, rq, k_all, v_all, ss, lay,
        layer=jnp.int32(layer), slot=jnp.int32(slot), **kw
    )
    want = ops.sparse_prefill(q, rq, kp, vp, ss, lay, **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_model_prefill_backend_parity_and_chunk_identity():
    """Model-level: pallas == reference through a full Transformer, and
    prefill_chunk reproduces single-shot prefill bitwise (store included)."""
    from repro.configs import get_config, smoke_variant
    from repro.models import Transformer

    base = smoke_variant(get_config("llama3.2-3b"))

    def build(backend):
        cfg = dataclasses.replace(
            base,
            sparse=dataclasses.replace(
                base.sparse, token_budget=128, backend=backend,
                sparse_prefill=True, prefill_block_q=64,
            ),
        )
        model = Transformer(cfg)
        params = model.init(KEY)
        tokens = jax.random.randint(KEY, (1, 448), 0, cfg.vocab_size)
        return model, params, tokens

    model, params, tokens = build("pallas")
    lg, cache_s = model.prefill(params, tokens, max_context=512)

    model_r, params_r, _ = build("reference")
    lg_r, _ = model_r.prefill(params_r, tokens, max_context=512)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(lg_r), atol=2e-4, rtol=1e-4
    )

    cache = model.init_cache(1, 512)
    last = None
    for off, n in ((0, 128), (128, 64), (192, 128), (320, 128)):
        buf = np.zeros((128,), np.int32)
        buf[:n] = np.asarray(tokens[0, off:off + n])
        last, cache = model.prefill_chunk(
            params, cache, jnp.int32(0), jnp.asarray(buf),
            jnp.int32(off), jnp.int32(n),
        )
    assert np.array_equal(np.asarray(last), np.asarray(lg[0]))
    np.testing.assert_array_equal(
        np.asarray(cache["pos0"]["pcodes"]),
        np.asarray(cache_s["pos0"]["pcodes"]),
    )

    # decode parity after the chunked prefill (store rebuilt once)
    cache = model.refresh_slot_store(cache, jnp.int32(0))
    cache = dict(cache)
    cache["seq_len"] = jnp.full((1,), 448, jnp.int32)
    d1, _ = model.decode_step(params, cache, tokens[:, -1])
    d2, _ = model.decode_step(params, cache_s, tokens[:, -1])
    assert np.array_equal(np.asarray(d1), np.asarray(d2))


def test_engine_sparse_prefill_serves_and_aligns():
    """Serving path: the engine with sparse prefill on produces the same
    tokens as with it off at a budget covering the whole context, across
    chunked prefill + prefix-cache reuse + decode."""
    from repro.configs import get_config, smoke_variant
    from repro.serving import Engine, Request
    from repro.config import ServeConfig

    base = smoke_variant(get_config("llama3.2-3b"))
    serve = ServeConfig(
        max_batch=2, max_context=512, prefill_chunk=128,
        prefill_tokens_per_tick=192, temperature=1e-4,
    )
    prompts = [
        list(range(100, 100 + 300)),
        list(range(100, 100 + 300)),           # shared prefix
        list(range(7, 7 + 210)),
    ]

    def run(sp, scale=8.0):
        cfg = dataclasses.replace(
            base,
            sparse=dataclasses.replace(
                base.sparse, token_budget=128, backend="pallas",
                sparse_prefill=sp, prefill_block_q=64,
                prefill_topk_scale=scale,      # generous: selection exact
            ),
        )
        from repro.models import Transformer as T

        params = T(cfg).init(KEY)
        eng = Engine(cfg, params, serve, seed=0)
        if sp:
            assert eng.scheduler.chunk_align == 64
        for i, p in enumerate(prompts):
            eng.submit(Request(req_id=i, prompt=list(p), max_new_tokens=4))
        done = eng.run_until_done()
        return {r.req_id: list(r.output) for r in done}

    out_sparse = run(True)
    out_dense = run(False)
    assert out_sparse == out_dense
