"""Fused-vs-staged decode parity: the single-launch fused kernel must
reproduce the staged three-kernel pipeline — same selected page SETS per
(sequence, kv head) and attention outputs within flash-accumulation
tolerance — across quant schemes, non-uniform block-size layouts, ragged
sequence lengths, and sink/local page forcing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import PallasBackend
from repro.config import SparseConfig
from repro.core.centroids import rank_query
from repro.core.ragged import layout_for, uniform_layout
from repro.core.selection import select_page_table
from repro.kernels import ops

pytestmark = pytest.mark.kernel

PALLAS = PallasBackend(interpret=True)
KEY = jax.random.PRNGKey(0)

B, N_KV, G, S, D = 2, 4, 2, 2048, 64
NONUNIFORM = (16, 32, 64, 32)


def _qkv(seed=0, dtype=jnp.float32):
    key = jax.random.fold_in(KEY, seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, N_KV * G, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, N_KV, S, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, N_KV, S, D), dtype)
    return q, k, v


def _page_sets(table, valid):
    """-> {(b, h): frozenset(valid physical pages)}."""
    t, m = np.asarray(table), np.asarray(valid)
    return {
        (b, h): frozenset(t[b, h][m[b, h]].tolist())
        for b in range(t.shape[0])
        for h in range(t.shape[1])
    }


def _staged_and_fused(lay, cfg, quant, seq_len, seed=0):
    q, k, v = _qkv(seed)
    store = PALLAS.build_store(k, lay, cfg.centroid_method, quant=quant)
    out_s, _, _ = PALLAS.decode(q, k, v, store, lay, cfg, seq_len=seq_len)
    rq = rank_query(q, cfg.centroid_method, D)
    out_f, tbl_f, vld_f = ops.fused_decode(
        q, rq, k, v, store, lay,
        sink_pages=cfg.sink_pages, local_pages=cfg.local_pages,
        seq_len=seq_len, interpret=True,
    )
    scores = PALLAS.scores(rq, store, lay, N_KV)
    tbl_s, vld_s = select_page_table(
        scores, lay, seq_len=seq_len,
        sink_pages=cfg.sink_pages, local_pages=cfg.local_pages,
    )
    return out_s, (tbl_s, vld_s), out_f, (tbl_f, vld_f)


@pytest.mark.parametrize("quant", ["none", "int4_asym", "int8_asym"])
@pytest.mark.parametrize(
    "blocks", [NONUNIFORM, (32,) * N_KV], ids=["nonuniform", "uniform"]
)
def test_fused_parity_quant_and_layout_sweep(quant, blocks):
    lay = layout_for(blocks, S, 16, 512)
    cfg = SparseConfig(token_budget=512, quant=quant)
    seq_len = jnp.array([S, S // 2], jnp.int32)
    out_s, (t_s, v_s), out_f, (t_f, v_f) = _staged_and_fused(
        lay, cfg, quant, seq_len
    )
    np.testing.assert_allclose(
        np.asarray(out_s), np.asarray(out_f), atol=1e-5
    )
    assert _page_sets(t_s, v_s) == _page_sets(t_f, v_f)


@pytest.mark.parametrize(
    "seq", [(31, 100), (1, 2047), (512, 2048)], ids=["tiny", "edge", "half"]
)
def test_fused_parity_ragged_seq_len(seq):
    """Ragged live lengths: partially-live pages, heads whose live block
    count drops below K_h, and the 1-token edge case."""
    lay = layout_for(NONUNIFORM, S, 16, 512)
    cfg = SparseConfig(token_budget=512)
    seq_len = jnp.array(seq, jnp.int32)
    out_s, (t_s, v_s), out_f, (t_f, v_f) = _staged_and_fused(
        lay, cfg, "int4_asym", seq_len, seed=3
    )
    np.testing.assert_allclose(
        np.asarray(out_s), np.asarray(out_f), atol=1e-5
    )
    assert _page_sets(t_s, v_s) == _page_sets(t_f, v_f)


@pytest.mark.parametrize("sink,local", [(0, 0), (2, 8), (1, 4)])
def test_fused_sink_local_forcing(sink, local):
    """Pinned sink/local pages always survive fused selection, exactly as
    the staged mask_and_pin path keeps them."""
    lay = layout_for(NONUNIFORM, S, 16, 512)
    cfg = SparseConfig(token_budget=512, sink_pages=sink, local_pages=local)
    seq_len = jnp.array([S, 777], jnp.int32)
    out_s, (t_s, v_s), out_f, (t_f, v_f) = _staged_and_fused(
        lay, cfg, "int4_asym", seq_len, seed=7
    )
    np.testing.assert_allclose(
        np.asarray(out_s), np.asarray(out_f), atol=1e-5
    )
    sets_f = _page_sets(t_f, v_f)
    assert sets_f == _page_sets(t_s, v_s)
    sl = np.asarray(seq_len)
    for (b, h), pages in sets_f.items():
        for p in range(sink):                   # forced sink pages
            if p * lay.page_size < sl[b]:
                assert p in pages, (b, h, p, sorted(pages))
        last_live = (int(sl[b]) - 1) // lay.page_size
        if local > 0:
            assert last_live in pages, (b, h, last_live)


def test_fused_backend_knob_is_config_only():
    """``SparseConfig.fused_decode`` swaps the execution path through the
    SAME backend ``decode`` entry point."""
    lay = uniform_layout(N_KV, 32, S, 16, 512)
    q, k, v = _qkv(seed=5)
    store = PALLAS.build_store(k, lay, "quest", quant="int4_asym")
    staged_cfg = SparseConfig(token_budget=512)
    fused_cfg = dataclasses.replace(staged_cfg, fused_decode=True)
    out_s, _, pages_s = PALLAS.decode(q, k, v, store, lay, staged_cfg)
    out_f, tbl, pages_f = PALLAS.decode(q, k, v, store, lay, fused_cfg)
    assert tbl.shape == (B, N_KV, lay.selected_pages)
    # the fused kernel's valid mask counts the pages the staged table reads
    np.testing.assert_array_equal(np.asarray(pages_s), np.asarray(pages_f))
    np.testing.assert_allclose(
        np.asarray(out_s), np.asarray(out_f), atol=1e-5
    )


def test_fused_dma_window_covers_oversized_blocks():
    """Blocks LARGER than the config's candidate sizes must not be
    truncated by the fused kernel's DMA window: the ops layer reconciles
    the static window with the layout's own maximum (regression test for
    the config-derived window silently halving 128-token blocks)."""
    lay = layout_for((128, 128, 64, 64), S, 16, 512)
    cfg = SparseConfig(token_budget=512)        # candidates max out at 64
    fused_cfg = dataclasses.replace(cfg, fused_decode=True)
    q, k, v = _qkv(seed=11)
    store = PALLAS.build_store(k, lay, "quest", quant="int4_asym")
    seq_len = jnp.array([S, S // 2], jnp.int32)
    out_s, _, _ = PALLAS.decode(q, k, v, store, lay, cfg, seq_len=seq_len)
    out_f, _, _ = PALLAS.decode(q, k, v, store, lay, fused_cfg, seq_len=seq_len)
    np.testing.assert_allclose(
        np.asarray(out_s), np.asarray(out_f), atol=1e-5
    )


def test_fused_accepts_prepaged_cache_view():
    """The fused kernel consumes the decode cache's native paged KV layout
    without reshaping; dense input is just a convenience view."""
    lay = layout_for(NONUNIFORM, S, 16, 512)
    cfg = SparseConfig(token_budget=512)
    q, k, v = _qkv(seed=9)
    store = PALLAS.build_store(k, lay, "quest", quant="none")
    rq = rank_query(q, "quest", D)
    kp = k.reshape(B, N_KV, S // 16, 16, D)
    vp = v.reshape(B, N_KV, S // 16, 16, D)
    out_dense, t1, v1 = ops.fused_decode(
        q, rq, k, v, store, lay, seq_len=None, interpret=True
    )
    out_paged, t2, v2 = ops.fused_decode(
        q, rq, kp, vp, store, lay, seq_len=None, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_allclose(
        np.asarray(out_dense), np.asarray(out_paged), atol=1e-6
    )


@pytest.mark.parametrize("layer", ["first", "last"])
def test_fused_reads_stacked_pools_at_layer(layer):
    """The decode step hands the kernel every layer's pools and store codes
    stacked, with the layer to read: that call must equal the call on the
    layer's own slice, with layers holding different KV (a wrong layer
    offset reads another layer's pages and scores)."""
    L = 3
    lay = layout_for(NONUNIFORM, S, 16, 512)
    cfg = SparseConfig(token_budget=512)
    seq_len = jnp.array([S - 1, 777], jnp.int32)
    qkv = [_qkv(seed=20 + l) for l in range(L)]
    q = qkv[0][0]
    rq = rank_query(q, cfg.centroid_method, D)
    stores = [
        PALLAS.build_store(k, lay, cfg.centroid_method, quant="int4_asym")
        for _, k, _ in qkv
    ]
    paged = lambda a: a.reshape(B, N_KV, S // 16, 16, D)
    k_all = jnp.stack([paged(k) for _, k, _ in qkv])
    v_all = jnp.stack([paged(v) for _, _, v in qkv])
    l = 0 if layer == "first" else L - 1
    store_l = stores[l]
    stacked = dataclasses.replace(
        store_l, codes=jnp.stack([st.codes for st in stores])
    )
    kw = dict(sink_pages=cfg.sink_pages, local_pages=cfg.local_pages,
              seq_len=seq_len, interpret=True)
    got = ops.fused_decode(
        q, rq, k_all, v_all, stacked, lay, layer=jnp.int32(l), **kw
    )
    want = ops.fused_decode(
        q, rq, paged(qkv[l][1]), paged(qkv[l][2]), store_l, lay, **kw
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _stack_slots(caches):
    """Single-sequence caches (one max_context) -> one cache whose batch
    slots hold them, at their own lengths."""
    cache = dict(caches[0])
    for key in cache:
        if key.startswith("pos"):
            cache[key] = jax.tree.map(
                lambda *a: jnp.concatenate(a, axis=1), *[c[key] for c in caches]
            )
    cache["seq_len"] = jnp.concatenate([c["seq_len"] for c in caches])
    return cache


def test_fused_end_to_end_decode_step_matches_staged():
    """Model-level: a smoke Transformer with backend="pallas" produces the
    same decode logits and caches with the fused launch as with the staged
    pipeline and the reference backend (paged cache, layer scan, store
    append included) over several steps, with slots at different lengths:
    one starts at a page's last token (``seq_len % 16 == 15``) and the
    others cross a page boundary on the way."""
    from repro.configs import get_config, smoke_variant
    from repro.models import Transformer

    base = smoke_variant(get_config("llama3.2-3b"))
    lens, steps = (300, 271, 287), 6

    def run(backend, fused):
        cfg = dataclasses.replace(
            base,
            sparse=dataclasses.replace(
                base.sparse, token_budget=128, backend=backend,
                fused_decode=fused,
            ),
        )
        model = Transformer(cfg)
        params = model.init(KEY)
        caches = []
        for i, n in enumerate(lens):
            tokens = jax.random.randint(
                jax.random.fold_in(KEY, i), (1, n), 0, cfg.vocab_size
            )
            caches.append(model.prefill(params, tokens, max_context=320)[1])
        cache = _stack_slots(caches)
        cache["_decode_pages"] = jnp.zeros((cfg.n_layers, len(lens)), jnp.int32)
        step = jax.jit(model.decode_step)
        tok = jnp.array([1, 2, 3], jnp.int32)
        logits, pages = [], []
        for _ in range(steps):
            out, cache = step(params, cache, tok)
            logits.append(np.asarray(out))
            pages.append(np.asarray(cache["_decode_pages"]))
            tok = jnp.asarray([5, 7, 11], jnp.int32)
        return logits, pages, jax.tree.map(np.asarray, cache)

    fused = run("pallas", True)
    for other in (run("pallas", False), run("reference", False)):
        for a, b in zip(fused[0], other[0]):
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)
        for a, b in zip(fused[1], other[1]):
            np.testing.assert_array_equal(a, b)
        c_f, c_o = fused[2], other[2]
        np.testing.assert_array_equal(c_f["seq_len"], np.add(lens, steps))
        np.testing.assert_array_equal(c_f["seq_len"], c_o["seq_len"])
        for key in ("codes", "scale", "zero"):
            np.testing.assert_array_equal(c_f["pos0"][key], c_o["pos0"][key])
        for key in ("k", "v"):
            np.testing.assert_allclose(
                c_f["pos0"][key], c_o["pos0"][key], atol=2e-4, rtol=1e-4
            )
