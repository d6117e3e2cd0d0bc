"""Unified AttentionBackend API: registry resolution + backend parity.

Parity contract: for the same inputs, ``"reference"`` and ``"pallas"``
produce IDENTICAL page tables (the stores are byte-identical because both
quantize through core/quantization) and near-identical attention outputs;
both converge to the ``"dense"`` full-attention oracle when the token
budget covers the whole context.  Swept across quant schemes and
non-uniform per-head block sizes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import (
    AttentionBackend,
    available_backends,
    build_plan,
    get_backend,
    register_backend,
)
from repro.config import ModelConfig, SparseConfig
from repro.core.ragged import layout_for

KEY = jax.random.PRNGKey(0)

#: small shapes, non-uniform per-head block sizes (all three candidates)
B, N_KV, G, S, D = 2, 4, 2, 2048, 64
BLOCK_SIZES = (16, 32, 64, 32)
BUDGET = 512


def _qkv(seed=0):
    key = jax.random.fold_in(KEY, seed)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, N_KV * G, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, N_KV, S, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, N_KV, S, D))
    return q, k, v


# -- registry ----------------------------------------------------------------


def test_registry_resolves_all_three_backends():
    assert set(available_backends()) >= {"dense", "reference", "pallas"}
    for name in ("dense", "reference", "pallas"):
        be = get_backend(name)
        assert isinstance(be, AttentionBackend) and be.name == name


def test_registry_unknown_backend_raises():
    with pytest.raises(KeyError, match="unknown attention backend"):
        get_backend("nope")


def test_register_backend_is_one_call():
    from repro.backends import base as backends_base

    class Fourth(type(get_backend("reference"))):
        name = "fourth-for-test"

    try:
        register_backend(Fourth())
        assert "fourth-for-test" in available_backends()
    finally:  # don't leak into the process-global registry
        backends_base._REGISTRY.pop("fourth-for-test", None)
    assert "fourth-for-test" not in available_backends()


def test_sparse_config_default_backend_resolves():
    assert get_backend(SparseConfig().backend).name == "reference"


# -- plan --------------------------------------------------------------------


def _model_cfg(**sparse_kw):
    return ModelConfig(
        name="t", family="dense", n_layers=2, d_model=128, n_heads=8,
        n_kv_heads=4, d_ff=256, vocab_size=128, head_dim=D,
        sparse=SparseConfig(
            token_budget=BUDGET,
            block_sizes=(BLOCK_SIZES, BLOCK_SIZES),
            **sparse_kw,
        ),
    )


def test_build_plan_is_cached_and_static():
    cfg = _model_cfg()
    p1 = build_plan(cfg, S)
    p2 = build_plan(cfg, S)
    assert p1 is p2, "plans must be derived once per (model_cfg, context)"
    assert p1.active and len(p1.layouts) == 2
    assert p1.token_budget == BUDGET
    assert p1.layout(0).block_sizes == BLOCK_SIZES
    assert p1.rank_key_width == 128  # quest: 2*D padded to lane boundary
    assert p1.offsets.shape == (2, N_KV)
    assert not build_plan(cfg, BUDGET).active  # context too short for sparse


# -- parity ------------------------------------------------------------------


@pytest.mark.parametrize("quant", ["none", "int8_asym", "int4_asym"])
def test_backend_parity_page_tables_and_outputs(quant):
    """reference and pallas: identical page tables, near-identical outputs."""
    lay = layout_for(BLOCK_SIZES, S, 16, BUDGET)
    sparse = SparseConfig(token_budget=BUDGET, quant=quant)
    q, k, v = _qkv()
    seq_len = jnp.array([S, S // 2], jnp.int32)

    outs, tables, pages = {}, {}, {}
    for name in ("reference", "pallas"):
        be = get_backend(name)
        store = be.build_store(k, lay, "quest", quant=quant)
        out, table, pages[name] = be.decode(
            q, k, v, store, lay, sparse, seq_len=seq_len
        )
        outs[name] = np.asarray(out)
        tables[name] = np.asarray(table)

    np.testing.assert_array_equal(tables["reference"], tables["pallas"])
    np.testing.assert_array_equal(pages["reference"], pages["pallas"])
    np.testing.assert_allclose(
        outs["reference"], outs["pallas"], atol=1e-4, rtol=1e-4
    )


@pytest.mark.parametrize("layer", [0, 2])
def test_append_refreshes_stacked_codes_at_layer(layer):
    """Decode-time append on KV and codes stacked over layers (the decode
    scan's carried form) writes layer ``layer``'s row as the append on that
    layer's own slice does, and leaves the other layers as they were."""
    from repro.core.stacked import as_arrays

    L = 3
    lay = layout_for(BLOCK_SIZES, S, 16, BUDGET)
    la, offs = as_arrays(lay), jnp.asarray(lay.offsets[:-1])
    sparse = SparseConfig(token_budget=BUDGET)
    be = get_backend("reference")
    ks = [_qkv(seed=30 + l)[1] for l in range(L)]
    stores = [be.build_store(k, lay, "quest", quant="int4_asym") for k in ks]
    paged = lambda k: k.reshape(B, N_KV, S // 16, 16, D)
    codes = jnp.stack([st.codes for st in stores])
    seq_len = jnp.array([S - 100, 777], jnp.int32)   # mid-block: rows change
    got = be.append(
        dataclasses.replace(stores[layer], codes=codes),
        jnp.stack([paged(k) for k in ks]), la, offs, seq_len, sparse,
        layer=jnp.int32(layer),
    ).codes
    want = be.append(
        stores[layer], paged(ks[layer]), la, offs, seq_len, sparse
    ).codes
    assert not np.array_equal(want, stores[layer].codes)
    for l in range(L):
        np.testing.assert_array_equal(got[l], want if l == layer else codes[l])


@pytest.mark.parametrize("layer,slot", [(0, 1), (2, 0)])
def test_score_rows_refresh_reads_stacked_pool_at_layer_and_slot(layer, slot):
    """Chunked prefill's scoring-segment refresh on the K pool stacked over
    layers and slots (the prefill scan's carried form) reads its window at
    ``(layer, slot)`` as the refresh on that slot's own slice does."""
    from repro.core.stacked import as_arrays

    L = 3
    lay = layout_for(BLOCK_SIZES, S, 16, BUDGET)
    la, offs = as_arrays(lay), jnp.asarray(lay.offsets[:-1])
    sparse = SparseConfig(token_budget=BUDGET)
    be = get_backend("reference")
    paged = lambda k: k.reshape(B, N_KV, S // 16, 16, D)
    k_all = jnp.stack([paged(_qkv(seed=50 + l)[1]) for l in range(L)])
    k_slot = k_all[layer, slot][None]
    store = be.prefill_score_rows(
        jnp.zeros_like(k_slot), la, offs, sparse, quant="int4_asym"
    )
    chunk = (jnp.int32(512), jnp.int32(768), sparse, 256 + 2 * 64)
    got = be.refresh_score_rows(
        store, k_all, la, offs, *chunk,
        layer=jnp.int32(layer), slot=jnp.int32(slot),
    )
    want = be.refresh_score_rows(store, k_slot, la, offs, *chunk)
    assert not np.array_equal(want.codes, store.codes)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("quant", ["none", "int8_asym", "int4_asym"])
def test_backends_match_dense_oracle_at_full_budget(quant):
    """Every sparse backend == the dense oracle when the budget covers the
    context (selection keeps everything; quantization only affects ranking)."""
    lay = layout_for(BLOCK_SIZES, S, 16, S)
    sparse = SparseConfig(token_budget=S, quant=quant)
    q, k, v = _qkv(seed=1)

    dense = get_backend("dense")
    out_d, table_d, pages_d = dense.decode(q, k, v, None, lay, sparse)
    assert table_d is None, "dense oracle has no page table"
    # full attention reads every page of every KV head
    assert np.all(np.asarray(pages_d) == k.shape[1] * S // 16)
    out_d = np.asarray(out_d)

    for name in ("reference", "pallas"):
        be = get_backend(name)
        store = be.build_store(k, lay, "quest", quant=quant)
        out, _, _ = be.decode(q, k, v, store, lay, sparse)
        np.testing.assert_allclose(
            np.asarray(out), out_d, atol=2e-5, rtol=1e-4,
        )


def test_store_bytes_identical_across_backends():
    """The unified quantization path must make reference and pallas stores
    byte-identical (prerequisite for page-table parity)."""
    lay = layout_for(BLOCK_SIZES, S, 16, BUDGET)
    _, k, _ = _qkv(seed=2)
    for quant in ("none", "int8_asym", "int4_asym"):
        s_ref = get_backend("reference").build_store(k, lay, "quest", quant=quant)
        s_krn = get_backend("pallas").build_store(k, lay, "quest", quant=quant)
        assert (s_ref.bits, s_ref.symmetric) == (s_krn.bits, s_krn.symmetric)
        if quant == "none":
            np.testing.assert_allclose(
                np.asarray(s_ref.codes), np.asarray(s_krn.codes), atol=1e-6
            )
        else:
            np.testing.assert_array_equal(
                np.asarray(s_ref.codes), np.asarray(s_krn.codes)
            )
            np.testing.assert_allclose(
                np.asarray(s_ref.scale), np.asarray(s_krn.scale), atol=1e-6
            )


def test_model_backend_swap_is_config_only():
    """Switching SparseConfig.backend changes execution, not semantics:
    dense-backend logits differ from sparse ones only through selection."""
    import repro.models as models
    from repro.configs import get_config, smoke_variant

    base = smoke_variant(get_config("llama3.2-3b"))
    tokens = jax.random.randint(KEY, (1, 160), 0, base.vocab_size)

    def logits(backend):
        cfg = dataclasses.replace(
            base,
            sparse=dataclasses.replace(
                base.sparse, token_budget=64, backend=backend
            ),
        )
        model = models.Transformer(cfg)
        params = model.init(KEY)
        _, cache = model.prefill(params, tokens[:, :-1], max_context=192)
        return np.asarray(model.decode_step(params, cache, tokens[:, -1])[0])

    l_dense = logits("dense")
    l_ref = logits("reference")
    # the dense backend ignores selection -> generally different logits,
    # but both must be finite and same-shaped (same cache structure).
    assert l_dense.shape == l_ref.shape
    assert np.isfinite(l_dense).all() and np.isfinite(l_ref).all()


def test_decode_step_returns_read_only_cache_leaves_as_they_came():
    """The decode layer scan carries the leaves a step writes (KV pools,
    centroid codes) and scans over the rest: the leaves a step only reads
    (store scale/zero, the prefill scoring segment, the plan descriptors)
    leave the step as the very values that came in, not as scan outputs."""
    import repro.models as models
    from repro.configs import get_config, smoke_variant

    base = smoke_variant(get_config("llama3.2-3b"))
    cfg = dataclasses.replace(
        base, sparse=dataclasses.replace(
            base.sparse, token_budget=64, sparse_prefill=True
        ),
    )
    model = models.Transformer(cfg)
    params = jax.eval_shape(model.init, KEY)
    cache = jax.eval_shape(lambda: model.init_cache(2, 192))
    tokens = jax.ShapeDtypeStruct((2,), jnp.int32)
    jaxpr = jax.make_jaxpr(model.decode_step)(params, cache, tokens).jaxpr
    n_params = len(jax.tree.leaves(params))
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(cache)[0]]
    ins = dict(zip(paths, jaxpr.invars[n_params:]))
    outs = dict(zip(paths, jaxpr.outvars[1:]))
    read_only = [p for p in paths if not p.endswith(
        ("['k']", "['v']", "['codes']", "['seq_len']"))]
    assert any(p.endswith("['pcodes']") for p in read_only)
    for p in read_only:
        assert outs[p] is ins[p], p
    for p in paths:
        if p.endswith(("['k']", "['v']", "['codes']")):
            assert outs[p] is not ins[p], p


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "unpaged"])
def test_decode_steps_continue_prefill_through_stacked_pools(paged):
    """Each layer writes its new token into its own layer of the pools the
    decode scan carries: two decode steps continue a prefill as a longer
    prefill does.  Full attention over the sparse plan's paged cache (the
    dense backend) and over an inactive plan's unpaged cache."""
    import repro.models as models
    from repro.configs import get_config, smoke_variant

    base = smoke_variant(get_config("llama3.2-3b"))
    cfg = dataclasses.replace(
        base, sparse=dataclasses.replace(
            base.sparse, token_budget=64, backend="dense", enabled=paged
        ),
    )
    model = models.Transformer(cfg)
    params = model.init(KEY)
    S, ctx = 127, 192
    tokens = jax.random.randint(KEY, (2, S + 2), 0, cfg.vocab_size)
    _, cache = model.prefill(params, tokens[:, :S], max_context=ctx)
    assert (cache["pos0"]["k"].ndim == 6) == paged
    for n in (S, S + 1):
        logits, cache = model.decode_step(params, cache, tokens[:, n])
        want, _ = model.prefill(params, tokens[:, :n + 1], max_context=ctx)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(want), atol=3e-5, rtol=1e-3
        )
