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
