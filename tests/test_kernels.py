"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py).

Every Pallas kernel runs in interpret mode on CPU; TPU is the target."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backends import PallasBackend, get_backend
from repro.core.centroids import rank_query
from repro.core.quantization import unpack_split_half
from repro.core.ragged import layout_for
from repro.core.selection import select_page_table
from repro.kernels import block_centroid, ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.topk_threshold import topk_threshold

#: interpret-forced pallas backend for CPU kernel validation
PALLAS = PallasBackend(interpret=True)

KEY = jax.random.PRNGKey(0)


# -- flash attention ---------------------------------------------------------


@pytest.mark.parametrize(
    "B,Hq,Hkv,S,D,dtype",
    [
        (1, 2, 1, 256, 64, jnp.float32),
        (2, 4, 2, 384, 128, jnp.float32),
        (1, 4, 4, 256, 128, jnp.bfloat16),
        (1, 8, 2, 512, 64, jnp.float32),
    ],
)
def test_flash_attention_sweep(B, Hq, Hkv, S, D, dtype):
    q = jax.random.normal(KEY, (B, Hq, S, D), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Hkv, S, D), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hkv, S, D), dtype)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    atol = 5e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


def test_flash_attention_noncausal():
    q = jax.random.normal(KEY, (1, 2, 256, 64))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 2, 256, 64))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 2, 256, 64))
    got = flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)


# -- block centroid pooling ----------------------------------------------------


@pytest.mark.parametrize("method", ["mean", "quest", "arkvale"])
@pytest.mark.parametrize("bsz,S,D", [(16, 1024, 64), (32, 2048, 128), (64, 1024, 64)])
def test_pool_rank_keys_sweep(method, bsz, S, D):
    k = jax.random.normal(KEY, (2, 3, S, D))
    got = block_centroid.pool_rank_keys(k, bsz, method, chunk=512, interpret=True)
    want = ref.pool_rank_keys_ref(k, bsz, method)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# -- kernel 1: estimation -------------------------------------------------------


@pytest.mark.parametrize("method", ["mean", "quest", "arkvale"])
@pytest.mark.parametrize("quant", ["none", "int4_asym", "int8_asym"])
def test_centroid_scores_vs_ref(method, quant):
    B, n_kv, g, S, D = 2, 4, 2, 2048, 64
    lay = layout_for((16, 32, 64, 32), S, 16, 512)
    k = jax.random.normal(KEY, (B, n_kv, S, D))
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, n_kv * g, D))
    store = PALLAS.build_store(k, lay, method, quant=quant)
    rq = rank_query(q, method, D)
    got = PALLAS.scores(rq, store, lay, n_kv)

    # oracle: dequantize the store the slow way, score densely
    if store.bits == 0:
        rk = store.codes
    else:
        codes = (
            unpack_split_half(store.codes) if store.bits == 4 else store.codes
        ).astype(jnp.float32)
        rk = jnp.zeros(codes.shape, jnp.float32)
        for h in range(n_kv):
            seg = slice(lay.offsets[h], lay.offsets[h + 1])
            rk = rk.at[:, seg].set(
                codes[:, seg] * store.scale[:, h : h + 1]
                + store.zero[:, h : h + 1]
            )
    flat = ref.centroid_scores_ref(rq, rk, n_kv, lay.tile_head, lay.tile_rows)
    want = ops.flat_to_padded(flat, lay)
    g_ = np.asarray(got)
    w_ = np.asarray(want)
    m = w_ > -1e29
    np.testing.assert_allclose(g_[m], w_[m], atol=2e-4, rtol=1e-4)


def test_quantized_scores_close_to_exact():
    """INT4-asym scores stay close to exact scores (ranking-preserving)."""
    B, n_kv, g, S, D = 1, 2, 2, 2048, 64
    lay = layout_for((32, 32), S, 16, 512)
    k = jax.random.normal(KEY, (B, n_kv, S, D))
    q = jax.random.normal(jax.random.fold_in(KEY, 7), (B, n_kv * g, D))
    rq = rank_query(q, "quest", D)
    s_exact = PALLAS.scores(
        rq, PALLAS.build_store(k, lay, "quest", quant="none"), lay, n_kv)
    s_q = PALLAS.scores(
        rq, PALLAS.build_store(k, lay, "quest", quant="int4_asym"), lay, n_kv)
    m = np.asarray(s_exact) > -1e29
    rel = np.abs(np.asarray(s_q)[m] - np.asarray(s_exact)[m])
    scale = np.abs(np.asarray(s_exact)[m]).mean()
    assert rel.mean() < 0.05 * scale


# -- kernel 2: top-k threshold ---------------------------------------------------


@pytest.mark.parametrize("M", [128, 512, 2048])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_topk_threshold_exact(M, seed):
    B, H = 2, 4
    key = jax.random.fold_in(KEY, seed)
    scores = jax.random.normal(key, (B, H, M)) * 10
    ks = tuple(int(x) for x in np.random.default_rng(seed).integers(1, M, H))
    thr, cnt = topk_threshold(scores, ks, interpret=True)
    thr_ref, cnt_ref = ref.topk_threshold_ref(scores, ks)
    np.testing.assert_array_equal(np.asarray(thr), np.asarray(thr_ref))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))


def test_topk_threshold_with_ties_and_infs():
    scores = jnp.array([[[1.0, 2.0, 2.0, 2.0, -1e30, 0.5, -2.0, 2.0]]])
    thr, cnt = topk_threshold(scores, (3,), interpret=True)
    assert float(thr[0, 0]) == 2.0
    assert int(cnt[0, 0]) == 0  # nothing strictly above 2.0? no: 1.0<2, so...
    # strictly-greater count of values > 2.0 is 0; ties fill all 3 slots
    thr2, cnt2 = topk_threshold(scores, (5,), interpret=True)
    assert float(thr2[0, 0]) == 1.0
    assert int(cnt2[0, 0]) == 4


# -- kernel 3: paged attention ----------------------------------------------------


@pytest.mark.parametrize(
    "B,n_kv,g,S,D,dtype",
    [
        (2, 4, 2, 2048, 64, jnp.float32),
        (1, 2, 4, 1024, 128, jnp.float32),
        (2, 8, 1, 2048, 64, jnp.bfloat16),
    ],
)
def test_paged_attention_sweep(B, n_kv, g, S, D, dtype):
    lay = layout_for((32,) * n_kv, S, 16, 512)
    k = jax.random.normal(KEY, (B, n_kv, S, D), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 1), (B, n_kv, S, D), dtype)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, n_kv * g, D), dtype)
    scores = jax.random.normal(jax.random.fold_in(KEY, 3),
                               (B, n_kv, lay.max_blocks))
    table, valid = select_page_table(scores, lay)
    seq_len = jnp.full((B,), S, jnp.int32).at[0].set(S // 2)
    got = ops.paged_attention(q, k, v, table, valid, 16, seq_len, interpret=True)
    kp = k.reshape(B, n_kv, S // 16, 16, D)
    vp = v.reshape(B, n_kv, S // 16, 16, D)
    want = ref.paged_attention_ref(q, kp, vp, table, valid, seq_len, 16)
    atol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


def test_fused_kernel_pipeline_matches_reference_pipeline():
    from repro.config import SparseConfig

    B, n_kv, g, S, D = 2, 4, 2, 2048, 64
    lay = layout_for((16, 32, 64, 32), S, 16, 512)
    k = jax.random.normal(KEY, (B, n_kv, S, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 1), (B, n_kv, S, D))
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, n_kv * g, D))
    seq_len = jnp.array([S, S // 2], jnp.int32)
    cfg = SparseConfig(token_budget=512, block_sizes=((16, 32, 64, 32),))
    ref_be = get_backend("reference")
    store_ref = ref_be.build_store(k, lay, "quest", quant="none")
    store_krn = PALLAS.build_store(k, lay, "quest", quant="none")
    out_ref, tbl_ref, pg_ref = ref_be.decode(
        q, k, v, store_ref, lay, cfg, seq_len=seq_len)
    out_krn, tbl_krn, pg_krn = PALLAS.decode(
        q, k, v, store_krn, lay, cfg, seq_len=seq_len)
    np.testing.assert_array_equal(np.asarray(tbl_ref), np.asarray(tbl_krn))
    np.testing.assert_array_equal(np.asarray(pg_ref), np.asarray(pg_krn))
    np.testing.assert_allclose(
        np.asarray(out_ref), np.asarray(out_krn), atol=1e-5
    )
