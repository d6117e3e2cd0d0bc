"""Selection + page-table expansion tests (properties 1, 3, 4)."""
import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - fallback: deterministic examples
    from _hypothesis_fallback import given, settings, strategies as st

from repro.backends import get_backend
from repro.config import SparseConfig
from repro.core import dense_decode_attention, layout_for, select_page_table
from repro.core.selection import pages_to_token_mask


def _scores(key, lay, B=2):
    s = jax.random.normal(key, (B, lay.n_heads, lay.max_blocks))
    return jnp.where(jnp.asarray(lay.pad_mask)[None], s, -1e30)


def test_page_table_shape_and_range():
    lay = layout_for((16, 32, 64, 32), 2048, 16, 512)
    table, valid = select_page_table(_scores(jax.random.PRNGKey(0), lay), lay)
    assert table.shape == (2, 4, lay.selected_pages)
    assert valid.all()
    assert (table >= 0).all() and (table < lay.n_pages).all()


def test_no_duplicate_pages_per_head():
    lay = layout_for((16, 32, 64, 32), 2048, 16, 512)
    table, valid = select_page_table(_scores(jax.random.PRNGKey(1), lay), lay)
    t = np.asarray(table)
    for b in range(t.shape[0]):
        for h in range(t.shape[1]):
            assert len(set(t[b, h])) == t.shape[2], "duplicate pages selected"


def test_sink_and_local_always_selected():
    lay = layout_for((16, 32, 64, 32), 2048, 16, 512)
    scores = _scores(jax.random.PRNGKey(2), lay) - 100.0  # nothing attractive
    table, valid = select_page_table(
        scores, lay, sink_pages=1, local_pages=4
    )
    mask = np.asarray(pages_to_token_mask(table, valid, lay))
    assert mask[..., :16].all(), "sink page must always be covered"
    assert mask[..., -64:].all(), "local window must always be covered"


def test_budget_exact_token_coverage():
    lay = layout_for((16, 32, 64, 32), 2048, 16, 512)
    table, valid = select_page_table(_scores(jax.random.PRNGKey(3), lay), lay)
    mask = np.asarray(pages_to_token_mask(table, valid, lay))
    covered = mask.sum(-1)
    assert (covered == 512).all(), f"every head covers exactly T tokens, got {covered}"


def test_seq_len_masks_future_blocks():
    lay = layout_for((16, 32), 2048, 16, 512)
    scores = _scores(jax.random.PRNGKey(4), lay, B=2)
    seq_len = jnp.array([512, 2048], jnp.int32)
    table, valid = select_page_table(scores, lay, seq_len=seq_len)
    t = np.asarray(table)
    v = np.asarray(valid)
    pos = t * 16
    assert (pos[0][v[0]] < 512).all(), "sequence 0 must not select past seq_len"


def test_sparse_equals_dense_at_full_budget():
    """Property 4: budget >= context -> sparse == dense attention."""
    key = jax.random.PRNGKey(5)
    B, n_kv, g, S, D = 2, 4, 2, 1024, 64
    lay = layout_for((16, 32, 64, 32), S, 16, S)
    k = jax.random.normal(key, (B, n_kv, S, D))
    v = jax.random.normal(jax.random.fold_in(key, 1), (B, n_kv, S, D))
    q = jax.random.normal(jax.random.fold_in(key, 2), (B, n_kv * g, D))
    backend = get_backend("reference")
    for method in ("mean", "quest", "arkvale"):
        cfg = SparseConfig(token_budget=S, centroid_method=method)
        store = backend.build_store(k, lay, method, quant="none")
        out_s, _, _ = backend.decode(q, k, v, store, lay, cfg)
        out_d = dense_decode_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out_s), np.asarray(out_d), atol=2e-5, rtol=1e-4,
        )


@settings(max_examples=20, deadline=None)
@given(
    bs=st.lists(st.sampled_from([16, 32, 64]), min_size=2, max_size=6),
    seed=st.integers(0, 100),
)
def test_selection_respects_topk_semantics(bs, seed):
    """Selected blocks are exactly the K_h highest-scoring (ignoring pins)."""
    lay = layout_for(tuple(bs), 2048, 16, 512)
    scores = _scores(jax.random.PRNGKey(seed), lay, B=1)
    table, valid = select_page_table(scores, lay, sink_pages=0, local_pages=0)
    t = np.asarray(table)[0]
    s = np.asarray(scores)[0]
    for h in range(lay.n_heads):
        ppb = lay.pages_per_block[h]
        sel_blocks = sorted(set(int(p) // ppb for p in t[h]))
        k_h = lay.top_k[h]
        top_blocks = sorted(
            np.argsort(-s[h, : lay.n_blocks[h]])[:k_h].tolist()
        )
        assert sel_blocks == top_blocks
