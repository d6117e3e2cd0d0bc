"""The prefill chunk's in-place write of the KV pools it carries.

``Transformer.prefill_chunk`` carries the KV pools (and, on the sparse
path, the running scoring segment) through its layer scan and writes a
chunk with one dynamic-update-slice of the chunk's window at the layer and
slot (:func:`repro.models.transformer._write_chunk`).  The oracle is the
scatter form that wrote the chunk before, kept here: row ``i`` of the chunk
lands at position ``offset + i`` when ``i < n_valid`` and the position lies
in the pool, and nothing else moves.  The edges are a chunk with padding
rows (``n_valid`` below the buffer), a chunk whose buffer runs past the
pool's end, and a chunk that starts inside a page.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.transformer as tmod
from repro.configs import get_config, smoke_variant

KEY = jax.random.PRNGKey(0)
L, B, N_KV, S, D, PS, C = 3, 2, 2, 256, 16, 16, 128

#: (offset, n_valid) of a chunk buffer of C rows in a pool of S tokens
CASES = {
    "padding": (64, 50),           # rows 50.. of the buffer are padding
    "pool_end": (192, 64),         # the buffer runs 64 rows past the pool
    "inside_page": (72, 100),      # starts 8 tokens into a page
}


def _scatter_write(pool, new, layer, slot, offset, n_valid):
    """The scatter form: padding rows and rows past the pool's end get an
    out-of-bounds position and are dropped."""
    rel = jnp.arange(new.shape[0])
    if pool.ndim == 6:
        S_max = pool.shape[3] * pool.shape[4]
        pos = jnp.where(rel < n_valid, offset + rel, S_max)
        ps = pool.shape[4]
        return pool.at[layer, slot, :, pos // ps, pos % ps].set(
            new.astype(pool.dtype)
        )
    pos = jnp.where(rel < n_valid, offset + rel, pool.shape[3])
    return pool.at[layer, slot, :, pos].set(new.astype(pool.dtype))


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "unpaged"])
def test_chunk_write_matches_scatter(paged, case):
    offset, n_valid = CASES[case]
    shape = (L, B, N_KV, S // PS, PS, D) if paged else (L, B, N_KV, S, D)
    pool = jax.random.normal(KEY, shape, jnp.float32).astype(jnp.bfloat16)
    new = jax.random.normal(jax.random.fold_in(KEY, 1), (C, N_KV, D))
    args = (jnp.int32(2), jnp.int32(1), jnp.int32(offset), jnp.int32(n_valid))
    got = tmod._write_chunk(pool, new, *args)
    want = _scatter_write(pool, new, *args)
    assert not np.array_equal(np.asarray(want), np.asarray(pool))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _model(branch):
    base = smoke_variant(get_config("llama3.2-3b"))
    cfg = dataclasses.replace(
        base, n_layers=L,
        sparse=dataclasses.replace(
            base.sparse, token_budget=64, backend="reference",
            enabled=branch != "dense",
            sparse_prefill=branch == "sparse",
        ),
    )
    return tmod.Transformer(cfg)


def _stale_cache(model):
    """A two-slot cache whose KV rows hold stale values, as a reused slot's
    do."""
    cache = model.init_cache(B, S)
    entry = dict(cache["pos0"])
    for i, name in enumerate(("k", "v")):
        a = entry[name]
        entry[name] = jax.random.normal(
            jax.random.fold_in(KEY, 10 + i), a.shape, jnp.float32
        ).astype(a.dtype)
    return {**cache, "pos0": entry}


@pytest.mark.parametrize("case", ["padding", "pool_end"])
@pytest.mark.parametrize(
    "branch", ["sparse", "dense_paged", "dense"],
)
def test_prefill_chunk_matches_scatter_form(monkeypatch, branch, case):
    """Logits and every cache leaf equal, bit for bit, the same chunk
    written with the scatter form: the sparse branch (the running scoring
    segment carried and refreshed at the layer and slot), the dense branch
    over the sparse plan's paged cache (the reference rung) and over an
    inactive plan's unpaged cache."""
    offset, n_valid = CASES[case]
    model = _model(branch)
    params = model.init(KEY)
    cache = _stale_cache(model)
    assert (cache["pos0"]["k"].ndim == 6) == (branch != "dense")
    assert ("pcodes" in cache["pos0"]) == (branch == "sparse")
    tokens = jax.random.randint(KEY, (C,), 0, model.cfg.vocab_size)
    args = (params, cache, jnp.int32(1), tokens, jnp.int32(offset),
            jnp.int32(n_valid))
    # a fresh function for each trace: jit would reuse the first trace
    logits, got = jax.jit(lambda *a: model.prefill_chunk(*a))(*args)
    monkeypatch.setattr(tmod, "_write_chunk", _scatter_write)
    want_logits, want = jax.jit(lambda *a: model.prefill_chunk(*a))(*args)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path)
        )
    # the chunk landed in slot 1 alone
    k0, k1 = np.asarray(cache["pos0"]["k"]), np.asarray(got["pos0"]["k"])
    np.testing.assert_array_equal(k1[:, 0], k0[:, 0])
    assert not np.array_equal(k1[:, 1], k0[:, 1])


def test_prefill_chunk_returns_read_only_cache_leaves_as_they_came():
    """The prefill layer scan carries the leaves a chunk writes (KV pools,
    the running scoring segment) and scans over the rest: the leaves a
    chunk only reads or leaves alone (the decode store, the plan
    descriptors, the lengths) leave the step as the very values that came
    in, not as scan outputs."""
    model = _model("sparse")
    params = jax.eval_shape(model.init, KEY)
    cache = jax.eval_shape(lambda: model.init_cache(B, S))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    jaxpr = jax.make_jaxpr(model.prefill_chunk)(
        params, cache, i32, jax.ShapeDtypeStruct((C,), jnp.int32), i32, i32
    ).jaxpr
    n_params = len(jax.tree.leaves(params))
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(cache)[0]]
    ins = dict(zip(paths, jaxpr.invars[n_params:]))
    outs = dict(zip(paths, jaxpr.outvars[1:]))
    written = ("['k']", "['v']", "['pcodes']", "['pscale']", "['pzero']")
    read_only = [p for p in paths if not p.endswith(written)]
    assert any(p.endswith("['codes']") for p in read_only)
    for p in read_only:
        assert outs[p] is ins[p], p
    for p in paths:
        if p.endswith(written):
            assert outs[p] is not ins[p], p
