"""Compile the serving path's Pallas kernels for a TPU v5e chip.

Interpret mode (every other kernel test) cannot see the TPU's block-shape
rules, unsupported in-kernel ops or fast-memory limits; the TPU compiler,
installed here, refuses those for a chip that is described and not
attached.  One compile per kernel at llama3.2-3b's published attention
widths (24 query / 8 KV heads, head_dim 128), batch 2, an 8192-token
context and a mixed 16/32/64 block-size layout — the shapes the serving
engine hands each kernel inside its layer scan.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backends import CentroidStore
from repro.configs import get_config
from repro.core.centroids import padded_rank_key_width
from repro.core.ragged import layout_for
from repro.core.stacked import as_arrays
from repro.kernels import ops

CFG = get_config("llama3.2-3b")
SP = CFG.sparse
B, CTX, CHUNK = 2, 8192, 256
NQ, NKV, D = CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim
DP = padded_rank_key_width(D, SP.centroid_method)
PS = SP.page_size
PPB_MAX = SP.max_block_size // PS
MIXED = (16, 32, 64, 16, 32, 64, 16, 32)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


@pytest.fixture(scope="module")
def layout():
    return layout_for(MIXED, CTX, PS, SP.budget_for(CTX))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _layout_args(sds, layout):
    # traced layout arrays, as the layer scan hands them to the kernels
    return jax.tree.map(lambda a: sds(a.shape, a.dtype), as_arrays(layout))


def _decode_args(sds, layout, batch):
    return (
        sds((batch, NQ, D), jnp.bfloat16),
        sds((batch, NKV, CTX // PS, PS, D), jnp.bfloat16),
        sds((batch, NKV, CTX // PS, PS, D), jnp.bfloat16),
        sds((batch, layout.total_rows, DP // 2), jnp.uint8),   # int4 codes
        sds((batch, NKV, DP), jnp.float32),
        sds((batch, NKV, DP), jnp.float32),
        sds((batch,), jnp.int32),
    )


def test_fused_decode_compiles_for_v5e(sds, layout):
    def step(q, kp, vp, codes, scale, zero, seq_len, la):
        store = CentroidStore(codes, scale, zero, 4, False)
        rq = jnp.zeros(q.shape[:2] + (DP,), jnp.float32) + q[..., :1]
        return ops.fused_decode(
            q, rq, kp, vp, store, la,
            sink_pages=SP.sink_pages, local_pages=SP.local_pages,
            seq_len=seq_len, max_pages_per_block=PPB_MAX, interpret=False,
        )

    _compile(step, *_decode_args(sds, layout, B), _layout_args(sds, layout))


@pytest.mark.parametrize("stage", ["centroid_score", "paged_attention"])
def test_staged_decode_kernels_compile_for_v5e(sds, layout, stage):
    """The staged pipeline is the ladder's second rung: estimation kernel,
    XLA top-k, paged-attention kernel."""

    def step(q, kp, vp, codes, scale, zero, seq_len, la):
        store = CentroidStore(codes, scale, zero, 4, False)
        rq = jnp.zeros(q.shape[:2] + (DP,), jnp.float32) + q[..., :1]
        if stage == "centroid_score":
            return ops.centroid_scores(rq, store, la, NKV, interpret=False)
        table = jnp.zeros((B, NKV, layout.selected_pages), jnp.int32)
        return ops.paged_attention(
            q, kp, vp, table, table >= 0, PS, seq_len, interpret=False
        )

    _compile(step, *_decode_args(sds, layout, B), _layout_args(sds, layout))


def test_sparse_prefill_compiles_for_v5e(sds, layout):
    """One engine prefill chunk: batch 1 (the slot), CHUNK query tokens."""

    def step(q, kp, vp, codes, scale, zero, offset, la):
        store = CentroidStore(codes, scale, zero, 4, False)
        rq = jnp.zeros(q.shape[:3] + (DP,), jnp.float32) + q[..., :1]
        return ops.sparse_prefill(
            q, rq, kp, vp, store, la,
            sink_pages=SP.sink_pages, local_pages=SP.local_pages,
            block_q=SP.prefill_block_q, n_valid=offset + CHUNK,
            chunk_offset=offset, max_pages_per_block=PPB_MAX,
            interpret=False,
        )

    rows = layout.total_rows
    _compile(
        step,
        sds((1, NQ, CHUNK, D), jnp.bfloat16),
        sds((1, NKV, CTX // PS, PS, D), jnp.bfloat16),
        sds((1, NKV, CTX // PS, PS, D), jnp.bfloat16),
        sds((1, rows, DP // 2), jnp.uint8),
        sds((1, rows, 1), jnp.float32),                         # per-row
        sds((1, rows, 1), jnp.float32),
        sds((), jnp.int32),
        _layout_args(sds, layout),
    )
