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
import re

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


def _hlo_instructions(hlo: str):
    """-> [(name, dims of the result or of a tuple's first element, opcode,
    operand names, line)] over every computation; names are module-unique."""
    pat = re.compile(
        r"^\s*(?:ROOT )?%(\S+) = \(?\w+\[([\d,]*)\][^=]*? ([\w-]+)\(([^)]*)\)"
    )
    out = []
    for line in hlo.splitlines():
        m = pat.match(line)
        if m:
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            operands = re.findall(r"%([\w.-]+)", m.group(4))
            out.append((m.group(1), dims, m.group(3), operands, line))
    return out


def _small_model(**sparse):
    """llama3.2-3b's attention widths at three layers and a small FFN and
    vocabulary, on the Pallas path with the mixed block sizes."""
    import dataclasses

    from repro.models import Transformer

    L = 3
    cfg = dataclasses.replace(
        CFG, n_layers=L, d_model=512, d_ff=1024, vocab_size=1024,
        head_dim=D,
        sparse=dataclasses.replace(
            SP, backend="pallas", fused_decode=True, sparse_prefill=True,
            token_budget=512, block_sizes=(MIXED,) * L, **sparse,
        ),
    )
    return Transformer(cfg, context_len=2048), L


def _compile_step(sds, model, step, cache, *args):
    """HLO text of ``step`` (params, cache donated, *args) compiled for
    v5e, with the Pallas backend pinned to Mosaic."""
    from repro.backends import get_backend

    as_sds = lambda a: sds(a.shape, a.dtype)
    params = jax.tree.map(
        as_sds, jax.eval_shape(model.init, jax.random.PRNGKey(0))
    )
    backend = get_backend("pallas")
    interpret, backend.interpret = backend.interpret, False
    try:
        # the engine's form: the cache donated
        return jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, *args
        ).compile().as_text()
    finally:
        backend.interpret = interpret


def _small_cache(sds, model, batch, ctx):
    return jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init_cache(batch, ctx)),
    )


def _assert_pools_in_place(hlo, layer, L):
    """No copy, slice, update-slice or allocation of one layer's pool
    ``layer``, its unit-layer form or the stack of ``L`` in ``hlo``, and
    the donated stacked k/v parameters of the entry computation alias
    outputs.  At these widths the whole stack fits the chip's fast memory
    (memory space ``S(1)``), and the compiler may stage it there with an
    asynchronous copy in and out; that is exempt, as a deployment-sized
    pool (gigabytes) never fits."""
    pool_shapes = {layer, (1,) + layer, (L,) + layer}
    shapes = {name: dims for name, dims, *_ in _hlo_instructions(hlo)}
    bad = []
    for name, dims, op, operands, line in _hlo_instructions(hlo):
        if op == "copy-start" and "S(1)}" in line.split("copy-start(")[0]:
            hit = False            # staged in, or out of, fast memory
        elif op in ("copy", "copy-start", "dynamic-slice") or (
            op == "custom-call" and "AllocateBuffer" in line
        ):
            hit = dims in pool_shapes
        elif op == "fusion":       # a materialised layer slice
            hit = dims in pool_shapes - {(L,) + layer} or (
                name.startswith("copy") and dims in pool_shapes
            )
        elif op == "dynamic-update-slice":   # a layer written into a stack
            hit = shapes.get(operands[1]) in pool_shapes
        else:
            hit = False
        if hit:
            bad.append(f"{op} {name} {dims}")
    assert not bad, bad

    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    params_at = [
        int(m.group(1))
        for m in re.finditer(
            r"= bf16\[" + ",".join(map(str, (L,) + layer))
            + r"\][^\n]*? parameter\((\d+)\)", entry,
        )
    ]
    assert len(params_at) == 2, params_at
    header = hlo[:hlo.index("\n")]
    for p in params_at:
        assert re.search(rf"\({p}, \{{\}}, (may|must)-alias\)", header), p


def test_decode_step_updates_kv_pools_in_place_on_v5e(sds):
    """The decode step's layer scan carries the stacked KV pools and writes
    each new token in place: the compiled program holds no copy, slice,
    update-slice or allocation of one layer's pool or of the whole stack,
    and the donated pools are the step's outputs."""
    batch, ctx = 2, 2048
    model, L = _small_model()
    cache = _small_cache(sds, model, batch, ctx)
    cache["_decode_pages"] = sds((L, batch), jnp.int32)
    hlo = _compile_step(
        sds, model, model.decode_step, cache, sds((batch,), jnp.int32)
    )
    assert "tpu_custom_call" in hlo
    _assert_pools_in_place(hlo, (batch, NKV, ctx // PS, PS, D), L)


@pytest.mark.parametrize("branch", ["sparse", "dense"])
def test_prefill_chunk_updates_kv_pools_in_place_on_v5e(sds, branch):
    """The prefill chunk's layer scan carries the stacked KV pools (and the
    running scoring segment) and writes the chunk in place at the layer
    and slot; the sparse prefill kernel reads the pools where they lie.
    The compiled program holds no copy, slice, update-slice or allocation
    of one layer's pool or of the whole stack, and the donated pools are
    the step's outputs.  The dense branch (no sparse plan: the dense cache
    and masked attention over one slot of one layer) is held to the same."""
    batch, ctx = 2, 2048
    sparse = branch == "sparse"
    model, L = _small_model(enabled=sparse)
    cache = _small_cache(sds, model, batch, ctx)
    if sparse:
        cache["_prefill_blocks"] = sds((L,), jnp.uint32)
        layer = (batch, NKV, ctx // PS, PS, D)
    else:
        layer = (batch, NKV, ctx, D)
    i32 = sds((), jnp.int32)
    hlo = _compile_step(
        sds, model, model.prefill_chunk, cache,
        i32, sds((CHUNK,), jnp.int32), i32, i32,
    )
    assert ("tpu_custom_call" in hlo) == sparse
    _assert_pools_in_place(hlo, layer, L)
