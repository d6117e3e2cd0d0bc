"""shard_map partitioning of the variable-block-size Pallas kernels.

The fused decode and sparse prefill kernels iterate a ``(batch, kv-head)``
(resp. ``(batch, kv-head, query-block)``) grid whose cells are fully
independent — the natural partitioning for a ``(data, model)`` serving mesh
is therefore *batch over data, kv heads over model*.  GSPMD cannot
partition a ``pallas_call`` (it is an opaque custom call and would be
replicated, all-gathering the sharded KV pool every step), so this module
wraps the kernel entry points in :func:`jax.shard_map`:
every device launches the SAME kernel over only its own batch rows and kv
heads.

Partitioning contract (mirrors the rule table in
:mod:`repro.distributed.sharding`):

- batch axes (``q``/``rq``/KV pages/store codes/``seq_len``) shard over the
  rule's ``batch`` axis when the batch divides it, else replicate;
- the kv-head axis (KV pages, decode-store ``scale``/``zero``, and the
  per-head ragged descriptors ``row_offsets``/``n_blocks``/``top_k``/
  ``block_sizes``/``pages_per_block``) shards over the ``kv_heads`` rule
  axis when ``n_kv`` divides it — GQA stacks with fewer kv heads than the
  model axis degrade to replication, the standard GQA-TP practice;
- the flat store row axis is NEVER sharded: per-head row segments are
  ragged, so every shard keeps the full ``total_rows`` axis and its sliced
  ``row_offsets`` descriptor indexes straight into it;
- q heads ride the kv-head shard (the layout is kv-head-major:
  ``n_q = n_kv * group``), so a contiguous model-axis slice of the q-head
  axis is exactly the local kv heads' GQA group.

Bitwise parity: each grid cell's arithmetic is untouched — a cell computes
on identical inputs whether it runs on one device or sixteen — and the
wrapper re-gathers the kv-head axis of the attention output immediately
after the kernel (``with_sharding_constraint`` to a head-replicated spec).
Downstream reductions over heads (``out_project``) therefore see the full
head axis in the original order, making sharded serving token-identical to
the single-device path (the acceptance oracle in
``tests/test_distributed.py``).  Static kernel bounds (``seg``/``p_sel``)
are global maxima and identical on every shard, so all devices compile the
same kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.stacked import LayoutArrays, as_arrays
from repro.distributed.sharding import AxisVal, current_context
from repro.kernels import ops

# ---------------------------------------------------------------------------
# serving rule table
# ---------------------------------------------------------------------------

#: Logical-axis rules for the mesh-native serving engine.  Everything the
#: engine computes outside the kernels stays batch-sharded/replicated (no
#: cross-batch reductions exist, so batch sharding is bitwise-exact); the
#: kv-head axis is sharded only where it is stored (KV pool, decode store)
#: and inside the shard_map'd kernel region.  ``heads``/``mlp``/``vocab``
#: deliberately replicate: sharding them would re-order the float
#: reductions in out-projections and the LM head, breaking the
#: token-identity oracle.
SERVING_RULES: Dict[str, AxisVal] = {
    "batch": "data",
    "kv_heads": "model",
    "heads": None,
    "kv_pages": None,
    "kv_seq": None,
    "seq": None,
    "head_dim": None,
    "embed": None,
    "mlp": None,
    "vocab": None,
    "experts": None,
    "moe_group": None,
    "layers": None,
    "centroid_rows": None,
    "rank_width": None,
    "fsdp": None,
}


def serving_rules(overrides: Optional[Dict[str, AxisVal]] = None) -> Dict[str, AxisVal]:
    rules = dict(SERVING_RULES)
    if overrides:
        rules.update(overrides)
    return rules


# ---------------------------------------------------------------------------
# spec derivation
# ---------------------------------------------------------------------------


def _mesh_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _pick_axis(mesh, rule_val: AxisVal, dim: int) -> Optional[str]:
    """First mesh axis named by the rule that is >1 and divides ``dim``
    (single-axis shard_map specs; non-dividing axes degrade to
    replication, matching the rule-table divisibility guard)."""
    if rule_val is None:
        return None
    sizes = _mesh_sizes(mesh)
    axes = (rule_val,) if isinstance(rule_val, str) else tuple(rule_val)
    for a in axes:
        n = sizes.get(a, 1)
        if n > 1 and dim % n == 0:
            return a
    return None


def shard_axes(
    mesh, rules: Dict[str, AxisVal], batch: int, n_kv: int
) -> Tuple[Optional[str], Optional[str]]:
    """-> ``(batch_axis, head_axis)`` mesh-axis names (or None) for a
    kernel launch over ``batch`` sequences and ``n_kv`` kv heads."""
    ba = _pick_axis(mesh, rules.get("batch"), batch)
    ha = _pick_axis(mesh, rules.get("kv_heads"), n_kv)
    return ba, ha


def _layout_specs(la: LayoutArrays, ha: Optional[str]) -> LayoutArrays:
    """Per-leaf PartitionSpecs for a LayoutArrays pytree: head-axis arrays
    shard over ``ha``; the tile->head map (flat-row axis) replicates."""
    h1 = P(ha)
    h2 = P(ha, None)
    children = (
        h2,        # scatter_rows   [H, max_blocks]
        h2,        # pad_mask       [H, max_blocks]
        h2,        # block_starts   [H, max_blocks]
        h1,        # block_sizes    [H]
        h2,        # slot_map       [H, P_sel]
        h2,        # within_map     [H, P_sel]
        h1,        # pages_per_block[H]
        P(None),   # tile_head      [n_tiles] (flat-row axis: full)
        h2,        # topk_valid     [H, max_top_k]
        h1,        # row_offsets    [H]
        h1,        # n_blocks       [H]
        h1,        # top_k          [H]
    )
    _, aux = la.tree_flatten()
    return LayoutArrays(*children, *aux)


def _store_spec_tree(store, ba, ha, *, head_aligned_params: bool,
                     stacked: bool = False):
    """Spec pytree for a CentroidStore, built by mapping over the store
    itself so None leaves (f32 stores carry no scale/zero) keep the tree
    structure.  ``codes [B, rows, Cw]`` shard batch only (ragged per-head
    row segments stay whole); ``stacked`` codes carry a leading layer axis,
    never sharded.  ``head_aligned_params`` says which store
    kind the CALLER holds — the decode store's per-head ``[B, n_kv, Dp]``
    affine params shard the head axis, the prefill score segment's per-row
    ``[B, rows, 1]`` params replicate their row axis (an explicit flag, not
    shape sniffing: the two layouts can coincide on degenerate shapes)."""
    pspec = P(ba, ha, None) if head_aligned_params else P(ba, None, None)
    cspec = P(None, ba, None, None) if stacked else P(ba, None, None)
    leaves, treedef = jax.tree_util.tree_flatten(store)
    specs = [cspec if i == 0 else pspec for i, _ in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# fused decode
# ---------------------------------------------------------------------------


def fused_decode(
    q: jax.Array,               # [B, n_q, D]
    rq: jax.Array,              # [B, n_q, Dp] rank queries
    k: jax.Array,               # paged [B, n_kv, nP, page, D] or dense 4-D
    v: jax.Array,
    store,                      # repro.backends.CentroidStore (duck-typed)
    layout,                     # RaggedLayout or LayoutArrays
    sink_pages: int = 1,
    local_pages: int = 4,
    seq_len: Optional[jax.Array] = None,
    max_pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
    layer=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Mesh-partitioned :func:`repro.kernels.ops.fused_decode`.

    Under an active sharding context with a shardable axis the launch is
    shard_map'd (batch over ``data``, kv heads over ``model``); otherwise
    this is exactly the single-device entry point.  With ``layer`` given,
    ``k``/``v``/``store.codes`` are stacked over layers (see
    :func:`repro.kernels.ops.fused_decode`); the layer axis is never
    sharded.  The returned attention output is re-gathered over heads (see
    module docstring); the page table/valid stay kv-head-sharded.
    """
    ctx = current_context()
    la = as_arrays(layout)
    kp, vp, codes, layer = ops.stacked_operands(
        k, v, store.codes, la.page_size, layer
    )
    store = dataclasses.replace(store, codes=codes)
    B = q.shape[0]
    n_kv = kp.shape[2]

    ba = ha = None
    if ctx is not None:
        ba, ha = shard_axes(ctx.mesh, ctx.rules, B, n_kv)
    if ba is None and ha is None:
        return ops.fused_decode(
            q, rq, kp, vp, store, la,
            sink_pages=sink_pages, local_pages=local_pages,
            seq_len=seq_len,
            max_pages_per_block=max_pages_per_block,
            interpret=interpret, layer=layer,
        )
    mesh = ctx.mesh

    if seq_len is None:
        seq_len = jnp.full((B,), la.context_len, jnp.int32)
    else:
        seq_len = jnp.broadcast_to(jnp.asarray(seq_len, jnp.int32), (B,))

    def local_call(q_l, rq_l, kp_l, vp_l, store_l, la_l, seq_l, layer_l):
        return ops.fused_decode(
            q_l, rq_l, kp_l, vp_l, store_l, la_l,
            sink_pages=sink_pages, local_pages=local_pages,
            seq_len=seq_l,
            max_pages_per_block=max_pages_per_block,
            interpret=interpret, layer=layer_l,
        )

    qs = P(ba, ha, None)
    kvs = P(None, ba, ha, None, None, None)
    out, table, valid = jax.shard_map(
        local_call,
        mesh=mesh,
        in_specs=(
            qs, qs, kvs, kvs,
            _store_spec_tree(
                store, ba, ha, head_aligned_params=True, stacked=True
            ),
            _layout_specs(la, ha),
            P(ba),
            P(),
        ),
        out_specs=(qs, P(ba, ha, None), P(ba, ha, None)),
        check_vma=False,
    )(q, rq, kp, vp, store, la, seq_len, jnp.asarray(layer, jnp.int32))
    # head-gather for bitwise-identical downstream reductions (out_project
    # sums over the FULL head axis in the single-device order).
    out = jax.lax.with_sharding_constraint(
        out, NamedSharding(mesh, P(ba, None, None))
    )
    return out, table, valid


# ---------------------------------------------------------------------------
# sparse prefill
# ---------------------------------------------------------------------------


def sparse_prefill(
    q: jax.Array,               # [B, Hq, Sq, D]
    rq: jax.Array,              # [B, Hq, Sq, Dp] per-token rank queries
    k: jax.Array,               # paged [B, n_kv, nP, page, D] or dense 4-D
    v: jax.Array,
    score_store,                # duck-typed: codes/scale/zero/bits/symmetric
    layout,                     # RaggedLayout or LayoutArrays
    sink_pages: int = 1,
    local_pages: int = 4,
    block_q: int = 64,
    topk_scale: float = 1.0,
    n_valid: Optional[jax.Array] = None,
    chunk_offset=0,
    max_pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
    layer=None,
    slot=None,
) -> Tuple[jax.Array, jax.Array]:
    """Mesh-partitioned :func:`repro.kernels.ops.sparse_prefill` — same
    partitioning contract as :func:`fused_decode`.  With ``layer`` and
    ``slot`` given, ``k``/``v`` are the pools stacked over layers and slots
    (see :func:`repro.kernels.ops.sparse_prefill`); the layer axis is never
    sharded.  A prefill chunk is one slot's queries (batch 1, replicated)
    against pools whose slot axis shards over ``data``: every data shard
    then launches at the slot's row local to it, clamped into its own rows,
    and only the shard that holds the slot contributes its output to a sum
    over the data axis (the others add zeros, so the sum is exact)."""
    ctx = current_context()
    la = as_arrays(layout)
    kp, vp, _, layer = ops.stacked_operands(k, v, None, la.page_size, layer)
    slot = jnp.asarray(0 if slot is None else slot, jnp.int32)
    B = q.shape[0]
    n_kv = kp.shape[2]

    ba = ha = pa = None
    if ctx is not None:
        ba, ha = shard_axes(ctx.mesh, ctx.rules, B, n_kv)
        pa, _ = shard_axes(ctx.mesh, ctx.rules, kp.shape[1], n_kv)
    if ba is None and ha is None and pa is None:
        return ops.sparse_prefill(
            q, rq, kp, vp, score_store, la,
            sink_pages=sink_pages, local_pages=local_pages,
            block_q=block_q, topk_scale=topk_scale,
            n_valid=n_valid, chunk_offset=chunk_offset,
            max_pages_per_block=max_pages_per_block,
            interpret=interpret, layer=layer, slot=slot,
        )
    mesh = ctx.mesh
    # the data axis that holds the slot, where the queries are not sharded
    owner = pa if ba is None else None

    if n_valid is None:
        n_valid = jnp.asarray(chunk_offset + q.shape[2], jnp.int32)
    n_valid = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,))
    chunk_offset = jnp.asarray(chunk_offset, jnp.int32)

    def local_call(q_l, rq_l, kp_l, vp_l, store_l, la_l, nv_l, co_l,
                   layer_l, slot_l):
        if owner is not None:
            rows = kp_l.shape[1]
            here = slot_l - jax.lax.axis_index(owner) * rows
            mine = (here >= 0) & (here < rows)
            slot_l = jnp.clip(here, 0, rows - 1)
        out, n_att = ops.sparse_prefill(
            q_l, rq_l, kp_l, vp_l, store_l, la_l,
            sink_pages=sink_pages, local_pages=local_pages,
            block_q=block_q, topk_scale=topk_scale,
            n_valid=nv_l, chunk_offset=co_l,
            max_pages_per_block=max_pages_per_block,
            interpret=interpret, layer=layer_l, slot=slot_l,
        )
        if owner is not None:
            out = jax.lax.psum(jnp.where(mine, out, 0), owner)
            n_att = jax.lax.psum(jnp.where(mine, n_att, 0), owner)
        return out, n_att

    qs = P(ba, ha, None, None)
    kvs = P(None, ba or pa, ha, None, None, None)
    out, n_att = jax.shard_map(
        local_call,
        mesh=mesh,
        in_specs=(
            qs, qs, kvs, kvs,
            _store_spec_tree(score_store, ba, ha, head_aligned_params=False),
            _layout_specs(la, ha),
            P(ba),
            P(),
            P(),
            P(),
        ),
        out_specs=(qs, P(ba, ha, None)),
        check_vma=False,
    )(q, rq, kp, vp, score_store, la, n_valid, chunk_offset,
      jnp.asarray(layer, jnp.int32), slot)
    out = jax.lax.with_sharding_constraint(
        out, NamedSharding(mesh, P(ba, None, None, None))
    )
    return out, n_att
