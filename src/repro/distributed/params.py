"""Param/cache pytree -> logical axis names -> NamedShardings.

Suffix-based mapping from tree paths to logical axes, composed with a
per-(shape-kind, model-size) rules profile:

- **train**: batch over (pod, data); FSDP ("fsdp" -> data axes) shards the
  d_model-ish param dims so optimizer state scales with the full mesh
  (ZeRO-3 semantics via GSPMD: per-layer all-gather inside the scan);
  heads/mlp/vocab/experts over model (tensor/expert parallel).
- **decode**: batch over data when global_batch >= data axis; otherwise
  context-parallel KV (pages over data).  kv_heads shard over model when
  divisible, else the head_dim shards (GQA-TP fallback).  Params keep FSDP
  only for models too big for pure TP (>= ~60B).
- **prefill**: like decode but batch is usually shardable.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.config import MeshPlan, ModelConfig, ShapeConfig
from repro.distributed.sharding import AxisVal

# ---------------------------------------------------------------------------
# logical rules profiles
# ---------------------------------------------------------------------------

FSDP_PARAM_THRESHOLD = 60e9   # serving: fall back to FSDP above this
PURE_FSDP_THRESHOLD = 20e9    # training: below this, pure FSDP beats TP


EDP_EXPERT_BYTES = 1e9  # per-layer expert weights below this: expert-data-
#                         parallel (weights ride the FSDP all-gather; tokens
#                         never move) beats token-movement EP — measured in
#                         EXPERIMENTS.md §Perf (granite: 400s -> see log).


def _expert_layer_bytes(cfg: ModelConfig) -> float:
    if cfg.moe is None:
        return 0.0
    gated = 3 if cfg.activation in ("swiglu", "geglu") else 2
    return cfg.moe.n_experts * gated * cfg.d_model * cfg.d_ff * 2.0


def rules_for(
    cfg: ModelConfig, shape: ShapeConfig, plan: MeshPlan
) -> Dict[str, AxisVal]:
    data_axes: Tuple[str, ...] = plan.data_axes
    big = cfg.param_count() >= FSDP_PARAM_THRESHOLD
    expert_axis = (
        "model" if _expert_layer_bytes(cfg) >= EDP_EXPERT_BYTES else None
    )
    # Head-aligned TP only: sharding the fused qkv output dim when
    # n_heads % axis != 0 makes the [B,S,H,hd] reshape unsatisfiable and
    # GSPMD falls back to full replication copies per layer (the measured
    # attention all-gather storm, §Perf).  Indivisible head counts instead
    # replicate attention weights over model (FSDP still shards them over
    # data) and keep attention compute model-replicated.
    heads_ok = cfg.n_heads % plan.model_size == 0
    kv_ok = cfg.n_kv_heads % plan.model_size == 0
    rules: Dict[str, AxisVal] = {
        "heads": "model" if heads_ok else None,
        "kv_heads": "model" if kv_ok else None,
        "mlp": "model",
        "vocab": "model",
        "experts": expert_axis,
        # MoE token groups shard over the FULL mesh: the dispatch/combine
        # tensors and capacity buffers then stay rank-local (the G-global
        # [G,E,C,d] all-reduce across model was the baseline's 400s storm).
        "moe_group": ("pod", "data", "model") if plan.multi_pod else ("data", "model"),
        "embed": None,
        "seq": None,
        "layers": None,
        "head_dim": None,
        "kv_pages": None,
        "kv_seq": None,
    }
    if shape.kind == "train":
        all_axes = data_axes + ("model",)
        # rwkv's sequential time scan defeats loop-invariant hoisting of
        # FSDP weight gathers (XLA re-gathers per timestep: 50.7 s -> 688 s
        # measured, §Perf) — keep TP weights resident for token-recurrent
        # stacks.  rglru uses associative_scan (no inner while) and
        # benefits from pure FSDP (14.2 -> 3.1 s).
        has_time_scan = any(k == "rwkv" for k in cfg.layer_pattern)
        if cfg.param_count() < PURE_FSDP_THRESHOLD and not has_time_scan:
            # small models on a big mesh: TP activation all-reduces dwarf
            # the FSDP weight gathers — run pure FSDP over the full mesh
            # (batch over every axis, weights fully sharded, no TP).
            # Measured: llama3.2-3b train collective 3.0s -> see §Perf.
            rules["batch"] = all_axes
            rules["fsdp"] = all_axes
            rules["heads"] = rules["kv_heads"] = None
            rules["mlp"] = None
            rules["vocab"] = None
            rules["moe_group"] = all_axes
        else:
            rules["batch"] = data_axes
            rules["fsdp"] = data_axes
    elif shape.kind == "prefill":
        rules["batch"] = data_axes
        rules["fsdp"] = data_axes if big else None
        # full-mesh MoE groups help when tokens are mesh-wide (train); in
        # prefill the batch only spans the data axis and the model-axis
        # resharding leaks into the attention pair-scan carries (§Perf 1.5)
        rules["moe_group"] = data_axes
    else:  # decode
        batch_shardable = shape.global_batch >= plan.data_size
        rules["batch"] = data_axes if batch_shardable else None
        rules["fsdp"] = data_axes if big else None
        rules["kv_pages"] = None if batch_shardable else data_axes
        if cfg.n_kv_heads % plan.model_size != 0:
            # GQA-TP fallback when kv heads don't divide the model axis:
            # shard the head_dim.  (Sharding the KV pool by PAGES was
            # hypothesized to be cheaper — only selected pages would move —
            # but GSPMD cannot partition dynamic page gathers and
            # all-gathers the whole pool: 0.017s -> 0.9s collective,
            # REFUTED in §Perf 3.2.  The serving engine now sidesteps GSPMD
            # entirely with shard_map'd kernels —
            # :mod:`repro.distributed.kernel_partition` — which keep the KV
            # pool kv-head-sharded without any pool gather.)
            rules["kv_heads"] = None
            rules["head_dim"] = "model"
    return rules


# ---------------------------------------------------------------------------
# param path -> logical axes
# ---------------------------------------------------------------------------


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


_PARAM_SUFFIXES = [
    # (suffix match, logical axes for the UNSTACKED param)
    ("attn/wq/w", ("fsdp", "heads")),
    ("attn/wk/w", ("fsdp", "kv_heads")),
    ("attn/wv/w", ("fsdp", "kv_heads")),
    ("attn/wo/w", ("heads", "fsdp")),
    ("attn/wq/b", ("heads",)),
    ("attn/wk/b", ("kv_heads",)),
    ("attn/wv/b", ("kv_heads",)),
    ("attn/wo/b", (None,)),
    ("ffn/up/w", ("fsdp", "mlp")),
    ("ffn/gate/w", ("fsdp", "mlp")),
    ("ffn/down/w", ("mlp", "fsdp")),
    ("ffn/up/b", ("mlp",)),
    ("ffn/gate/b", ("mlp",)),
    ("ffn/down/b", (None,)),
    ("ffn/router/w", ("fsdp", None)),
    ("ffn/router/b", (None,)),
    ("ffn/up", ("experts", "fsdp", "mlp")),     # MoE [E, d, ff]
    ("ffn/gate", ("experts", "fsdp", "mlp")),
    ("ffn/down", ("experts", "mlp", "fsdp")),
    ("rec/in_gelu/w", ("fsdp", "mlp")),
    ("rec/in_rec/w", ("fsdp", "mlp")),
    ("rec/conv_w", (None, "mlp")),
    ("rec/conv_b", ("mlp",)),
    ("rec/w_a/w", (None, "mlp")),
    ("rec/w_x/w", (None, "mlp")),
    ("rec/lam", ("mlp",)),
    ("rec/out/w", ("mlp", "fsdp")),
    ("tmix/wr/w", ("fsdp", "mlp")),
    ("tmix/wk/w", ("fsdp", "mlp")),
    ("tmix/wv/w", ("fsdp", "mlp")),
    ("tmix/wg/w", ("fsdp", "mlp")),
    ("tmix/ww/w", ("fsdp", "mlp")),
    ("tmix/wo/w", ("mlp", "fsdp")),
    ("tmix/mu", (None, None)),
    ("tmix/u", (None,)),
    ("tmix/w_bias", (None,)),
    ("tmix/ln_x/scale", (None,)),
    ("embed", ("vocab", "fsdp")),
    ("lm_head", ("fsdp", "vocab")),
    ("norm1/scale", (None,)),
    ("norm2/scale", (None,)),
    ("final_norm/scale", (None,)),
]


def logical_axes_for_param(path_str: str, ndim: int) -> Tuple[Optional[str], ...]:
    for suffix, axes in _PARAM_SUFFIXES:
        if path_str.endswith(suffix):
            if len(axes) == ndim:
                return axes
            if len(axes) == ndim - 1:
                return (None,) + tuple(axes)  # stacked cycle dim
    return (None,) * ndim  # replicate by default


_CACHE_RULES = [
    ("seq_len", ("batch",)),
    # dense KV [nc, B, n_kv, S, hd] and paged [nc, B, n_kv, nP, page, hd]
    # (the sparse-active decode cache's native layout)
    ("/k", (None, "batch", "kv_heads", "kv_pages", "head_dim")),
    ("/v", (None, "batch", "kv_heads", "kv_pages", "head_dim")),
    ("/k", (None, "batch", "kv_heads", "kv_pages", None, "head_dim")),
    ("/v", (None, "batch", "kv_heads", "kv_pages", None, "head_dim")),
    ("/codes", (None, "batch", "kv_pages", None)),
    ("/scale", (None, "batch", None, None)),
    ("/zero", (None, "batch", None, None)),
    # prefill scoring segment (per-ROW affine): rows stay whole per shard
    ("/pcodes", (None, "batch", None, None)),
    ("/pscale", (None, "batch", None, None)),
    ("/pzero", (None, "batch", None, None)),
    ("/h", (None, "batch", "mlp")),
    ("/conv", (None, "batch", None, "mlp")),
    ("/S", (None, "batch", "heads", None, None)),
    ("/xprev", (None, "batch", None)),
]


#: cache entries planted by the engine/obs layers (plan layout mirrors,
#: telemetry counters, selected/predicted page masks): replicated small
#: tensors by design, exempt from the suffix rule table.
_PLANTED_CACHE_PREFIXES = (
    "_layouts",
    "_offsets",
    "_telemetry",
    "_decode_pages",
    "_prefill_blocks",
    "_sel_pages",
    "_pre_pages",
)


def _match_cache_rule(
    path_str: str, ndim: int
) -> Optional[Tuple[Optional[str], ...]]:
    """The rule-table axes for a cache leaf, or None when nothing matches."""
    if path_str.startswith(_PLANTED_CACHE_PREFIXES):
        return (None,) * ndim
    # rest-layer entries have no leading cycle axis: match against the rule
    # minus its leading cycle dim so a paged rest KV entry (ndim 5) never
    # collides with the cycle-stacked dense rule of the same length.
    rest = path_str.startswith("rest")
    for suffix, axes in _CACHE_RULES:
        if path_str.endswith(suffix) or (suffix == "seq_len" and path_str == "seq_len"):
            if rest:
                if len(axes) == ndim + 1 and axes[0] is None:
                    return tuple(axes[1:])
            elif len(axes) == ndim:
                return axes
    return None


def logical_axes_for_cache(path_str: str, ndim: int) -> Tuple[Optional[str], ...]:
    axes = _match_cache_rule(path_str, ndim)
    return axes if axes is not None else (None,) * ndim


def cache_leaf_covered(path_str: str, ndim: int) -> bool:
    """True when a cache leaf is EXPLICITLY covered by the sharding rule
    table (or a sanctioned engine-planted entry) rather than falling through
    to the silent replicate-by-default branch.  The contracts verifier uses
    this to fail loudly on uncovered leaves — silent replication of a new
    KV-cache entry is a memory-scaling bug, not a default."""
    return _match_cache_rule(path_str, ndim) is not None


# ---------------------------------------------------------------------------
# spec resolution (shape-aware)
# ---------------------------------------------------------------------------


def _axis_size(mesh: Mesh, axis) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if isinstance(axis, str):
        return sizes.get(axis, 1)
    return int(np.prod([sizes.get(a, 1) for a in axis]))


def spec_from_logical(
    mesh: Mesh,
    rules: Dict[str, AxisVal],
    logical: Tuple[Optional[str], ...],
    shape: Tuple[int, ...],
) -> PartitionSpec:
    out = []
    used = set()
    for dim, name in zip(shape, logical):
        val = rules.get(name) if name else None
        if val is None:
            out.append(None)
            continue
        axes = (val,) if isinstance(val, str) else tuple(val)
        axes = [a for a in axes if a in mesh.axis_names and a not in used]
        keep = []
        size = 1
        for a in axes:
            nxt = size * _axis_size(mesh, a)
            # jit argument shardings require exact divisibility
            if dim % nxt == 0:
                keep.append(a)
                size = nxt
        used.update(keep)
        if not keep:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(tuple(keep))
    return PartitionSpec(*out)


def constrain_tree_like_params(tree):
    """Constrain every leaf of a param-shaped tree (e.g. gradients) to its
    param sharding under the ACTIVE sharding context.  Applied to grads so
    GSPMD reduce-scatters per layer instead of all-reducing into a full
    replicated (HBM-blowing) grad stack.  No-op outside a context."""
    from repro.distributed.sharding import current_context

    ctx = current_context()
    if ctx is None:
        return tree
    mesh, rules = ctx.mesh, ctx.rules
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        if leaf is None or not hasattr(leaf, "shape"):
            out.append(leaf)
            continue
        ps = _path_str(path)
        logical = logical_axes_for_param(ps, len(leaf.shape))
        spec = spec_from_logical(mesh, rules, logical, tuple(leaf.shape))
        out.append(
            jax.lax.with_sharding_constraint(leaf, NamedSharding(mesh, spec))
        )
    return jax.tree_util.tree_unflatten(treedef, out)


def shard_param_cotangents(params_tree):
    """Identity on the forward pass; on the backward pass (a) casts param
    cotangents to the param dtype (bf16 grad reduction — halves the DP-
    reduction bytes and the stacked-grad HBM temp; AdamW re-upcasts against
    the f32 master) and (b) constrains them to the param shardings.
    Applied INSIDE the layer scan body — §Perf iteration 2.6."""
    dtypes = jax.tree.map(lambda x: x.dtype, params_tree)

    @jax.custom_vjp
    def ident(tree):
        return tree

    def fwd(tree):
        return tree, None

    def bwd(_, g):
        g = jax.tree.map(
            lambda gi, dt: gi.astype(dt) if gi is not None else None,
            g, dtypes,
        )
        # barrier: stops XLA from fusing the optimizer's f32 upcast into
        # the grad producer, which would let the partitioner place the DP
        # all-reduce on the f32 side (2x traffic — measured, §Perf 2.6).
        g = jax.lax.optimization_barrier(g)
        return (constrain_tree_like_params(g),)

    ident.defvjp(fwd, bwd)
    return ident(params_tree)


def cast_cotangent(x, dtype):
    """Identity forward; cast the cotangent to ``dtype`` on the way back.
    Applied to the layer-scan carry so the entire backward chain (and thus
    every dW einsum and its DP all-reduce) runs in the compute dtype
    instead of the f32 the loss head upcasts to."""

    @jax.custom_vjp
    def ident(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, g):
        return (g.astype(dtype),)

    ident.defvjp(fwd, bwd)
    return ident(x)


def tree_shardings(
    tree_shapes,
    mesh: Mesh,
    rules: Dict[str, AxisVal],
    kind: str = "param",
):
    """Map a pytree of ShapeDtypeStructs -> NamedShardings."""
    mapper = logical_axes_for_param if kind == "param" else logical_axes_for_cache
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree_shapes)
    out = []
    for path, leaf in flat:
        ps = _path_str(path)
        logical = mapper(ps, len(leaf.shape))
        spec = spec_from_logical(mesh, rules, logical, tuple(leaf.shape))
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)
