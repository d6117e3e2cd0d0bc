"""Configuration files -> the program's ``ModelConfig``, and seeded weights.

A configuration file (``configs/<name>.json``) holds the published sizes
under their Hugging Face keys, the depth it is cut to, and the
``SparseConfig`` the cells run with.  Where it names the program's
architecture registry entry (``registry``), the cell runs that entry with
every field the file states replaced (``FIELDS``), and a file whose widths,
layer kinds or experts disagree with the entry stops the run.  Fields the
file does not state come from the entry, so a mechanism that the program
adds to ``ModelConfig`` and to its registry entry reaches the cell with no
edit here.

Weights are drawn here, from the seed, in the program's parameter layout
(checked against ``jax.eval_shape`` of the program's own ``init``): the
plain reference draws the same weights through the same function, so it
never takes an array the program made.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

#: published key -> ModelConfig field, copied from the file.
FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "qkv_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
    "sliding_window": "local_window",
    "num_hidden_layers": "n_layers",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "torch_dtype": "dtype",
}
#: the depth is the cut; the registry entries carry program defaults for
#: the norm's epsilon, RoPE's theta and the dtype where the files state the
#: published values, so those four replace the entry's without a check.
UNCHECKED = ("n_layers", "norm_eps", "rope_theta", "dtype")
#: published ``layer_types`` -> the program's layer kinds
LAYER_KINDS = {"full_attention": "attn", "sliding_attention": "local_attn"}


def load_config_file(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def period(kinds) -> tuple:
    """The shortest prefix that, repeated, gives ``kinds``."""
    kinds = tuple(kinds)
    return next(kinds[:p] for p in range(1, len(kinds) + 1)
                if all(k == kinds[i % p] for i, k in enumerate(kinds)))


def mapped_keys(spec: dict) -> dict:
    """Published key -> ModelConfig field, for the keys the file states.
    With routed experts (``num_experts``), every layer's FFN is routed: its
    width is ``moe_intermediate_size``, and ``intermediate_size`` (a dense
    width the stack does not use) maps to nothing.  A ``sliding_window``
    that ``use_sliding_window: false`` switches off (Qwen's published
    files) maps to nothing either."""
    keys = {k: f for k, f in FIELDS.items() if spec.get(k) is not None}
    if spec.get("use_sliding_window") is False:
        keys.pop("sliding_window", None)
    keys["hidden_act"] = "activation"
    if "num_experts" in spec:
        keys.pop("intermediate_size", None)
        keys["moe_intermediate_size"] = "d_ff"
    return keys


def published_fields(spec: dict) -> dict:
    """ModelConfig fields the file states (``mapped_keys``), with
    ``layer_pattern`` the shortest period of the published ``layer_types``
    and ``moe`` the pair (``num_experts``, ``num_experts_per_tok``)."""
    name = spec["name"]
    if spec["hidden_act"] != "silu":
        raise ValueError(f"{name}: hidden_act {spec['hidden_act']!r}: expected "
                         "a SwiGLU (silu) MLP")
    out = {f: spec[k] for k, f in mapped_keys(spec).items()}
    out["activation"] = "swiglu"
    if "layer_types" in spec:
        unknown = set(spec["layer_types"]) - set(LAYER_KINDS)
        if unknown:
            raise ValueError(f"{name}: layer_types {sorted(unknown)} have no "
                             "kind in the program")
        out["layer_pattern"] = period(LAYER_KINDS[t] for t in spec["layer_types"])
    if "num_experts" in spec:
        if set(spec.get("mlp_layer_types", ["sparse"])) != {"sparse"}:
            raise ValueError(f"{name}: dense and routed MLP layers mixed: the "
                             "program routes every layer or none")
        out["moe"] = (spec["num_experts"], spec["num_experts_per_tok"])
    return out


def sparse_layers(cfg) -> tuple:
    """Indices of the layers that run AB-Sparse global attention (kind
    ``attn``), in order.  The i-th of them reads row i of the committed
    block-size assignment (``SparseConfig.layer_block_sizes(i, ...)``)."""
    return tuple(i for i, kind in enumerate(cfg.layer_kinds) if kind == "attn")


def architecture(spec: dict, registry=None):
    """-> ``ModelConfig`` of the file's architecture at its depth, with the
    default ``SparseConfig``.  ``registry``: the entry to start from (the
    one the file names, by default); with neither, the file alone."""
    from repro.config import MoEConfig, ModelConfig
    from repro.configs import get_config

    name = spec["name"]
    fields = published_fields(spec)
    moe = fields.pop("moe", None)
    if registry is None and spec.get("registry"):
        registry = get_config(spec["registry"])
    if registry is None:
        cfg = ModelConfig(name=name, family="dense", **fields)
    else:
        cfg = dataclasses.replace(registry, name=name, **fields)
        for key, field in mapped_keys(spec).items():
            if field in UNCHECKED:
                continue
            attr = "resolved_head_dim" if field == "head_dim" else field
            if getattr(cfg, attr) != getattr(registry, attr):
                raise ValueError(
                    f"{name}: {key}={spec[key]} in the configuration file but "
                    f"the program's registry has {attr}={getattr(registry, attr)}")
        if moe is not None and (registry.moe is None or moe != (
                registry.moe.n_experts, registry.moe.experts_per_token)):
            raise ValueError(f"{name}: num_experts, num_experts_per_tok = {moe} "
                             f"in the configuration file but the program's "
                             f"registry has moe={registry.moe}")
    if moe is not None:
        cfg = dataclasses.replace(cfg, family="moe",
                                  moe=cfg.moe or MoEConfig(*moe))
    if cfg.n_layers % len(cfg.layer_pattern):
        raise ValueError(f"{name}: {cfg.n_layers} layers split the period "
                         f"{cfg.layer_pattern}: cut the depth to whole periods")
    if "layer_types" in spec:
        want = tuple(LAYER_KINDS[t] for t in spec["layer_types"][:cfg.n_layers])
        ran = cfg if registry is None else dataclasses.replace(
            registry, n_layers=cfg.n_layers)
        have = ran.layer_kinds
        if want != have:
            raise ValueError(f"{name}: layer_types cut to {cfg.n_layers} layers "
                             f"are {want}, the program runs {have}")
    return cfg


def model_config(spec: dict, registry=None):
    """-> ``ModelConfig`` as the cell runs it (depth cut, sparse settings,
    committed block-size assignment: one row for each layer of
    ``sparse_layers``), checked against the registry entry (see
    ``architecture``)."""
    from repro.config import SparseConfig

    cfg = architecture(spec, registry)
    sp = dict(spec["sparse"])
    sp["candidate_block_sizes"] = tuple(sp["candidate_block_sizes"])
    if spec["block_sizes"] is None:
        raise ValueError(f"{spec['name']}: no block-size assignment committed")
    sp["block_sizes"] = tuple(tuple(int(b) for b in row)
                              for row in spec["block_sizes"])
    n_sparse = len(sparse_layers(cfg))
    if len(sp["block_sizes"]) != n_sparse:
        raise ValueError(f"{spec['name']}: {len(sp['block_sizes'])} block-size "
                         f"rows for {n_sparse} global-attention layers")
    return dataclasses.replace(cfg, sparse=SparseConfig(**sp))


def key_from_seed(seed: int):
    """A JAX key from any whole seed, 64-bit ones included."""
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])), int(words[1]))


def _draw(key, path: str, shape, dtype):
    """Init scale of each leaf kind: projections std d_in**-0.5 (unit
    variance out), embeddings unit normal, biases and norm gains perturbed
    so that their paths matter."""
    import jax
    import jax.numpy as jnp

    if path.endswith("scale"):              # RMSNorm gain
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if path.endswith("/b"):                 # projection bias
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if path == "embed":
        return jax.random.normal(key, shape, dtype)
    fan_in = shape[-2]
    return (jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5).astype(dtype)


def init_params(cfg, seed: int):
    """Seeded weights in the program's layout, built on the device in one
    jitted call, in the dtype they are served in."""
    import jax

    from repro.models import Transformer

    shapes = jax.eval_shape(Transformer(cfg).init, jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
             for p, _ in leaves]

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(tree, [
            _draw(k, path, leaf.shape, leaf.dtype)
            for k, path, (_, leaf) in zip(keys, paths, leaves)
        ])

    return jax.jit(build)(key_from_seed(seed))
