"""Configuration files -> the program's ``ModelConfig``, and seeded weights.

A configuration file (``configs/<name>.json``) holds the published sizes
under their Hugging Face keys, the depth it is cut to, and the
``SparseConfig`` the cells run with.  Where it names the program's
architecture registry entry, every width is checked against it, so a file
and a registry entry that disagree stop the run.

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

#: published key -> ModelConfig field, compared before any run.
WIDTH_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "resolved_head_dim",
    "vocab_size": "vocab_size",
    "qkv_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
}


def load_config_file(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def model_config(spec: dict):
    """-> ``ModelConfig`` as the cell runs it (depth cut, sparse settings,
    committed block-size assignment), checked against the program's
    registry entry where the file names one."""
    from repro.config import ModelConfig, SparseConfig
    from repro.configs import get_config

    if spec["hidden_act"] != "silu":
        raise ValueError(f"{spec['name']}: expected a SwiGLU (silu) MLP")
    base = ModelConfig(
        name=spec["name"], family="dense",
        n_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        d_ff=spec["intermediate_size"], vocab_size=spec["vocab_size"],
        head_dim=spec["head_dim"], activation="swiglu",
        qkv_bias=spec["qkv_bias"], tie_embeddings=spec["tie_word_embeddings"],
    )
    if spec.get("registry"):
        reg = get_config(spec["registry"])
        for key, field in WIDTH_KEYS.items():
            if getattr(reg, field) != getattr(base, field):
                raise ValueError(
                    f"{spec['name']}: {key}={spec[key]} in the configuration "
                    f"file but the program's registry has "
                    f"{field}={getattr(reg, field)}")
        if (reg.activation, reg.layer_pattern, reg.moe) != (
                "swiglu", ("attn",), None):
            raise ValueError(f"{spec['name']}: registry entry is not a dense "
                             "SwiGLU attention stack")
    sp = dict(spec["sparse"])
    sp["candidate_block_sizes"] = tuple(sp["candidate_block_sizes"])
    if spec["block_sizes"] is None:
        raise ValueError(f"{spec['name']}: no block-size assignment committed")
    sp["block_sizes"] = tuple(tuple(int(b) for b in row)
                              for row in spec["block_sizes"])
    if len(sp["block_sizes"]) != spec["num_hidden_layers"]:
        raise ValueError(f"{spec['name']}: block sizes do not match the depth")
    return dataclasses.replace(
        base,
        n_layers=spec["num_hidden_layers"],
        norm_eps=spec["rms_norm_eps"],
        rope_theta=spec["rope_theta"],
        dtype=spec["torch_dtype"],
        sparse=SparseConfig(**sp),
    )


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
