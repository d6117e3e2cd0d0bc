"""Kernel 1 — fused query-centroid estimation (paper §3.4).

TPU realization of the paper's prefix-sum-indexed variable-length batched
estimation: all heads' rank-key segments live in ONE flattened
``[total_rows, Dp]`` array (per sequence), padded per head to the 128-row
tile.  Because block-size assignments are frozen at calibration time, the
``tile -> (owning head)`` map is a compile-time constant delivered via
scalar prefetch; its value drives the BlockSpec index maps for the per-head
scale/zero vectors and the GQA query group.  One ``pallas_call`` covers all
ragged segments — no padding beyond tile alignment, no per-head launches.

INT4 dequantization is fused: packed nibbles (split-half layout: byte ``j``
holds channels ``j`` and ``j + Dp/2``) are unpacked in VREGs with shifts +
a lane-wise concat (no cross-lane shuffle), multiplied by the per-(head,
channel) scale and offset by the zero point, then hit the MXU against the
query group.  HBM traffic for the estimation stage is Dp/2 bytes per
centroid — 4x less than BF16 (the paper's Fig. 10/11 advantage).

GQA aggregation (max over the group's query heads) happens in-kernel, so
the output is one score per centroid row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def dequant_rows(codes, scale, zero, bits: int, symmetric: bool):
    """In-register dequant of packed store rows ``[R, Cw]`` -> f32 rank keys
    ``[R, Dp]`` — the ONE definition of the store's wire format on the
    kernel side (INT4 split-half: byte ``j`` holds channels ``j`` and
    ``j + Dp/2``), shared by the staged estimation kernel and the fused
    decode kernel so their numerics cannot drift apart."""
    if bits == 0:
        return codes.astype(jnp.float32)
    # widen to int32 first: Mosaic has no uint8 -> f32 cast
    c = codes.astype(jnp.int32)
    if bits == 4:
        lo = (c & 0xF).astype(jnp.float32)
        hi = ((c >> 4) & 0xF).astype(jnp.float32)
        q = jnp.concatenate([lo, hi], axis=-1)             # [R, Dp]
    else:
        q = c.astype(jnp.float32)
    if symmetric:
        half = 2.0 ** (bits - 1) - 1.0
        return (q - half) * scale
    return q * scale + zero


def group_scores(rq, rk):
    """Block scores of one GQA group: ``rq [g, Dp]`` x ``rk [R, Dp]`` ->
    ``[1, R]`` (max over the group).  Lane-major so the row feeds the
    in-kernel selection directly; shared by every estimation site so the
    staged, fused and prefill paths score identically."""
    s = jax.lax.dot_general(
        rq, rk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                      # [g, R]
    return jnp.max(s, axis=0, keepdims=True)


def _score_kernel_quantized(
    tile_head_ref,            # scalar prefetch [n_tiles]
    codes_ref,                # [1, R, Cw] uint8
    scale_ref,                # [1, 1, 1, Dp] f32
    zero_ref,                 # [1, 1, 1, Dp] f32
    rq_ref,                   # [1, 1, g, Dp] f32
    out_ref,                  # [1, 1, R]
    *, symmetric: bool, bits: int,
):
    rk = dequant_rows(
        codes_ref[0], scale_ref[0, 0], zero_ref[0, 0], bits, symmetric
    )                                                      # [R, Dp]
    out_ref[0] = group_scores(rq_ref[0, 0], rk)


def _score_kernel_f32(tile_head_ref, rk_ref, rq_ref, out_ref):
    rk = rk_ref[0].astype(jnp.float32)                     # [R, Dp]
    out_ref[0] = group_scores(rq_ref[0, 0].astype(jnp.float32), rk)


@functools.partial(
    jax.jit,
    static_argnames=("tile_rows", "symmetric", "bits", "interpret"),
)
def centroid_scores_quantized(
    rq: jax.Array,            # [B, n_kv * g, Dp] rank queries (f32)
    codes: jax.Array,         # [B, total_rows, Dp//(8//bits)] packed uint8
    scale: jax.Array,         # [B, n_kv, Dp] f32 per-(head, channel)
    zero: jax.Array,          # [B, n_kv, Dp] f32
    tile_head: jax.Array,     # [n_tiles] int32 tile -> head map (prefetched)
    tile_rows: int,
    symmetric: bool,
    bits: int,
    interpret: bool = False,
) -> jax.Array:
    """-> flat scores [B, total_rows] (max over the GQA query group)."""
    B, n_q, Dp = rq.shape
    n_kv = scale.shape[1]
    g = n_q // n_kv
    total_rows = codes.shape[1]
    n_tiles = total_rows // tile_rows
    tile_head_arr = jnp.asarray(tile_head, dtype=jnp.int32)
    assert tile_head_arr.shape == (n_tiles,), (tile_head_arr.shape, n_tiles)
    rq3 = rq.reshape(B, n_kv, g, Dp)

    kernel = functools.partial(
        _score_kernel_quantized, symmetric=symmetric, bits=bits
    )
    cw = codes.shape[-1]
    # per-head params get a unit axis so their block's last two dims equal
    # the array's (the TPU (8, 128) block rule); the score row likewise.
    param_spec = pl.BlockSpec((1, 1, 1, Dp), lambda b, t, th: (b, th[t], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_tiles),
        in_specs=[
            pl.BlockSpec((1, tile_rows, cw), lambda b, t, th: (b, t, 0)),
            param_spec,
            param_spec,
            pl.BlockSpec((1, 1, g, Dp), lambda b, t, th: (b, th[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_rows), lambda b, t, th: (b, 0, t)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, total_rows), jnp.float32),
        interpret=interpret,
    )(
        tile_head_arr, codes,
        scale.reshape(B, n_kv, 1, Dp), zero.reshape(B, n_kv, 1, Dp), rq3,
    )
    return out.reshape(B, total_rows)


@functools.partial(
    jax.jit, static_argnames=("n_kv", "tile_rows", "interpret")
)
def centroid_scores_f32(
    rq: jax.Array,            # [B, n_kv * g, Dp]
    rank_keys: jax.Array,     # [B, total_rows, Dp] f32 (unquantized store)
    n_kv: int,
    tile_head: jax.Array,     # [n_tiles] int32
    tile_rows: int,
    interpret: bool = False,
) -> jax.Array:
    B, n_q, Dp = rq.shape
    g = n_q // n_kv
    total_rows = rank_keys.shape[1]
    n_tiles = total_rows // tile_rows
    tile_head_arr = jnp.asarray(tile_head, dtype=jnp.int32)
    rq3 = rq.reshape(B, n_kv, g, Dp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_tiles),
        in_specs=[
            pl.BlockSpec((1, tile_rows, Dp), lambda b, t, th: (b, t, 0)),
            pl.BlockSpec((1, 1, g, Dp), lambda b, t, th: (b, th[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_rows), lambda b, t, th: (b, 0, t)),
    )
    out = pl.pallas_call(
        _score_kernel_f32,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, total_rows), jnp.float32),
        interpret=interpret,
    )(tile_head_arr, rank_keys, rq3)
    return out.reshape(B, total_rows)
