"""Kernel 2 — batched per-head Top-K via exact k-th-value radix select.

The paper's CUDA kernel batches variable-length per-head Top-K_h selection
(K_h = T / B_h) using the prefix-sum offsets from Kernel 1.  GPU selection
kernels lean on shared-memory atomics / warp ballots; neither exists on TPU.
The TPU-native equivalent: compute the exact k-th largest score per head by
**binary search over the sortable-integer encoding of f32** — 32 fixed
iterations of a fully-vectorized compare+count over the head's score row.
No data-dependent control flow, no sort, O(32·N) vector work, and every
head is one grid cell of a single batched launch (the padded 2-D score view
makes row lengths uniform; pads sit at -inf and never win).

The returned threshold (plus tie-count) deterministically defines the
selected set: ``score > thr`` picks ``count_gt`` blocks, and the remaining
``K - count_gt`` slots are filled from ties (``score == thr``) in index
order.  :func:`repro.kernels.ops.topk_blocks` performs that expansion.

Sortable encoding: for f32 bits x (int32), ``u = x XOR (asr(x,31) | 0x8000_0000)``
is order-isomorphic to the float ordering (sign bit flipped for positives,
all bits flipped for negatives).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _to_sortable(x_f32: jax.Array) -> jax.Array:
    x = jax.lax.bitcast_convert_type(x_f32, jnp.int32)
    mask = jax.lax.shift_right_arithmetic(x, 31) | jnp.int32(-2147483648)
    return jax.lax.bitcast_convert_type(x ^ mask, jnp.uint32)


def _from_sortable(u: jax.Array) -> jax.Array:
    ui = jax.lax.bitcast_convert_type(u, jnp.int32)
    # positive floats had the sign bit set; negatives were fully flipped.
    is_pos = ui < 0  # sign bit set in sortable space
    mask = jnp.where(is_pos, jnp.int32(-2147483648), jnp.int32(-1))
    return jax.lax.bitcast_convert_type(ui ^ mask, jnp.float32)


_SIGN = -(2**31)


def sortable_key(x_f32: jax.Array) -> jax.Array:
    """Signed int32 key whose order is the float order: the
    :func:`_to_sortable` encoding with its sign bit flipped, so code inside
    a Mosaic kernel (which has no unsigned vector compares) can rank
    scores with signed ops only."""
    x = jax.lax.bitcast_convert_type(x_f32, jnp.int32)
    return x ^ (jax.lax.shift_right_arithmetic(x, 31) & jnp.int32(0x7FFFFFFF))


def topk_row_mask(s: jax.Array, k) -> jax.Array:
    """In-kernel exact top-``k`` of one score row ``s [1, N]`` f32 ->
    bool ``[1, N]`` with exactly ``min(k, N)`` entries set.

    The k-th largest value comes from the 32-step bit search over the
    sortable encoding (as in :func:`_kth_kernel`); ties at that value are
    taken in index order, which reproduces ``lax.top_k``'s selected SET.
    The first ``k - count_gt`` ties are found by bisecting on the index
    (no prefix sum: Mosaic has no ``cumsum``)."""
    n = s.shape[-1]
    key = sortable_key(s)
    sign = jnp.int32(_SIGN)

    def bit_step(i, t):                    # t: the threshold's unsigned bits
        cand = t | jax.lax.shift_left(jnp.int32(1), 31 - i)
        cnt = jnp.sum((key >= (cand ^ sign)).astype(jnp.int32))
        return jnp.where(cnt >= k, cand, t)

    thr = jax.lax.fori_loop(0, 32, bit_step, jnp.int32(0)) ^ sign
    gt = key > thr
    tie = key == thr
    need = k - jnp.sum(gt.astype(jnp.int32))
    idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)

    def bisect(_, lohi):                   # smallest c: #ties at idx <= c >= need
        lo, hi = lohi
        mid = (lo + hi) // 2
        ok = jnp.sum((tie & (idx <= mid)).astype(jnp.int32)) >= need
        return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)

    cut, _ = jax.lax.fori_loop(
        0, int(n).bit_length(), bisect, (jnp.int32(0), jnp.int32(n - 1))
    )
    return gt | (tie & (idx <= cut) & (need > 0))


def next_selected(sel: jax.Array, after) -> jax.Array:
    """Smallest index of ``sel [1, N]`` greater than ``after`` (a scalar),
    or ``N`` when none is left — walks a selection in index order without
    compacting it."""
    idx = jax.lax.broadcasted_iota(jnp.int32, sel.shape, 1)
    return jnp.min(jnp.where(sel & (idx > after), idx, sel.shape[-1]))


def _kth_kernel(k_ref, scores_ref, thr_ref, cnt_ref):
    h = pl.program_id(1)
    k = k_ref[h]
    s = scores_ref[0, 0]                       # [M] f32
    u = _to_sortable(s)                        # [M] uint32

    def body(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - jnp.uint32(i)))
        cnt = jnp.sum((u >= cand).astype(jnp.int32))
        return jnp.where(cnt >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, body, jnp.uint32(0))
    thr_ref[0, 0] = _from_sortable(t)
    cnt_ref[0, 0] = jnp.sum((u > t).astype(jnp.int32))


def topk_threshold(
    scores: jax.Array,          # [B, H, M] padded 2-D scores (-inf pads)
    k_per_head,                 # [H] K_h per head (array or tuple)
    interpret: bool = False,
):
    """-> (threshold [B, H] f32 — exact K_h-th largest, count_gt [B, H] i32
    — strictly-greater count, for deterministic tie handling)."""
    if isinstance(k_per_head, (tuple, list)):
        k_per_head = jnp.asarray(np.asarray(k_per_head), jnp.int32)
    return _topk_threshold(scores, k_per_head, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _topk_threshold(scores, k_per_head, interpret: bool = False):
    B, H, M = scores.shape
    k_arr = jnp.asarray(k_per_head, dtype=jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H),
        in_specs=[pl.BlockSpec((1, 1, M), lambda b, h, k: (b, h, 0))],
        out_specs=[
            pl.BlockSpec((1, 1), lambda b, h, k: (b, h)),
            pl.BlockSpec((1, 1), lambda b, h, k: (b, h)),
        ],
    )
    thr, cnt = pl.pallas_call(
        _kth_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.int32),
        ],
        interpret=interpret,
    )(k_arr, scores.astype(jnp.float32))
    return thr, cnt
