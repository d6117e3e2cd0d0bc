"""Pallas TPU kernels for the AB-Sparse hot spots (paper §3.4).

- flash_attention   dense causal prefill attention
- centroid_score    Kernel 1: fused INT4-dequant ragged estimation
- topk_threshold    Kernel 2: exact k-th-value radix select
- paged_attention   Kernel 3: page-table-driven sparse decode attention
- fused_decode      Kernels 1+2+3 in ONE ragged-grid launch (decode path)
- block_centroid    fused rank-key pooling (cache build)

``ops`` holds the jit'd public wrappers; ``ref`` the pure-jnp oracles.
All kernels validate in interpret mode on CPU; the serving path's kernels
(fused decode, sparse prefill, staged estimation / paged attention) also
compile for TPU v5e (``tests/test_tpu_compile.py``).
"""
