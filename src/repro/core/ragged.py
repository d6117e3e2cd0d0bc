"""Static ragged layout for heterogeneous per-head block sizes.

TPU adaptation of the paper's Kernel-1 prefix-sum indexing (§3.4): because
block-size assignments are frozen at calibration time, every per-head
centroid count, prefix offset and tile->head map is a *compile-time
constant*.  This module materializes those constants once per
(layer, context_len) as plain Python tuples / numpy arrays, which:

- drive the ``BlockSpec.index_map`` of the Pallas estimation kernel via
  scalar prefetch (no dynamic indexing, zero padding waste beyond the
  128-row tile boundary),
- define the padded 2-D ``[n_heads, max_blocks]`` score view consumed by the
  batched Top-K stage,
- define the static slot/within maps that expand selected blocks into the
  uniform per-head page table (hierarchical divisibility, paper Kernel 3).

Key invariant (property-tested): the number of *selected pages* per head is
``K_h * B_h / page_size == T / page_size`` — identical for every head when
the token budget T is a multiple of every candidate block size.  Raggedness
is confined to the estimation stage; the attention stage is uniform.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import cached_property
from typing import Tuple

import numpy as np


@dataclass(frozen=True)
class RaggedLayout:
    """Frozen per-(layer, context) layout. Hashable => usable as a jit static."""

    block_sizes: Tuple[int, ...]   # B_h per kv head
    context_len: int
    page_size: int
    token_budget: int
    tile_rows: int = 128           # centroid rows per kernel tile

    def __post_init__(self):
        for b in self.block_sizes:
            assert b % self.page_size == 0, (b, self.page_size)
            assert self.token_budget % b == 0, (
                f"token budget {self.token_budget} must be a multiple of every "
                f"assigned block size (got B={b}) so the selected-page count "
                "is head-uniform"
            )
            assert self.context_len % b == 0, (self.context_len, b)

    # -- per-head static quantities -----------------------------------------

    @property
    def n_heads(self) -> int:
        return len(self.block_sizes)

    @cached_property
    def n_blocks(self) -> Tuple[int, ...]:
        return tuple(self.context_len // b for b in self.block_sizes)

    @cached_property
    def pages_per_block(self) -> Tuple[int, ...]:
        return tuple(b // self.page_size for b in self.block_sizes)

    @cached_property
    def top_k(self) -> Tuple[int, ...]:
        """K_h = T / B_h (exact division enforced above)."""
        return tuple(
            min(self.token_budget // b, n)
            for b, n in zip(self.block_sizes, self.n_blocks)
        )

    @property
    def n_pages(self) -> int:
        return self.context_len // self.page_size

    @property
    def selected_pages(self) -> int:
        """Uniform per-head selected page count (= token budget in pages)."""
        sel = {
            k * s for k, s in zip(self.top_k, self.pages_per_block)
        }
        assert len(sel) == 1, f"selected-page count not uniform: {sel}"
        return sel.pop()

    # -- flattened ragged layout (estimation stage) -------------------------

    @cached_property
    def padded_n_blocks(self) -> Tuple[int, ...]:
        r = self.tile_rows
        return tuple(((n + r - 1) // r) * r for n in self.n_blocks)

    @cached_property
    def offsets(self) -> Tuple[int, ...]:
        """Prefix-sum offsets into the flattened padded centroid array
        (the paper's offset array, here compile-time)."""
        off = [0]
        for p in self.padded_n_blocks:
            off.append(off[-1] + p)
        return tuple(off)

    @property
    def total_rows(self) -> int:
        return self.offsets[-1]

    @property
    def n_tiles(self) -> int:
        return self.total_rows // self.tile_rows

    @cached_property
    def tile_head(self) -> np.ndarray:
        """Head id owning each tile (scalar-prefetch input of Kernel 1)."""
        out = np.empty(self.n_tiles, dtype=np.int32)
        t = 0
        for h, p in enumerate(self.padded_n_blocks):
            for _ in range(p // self.tile_rows):
                out[t] = h
                t += 1
        return out

    @cached_property
    def tile_local(self) -> np.ndarray:
        """Tile index within its head segment."""
        out = np.empty(self.n_tiles, dtype=np.int32)
        t = 0
        for p in self.padded_n_blocks:
            for i in range(p // self.tile_rows):
                out[t] = i
                t += 1
        return out

    @cached_property
    def row_valid(self) -> np.ndarray:
        """Bool mask over flattened rows: True for real (non-pad) blocks."""
        out = np.zeros(self.total_rows, dtype=bool)
        for h in range(self.n_heads):
            out[self.offsets[h] : self.offsets[h] + self.n_blocks[h]] = True
        return out

    # -- padded 2-D score view (top-k stage) ---------------------------------

    @property
    def max_blocks(self) -> int:
        return max(self.padded_n_blocks)

    @cached_property
    def scatter_rows(self) -> np.ndarray:
        """[n_heads, max_blocks] gather indices mapping the flattened score
        vector into the padded 2-D view (out-of-segment slots point at row 0
        and are masked separately via ``pad_mask``)."""
        idx = np.zeros((self.n_heads, self.max_blocks), dtype=np.int32)
        for h in range(self.n_heads):
            n = self.n_blocks[h]
            idx[h, :n] = np.arange(self.offsets[h], self.offsets[h] + n)
        return idx

    @cached_property
    def pad_mask(self) -> np.ndarray:
        """[n_heads, max_blocks] True where a real block exists."""
        m = np.zeros((self.n_heads, self.max_blocks), dtype=bool)
        for h in range(self.n_heads):
            m[h, : self.n_blocks[h]] = True
        return m

    @cached_property
    def max_top_k(self) -> int:
        return max(self.top_k)

    @cached_property
    def topk_valid(self) -> np.ndarray:
        """[n_heads, max_top_k] True for the first K_h slots of each head."""
        m = np.zeros((self.n_heads, self.max_top_k), dtype=bool)
        for h, k in enumerate(self.top_k):
            m[h, :k] = True
        return m

    # -- block -> page expansion (attention stage) ---------------------------

    @cached_property
    def slot_map(self) -> np.ndarray:
        """[n_heads, selected_pages] -> which top-k slot produces page j."""
        out = np.zeros((self.n_heads, self.selected_pages), dtype=np.int32)
        for h, s in enumerate(self.pages_per_block):
            out[h] = np.arange(self.selected_pages) // s
        return out

    @cached_property
    def within_map(self) -> np.ndarray:
        """[n_heads, selected_pages] -> page offset within the block."""
        out = np.zeros((self.n_heads, self.selected_pages), dtype=np.int32)
        for h, s in enumerate(self.pages_per_block):
            out[h] = np.arange(self.selected_pages) % s
        return out

    @cached_property
    def pages_per_block_arr(self) -> np.ndarray:
        return np.asarray(self.pages_per_block, dtype=np.int32)

    # -- fused-decode ragged grid descriptor ---------------------------------

    @cached_property
    def row_offsets_arr(self) -> np.ndarray:
        """[n_heads] flat-row offset of each head's centroid segment — the
        per-(kv-head) grid-cell base address of the fused decode kernel."""
        return np.asarray(self.offsets[:-1], dtype=np.int32)

    @cached_property
    def n_blocks_arr(self) -> np.ndarray:
        """[n_heads] real (unpadded) block count per head."""
        return np.asarray(self.n_blocks, dtype=np.int32)

    @cached_property
    def top_k_arr(self) -> np.ndarray:
        """[n_heads] K_h — blocks each head selects in the fused kernel."""
        return np.asarray(self.top_k, dtype=np.int32)

    # -- stats ----------------------------------------------------------------

    @property
    def avg_block_size(self) -> float:
        return float(np.mean(self.block_sizes))

    @property
    def total_centroid_rows_unpadded(self) -> int:
        return sum(self.n_blocks)

    def memory_ratio_vs_uniform(self, uniform_block: int) -> float:
        """Centroid-count overhead relative to a uniform block size."""
        uniform_rows = self.n_heads * (self.context_len // uniform_block)
        return self.total_centroid_rows_unpadded / uniform_rows


def uniform_layout(
    n_heads: int,
    block_size: int,
    context_len: int,
    page_size: int,
    token_budget: int,
    tile_rows: int = 128,
) -> RaggedLayout:
    return RaggedLayout(
        block_sizes=(block_size,) * n_heads,
        context_len=context_len,
        page_size=page_size,
        token_budget=token_budget,
        tile_rows=tile_rows,
    )


def layout_for(
    block_sizes,
    context_len: int,
    page_size: int,
    token_budget: int,
    tile_rows: int = 128,
) -> RaggedLayout:
    # budget must divide by every candidate block size: round down to the lcm.
    lcm = 1
    for b in set(block_sizes):
        lcm = math.lcm(lcm, b)
    budget = max(lcm, (min(token_budget, context_len) // lcm) * lcm)
    return RaggedLayout(
        block_sizes=tuple(int(b) for b in block_sizes),
        context_len=context_len,
        page_size=page_size,
        token_budget=budget,
        tile_rows=tile_rows,
    )
