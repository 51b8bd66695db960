"""Group-tiled count kernel — the beyond-paper optimization of the Kyiv
bottleneck.

Baseline analysis (EXPERIMENTS.md §Perf): the k = k_max count-only step is
HBM-bound — every candidate pair fetches its two parent bitset rows, so
traffic is ``2·M·W·4`` bytes for M pairs even though only ``t·W·4`` bytes of
distinct parent data exist (each parent participates in ~g pairs within its
prefix group).

This kernel exploits the prefix-group structure *created by the paper's own
BFS*: candidate pairs at a level are exactly the within-group pairs, so they
tile into (bm × bm) block-pairs of parent rows. Each grid step loads two
row blocks into VMEM **once** and emits the full bm×bm popcount cross
matrix:

    traffic_tiled  ≈ 2·(g/bm)²·bm·W·4 = traffic_pairwise / (bm/2)

i.e. an ~bm/2× cut of the dominant roofline term (bm = 8 default → 4×;
validated against the dry-run in the §Perf log). FLOPs are unchanged — each
pair's AND+popcount happens exactly once.

Layout contract: the caller supplies a *group-aligned* parent matrix (each
prefix group zero-padded to a multiple of bm — ``build_group_tiles``), so
BlockSpec indices stay block-aligned. Zero padding rows yield zero counts
and are masked by the caller.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["intersect_count_tiled", "build_group_tiles", "counts_from_tiles"]


def _tiled_kernel(ti_ref, tj_ref, a_ref, b_ref, cnt_ref, *, bm: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    b = b_ref[...]  # (bm, bw)
    for i in range(bm):  # static unroll: row i of A against all rows of B
        w = jnp.bitwise_and(a_ref[i, :][None, :], b)
        pc = jnp.sum(jax.lax.population_count(w).astype(jnp.int32), axis=1)
        cnt_ref[0, i, :] += pc


@functools.partial(jax.jit, static_argnames=("block_rows", "block_words", "interpret"))
def intersect_count_tiled(
    bits: jax.Array,
    tile_i: jax.Array,
    tile_j: jax.Array,
    *,
    block_rows: int = 8,
    block_words: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Popcount cross-matrices for block-pairs of parent rows.

    bits: (t, W) uint32, t % block_rows == 0 (group-aligned, zero-padded).
    tile_i/tile_j: (T,) int32 *block* indices (row block r covers rows
    [r*block_rows, (r+1)*block_rows)).
    Returns (T, bm, bm) int32: counts[t, a, b] = |rows(tile_i[t]*bm+a) ∩
    rows(tile_j[t]*bm+b)|.
    """
    t, W = bits.shape
    bm = block_rows
    if t % bm:
        raise ValueError(f"t={t} not group-aligned to block_rows={bm}")
    bw = min(block_words, W)
    if W % bw:
        raise ValueError(f"W={W} not divisible by block_words={bw}")
    T = tile_i.shape[0]
    grid = (T, W // bw)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bw), lambda tt, j, ti, tj: (ti[tt], j)),
            pl.BlockSpec((bm, bw), lambda tt, j, ti, tj: (tj[tt], j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bm), lambda tt, j, ti, tj: (tt, 0, 0)),
        ],
    )
    cnt = pl.pallas_call(
        functools.partial(_tiled_kernel, bm=bm),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, bm, bm), jnp.int32)],
        interpret=interpret,
        name="intersect_count_tiled",
    )(tile_i.astype(jnp.int32), tile_j.astype(jnp.int32), bits, bits)[0]
    return cnt


def build_group_tiles(group_sizes: np.ndarray, bm: int = 8):
    """Group-aligned layout + tile list for a level's prefix groups.

    Returns:
      row_map: (t_padded,) original row index per padded row (-1 = padding)
      tile_i, tile_j: (T,) block indices (upper-triangular block pairs)
    """
    block_starts = []
    total_padded = 0
    for g in np.asarray(group_sizes, dtype=np.int64):
        padded = -(-g // bm) * bm
        block_starts.append((total_padded // bm, padded // bm, int(g)))
        total_padded += padded
    out_map = np.full(total_padded, -1, dtype=np.int64)
    cursor = 0
    for start_block, nb, g in block_starts:
        pos = start_block * bm
        out_map[pos : pos + g] = np.arange(cursor, cursor + g)
        cursor += g
    tiles_i, tiles_j = [], []
    for start_block, nb, g in block_starts:
        for a in range(nb):
            for b in range(a, nb):
                tiles_i.append(start_block + a)
                tiles_j.append(start_block + b)
    return (
        out_map,
        np.asarray(tiles_i, dtype=np.int32),
        np.asarray(tiles_j, dtype=np.int32),
    )


def counts_from_tiles(
    cnt_tiles: np.ndarray,
    tile_i: np.ndarray,
    tile_j: np.ndarray,
    row_map: np.ndarray,
    bm: int = 8,
):
    """Flatten tile cross-matrices back to (pair -> count) for the valid
    within-group pairs (i < j, both real rows). Returns (pairs (M,2) original
    row ids, counts (M,))."""
    pairs, counts = [], []
    for t in range(cnt_tiles.shape[0]):
        bi, bj = int(tile_i[t]), int(tile_j[t])
        for a in range(bm):
            ra = row_map[bi * bm + a]
            if ra < 0:
                continue
            for b in range(bm):
                rb = row_map[bj * bm + b]
                if rb < 0 or rb <= ra:
                    continue
                pairs.append((ra, rb))
                counts.append(int(cnt_tiles[t, a, b]))
    return np.asarray(pairs, dtype=np.int64).reshape(-1, 2), np.asarray(counts, dtype=np.int64)
