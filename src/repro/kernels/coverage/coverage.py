"""Pallas TPU coverage kernel: batched itemset-AND + per-word record-bit
accumulation.

The privacy risk engine asks, for every record, how many quasi-identifiers
cover it. The host formulation is a scalar scatter (expand each QI's row
bitset to indices, bump a counter per row) — exactly the shape of loop the
paper's bitset substrate exists to avoid. This kernel keeps the whole
question in the word domain:

* the itemset batch ``sets (M, K)`` rides in **scalar prefetch** (SMEM,
  flattened to ``(M * K,)``), like the indexed intersect kernels: each grid
  step's BlockSpec ``index_map`` reads the K item indices of set ``m`` and
  DMAs exactly those K parent bitset rows from HBM into VMEM — the gather is
  fused into the block fetch, no gathered (M, K, W) operand ever exists in
  HBM. Rows are read from the ``(t, 1, W)`` row layout
  (``kernels.intersect.intersect.as_rows``), the only layout in which one
  row is a block the chip's compiler accepts;
* the K-way AND produces the set's record mask in VMEM;
* instead of a scalar popcount, the mask is *transposed into bit planes*:
  a ``(32, bw)`` int32 accumulator tile (32 sublanes = the 32 bit positions
  of a word, bw lanes = the word block) accumulates ``(mask >> b) & 1``
  weighted by the set's int32 weight, summed over the M grid steps.

The output ``acc (32, W)`` is the per-record coverage count in word-major
layout (record ``r`` = word ``r // 32``, bit ``r % 32``); padding rows in
the batch carry weight 0 and therefore contribute nothing. The grid is
``(W // bw, M)`` — the set axis iterates fastest, so each output tile is
revisited on consecutive grid steps (the TPU accumulation contract, same as
the word-block loop of the intersect kernels).

Interpreted on the CPU backend and compiled by Mosaic on the TPU (bw a
multiple of 128 lanes, the accumulator a full 32-sublane tile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..intersect.intersect import as_rows

__all__ = ["coverage_accumulate_indexed"]


def _make_coverage_kernel(n_set_items: int):
    """Kernel body for a K-way AND: arity depends on the (static) set width."""

    def kernel(sets_ref, wt_ref, *refs):
        acc_ref = refs[-1]
        rows = refs[:-1]
        m = pl.program_id(1)
        w = rows[0][...]  # (1, bw)
        for r in rows[1:]:
            w = jnp.bitwise_and(w, r[...])

        @pl.when(m == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        bitpos = jax.lax.broadcasted_iota(jnp.uint32, acc_ref.shape, 0)
        sel = (jnp.right_shift(w, bitpos) & jnp.uint32(1)).astype(jnp.int32)
        acc_ref[...] += sel * wt_ref[m]

    return kernel


def _row_spec(t: int, k: int, bw: int) -> pl.BlockSpec:
    # item ``t`` of set ``m``: one parent row in the (rows, 1, W) layout of
    # ``kernels.intersect.intersect.as_rows``, its index read from the
    # flattened scalar-prefetched set table
    return pl.BlockSpec((None, 1, bw), lambda j, m, sets, wt: (sets[m * k + t], 0, j))


@functools.partial(jax.jit, static_argnames=("block_words", "interpret"))
def coverage_accumulate_indexed(
    bits: jax.Array,
    sets: jax.Array,
    weights: jax.Array,
    *,
    block_words: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """acc[b, w] = sum_m weights[m] * bit b of (AND_t bits[sets[m, t]])[w].

    Args:
      bits: (t, W) uint32 item bitsets, or their (t, 1, W) row layout.
      sets: (M, K) int32 item indices; short sets padded by repetition. The
        table rides in SMEM flattened to (M * K,): a 2-D table pads its last
        dim to 128 lanes there.
      weights: (M,) int32 per-set weight (0 for batch-padding rows).
      block_words: word-dimension VMEM tile (a multiple of 128 dividing W).
    Returns:
      acc (32, W) int32 — per-record coverage counts in word-major layout.
    """
    rows = as_rows(bits)
    _, _, W = rows.shape
    M, K = sets.shape
    bw = min(block_words, W)
    if W % bw:
        raise ValueError(f"W={W} not divisible by block_words={bw}")
    grid = (W // bw, M)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[_row_spec(t_, K, bw) for t_ in range(K)],
        out_specs=[pl.BlockSpec((32, bw), lambda j, m, sets, wt: (0, j))],
    )
    (acc,) = pl.pallas_call(
        _make_coverage_kernel(K),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((32, W), jnp.int32)],
        interpret=interpret,
        name="coverage_accumulate_indexed",
    )(sets.astype(jnp.int32).reshape(-1), weights.astype(jnp.int32), *([rows] * K))
    return acc
