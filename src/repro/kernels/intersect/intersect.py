"""Pallas TPU kernels for the Kyiv row-intersection bottleneck (Alg. 1 line 31).

Two data paths, each with a *write* and a *count-only* variant:

1. **Indexed** (`*_indexed`): the pair list rides in scalar-prefetch (SMEM,
   flattened to ``(2M,)``); each grid step's BlockSpec ``index_map`` reads
   the pair indices and DMAs exactly the two parent bitset rows it needs from
   HBM into VMEM. The row *gather* is thereby fused into the block fetch — no
   gathered copy of the parent level is ever materialised in HBM. This is the
   TPU analogue of the paper's "intersection directly on the stored level".
   Rows are read from the ``(t, 1, W)`` row layout (:func:`as_rows`) and
   per-pair counts/classes leave as 128-lane rows: the only block shapes the
   chip's compiler accepts for one row and one scalar per pair.

2. **Gathered** (`*_gathered`): operates on pre-gathered ``(M, W)`` operand
   matrices with ``(block_pairs, block_words)`` VMEM tiles — the layout- and
   lane-aligned path (word dim tiles are multiples of 128 uint32 lanes) used
   when the same parent row feeds many pairs and XLA's gather has already
   amortised.

The count-only variants implement the k = k_max fusion: the AND happens in
VMEM and only ``(M,)`` int32 counts are written back — the child bitset never
touches HBM. Combined with the Lemma 4.6 / Corollary 4.7 host-side pruning
this realises (and strengthens) the paper's "avoid the intersection at the
last level": on TPU the expensive part is the HBM write, and it is gone.

**Fused classify** (`*_classify_*`): the third pipeline stage. On top of the
AND + popcount, these kernels take the per-pair min parent popcount
(scalar-prefetch for the indexed path, a pre-gathered ``(M, 1)`` VMEM vector
for the gathered path) plus the threshold ``τ`` and emit a per-pair **class code**
computed in VMEM on the final word-block of each pair:

  * ``CLASS_SKIP``  (0) — absent (``|R_W| = 0``) or uniform
    (``|R_W| = min(|R_I|, |R_J|)``), Alg. 1 line 32;
  * ``CLASS_EMIT``  (1) — minimal τ-infrequent (``0 < |R_W| <= τ``),
    Alg. 1 lines 34-38;
  * ``CLASS_STORE`` (2) — survives to level k+1, Alg. 1 line 41.

This moves the driver's per-batch host classification (a ``(M,)`` gather +
three comparisons + boolean reductions in numpy) into the same VMEM pass
that already holds the popcount, so the host only receives ``(M,)`` codes it
can ``nonzero`` directly — the classify contract consumed by
``repro.core.kyiv`` when ``KyivConfig.fused_classify`` is on.

On the CPU backend the kernels are interpreted; on a TPU Mosaic compiles
them (``repro.core.placement.resolve_interpret`` decides, once). Word blocks
are multiples of 128 lanes — callers pad W (``ops.pad_words``) — and
``tests/test_tpu_compile.py`` compiles every variant for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import CLASS_EMIT, CLASS_SKIP, CLASS_STORE

__all__ = [
    "as_rows",
    "intersect_write_indexed",
    "intersect_count_indexed",
    "intersect_write_gathered",
    "intersect_count_gathered",
    "intersect_classify_write_indexed",
    "intersect_classify_count_indexed",
    "intersect_classify_write_gathered",
    "intersect_classify_write_gathered_donating",
    "intersect_classify_count_gathered",
]

_LANES = 128  # uint32 lanes per VPU register row
_SUBLANES = 8


def _classify_scalar(cnt, minp, tau):
    """Class codes for accumulated popcounts (elementwise: a lane row of
    the indexed kernels or a (bm, 1) tile of the gathered ones)."""
    skip = (cnt == 0) | (cnt == minp)
    emit = jnp.logical_not(skip) & (cnt <= tau)
    return jnp.where(skip, CLASS_SKIP, jnp.where(emit, CLASS_EMIT, CLASS_STORE)).astype(
        jnp.int32
    )


def as_rows(bits: jax.Array) -> jax.Array:
    """The indexed kernels' **row layout**: ``(t, W)`` -> ``(t, 1, W)``.

    Mosaic tiles the last two dims of a block by (8, 128), so a single-row
    ``(1, bw)`` block of a 2-D ``(t, W)`` array is refused on the chip.
    With the row axis leading, one row is the block ``(None, 1, bw)`` whose
    last two dims are legal (1 equals the full dim, bw is a lane multiple).
    The reshape is a relayout copy on the chip, so placements convert a
    level once when it becomes resident — the kernels keep their child
    output in this layout and the next level chains without a copy.
    """
    return bits if bits.ndim == 3 else bits.reshape(bits.shape[0], 1, bits.shape[1])


def _row_spec(bw: int, col: int) -> pl.BlockSpec:
    # one parent row per pair side, its index read from the flattened
    # scalar-prefetched pair table (2m: I parent, 2m + 1: J parent)
    return pl.BlockSpec((None, 1, bw), lambda m, j, idx, *_: (idx[2 * m + col], 0, j))


# Per-pair scalars (count, class) leave the kernel as one 128-lane row per
# pair: a (1, 1) block breaks the (8, 128) tiling rule and Mosaic cannot
# store a scalar to VMEM, so the value is broadcast across the row and read
# back at lane 0.
_LANE_ROW_SPEC = pl.BlockSpec((None, 1, _LANES), lambda m, j, *_: (m, 0, 0))


def _indexed_kernel(write: bool, classify: bool):
    """Kernel body for one (pair, word-block) grid step.

    The AND + popcount runs on the pair's two ``(1, bw)`` row blocks; the
    count accumulates across word blocks in the pair's lane row, and the
    fused variants classify on the final word block (``minp`` and ``tau``
    ride in SMEM).
    """

    def kernel(*refs):
        if classify:
            _idx_ref, minp_ref, tau_ref, a_ref, b_ref, *outs = refs
        else:
            _idx_ref, a_ref, b_ref, *outs = refs
        if write:
            child_ref, *outs = outs
        cnt_ref = outs[0]
        m = pl.program_id(0)
        j = pl.program_id(1)
        w = jnp.bitwise_and(a_ref[...], b_ref[...])
        if write:
            child_ref[...] = w
        pc = jnp.sum(jax.lax.population_count(w).astype(jnp.int32), axis=1, keepdims=True)

        @pl.when(j == 0)
        def _init():
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

        cnt_ref[...] += jnp.broadcast_to(pc, cnt_ref.shape)

        if classify:
            cls_ref = outs[1]

            # classification runs once, on the pair's final word block, when
            # the accumulated popcount is complete
            @pl.when(j == pl.num_programs(1) - 1)
            def _classify():
                cls_ref[...] = _classify_scalar(cnt_ref[...], minp_ref[m], tau_ref[0])

    return kernel


def _indexed_call(bits, pairs, minp, tau, *, write, bw, interpret):
    """Shared pallas_call of the four indexed variants.

    ``bits`` is ``(t, W)`` or already in :func:`as_rows` layout; the child
    comes back in the caller's layout. ``minp``/``tau`` are None for the
    unfused variants. Scalar prefetch holds the flattened ``(2M,)`` pair
    table (an ``(M, 2)`` table pads its last dim to 128 lanes in SMEM) plus,
    fused, the ``(M,)`` per-pair min parent count and ``tau``.
    """
    rows = as_rows(bits)
    _, _, W = rows.shape
    M = pairs.shape[0]
    if W % bw:
        raise ValueError(f"W={W} not divisible by block_words={bw}")
    classify = minp is not None
    scalars = [pairs.astype(jnp.int32).reshape(-1)]
    if classify:
        scalars += [minp.astype(jnp.int32), jnp.asarray(tau, jnp.int32).reshape(1)]
    out_specs, out_shape = [], []
    if write:
        out_specs.append(pl.BlockSpec((None, 1, bw), lambda m, j, *_: (m, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((M, 1, W), rows.dtype))
    for _ in range(2 if classify else 1):
        out_specs.append(_LANE_ROW_SPEC)
        out_shape.append(jax.ShapeDtypeStruct((M, 1, _LANES), jnp.int32))
    outs = pl.pallas_call(
        _indexed_kernel(write, classify),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(M, W // bw),
            in_specs=[_row_spec(bw, 0), _row_spec(bw, 1)],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(*scalars, rows, rows)
    if write:
        child, *outs = outs
        if bits.ndim == 2:
            child = child.reshape(M, W)
        return (child, *(o[:, 0, 0] for o in outs))
    return tuple(o[:, 0, 0] for o in outs)


def _pair_minp(parent_counts, pairs):
    pc = parent_counts.astype(jnp.int32)
    return jnp.minimum(pc[pairs[:, 0]], pc[pairs[:, 1]])


@functools.partial(jax.jit, static_argnames=("block_words", "interpret"))
def intersect_write_indexed(
    bits: jax.Array,
    pairs: jax.Array,
    *,
    block_words: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """child = bits[pairs[:,0]] & bits[pairs[:,1]]; counts = popcount(child).

    Args:
      bits: (t, W) uint32 parent-level bitsets, or their (t, 1, W) row layout.
      pairs: (M, 2) int32 row indices.
      block_words: word-dimension VMEM tile (a multiple of 128 dividing W).
    Returns:
      (child (M, W) uint32 — (M, 1, W) for row-layout input, counts (M,) int32)
    """
    return _indexed_call(
        bits, pairs, None, None, write=True, bw=min(block_words, bits.shape[-1]),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("block_words", "interpret"))
def intersect_count_indexed(
    bits: jax.Array,
    pairs: jax.Array,
    *,
    block_words: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """Count-only k=k_max path: popcount(bits[i] & bits[j]) with no HBM child write."""
    (cnt,) = _indexed_call(
        bits, pairs, None, None, write=False, bw=min(block_words, bits.shape[-1]),
        interpret=interpret,
    )
    return cnt


def _write_gathered_kernel(a_ref, b_ref, child_ref, cnt_ref):
    w = jnp.bitwise_and(a_ref[...], b_ref[...])
    child_ref[...] = w
    pc = jnp.sum(jax.lax.population_count(w).astype(jnp.int32), axis=1, keepdims=True)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    cnt_ref[...] += pc


def _count_gathered_kernel(a_ref, b_ref, cnt_ref):
    w = jnp.bitwise_and(a_ref[...], b_ref[...])
    pc = jnp.sum(jax.lax.population_count(w).astype(jnp.int32), axis=1, keepdims=True)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    cnt_ref[...] += pc


@functools.partial(jax.jit, static_argnames=("block_pairs", "block_words", "interpret"))
def intersect_write_gathered(
    a: jax.Array,
    b: jax.Array,
    *,
    block_pairs: int = 8,
    block_words: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """AND + popcount over aligned (M, W) operands with (bm, bw) VMEM tiles."""
    M, W = a.shape
    bm = min(block_pairs, M)
    bw = min(block_words, W)
    if M % bm or W % bw:
        raise ValueError(f"(M={M}, W={W}) not divisible by ({bm}, {bw})")
    grid = (M // bm, W // bw)
    child, cnt = pl.pallas_call(
        _write_gathered_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bw), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bw), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bw), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, W), a.dtype),
            jax.ShapeDtypeStruct((M, 1), jnp.int32),
        ],
        interpret=interpret,
    )(a, b)
    return child, cnt[:, 0]


@functools.partial(jax.jit, static_argnames=("block_pairs", "block_words", "interpret"))
def intersect_count_gathered(
    a: jax.Array,
    b: jax.Array,
    *,
    block_pairs: int = 8,
    block_words: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Count-only variant over aligned (M, W) operands."""
    M, W = a.shape
    bm = min(block_pairs, M)
    bw = min(block_words, W)
    if M % bm or W % bw:
        raise ValueError(f"(M={M}, W={W}) not divisible by ({bm}, {bw})")
    grid = (M // bm, W // bw)
    cnt = pl.pallas_call(
        _count_gathered_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bw), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bw), lambda i, j: (i, j)),
        ],
        out_specs=[pl.BlockSpec((bm, 1), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, 1), jnp.int32)],
        interpret=interpret,
    )(a, b)[0]
    return cnt[:, 0]


@functools.partial(jax.jit, static_argnames=("block_words", "interpret"))
def intersect_classify_write_indexed(
    bits: jax.Array,
    pairs: jax.Array,
    parent_counts: jax.Array,
    tau: jax.Array,
    *,
    block_words: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused child + popcount + class code, gather via scalar-prefetch.

    Args:
      bits: (t, W) uint32 parent-level bitsets, or their (t, 1, W) row layout.
      pairs: (M, 2) int32 row indices.
      parent_counts: (t,) int32 parent popcounts |R_I|; the per-pair minimum
        is gathered here and rides in SMEM, so SMEM use grows with the batch,
        never with the level.
      tau: scalar int32 threshold (traced — one executable per bucket).
    Returns:
      (child in the layout of ``bits``, counts (M,) int32, classes (M,) int32)
    """
    return _indexed_call(
        bits, pairs, _pair_minp(parent_counts, pairs), tau, write=True,
        bw=min(block_words, bits.shape[-1]), interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("block_words", "interpret"))
def intersect_classify_count_indexed(
    bits: jax.Array,
    pairs: jax.Array,
    parent_counts: jax.Array,
    tau: jax.Array,
    *,
    block_words: int = 2048,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused count-only k=k_max path: (counts, classes), no HBM child write."""
    return _indexed_call(
        bits, pairs, _pair_minp(parent_counts, pairs), tau, write=False,
        bw=min(block_words, bits.shape[-1]), interpret=interpret,
    )


def _classify_write_gathered_kernel(tau_ref, a_ref, b_ref, minp_ref, child_ref, cnt_ref, cls_ref):
    j = pl.program_id(1)
    w = jnp.bitwise_and(a_ref[...], b_ref[...])
    child_ref[...] = w
    pc = jnp.sum(jax.lax.population_count(w).astype(jnp.int32), axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    cnt_ref[...] += pc

    @pl.when(j == pl.num_programs(1) - 1)
    def _classify():
        cls_ref[...] = _classify_scalar(cnt_ref[...], minp_ref[...], tau_ref[0])


def _classify_count_gathered_kernel(tau_ref, a_ref, b_ref, minp_ref, cnt_ref, cls_ref):
    j = pl.program_id(1)
    w = jnp.bitwise_and(a_ref[...], b_ref[...])
    pc = jnp.sum(jax.lax.population_count(w).astype(jnp.int32), axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    cnt_ref[...] += pc

    @pl.when(j == pl.num_programs(1) - 1)
    def _classify():
        cls_ref[...] = _classify_scalar(cnt_ref[...], minp_ref[...], tau_ref[0])


def _intersect_classify_write_gathered(
    a: jax.Array,
    b: jax.Array,
    minp: jax.Array,
    tau: jax.Array,
    *,
    block_pairs: int = 8,
    block_words: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused AND + popcount + classify over pre-gathered aligned operands.

    ``minp`` is the (M,) int32 per-pair min parent popcount (pre-gathered on
    the same path that gathered ``a``/``b``).
    """
    M, W = a.shape
    bm = min(block_pairs, M)
    bw = min(block_words, W)
    if M % bm or W % bw:
        raise ValueError(f"(M={M}, W={W}) not divisible by ({bm}, {bw})")
    grid = (M // bm, W // bw)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bw), lambda i, j, tau: (i, j)),
            pl.BlockSpec((bm, bw), lambda i, j, tau: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j, tau: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bw), lambda i, j, tau: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j, tau: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, tau: (i, 0)),
        ],
    )
    child, cnt, cls = pl.pallas_call(
        _classify_write_gathered_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((M, W), a.dtype),
            jax.ShapeDtypeStruct((M, 1), jnp.int32),
            jax.ShapeDtypeStruct((M, 1), jnp.int32),
        ],
        interpret=interpret,
    )(
        jnp.asarray(tau, jnp.int32).reshape(1),
        a,
        b,
        minp.astype(jnp.int32).reshape(-1, 1),
    )
    return child, cnt[:, 0], cls[:, 0]


_CLS_W_GATHERED_STATICS = ("block_pairs", "block_words", "interpret")
intersect_classify_write_gathered = jax.jit(
    _intersect_classify_write_gathered, static_argnames=_CLS_W_GATHERED_STATICS
)
# Accelerator variant: donating the gathered `a` operand lets XLA alias the
# (same-shape, same-dtype) child output onto its buffer — the write path then
# allocates no extra HBM for the children. CPU backends do not support
# donation (warning + copy), so ops.LevelPipeline selects this variant only
# on tpu/gpu.
intersect_classify_write_gathered_donating = jax.jit(
    _intersect_classify_write_gathered,
    static_argnames=_CLS_W_GATHERED_STATICS,
    donate_argnums=(0,),
)


@functools.partial(jax.jit, static_argnames=("block_pairs", "block_words", "interpret"))
def intersect_classify_count_gathered(
    a: jax.Array,
    b: jax.Array,
    minp: jax.Array,
    tau: jax.Array,
    *,
    block_pairs: int = 8,
    block_words: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused count-only classify variant over pre-gathered operands."""
    M, W = a.shape
    bm = min(block_pairs, M)
    bw = min(block_words, W)
    if M % bm or W % bw:
        raise ValueError(f"(M={M}, W={W}) not divisible by ({bm}, {bw})")
    grid = (M // bm, W // bw)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bw), lambda i, j, tau: (i, j)),
            pl.BlockSpec((bm, bw), lambda i, j, tau: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j, tau: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j, tau: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, tau: (i, 0)),
        ],
    )
    cnt, cls = pl.pallas_call(
        _classify_count_gathered_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((M, 1), jnp.int32),
            jax.ShapeDtypeStruct((M, 1), jnp.int32),
        ],
        interpret=interpret,
    )(
        jnp.asarray(tau, jnp.int32).reshape(1),
        a,
        b,
        minp.astype(jnp.int32).reshape(-1, 1),
    )
    return cnt[:, 0], cls[:, 0]
