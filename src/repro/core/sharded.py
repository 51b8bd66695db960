"""Distributed (SPMD) shard_map level bodies for the Kyiv miner.

The paper parallelises level k with shared-memory threads (§4.4.4): the
stored level is shared, candidate pairs are divided among threads, and no
inter-thread communication happens during a level. The SPMD mapping:

  * candidate **pairs** shard over the ``data`` (and ``pod``) mesh axes —
    exactly-equal padded blocks (see ``core.balance.balanced_blocks``);
  * the parent-level **bitset words** optionally shard over ``model``
    (row-parallelism for datasets whose bitset rows exceed one device);
    per-shard partial popcounts are ``psum``-ed over ``model`` — the only
    collective in the level body, mirroring the paper's
    "no inter-thread communication" property;
  * the parent table is replicated over the pair axes (the shared-memory
    analogue). For the count-only (k = k_max) step no child bitsets are
    written, so per-device HBM traffic is the two fetched rows per pair.

This module holds exactly the jittable ``shard_map`` bodies
(``sharded_level_step``/``sharded_level_count_step`` and their
``*_classify_*`` fused twins — what the multi-pod dry-run lowers on the
production meshes) plus two thin wrappers. All mesh residency, pair
bucketing and device-put plumbing that used to be duplicated here now lives
in ``repro.core.placement.MeshPlacement``: ``make_sharded_pipeline`` is a
pipeline factory for ``mine_preprocessed(pipeline_factory=...)`` binding a
``MeshPlacement`` into the generic ``LevelPipeline``, and
``make_sharded_intersect`` is the older drop-in ``intersect_fn`` contract
(host classification, placement per batch) kept for compatibility —
numerics of both are identical to the sequential engines (tested on an
8-device CPU mesh in ``tests/test_sharded_driver.py``).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "sharded_level_step",
    "sharded_level_count_step",
    "sharded_level_classify_step",
    "sharded_level_classify_count_step",
    "sharded_coverage_step",
    "sharded_frontier_support_step",
    "make_sharded_intersect",
    "make_sharded_pipeline",
]


# Word axes may be a single ICI axis name ("model") or a tuple of axis
# names for hybrid DCN x ICI meshes (PartitionSpec and psum/all_gather both
# accept tuples, flattening major-to-minor in tuple order).
WordAxes = "str | tuple[str, ...] | None"


def _replicate_pairs_dim(x, pair_axes):
    """All-gather a pair-sharded per-pair vector back to the full batch.

    The tiled gather concatenates shards in flattened (major-to-minor)
    pair-axis index order — the same order ``P(pair_axes)`` splits them, so
    the result equals the out-spec reassembly but lands **replicated**:
    on a process-spanning mesh every host can read it without a
    cross-process transfer at materialization time.
    """
    return jax.lax.all_gather(x, pair_axes, axis=0, tiled=True)


def _local_intersect(
    bits_ref, pairs, *, word_axis, pair_axes, write_children: bool, replicate: bool
):
    a = jnp.take(bits_ref, pairs[:, 0], axis=0)
    b = jnp.take(bits_ref, pairs[:, 1], axis=0)
    child = jnp.bitwise_and(a, b)
    partial = jnp.sum(jax.lax.population_count(child).astype(jnp.int32), axis=1)
    counts = jax.lax.psum(partial, word_axis) if word_axis else partial
    if replicate:
        counts = _replicate_pairs_dim(counts, pair_axes)
    if write_children:
        return child, counts
    return counts


def sharded_level_step(
    mesh: Mesh,
    *,
    pair_axes: tuple[str, ...] = ("data",),
    word_axis: "WordAxes" = "model",
    replicate: bool = False,
):
    """Build the write-variant level body: (bits, pairs) -> (child, counts).

    bits: (t, W) uint32, sharded P(None, word_axis);
    pairs: (M, 2) int32, sharded P(pair_axes, None);
    child: (M, W), sharded P(pair_axes, word_axis); counts: (M,) P(pair_axes).

    ``replicate=True`` is the process-spanning variant: counts come back
    replicated (out-spec ``P()``) via a tiled pair-axis all-gather, so a
    multi-host coordinator can materialize them host-side without touching
    non-addressable shards. Children stay pair/word sharded either way.
    """
    in_specs = (P(None, word_axis), P(pair_axes, None))
    out_specs = (P(pair_axes, word_axis), P() if replicate else P(pair_axes))
    fn = shard_map(
        functools.partial(
            _local_intersect,
            word_axis=word_axis,
            pair_axes=pair_axes,
            write_children=True,
            replicate=replicate,
        ),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return jax.jit(fn), in_specs, out_specs


def sharded_level_count_step(
    mesh: Mesh,
    *,
    pair_axes: tuple[str, ...] = ("data",),
    word_axis: "WordAxes" = "model",
    replicate: bool = False,
):
    """Count-only (k = k_max) level body: (bits, pairs) -> counts."""
    in_specs = (P(None, word_axis), P(pair_axes, None))
    out_specs = P() if replicate else P(pair_axes)
    fn = shard_map(
        functools.partial(
            _local_intersect,
            word_axis=word_axis,
            pair_axes=pair_axes,
            write_children=False,
            replicate=replicate,
        ),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return jax.jit(fn), in_specs, out_specs


def _local_intersect_classify(
    bits_ref,
    pairs,
    minp,
    tau,
    *,
    word_axis,
    pair_axes,
    write_children: bool,
    replicate: bool,
):
    """Shard-local fused body: gather, AND, popcount(+psum), classify.

    ``minp`` is the per-pair min parent popcount (sharded with the pairs);
    classification runs after the word-axis ``psum`` so every pair shard
    classifies its own pairs from complete counts — still no inter-device
    communication beyond the popcount psum (plus, in the process-spanning
    ``replicate`` variant, the pair-axis all-gather of the per-pair outputs).
    """
    a = jnp.take(bits_ref, pairs[:, 0], axis=0)
    b = jnp.take(bits_ref, pairs[:, 1], axis=0)
    child = jnp.bitwise_and(a, b)
    partial = jnp.sum(jax.lax.population_count(child).astype(jnp.int32), axis=1)
    counts = jax.lax.psum(partial, word_axis) if word_axis else partial
    skip = (counts == 0) | (counts == minp)
    emit = jnp.logical_not(skip) & (counts <= tau)
    classes = jnp.where(skip, 0, jnp.where(emit, 1, 2)).astype(jnp.int32)
    if replicate:
        counts = _replicate_pairs_dim(counts, pair_axes)
        classes = _replicate_pairs_dim(classes, pair_axes)
    if write_children:
        return child, counts, classes
    return counts, classes


def sharded_level_classify_step(
    mesh: Mesh,
    *,
    pair_axes: tuple[str, ...] = ("data",),
    word_axis: "WordAxes" = "model",
    replicate: bool = False,
):
    """Fused write-variant level body: (bits, pairs, minp, tau) ->
    (child, counts, classes)."""
    in_specs = (P(None, word_axis), P(pair_axes, None), P(pair_axes), P())
    per_pair = P() if replicate else P(pair_axes)
    out_specs = (P(pair_axes, word_axis), per_pair, per_pair)
    fn = shard_map(
        functools.partial(
            _local_intersect_classify,
            word_axis=word_axis,
            pair_axes=pair_axes,
            write_children=True,
            replicate=replicate,
        ),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return jax.jit(fn), in_specs, out_specs


def sharded_level_classify_count_step(
    mesh: Mesh,
    *,
    pair_axes: tuple[str, ...] = ("data",),
    word_axis: "WordAxes" = "model",
    replicate: bool = False,
):
    """Fused count-only (k = k_max) level body: (bits, pairs, minp, tau) ->
    (counts, classes)."""
    in_specs = (P(None, word_axis), P(pair_axes, None), P(pair_axes), P())
    per_pair = P() if replicate else P(pair_axes)
    out_specs = (per_pair, per_pair)
    fn = shard_map(
        functools.partial(
            _local_intersect_classify,
            word_axis=word_axis,
            pair_axes=pair_axes,
            write_children=False,
            replicate=replicate,
        ),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return jax.jit(fn), in_specs, out_specs


def _local_coverage(bits_ref, sets, weights, *, pair_axes, n_set_items):
    """Shard-local coverage body (``kernels.coverage`` semantics): K-way AND
    over locally-held bitset words, bit-plane accumulation weighted per set,
    then a psum over the pair axes — words stay sharded, the set axis is
    reduced away, so the only collective is the accumulator psum (the
    record-coverage analogue of the level body's popcount psum)."""
    mask = jnp.take(bits_ref, sets[:, 0], axis=0)
    for t in range(1, n_set_items):
        mask = jnp.bitwise_and(mask, jnp.take(bits_ref, sets[:, t], axis=0))
    wt = weights.astype(jnp.int32)[:, None]
    rows = []
    for b in range(32):
        sel = (jnp.right_shift(mask, jnp.uint32(b)) & jnp.uint32(1)).astype(jnp.int32)
        rows.append(jnp.sum(sel * wt, axis=0))
    acc = jnp.stack(rows, axis=0)
    return jax.lax.psum(acc, pair_axes)


def sharded_coverage_step(
    mesh: Mesh,
    *,
    pair_axes: tuple[str, ...] = ("data",),
    word_axis: str | None = "model",
    n_set_items: int = 3,
):
    """Record-coverage body: (bits, sets, weights) -> acc (32, W).

    bits: (t, W) uint32, sharded P(None, word_axis);
    sets: (M, n_set_items) int32, sharded P(pair_axes, None);
    weights: (M,) int32, sharded P(pair_axes);
    acc: (32, W) int32, sharded P(None, word_axis) — replicated over pairs.
    """
    in_specs = (P(None, word_axis), P(pair_axes, None), P(pair_axes))
    out_specs = P(None, word_axis)
    fn = shard_map(
        functools.partial(
            _local_coverage, pair_axes=pair_axes, n_set_items=n_set_items
        ),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return jax.jit(fn), in_specs, out_specs


def sharded_frontier_support_step(
    mesh: Mesh,
    *,
    pair_axes: tuple[str, ...] = ("data",),
    k: int = 2,
    t_pad: int = 16,
    bits: int = 1,
    ipw: int = 1,
    replicate: bool = False,
):
    """Frontier support-test body, sharded over the pair axes:
    (ids, keys, pairs, valid) -> ok.

    ids: (t_pad, k) int32 and keys: (t_pad, w) int32, replicated P(None,
    None) — the parent id table and packed sorted key table are the shared
    (read-only) side, mirroring the level bodies' replicated bitsets;
    pairs: (M, 2) int32 sharded P(pair_axes, None); valid: (M,) bool
    P(pair_axes); ok: (M,) bool P(pair_axes). Each pair shard binary-searches
    its own candidates' prefix-drop subsets — no collective at all (the
    paper's "no inter-thread communication" §4.4.4 holds exactly here).
    ``replicate=True`` (process-spanning meshes) all-gathers ``ok`` back to
    the full batch so every host can partition it locally.
    """
    from ..kernels.frontier.frontier import support_ok_body

    in_specs = (P(None, None), P(None, None), P(pair_axes, None), P(pair_axes))
    out_specs = P() if replicate else P(pair_axes)

    def body(ids, keys, pairs, valid):
        ok = support_ok_body(
            ids, keys, pairs, valid, k=k, t_pad=t_pad, bits=bits, ipw=ipw
        )
        return _replicate_pairs_dim(ok, pair_axes) if replicate else ok

    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return jax.jit(fn), in_specs, out_specs


def make_sharded_pipeline(
    mesh: Mesh,
    *,
    pair_axes: tuple[str, ...] = ("data",),
    word_axis: str | None = None,
    locality_sort: bool = True,
    fused_classify: bool = True,
):
    """Pipeline factory for ``mine_preprocessed(pipeline_factory=...)``.

    Returns ``factory(bits, parent_counts, tau) -> LevelPipeline`` bound to
    one ``MeshPlacement``: level bitsets stay mesh-resident across batches,
    (with ``fused_classify=True``) classification comes back fused from the
    shard_map body, and the jitted step executables are shared across levels
    and placements of the same mesh through ``ops.EXEC_CACHE``.
    ``fused_classify=False`` selects the legacy step bodies and host
    classification — the baseline path.
    """
    from ..kernels.intersect.ops import LevelPipeline
    from .placement import MeshPlacement

    placement = MeshPlacement(mesh, pair_axes=pair_axes, word_axis=word_axis)

    def factory(bits: np.ndarray, parent_counts: np.ndarray, tau: int):
        return LevelPipeline(
            bits,
            parent_counts,
            tau=tau,
            placement=placement,
            fused_classify=fused_classify,
            locality_sort=locality_sort,
        )

    return factory


def make_sharded_intersect(
    mesh: Mesh,
    *,
    pair_axes: tuple[str, ...] = ("data",),
    word_axis: str | None = None,
):
    """Drop-in ``intersect_fn`` for ``mine_preprocessed`` running on a mesh.

    The pre-pipeline injection contract: classification stays on the host
    and the bitsets are re-placed per batch (one fresh ``LevelPipeline``
    each call). Kept for compatibility; new code should prefer
    :func:`make_sharded_pipeline`.
    """
    from ..kernels.intersect.ops import LevelPipeline
    from .placement import MeshPlacement

    placement = MeshPlacement(mesh, pair_axes=pair_axes, word_axis=word_axis)

    def intersect_fn(bits: np.ndarray, pairs: np.ndarray, write_children: bool):
        pipe = LevelPipeline(
            bits,
            np.zeros(bits.shape[0], dtype=np.int64),
            tau=0,
            placement=placement,
            fused_classify=False,
        )
        child, counts, _ = pipe.submit(pairs, write_children).result()
        return child, counts

    return intersect_fn
