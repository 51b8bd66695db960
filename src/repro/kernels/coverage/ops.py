"""Engine dispatch + batching for the coverage kernels.

Mirrors the structure of ``kernels.intersect.ops`` at a smaller scale: the
engine-specific binding lives in :func:`build_coverage_dispatch` (one bound
callable per executable bucket, shared process-wide through
:data:`EXEC_CACHE` so warm service requests never re-bind), and the generic
orchestration — batch splitting, bucket padding with weight-0 rows,
cross-batch accumulation — lives once in
:class:`CoverageEngine`, which is placement-generic: a
``repro.core.placement.BitsetPlacement`` supplies residency
(``prepare_coverage``) and per-batch execution (``coverage_dispatch``), so
host numpy, single-device jnp/pallas and the word-sharded mesh all serve the
same record-risk queries bit-identically.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ...core.exec_cache import exec_family
from ...obs import metrics as _om
from ...obs.trace import span as _obs_span
from ..intersect.ops import SMEM_PREFETCH_WORDS, _largest_divisor_tile
from . import coverage as _k
from .ref import acc_to_record_counts, coverage_accumulate_ref

_COV_BATCHES = _om.counter(
    "repro_coverage_batches_total",
    "Coverage accumulator batches dispatched through the placement.",
)

__all__ = [
    "EXEC_CACHE",
    "CoverageEngine",
    "build_coverage_dispatch",
    "coverage_cache_stats",
    "reset_coverage_cache",
]

# Coverage executables are the ``coverage`` family of the process-wide
# ``repro.core.exec_cache`` registry — one shared cache, per-family counters,
# one ``executables`` section in /stats.
EXEC_CACHE = exec_family("coverage")

_JIT_COVERAGE_REF = None  # bound lazily so importing this module stays cheap


def _jit_coverage_ref():
    global _JIT_COVERAGE_REF
    if _JIT_COVERAGE_REF is None:
        import jax

        _JIT_COVERAGE_REF = jax.jit(coverage_accumulate_ref)
    return _JIT_COVERAGE_REF


def coverage_cache_stats() -> dict:
    """Snapshot of the coverage executable-bucket cache (entries/hits/misses)."""
    return EXEC_CACHE.stats()


def reset_coverage_cache() -> None:
    EXEC_CACHE.clear()


def build_coverage_dispatch(
    engine: str,
    *,
    n_words: int,
    block_words: int,
    interpret: bool,
):
    """Bind one coverage executable bucket for a single-device engine:
    ``fn(bits, sets_j, weights_j) -> acc (32, W) int32`` (device array)."""
    if engine == "jnp":
        fn = _jit_coverage_ref()
        return lambda bits, sets_j, wt_j: fn(bits, sets_j, wt_j)
    if engine != "pallas":
        raise ValueError(f"engine must be jnp|pallas, got {engine!r}")
    bw = _largest_divisor_tile(n_words, block_words)
    return lambda bits, sets_j, wt_j: _k.coverage_accumulate_indexed(
        bits, sets_j, wt_j, block_words=bw, interpret=interpret
    )


class CoverageEngine:
    """Placement-generic batched coverage accumulation over one bitset matrix.

    Construction hands the item bitsets to the placement once
    (``placement.prepare_coverage`` — host array, single-device upload, or
    mesh word-sharding); every :meth:`accumulate` call then ships only the
    (tiny) itemset index batch. ``set_width`` bounds the itemset arity
    (normally ``kmax``); device executables bind per (arity, bucket) — at
    most ``kmax`` times a handful of buckets — so singleton batches never
    pay for k-way gathers.
    """

    def __init__(
        self,
        bits,
        *,
        placement,
        set_width: int,
        max_batch_sets: int | None = None,
    ):
        self.placement = placement
        self.set_width = max(1, int(set_width))
        self.n_words = int(bits.shape[1])
        # cap the per-dispatch working set (M * W int32 temporaries on the
        # jnp path) while keeping batches large enough to amortize dispatch
        self.max_batch_sets = max_batch_sets or max(
            256, (1 << 26) // max(self.n_words, 1)
        )
        if getattr(placement, "max_dispatch_pairs", None):
            # the indexed kernel prefetches the flattened (M * K,) set table
            # and (M,) weights into SMEM: bound M like the intersect pairs,
            # as a power of two so bucket padding stays inside the bound
            fit = SMEM_PREFETCH_WORDS // (self.set_width + 1)
            self.max_batch_sets = min(self.max_batch_sets, 1 << (fit.bit_length() - 1))
        self._state = placement.prepare_coverage(bits)

    def accumulate(
        self, sets: np.ndarray, weights: np.ndarray | None = None
    ) -> np.ndarray:
        """Weighted coverage accumulator over a batch of itemsets.

        ``sets`` is (M, k) int with k <= set_width; ``weights`` defaults to
        all-ones. Returns acc (32, n_words) int64, summed across dispatch
        batches.
        """
        sets = np.asarray(sets, dtype=np.int32)
        if sets.ndim != 2 or sets.shape[1] > self.set_width:
            raise ValueError(
                f"sets must be (M, <= {self.set_width}), got shape {sets.shape}"
            )
        m = sets.shape[0]
        total = np.zeros((32, self.n_words), dtype=np.int64)
        if m == 0:
            return total
        wt = (
            np.ones(m, dtype=np.int32)
            if weights is None
            else np.asarray(weights, dtype=np.int32)
        )
        with _obs_span("coverage.accumulate", sets=m):
            for s in range(0, m, self.max_batch_sets):
                chunk = sets[s : s + self.max_batch_sets]
                wchunk = wt[s : s + self.max_batch_sets]
                padded_m = self.placement.padded_size(chunk.shape[0])
                if padded_m != chunk.shape[0]:
                    pad = padded_m - chunk.shape[0]
                    chunk = np.pad(chunk, ((0, pad), (0, 0)), mode="edge")
                    wchunk = np.pad(wchunk, (0, pad))  # weight-0 padding rows
                _COV_BATCHES.inc()
                acc = self.placement.coverage_dispatch(self._state, chunk, wchunk)
                # mesh placements may pad the word axis; the pad words carry
                # no record bits, so slicing back to n_words is lossless
                total += np.asarray(acc)[:, : self.n_words].astype(np.int64)
        return total

    def record_counts(
        self, sets: np.ndarray, n_rows: int, weights: np.ndarray | None = None
    ) -> np.ndarray:
        """Per-record coverage counts (n_rows,) int64 for one itemset batch."""
        return acc_to_record_counts(self.accumulate(sets, weights), n_rows)
