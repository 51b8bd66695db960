"""Bitset placement layer: ONE abstraction for where level bitsets live.

Before this module existed, bitset placement was hard-coded three different
ways: ``kernels.intersect.ops.LevelPipeline`` branched on engine strings and
assumed a single device, ``core.sharded`` carried its own device-put /
pair-bucketing plumbing, and ``service.store`` pinned every version to a
single-device cache.  A :class:`BitsetPlacement` now answers the four
questions every consumer was answering ad hoc:

1. **residency** — how do a level's parent bitsets (and popcounts) become
   resident for the duration of a BFS level (:meth:`~BitsetPlacement.prepare`),
   and how does a long-lived array (the service's ``DatasetStore``) get
   placed once per version (:meth:`~BitsetPlacement.put_bits`);
2. **padding** — what batch sizes keep executables reused
   (:meth:`~BitsetPlacement.padded_size`): power-of-two buckets on a single
   device, additionally rounded to equal per-shard blocks on a mesh;
3. **dispatch** — how one padded pair batch executes
   (:meth:`~BitsetPlacement.dispatch`): host numpy, single-device jnp/pallas
   kernels, or a ``shard_map`` body with a word-axis popcount ``psum``;
4. **layout** — what word-tile multiple keeps stored bitsets placeable with
   zero re-packing (:attr:`~BitsetPlacement.store_word_tile`).

The same four answers serve two workloads: the mining level batches
(:meth:`~BitsetPlacement.prepare` / :meth:`~BitsetPlacement.dispatch`,
orchestrated by ``kernels.intersect.ops.LevelPipeline``) and the privacy
risk engine's record-coverage queries
(:meth:`~BitsetPlacement.prepare_coverage` /
:meth:`~BitsetPlacement.coverage_dispatch`, orchestrated by
``kernels.coverage.ops.CoverageEngine``) — itemset-level and record-level
questions over the same resident bitsets.

The generic batch orchestration (locality sort, async handles, padding
strips, inverse permutation) lives once in
``kernels.intersect.ops.LevelPipeline``, which takes a placement instead of
branching on engine strings.  All placements are bit-identical on mining
results and per-level counters (property-tested in ``tests/test_placement.py``
and the 8-device drivers in ``tests/test_sharded_driver.py`` /
``tests/test_mesh_service.py``).

Implementations
---------------

* :class:`HostPlacement` — numpy on the host; no padding, eager dispatch.
* :class:`DevicePlacement` — one JAX device (``jnp`` oracle under jit or the
  Pallas kernels); parent bitsets uploaded once per level, executables bound
  per power-of-two bucket through the process-wide ``EXEC_CACHE``.
* :class:`MeshPlacement` — SPMD mesh: candidate pairs shard over the
  ``data`` (+``pod``) axes, bitset **words** shard over the ``model`` axis
  (row-parallelism for datasets whose bitset rows exceed one device), and
  per-shard partial popcounts are ``psum``-ed — the only collective in the
  level body, mirroring the paper's "no inter-thread communication"
  property (§4.4.4).

``make_placement`` / ``resolve_placement`` are the one factory the driver,
the service and the launchers all go through.
"""

from __future__ import annotations

from typing import Any, Protocol, runtime_checkable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.intersect import intersect as _kernels
from ..kernels.intersect import ops as _ops
from ..obs import cost as _obs_cost
from ..obs import metrics as _om
from .bitops import popcount_rows

__all__ = [
    "BitsetPlacement",
    "HostPlacement",
    "DevicePlacement",
    "MeshPlacement",
    "is_compile_refusal",
    "is_device_failure",
    "make_placement",
    "resolve_interpret",
    "resolve_placement",
    "set_fault_hook",
]

# -- fault seam --------------------------------------------------------------
#
# Device and mesh dispatch paths call ``_guard(site)`` immediately before
# executing on the accelerator. The hook is the one process-wide seam both
# the fault-injection harness (``repro.service.faults``) and ad-hoc chaos
# experiments use to simulate XLA OOMs / device loss without touching the
# kernels; production leaves it None (a single attribute read per batch).
# Host dispatch is deliberately unguarded — it is the degradation target and
# must stay failure-free.

_fault_hook = None


def set_fault_hook(hook):
    """Install ``hook(site: str)`` ahead of every device/mesh dispatch
    (sites: "dispatch", "frontier", "coverage"). Returns the previous hook
    so callers can restore it."""
    global _fault_hook
    prev, _fault_hook = _fault_hook, hook
    return prev


_DISPATCHES = _om.counter(
    "repro_placement_dispatch_total",
    "Placement-layer dispatches by seam and backend kind.",
    ("site", "kind"),
)


def _count_dispatch(site: str, kind: str) -> None:
    _DISPATCHES.inc(site=site, kind=kind)


def _guard(site: str, kind: str = "device") -> None:
    # metrics first: a dispatch that the fault hook kills still happened
    # (chaos runs want to see attempted-vs-degraded rates). Host dispatch
    # never routes through here — it must stay failure-free (see above) —
    # so HostPlacement methods call _count_dispatch directly.
    _count_dispatch(site, kind)
    _obs_cost.add(device_dispatches=1)
    if _fault_hook is not None:
        _fault_hook(site)


# Substrings that mark an exception as an accelerator-runtime failure (XLA
# OOM, device loss, transfer errors) rather than a programming error. The
# service's degradation path only retries/degrades on these.
_DEVICE_FAILURE_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "out of memory",
    "OUT_OF_MEMORY",
    "DEVICE_LOST",
    "device lost",
    "FAILED_PRECONDITION: device",
    "DATA_LOSS",
)

# Substrings of the chip compiler's refusals. A kernel or program the
# compiler rejects fails the same way on every retry, so it is a bug to
# surface, never a device fault to degrade around — even though a
# fast-memory refusal reads "RESOURCE_EXHAUSTED: Allocation (size=...)
# would exceed memory (size=1048576) :: ... space=smem".
_COMPILE_REFUSAL_MARKERS = (
    "Mosaic failed to compile",
    "compile permanent error",
    "would exceed memory (size=",
    "space=smem",
    "space=vmem",
)


def is_compile_refusal(exc: BaseException) -> bool:
    """Did the chip's compiler (or the Pallas lowering) refuse a program?"""
    msg = str(exc)
    return any(marker in msg for marker in _COMPILE_REFUSAL_MARKERS)


def is_device_failure(exc: BaseException) -> bool:
    """Is ``exc`` a device/runtime failure worth retrying on, or degrading
    Device/Mesh -> Host placement for — as opposed to a bug that would fail
    identically on the host or on every retry? Injected faults mark
    themselves with an ``is_device_failure`` attribute; compile and lowering
    refusals never count; real runtime faults are ``jax.errors.JaxRuntimeError``
    (what jaxlib raises) or carry a runtime marker in their message."""
    if getattr(exc, "is_device_failure", False):
        return True
    if is_compile_refusal(exc):
        return False
    if isinstance(exc, jax.errors.JaxRuntimeError):
        return True
    msg = str(exc)
    return any(marker in msg for marker in _DEVICE_FAILURE_MARKERS)


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Pallas interpret mode, decided once from the backend: interpreted on
    the CPU backend, compiled by Mosaic everywhere else. ``None`` takes the
    backend's answer; an explicit value that disagrees raises, so an
    interpreted kernel can never pass for the chip (nor a Mosaic kernel be
    sent to the CPU)."""
    backend = jax.default_backend()
    resolved = backend == "cpu"
    if interpret is not None and bool(interpret) != resolved:
        raise ValueError(
            f"interpret={interpret} on the {backend!r} backend: Pallas kernels "
            f"are {'interpreted' if resolved else 'compiled'} there — leave "
            "interpret unset"
        )
    return resolved


@runtime_checkable
class BitsetPlacement(Protocol):
    """Where bitsets live and how an intersect+classify batch executes.

    ``kind`` names the placement ("host" / "device" / "mesh");
    ``store_word_tile`` is the word-count multiple stored bitset matrices
    must be padded to so :meth:`put_bits` never re-packs (1 for host and
    the jnp device engine, 128 lanes for the Pallas engine, the word-shard
    count on a mesh).
    """

    kind: str
    store_word_tile: int

    def prepare(self, bits, parent_counts, tau: int, *, fused_classify: bool) -> Any:
        """Make one level's parent bitsets + popcounts resident; returns an
        opaque state consumed by :meth:`dispatch` for every batch of the
        level."""
        ...

    def padded_size(self, m: int, *, pad_buckets: bool = True) -> int:
        """Batch size ``m`` padded to this placement's executable bucket."""
        ...

    def warm_buckets(
        self, n_words: int, *, fused: bool, write_children: bool
    ) -> tuple[int, ...]:
        """Bucket sizes with an already-bound intersect executable for this
        placement signature at ``n_words`` words, ascending — empty when
        dispatch has no per-bucket executables (host eager, mesh
        shape-polymorphic). The sampling tier pads boundary recounts to
        these so refinement hits warm executables instead of minting new
        single-use buckets."""
        ...

    def dispatch(self, state: Any, padded_pairs: np.ndarray, write_children: bool):
        """Execute one padded batch; returns ``(child | None, counts,
        classes | None)`` as placement-native arrays (numpy or device;
        ``LevelPipeline`` materializes and strips padding)."""
        ...

    def put_bits(self, bits: np.ndarray):
        """Place a long-lived bitset matrix (the dataset store's cache)."""
        ...

    def prepare_coverage(self, bits):
        """Make an item bitset matrix resident for record-coverage queries
        (the privacy risk engine); returns an opaque state consumed by
        :meth:`coverage_dispatch` for every itemset batch."""
        ...

    def coverage_dispatch(self, state, padded_sets: np.ndarray, padded_weights: np.ndarray):
        """Execute one padded coverage batch (``kernels.coverage``):
        returns the ``(32, W)`` int32 accumulator as a placement-native
        array. Batch padding rows carry weight 0."""
        ...

    def prepare_frontier(self, itemsets: np.ndarray, counts: np.ndarray, n_symbols: int) -> Any:
        """Make one BFS level's *id table* resident for frontier ops
        (candidate generation + support tests). Host returns the exact
        ``ItemsetIndex`` of the reference path; device/mesh upload the
        padded id table and packed sorted parent key table."""
        ...

    def frontier_dispatch(self, state: Any, lo: int, hi: int, n_pairs: int):
        """Generate + support-test the candidate pairs of one prefix-group
        span. Host returns ``(CandidateBatch, ok)`` numpy (today's path);
        device/mesh return ``(pairs (bucket, 2), ok (bucket,))`` device
        arrays, padding rows marked not-ok."""
        ...

    def frontier_mask(self, state: Any, pairs, ok):
        """Neutralise pruned candidates (self-pairs -> CLASS_SKIP) without
        reordering; returns ``(pairs, n_ok)`` placement-native."""
        ...

    def frontier_partition(self, classes):
        """One compaction pass over fused class codes: returns ``(order,
        n_emit, n_store)`` placement-native, segments in candidate order."""
        ...

    def frontier_take(self, bits, rows: np.ndarray):
        """Gather stored child rows device-to-device into the layout the
        next level's :meth:`prepare` expects."""
        ...

    def release(self, state: Any) -> None:
        """Eagerly drop device buffers a :meth:`prepare` /
        :meth:`prepare_frontier` state owns (level retirement) — buffers the
        caller handed in stay alive."""
        ...

    def describe(self) -> dict:
        """Human/JSON-friendly placement info for ``/stats``."""
        ...


class HostPlacement:
    """Bitsets stay in host numpy; dispatch is eager and unpadded."""

    kind = "host"
    store_word_tile = 1

    def prepare(self, bits, parent_counts, tau: int, *, fused_classify: bool):
        return (
            np.asarray(bits),
            np.asarray(parent_counts, dtype=np.int64),
            int(tau),
            fused_classify,
        )

    def padded_size(self, m: int, *, pad_buckets: bool = True) -> int:
        return m  # host gathers have no executable buckets to reuse

    def warm_buckets(
        self, n_words: int, *, fused: bool, write_children: bool
    ) -> tuple[int, ...]:
        return ()

    def dispatch(self, state, padded_pairs: np.ndarray, write_children: bool):
        _count_dispatch("dispatch", "host")
        bits, pc, tau, fused = state
        a = bits[padded_pairs[:, 0]]
        b = bits[padded_pairs[:, 1]]
        child = np.bitwise_and(a, b)
        counts = popcount_rows(child)
        classes = None
        if fused:
            minp = np.minimum(pc[padded_pairs[:, 0]], pc[padded_pairs[:, 1]])
            classes = _ops.classify_counts_host(counts, minp, tau)
        return (child if write_children else None), counts, classes

    def put_bits(self, bits: np.ndarray):
        return np.ascontiguousarray(bits)

    def prepare_coverage(self, bits):
        return np.ascontiguousarray(np.asarray(bits, dtype=np.uint32))

    def coverage_dispatch(self, state, padded_sets, padded_weights):
        from ..kernels.coverage.ref import coverage_accumulate_host

        _count_dispatch("coverage", "host")
        return coverage_accumulate_host(state, padded_sets, padded_weights)

    # -- frontier (the numpy reference path, bit-identical by construction) --

    def prepare_frontier(self, itemsets, counts, n_symbols: int):
        from .support import ItemsetIndex

        return ItemsetIndex(itemsets, counts, n_symbols=n_symbols)

    def frontier_dispatch(self, state, lo: int, hi: int, n_pairs: int):
        """Numpy reference: materialise the span's candidate batch
        (``repeat``/``cumsum``) and run the packed-key support test — exactly
        the pre-frontier host path, shifted behind the placement API."""
        from .prefix import CandidateBatch, Level, generate_candidates
        from .support import support_test

        _count_dispatch("frontier", "host")
        itemsets = state.itemsets[lo:hi].astype(np.int32)
        counts = np.zeros(hi - lo, dtype=np.int64)
        batch = generate_candidates(Level(k=0, itemsets=itemsets, counts=counts, bits=None))
        batch = CandidateBatch(
            i_idx=batch.i_idx + lo, j_idx=batch.j_idx + lo, itemsets=batch.itemsets
        )
        return batch, support_test(batch.itemsets, state)

    def frontier_mask(self, state, pairs, ok):
        return pairs[ok], int(ok.sum())

    def frontier_partition(self, classes):
        order = np.argsort(classes, kind="stable")
        return order, int((classes == 1).sum()), int((classes == 2).sum())

    def release(self, state) -> None:
        pass  # host arrays are the caller's; nothing device-side to drop

    def describe(self) -> dict:
        return {"kind": self.kind, "engine": "numpy", "devices": 0}

    def __repr__(self) -> str:
        return "HostPlacement()"


class DevicePlacement:
    """One JAX device: the jnp oracle under jit or the Pallas kernels.

    Parent bitsets and popcounts upload once per level; every batch ships
    only the (tiny) padded pair list, and the bound dispatch callable is
    shared process-wide per bucket shape through ``ops.EXEC_CACHE``.

    With the Pallas engine the word axis is padded to a multiple of 128
    lanes (``store_word_tile``: the dataset store builds bitsets that way,
    so only foreign callers pay a pad), and the indexed kernels read the
    dense ``(t, S, 128)`` row layout
    (``kernels.intersect.intersect.as_dense_rows``, W padded to a multiple
    of 1,024 words): :meth:`prepare` converts a level once, and the kernels
    hand children back in that layout so the next level chains without a
    copy. The coverage kernel keeps the ``(t, 1, W)`` layout of ``as_rows``.
    ``interpret`` is resolved from the backend (:func:`resolve_interpret`).
    """

    kind = "device"

    def __init__(
        self,
        engine: str = "jnp",
        *,
        interpret: bool | None = None,
        indexed: bool = True,
        block_pairs: int = 8,
        block_words: int = 8192,
    ):
        if engine not in ("jnp", "pallas"):
            raise ValueError(f"DevicePlacement engine must be jnp|pallas, got {engine!r}")
        self.engine = engine
        self.interpret = resolve_interpret(interpret)
        self.indexed = indexed
        self.block_pairs = block_pairs
        self.block_words = block_words
        pallas = engine == "pallas"
        self.store_word_tile = _ops.LANES if pallas else 1
        self._row_layout = pallas and indexed
        # scalar-prefetched pair tables must fit SMEM (LevelPipeline chunks)
        self.max_dispatch_pairs = _ops.MAX_INDEXED_PAIRS if self._row_layout else None
        # gathered write path: donate the gathered operand on accelerator
        # backends so the child output aliases its buffer; CPU donation is
        # unsupported (warning + copy), so gate on backend.
        self.donate = jax.default_backend() in ("tpu", "gpu")

    def _resident(self, bits, layout=None):
        """``(array, owned, n_words)``: bits on the device, lane-padded for
        the Pallas engine and then in ``layout`` when given; ``owned`` when
        this call created the array, so :meth:`release` may drop it;
        ``n_words`` the lane-padded words of a row before ``layout``."""
        out = bits if isinstance(bits, jax.Array) else jnp.asarray(bits)
        if self.engine == "pallas":
            out = _ops.pad_words(out)
        n_words = int(np.prod(out.shape[1:]))
        if layout is not None:
            out = layout(out)
        return out, out is not bits, n_words

    def prepare(self, bits, parent_counts, tau: int, *, fused_classify: bool):
        dev, owned, n_words = self._resident(
            bits, _kernels.as_dense_rows if self._row_layout else None
        )
        return (
            dev,
            jnp.asarray(np.asarray(parent_counts), dtype=jnp.int32),
            jnp.int32(int(tau)),
            n_words,
            fused_classify,
            owned,
        )

    def word_blocks(self, state, write_children: bool) -> int | None:
        """Grid steps per pair of the indexed kernels on a prepared level."""
        if not self._row_layout:
            return None
        return _kernels.row_blocks(int(state[0].shape[1]), write_children)

    def padded_size(self, m: int, *, pad_buckets: bool = True) -> int:
        return _ops.next_bucket(m) if pad_buckets else m

    def warm_buckets(
        self, n_words: int, *, fused: bool, write_children: bool
    ) -> tuple[int, ...]:
        # this placement's dispatch keys are the 10-tuples built below;
        # keep the positional reads in lockstep with that key layout
        buckets = set()
        for key in _ops.EXEC_CACHE.keys():
            if (
                len(key) == 10
                and key[0] == self.engine
                and key[1] == self.indexed
                and key[2] == fused
                and key[3] == write_children
                and key[4] == n_words
                and isinstance(key[5], int)
                and key[6] == self.block_pairs
                and key[7] == self.block_words
                and key[8] == self.interpret
                and key[9] == self.donate
            ):
                buckets.add(int(key[5]))
        return tuple(sorted(buckets))

    def dispatch(self, state, padded_pairs: np.ndarray, write_children: bool):
        _guard("dispatch")
        bits, pc, tau, n_words, fused, _owned = state
        bucket = int(padded_pairs.shape[0])
        key = (
            self.engine,
            self.indexed,
            fused,
            write_children,
            n_words,
            bucket,
            self.block_pairs,
            self.block_words,
            self.interpret,
            self.donate,
        )
        fn = _ops.EXEC_CACHE.get(
            key,
            lambda: _ops.build_engine_dispatch(
                self.engine,
                indexed=self.indexed,
                fused_classify=fused,
                write_children=write_children,
                n_words=n_words,
                bucket=bucket,
                block_pairs=self.block_pairs,
                block_words=self.block_words,
                interpret=self.interpret,
                donate=self.donate,
            ),
        )
        return fn(bits, jnp.asarray(padded_pairs), pc, tau)

    def put_bits(self, bits: np.ndarray):
        return jnp.asarray(bits)

    def prepare_coverage(self, bits):
        return self._resident(bits, _kernels.as_rows if self._row_layout else None)[0]

    def coverage_dispatch(self, state, padded_sets, padded_weights):
        _guard("coverage")
        from ..kernels.coverage import ops as _cov

        n_words = int(state.shape[-1])
        bucket, width = int(padded_sets.shape[0]), int(padded_sets.shape[1])
        key = (
            "coverage",
            self.engine,
            width,
            n_words,
            bucket,
            self.block_words,
            self.interpret,
        )
        fn = _cov.EXEC_CACHE.get(
            key,
            lambda: _cov.build_coverage_dispatch(
                self.engine,
                n_words=n_words,
                block_words=self.block_words,
                interpret=self.interpret,
            ),
        )
        return fn(state, jnp.asarray(padded_sets), jnp.asarray(padded_weights))

    # -- frontier -----------------------------------------------------------

    def prepare_frontier(self, itemsets, counts, n_symbols: int):
        from ..kernels.frontier import ops as _fops

        itemsets = np.asarray(itemsets, dtype=np.int32)
        ids, keys, t_pad = _fops.make_level_tables(itemsets, n_symbols)
        from .prefix import group_reps

        return {
            "k": int(itemsets.shape[1]),
            "n_symbols": int(n_symbols),
            "t": int(itemsets.shape[0]),
            "t_pad": t_pad,
            "ids": jnp.asarray(ids),
            "keys": jnp.asarray(keys),
            "reps": group_reps(itemsets).astype(np.int32),
        }

    def frontier_dispatch(self, state, lo: int, hi: int, n_pairs: int):
        _guard("frontier")
        from ..kernels.frontier import ops as _fops

        row_bucket, bucket = _fops.gen_buckets(hi - lo, n_pairs)
        key = (
            "gen-support",
            state["k"],
            state["n_symbols"],
            state["t_pad"],
            row_bucket,
            bucket,
        )
        fn = _fops.EXEC_CACHE.get(
            key,
            lambda: _fops.build_gen_support(
                k=state["k"],
                n_symbols=state["n_symbols"],
                t_pad=state["t_pad"],
                row_bucket=row_bucket,
                bucket=bucket,
            ),
        )
        reps_b = _fops.pad_reps(state["reps"][lo:hi], row_bucket)
        return fn(
            state["ids"],
            state["keys"],
            jnp.asarray(reps_b),
            jnp.int32(lo),
            jnp.int32(n_pairs),
        )

    def frontier_mask(self, state, pairs, ok):
        from ..kernels.frontier import ops as _fops

        fn = _fops.mask_pruned  # module-level jit: re-traces per shape
        return fn(pairs, ok)

    def frontier_partition(self, classes):
        from ..kernels.frontier import ops as _fops

        fn = _fops.partition  # module-level jit: re-traces per shape
        return fn(classes)

    def frontier_take(self, bits, rows: np.ndarray):
        """Gather stored child rows (device-to-device) for the next level."""
        return bits[jnp.asarray(rows)]

    def release(self, state) -> None:
        """Retire a level eagerly: delete the device buffers this placement
        uploaded itself. Arrays the caller passed in (an already-resident
        ``jax.Array`` — e.g. the dataset store's version cache, or child
        bitsets chained from the previous level) are left alone."""
        if isinstance(state, dict):  # frontier state: ids/keys are uploads
            for name in ("ids", "keys"):
                arr = state.get(name)
                if isinstance(arr, jax.Array) and not arr.is_deleted():
                    arr.delete()
            return
        if isinstance(state, tuple) and len(state) == 6:
            bits, pc, *_rest, owned = state
            if owned:
                for arr in (bits, pc):
                    if isinstance(arr, jax.Array) and not arr.is_deleted():
                        arr.delete()

    def describe(self) -> dict:
        return {
            "kind": self.kind,
            "engine": self.engine,
            "devices": 1,
            "backend": jax.default_backend(),
            "indexed": self.indexed,
            "interpret": self.interpret,
        }

    def __repr__(self) -> str:
        return f"DevicePlacement(engine={self.engine!r})"


# Which (padded_words, bucket) shapes each mesh step-fn variant has already
# traced. Mesh executables are shape-polymorphic jits (one EXEC_CACHE entry
# per variant, retraced per input shape inside jax's own jit cache), so the
# warm-bucket question — "which batch sizes are free?" — is answered by
# *recording dispatched shapes* rather than enumerating cache keys the way
# DevicePlacement does. Stale entries after an exec-cache reset are harmless:
# a warm hint only changes padding, never results.
_MESH_WARM: dict[tuple, set[tuple[int, int]]] = {}


class MeshPlacement:
    """SPMD mesh: pairs shard over ``pair_axes``, words over ``word_axis``.

    The level body is a ``shard_map`` whose only collective is the word-axis
    popcount ``psum`` (classification happens after it, per pair shard, with
    zero extra communication).  Stored bitset matrices placed through
    :meth:`put_bits` must have a word count that is a multiple of
    :attr:`store_word_tile` (= the word-shard count) — the ``DatasetStore``
    aligns its tile to this, so serving a mesh never re-packs bits.

    ``word_axis`` may be one axis name or a tuple of names — the hybrid
    DCN x ICI layout shards words over both the in-host and the cross-host
    axes.  A mesh whose devices span processes flips the placement into its
    process-spanning variants: host arrays are placed shard-by-shard with
    ``jax.make_array_from_callback`` (a plain ``device_put`` cannot address
    remote shards), and the step bodies all-gather per-pair outputs over the
    pair axes (``replicate=True`` in ``core.sharded``) so counts and class
    codes materialize host-side on every process without touching
    non-addressable shards.
    """

    kind = "mesh"

    def __init__(
        self,
        mesh: Mesh,
        *,
        pair_axes: tuple[str, ...] = ("data",),
        word_axis: str | tuple[str, ...] | None = None,
        device_frontier: bool | None = None,
    ):
        self.mesh = mesh
        self.pair_axes = tuple(pair_axes)
        self.word_axis = tuple(word_axis) if isinstance(word_axis, list) else word_axis
        # mesh frontier ops re-shard stored children between levels, so each
        # batch runs a handful of small collectives (partition cumsum, child
        # all-gather). Real accelerator backends do these in microseconds;
        # the forced-host CPU mesh emulates them with thread rendezvous that
        # stalls for seconds. Same gating idiom as the donating kernels:
        # default on for tpu/gpu, opt-in (tests, experiments) on cpu.
        self.use_device_frontier = (
            jax.default_backend() in ("tpu", "gpu")
            if device_frontier is None
            else device_frontier
        )
        self.pair_shards = int(np.prod([mesh.shape[a] for a in self.pair_axes]))
        word_axes = (
            (word_axis,) if isinstance(word_axis, str) else tuple(word_axis or ())
        )
        self.word_shards = int(np.prod([mesh.shape[a] for a in word_axes])) if word_axes else 1
        self.store_word_tile = self.word_shards
        self.spans_processes = (
            len({d.process_index for d in mesh.devices.flat}) > 1
        )
        self._bits_sharding = NamedSharding(mesh, P(None, self.word_axis))
        self._pairs_sharding = NamedSharding(mesh, P(self.pair_axes, None))
        self._minp_sharding = NamedSharding(mesh, P(self.pair_axes))
        self._repl_sharding = NamedSharding(mesh, P())

    def _put(self, arr, sharding):
        """Place one array under ``sharding`` — the process-spanning variant
        assembles it from per-shard callbacks (every process feeds its own
        addressable shards from the replicated host copy)."""
        if self.spans_processes and not isinstance(arr, jax.Array):
            host = np.asarray(arr)
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx]
            )
        return jax.device_put(arr, sharding)

    # the jitted shard_map bodies are bound once per (mesh, axes, variant)
    # through EXEC_CACHE, so executables are shared across levels, placements
    # of the same mesh, and mining requests (warm-start on the service).
    def _step_fn(self, fused: bool, write_children: bool):
        from . import sharded as _sh

        replicate = self.spans_processes
        key = (
            "mesh",
            self.mesh,
            self.pair_axes,
            self.word_axis,
            fused,
            write_children,
            replicate,
        )

        def build():
            if fused:
                builder = (
                    _sh.sharded_level_classify_step
                    if write_children
                    else _sh.sharded_level_classify_count_step
                )
            else:
                builder = (
                    _sh.sharded_level_step if write_children else _sh.sharded_level_count_step
                )
            fn, _, _ = builder(
                self.mesh,
                pair_axes=self.pair_axes,
                word_axis=self.word_axis,
                replicate=replicate,
            )
            return fn

        return _ops.EXEC_CACHE.get(key, build)

    def prepare(self, bits, parent_counts, tau: int, *, fused_classify: bool):
        owned = not isinstance(bits, jax.Array)  # fresh placement -> releasable
        pc = np.asarray(parent_counts, dtype=np.int32)
        return (
            self.put_bits(bits),
            pc,
            jnp.asarray(pc),  # device copy for device-generated pair batches
            jnp.int32(int(tau)),
            fused_classify,
            owned,
        )

    def padded_size(self, m: int, *, pad_buckets: bool = True) -> int:
        from .balance import balanced_blocks

        bucket = _ops.next_bucket(m) if pad_buckets else m
        padded_m, _ = balanced_blocks(bucket, self.pair_shards)
        return padded_m

    def _warm_key(self, fused: bool, write_children: bool) -> tuple:
        return ("mesh", self.mesh, self.pair_axes, self.word_axis, fused, write_children)

    def warm_buckets(
        self, n_words: int, *, fused: bool, write_children: bool
    ) -> tuple[int, ...]:
        # mesh step fns are shape-polymorphic jits, so "warm" means "this
        # (words, bucket) shape was already traced" — dispatched shapes are
        # recorded in _MESH_WARM (see its note). Queries arrive at the
        # store's word count; executables trace at the shard-padded width.
        pw = n_words + (-n_words) % max(self.word_shards, 1)
        shapes = _MESH_WARM.get(self._warm_key(fused, write_children), ())
        return tuple(sorted(b for w, b in shapes if w == pw))

    def dispatch(self, state, padded_pairs, write_children: bool):
        _guard("dispatch", "mesh")
        bits, pc, pc_dev, tau, fused, _owned = state
        device_pairs = isinstance(padded_pairs, jax.Array)
        pairs_j = self._put(
            padded_pairs if device_pairs else np.ascontiguousarray(padded_pairs),
            self._pairs_sharding,
        )
        _MESH_WARM.setdefault(self._warm_key(fused, write_children), set()).add(
            (int(bits.shape[1]), int(padded_pairs.shape[0]))
        )
        if not fused:
            fn = self._step_fn(False, write_children)
            if write_children:
                child, cnt = fn(bits, pairs_j)
                return child, cnt, None
            return None, fn(bits, pairs_j), None
        # padding rows are self-pairs, so their minp is their parent count and
        # the fused classifier marks them CLASS_SKIP (count == min parent
        # count). Device-generated frontier batches never leave the device:
        # their minp gathers from the resident count copy.
        if device_pairs:
            minp = jnp.minimum(pc_dev[padded_pairs[:, 0]], pc_dev[padded_pairs[:, 1]])
            minp_j = jax.device_put(minp, self._minp_sharding)
        else:
            minp_j = self._put(
                np.minimum(pc[padded_pairs[:, 0]], pc[padded_pairs[:, 1]]),
                self._minp_sharding,
            )
        fn = self._step_fn(True, write_children)
        if write_children:
            return fn(bits, pairs_j, minp_j, tau)
        cnt, cls = fn(bits, pairs_j, minp_j, tau)
        return None, cnt, cls

    def put_bits(self, bits):
        """Word-shard a bitset matrix over the mesh.  Host arrays are padded
        to the shard multiple first (zero words = no rows); arrays already
        tile-aligned — the dataset store's layout — ship with zero re-packing
        copies, and jax arrays already on the mesh reshard in place."""
        if not isinstance(bits, jax.Array):
            bits = _ops.pad_words(np.ascontiguousarray(bits), self.word_shards)
        return self._put(bits, self._bits_sharding)

    def prepare_coverage(self, bits):
        return self.put_bits(bits)

    def coverage_dispatch(self, state, padded_sets, padded_weights):
        _guard("coverage", "mesh")
        from ..kernels.coverage import ops as _cov
        from . import sharded as _sh

        width = int(padded_sets.shape[1])
        key = ("coverage-mesh", self.mesh, self.pair_axes, self.word_axis, width)
        fn = _cov.EXEC_CACHE.get(
            key,
            lambda: _sh.sharded_coverage_step(
                self.mesh,
                pair_axes=self.pair_axes,
                word_axis=self.word_axis,
                n_set_items=width,
            )[0],
        )
        sets_j = self._put(np.ascontiguousarray(padded_sets), self._pairs_sharding)
        wt_j = self._put(np.ascontiguousarray(padded_weights), self._minp_sharding)
        return fn(state, sets_j, wt_j)

    # -- frontier -----------------------------------------------------------

    def prepare_frontier(self, itemsets, counts, n_symbols: int):
        from ..kernels.frontier import ops as _fops
        from .prefix import group_reps

        itemsets = np.asarray(itemsets, dtype=np.int32)
        ids, keys, t_pad = _fops.make_level_tables(itemsets, n_symbols)
        repl = NamedSharding(self.mesh, P(None, None))
        return {
            "k": int(itemsets.shape[1]),
            "n_symbols": int(n_symbols),
            "t": int(itemsets.shape[0]),
            "t_pad": t_pad,
            # id/key tables replicate over the mesh (the shared-memory
            # analogue); only the pair axis of the support test shards
            "ids": self._put(np.asarray(ids), repl),
            "keys": self._put(np.asarray(keys), repl),
            "reps": group_reps(itemsets).astype(np.int32),
        }

    def frontier_dispatch(self, state, lo: int, hi: int, n_pairs: int):
        _guard("frontier", "mesh")
        from ..kernels.frontier import ops as _fops
        from ..kernels.frontier.frontier import pack_params
        from . import sharded as _sh

        row_bucket = _fops.next_bucket(hi - lo, 16)
        bucket = self.padded_size(n_pairs)
        gen_fn = _fops.EXEC_CACHE.get(
            ("gen", row_bucket, bucket), lambda: _fops.build_gen(bucket=bucket)
        )
        reps_b = _fops.pad_reps(state["reps"][lo:hi], row_bucket)
        pairs, valid = gen_fn(jnp.asarray(reps_b), jnp.int32(lo), jnp.int32(n_pairs))
        if state["k"] < 2:  # candidate width 2: both subsets stored parents
            return pairs, valid
        bits_, ipw, _ = pack_params(state["n_symbols"], state["k"])
        key = (
            "mesh-support",
            self.mesh,
            self.pair_axes,
            state["k"],
            state["n_symbols"],
            state["t_pad"],
            bucket,
            self.spans_processes,
        )
        fn = _fops.EXEC_CACHE.get(
            key,
            lambda: _sh.sharded_frontier_support_step(
                self.mesh,
                pair_axes=self.pair_axes,
                k=state["k"],
                t_pad=state["t_pad"],
                bits=bits_,
                ipw=ipw,
                replicate=self.spans_processes,
            )[0],
        )
        if self.spans_processes:
            # generated on the default device; re-place shard-by-shard (a
            # cross-process device_put reshard is not addressable)
            pairs_sh = self._put(np.asarray(pairs), self._pairs_sharding)
            valid_sh = self._put(np.asarray(valid), self._minp_sharding)
        else:
            pairs_sh = jax.device_put(pairs, self._pairs_sharding)
            valid_sh = jax.device_put(valid, self._minp_sharding)
        ok = fn(state["ids"], state["keys"], pairs_sh, valid_sh)
        return pairs, ok

    def _replicated(self, arr):
        """Replicate a per-pair vector over the whole mesh. The frontier
        mask/partition bodies index and scatter over the full batch; fed a
        pair-sharded array, jit propagates its ``P(pair_axes)`` spec into a
        scatter that has no mesh in scope and fails. Replicated inputs keep
        those bodies plain single-program code on every device."""
        if self.spans_processes:
            return arr  # step bodies already all-gathered (replicate=True)
        return jax.device_put(arr, self._repl_sharding)

    def frontier_mask(self, state, pairs, ok):
        from ..kernels.frontier import ops as _fops

        fn = _fops.mask_pruned  # module-level jit: re-traces per shape
        return fn(self._replicated(pairs), self._replicated(ok))

    def frontier_partition(self, classes):
        from ..kernels.frontier import ops as _fops

        fn = _fops.partition  # module-level jit: re-traces per shape
        return fn(self._replicated(classes))

    def frontier_take(self, bits, rows: np.ndarray):
        """Gather stored child rows on the mesh: reshard to the level-bits
        layout (rows whole on every pair shard, words split) first, so the
        row gather needs no collective and its output is already placed the
        way the next level's :meth:`prepare` expects."""
        return jax.device_put(bits, self._bits_sharding)[jnp.asarray(rows)]

    def release(self, state) -> None:
        """Eager level retirement on the mesh — same ownership rule as the
        single-device placement (see :meth:`DevicePlacement.release`)."""
        if isinstance(state, dict):
            for name in ("ids", "keys"):
                arr = state.get(name)
                if isinstance(arr, jax.Array) and not arr.is_deleted():
                    arr.delete()
            return
        if isinstance(state, tuple) and len(state) == 6:
            bits, _pc, pc_dev, *_rest, owned = state
            if owned:
                for arr in (bits, pc_dev):
                    if isinstance(arr, jax.Array) and not arr.is_deleted():
                        arr.delete()

    def describe(self) -> dict:
        return {
            "kind": self.kind,
            "devices": int(np.prod(list(self.mesh.shape.values()))),
            "mesh_shape": dict(self.mesh.shape),
            "pair_axes": list(self.pair_axes),
            "word_axis": (
                list(self.word_axis)
                if isinstance(self.word_axis, tuple)
                else self.word_axis
            ),
            "pair_shards": self.pair_shards,
            "word_shards": self.word_shards,
            "spans_processes": self.spans_processes,
        }

    def __repr__(self) -> str:
        return (
            f"MeshPlacement(shape={dict(self.mesh.shape)}, "
            f"pair_axes={self.pair_axes}, word_axis={self.word_axis!r})"
        )


def make_placement(
    engine: str,
    *,
    interpret: bool | None = None,
    indexed: bool = True,
    block_pairs: int = 8,
    block_words: int = 8192,
) -> BitsetPlacement:
    """Placement for an engine name: ``numpy``/``host`` -> host,
    ``jnp``/``pallas`` -> single device."""
    if engine in ("numpy", "host"):
        return HostPlacement()
    if engine in ("jnp", "pallas"):
        return DevicePlacement(
            engine,
            interpret=interpret,
            indexed=indexed,
            block_pairs=block_pairs,
            block_words=block_words,
        )
    raise ValueError(
        f"no placement for engine {engine!r} (expected numpy|jnp|pallas; "
        "meshes are constructed explicitly via MeshPlacement)"
    )


def resolve_placement(config) -> BitsetPlacement:
    """The one factory between ``KyivConfig`` and a placement.

    ``config.placement`` wins when set (a :class:`BitsetPlacement` instance,
    or an engine-name string resolved through :func:`make_placement`);
    otherwise the legacy ``config.engine`` string selects host or
    single-device placement with the config's kernel knobs.
    """
    p = getattr(config, "placement", None)
    if p is not None and not isinstance(p, str):
        return p
    engine = p if isinstance(p, str) else config.engine
    return make_placement(
        engine,
        interpret=getattr(config, "interpret", None),
        indexed=getattr(config, "indexed_kernel", True),
    )
