"""Jit'd public wrappers around the intersection kernels, engine selection,
bucket padding, and the device-resident level pipeline.

The mining driver hands this module ragged pair lists; it pads them to shape
buckets (so device executables are reused across levels and batches),
dispatches to one of the engines and strips padding:

* ``numpy``  — host vectorised ``np.bitwise_and`` + popcount (``np.bitwise_count``
  on numpy>=2.0, an exact ``unpackbits`` fallback otherwise); the host
  reference engine.
* ``jnp``    — the jnp oracle under jit (XLA CPU/TPU).
* ``pallas`` — the Pallas kernels (interpreted on the CPU backend, compiled by
  Mosaic on the TPU — ``repro.core.placement.resolve_interpret`` decides).

Two dispatch surfaces:

* :func:`intersect_and_count` / :func:`intersect_classify` — one-shot calls.
  The ``classify`` variant is the fused path: it also takes the parent
  popcounts and τ and returns per-pair class codes (``CLASS_SKIP`` /
  ``CLASS_EMIT`` / ``CLASS_STORE``) computed on the engine, so the driver
  never re-derives the classification masks on the host.
* :class:`LevelPipeline` — the batch pipeline used by ``repro.core.kyiv``.
  It is **placement-generic**: a ``repro.core.placement.BitsetPlacement``
  supplies residency (parent bitsets + popcounts placed once per level),
  padding (executable buckets; per-shard blocks on a mesh) and dispatch
  (host numpy, single-device kernels, or shard_map bodies), while this class
  owns the generic orchestration — locality sort, async handles
  (``submit`` returns immediately; blocking only when ``result()`` converts
  to numpy), padding strips and inverse permutation. Host candidate
  generation / support tests of batch *n+1* therefore overlap the device
  intersection of batch *n* when the driver double-buffers. Engine-specific
  kernel binding lives in :func:`build_engine_dispatch` (bound once per
  bucket shape through :data:`EXEC_CACHE`); on accelerator backends the
  gathered write path donates its gathered operand so XLA aliases the child
  output onto it.

Locality-aware pair scheduling: :func:`locality_order` sorts a batch's pairs
by ``(i, j)`` so the indexed kernel's scalar-prefetch DMA re-fetches each
parent row once per *run* of equal ``i`` instead of once per pair; outputs
are un-permuted before the caller sees them. The default candidate generator
already emits ``i``-sorted batches, so the common case is a single O(M)
monotonicity check — the sort only triggers for externally supplied pair
lists (sharded re-balancing, resumed checkpoints, tests).

Padding contract: pair index rows added for padding point at row 0 twice; a
self-pair is *uniform* (count == min parent count), so fused classify marks
padding ``CLASS_SKIP``. All returned arrays are sliced back to the true
count, so callers never observe padding either way.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import intersect as _k
from . import ref as _ref
from ...obs import metrics as _om

_PIPE_BATCHES = _om.counter(
    "repro_intersect_batches_total",
    "Pair batches dispatched through the level pipeline.",
    ("mode",),
)
_PIPE_PAIRS = _om.counter(
    "repro_intersect_pairs_total",
    "Pairs dispatched through the level pipeline (padding included for "
    "mode=padded).",
    ("mode",),
)
_LEVELS_RETIRED = _om.counter(
    "repro_intersect_levels_retired_total",
    "Level residencies eagerly retired by the driver.",
)
from .ref import CLASS_EMIT, CLASS_SKIP, CLASS_STORE

__all__ = [
    "intersect_and_count",
    "intersect_classify",
    "classify_counts_host",
    "build_engine_dispatch",
    "locality_order",
    "next_bucket",
    "LevelPipeline",
    "BatchHandle",
    "ENGINES",
    "ExecutableCache",
    "EXEC_CACHE",
    "executable_cache_stats",
    "reset_executable_cache",
    "CLASS_SKIP",
    "CLASS_EMIT",
    "CLASS_STORE",
]

ENGINES = ("numpy", "jnp", "pallas")

_MIN_BUCKET = 256

# numpy<2.0 has no bitwise_count; degrade to an exact unpackbits popcount
# (mirrors repro.core.bitops, duplicated here because kernels must not
# import core — core imports kernels).
if hasattr(np, "bitwise_count"):

    def _popcount_rows(words: np.ndarray) -> np.ndarray:
        return np.bitwise_count(words).sum(axis=-1).astype(np.int64)

else:

    def _popcount_rows(words: np.ndarray) -> np.ndarray:
        words = np.ascontiguousarray(words)
        u8 = words.view(np.uint8)
        return np.unpackbits(u8, axis=-1).sum(axis=-1, dtype=np.int64)


def next_bucket(m: int, minimum: int = _MIN_BUCKET) -> int:
    """Smallest power-of-two bucket >= m (>= minimum) — bounds executable count."""
    b = minimum
    while b < m:
        b <<= 1
    return b


def _pad_pairs(pairs: np.ndarray, bucket: int) -> np.ndarray:
    m = pairs.shape[0]
    if m == bucket:
        return pairs
    out = np.zeros((bucket, 2), dtype=pairs.dtype)
    out[:m] = pairs
    return out


LANES = _k._LANES

# Scalar prefetch (SMEM) of one indexed dispatch holds the flattened pair
# table (2 words per pair) plus the fused variants' per-pair min parent
# count (1 word). v5e has 1 MiB of SMEM: a 2 MiB table was refused at
# compile time (RESOURCE_EXHAUSTED, "prefetched SMEM operand"), and compile
# time grows with the table, so one dispatch carries at most
# MAX_INDEXED_PAIRS pairs — 192 KiB of SMEM. LevelPipeline splits larger
# batches into chunks of this size.
SMEM_PREFETCH_WORDS = 3 << 14
MAX_INDEXED_PAIRS = SMEM_PREFETCH_WORDS // 3


def pad_words(bits, multiple: int = LANES):
    """Zero-pad the word axis (the last) to a multiple of ``multiple`` —
    128 lanes for the Pallas kernels, the word-shard count on a mesh. Pad
    words carry no rows, so every popcount is unchanged; numpy stays numpy,
    device arrays pad on device."""
    rem = (-int(bits.shape[-1])) % multiple
    if rem == 0:
        return bits
    widths = [(0, 0)] * (bits.ndim - 1) + [(0, rem)]
    return np.pad(bits, widths) if isinstance(bits, np.ndarray) else jnp.pad(bits, widths)


def _largest_divisor_tile(dim: int, preferred: int) -> int:
    """Largest word tile <= max(preferred, 128) that divides ``dim`` and is
    a multiple of 128 lanes — the only tiles the chip's compiler accepts for
    a word block. ``dim`` must itself be lane-aligned (:func:`pad_words`).

    Enumerates divisor pairs of ``dim // 128`` up to its square root, so
    prime word counts cost microseconds, not a linear scan.
    """
    if dim % LANES:
        raise ValueError(f"word count {dim} is not a multiple of {LANES} lanes")
    n, cap = dim // LANES, max(preferred // LANES, 1)
    if n <= cap:
        return dim
    best = 1
    d = 1
    while d * d <= n:
        if n % d == 0:
            for c in (d, n // d):
                if best < c <= cap:
                    best = c
        d += 1
    return best * LANES


def _pair_tile(bucket: int, block_pairs: int) -> int:
    """Pair tile of the gathered kernels: ``block_pairs`` when it divides
    the bucket (buckets are powers of two >= 256), else the whole batch —
    both satisfy the compiler's sublane rule."""
    return block_pairs if bucket % block_pairs == 0 else bucket


def locality_order(pairs: np.ndarray) -> tuple[np.ndarray | None, np.ndarray | None]:
    """Locality-aware pair schedule: stable sort by ``(i, j)``.

    Returns ``(order, inverse)`` such that ``pairs[order]`` is sorted and
    ``out[inverse]`` restores the caller's order, or ``(None, None)`` when the
    pairs are already ``i``-monotone (the common case — the prefix-join
    generator emits sorted batches), so the fast path is one O(M) check.
    """
    i = pairs[:, 0]
    if len(i) < 2 or bool(np.all(i[1:] >= i[:-1])):
        return None, None
    order = np.lexsort((pairs[:, 1], i))
    inverse = np.empty_like(order)
    inverse[order] = np.arange(len(order), dtype=order.dtype)
    return order, inverse


def classify_counts_host(
    counts: np.ndarray, minp: np.ndarray, tau: int
) -> np.ndarray:
    """Host reference of the device classification (Alg. 1 lines 32-41)."""
    counts = np.asarray(counts)
    skip = (counts == 0) | (counts == minp)
    emit = ~skip & (counts <= tau)
    return np.where(skip, CLASS_SKIP, np.where(emit, CLASS_EMIT, CLASS_STORE)).astype(
        np.int32
    )


# Module-level jit wrappers: a fresh ``jax.jit(f)`` per call would re-trace;
# binding once keeps the executable cache warm across batches and levels.
_JIT_PAIRS_REF = jax.jit(_ref.intersect_pairs_ref)
_JIT_COUNT_REF = jax.jit(_ref.intersect_count_ref)
_JIT_CLASSIFY_REF = jax.jit(_ref.intersect_classify_ref)
_JIT_CLASSIFY_COUNT_REF = jax.jit(_ref.intersect_classify_count_ref)


def executable_cache_stats() -> dict:
    """Snapshot of this family's executable-bucket cache (entries/hits/
    misses). The cache itself is the ``intersect`` family of the process-wide
    ``repro.core.exec_cache`` registry — one hit/miss surface per kernel
    family, one ``executables`` section in ``/stats``."""
    return EXEC_CACHE.stats()


def reset_executable_cache() -> None:
    EXEC_CACHE.clear()


def intersect_and_count(
    bits,
    pairs: np.ndarray,
    *,
    write_children: bool,
    engine: str = "numpy",
    interpret: bool | None = None,
    indexed: bool = True,
    block_pairs: int = 8,
    block_words: int = 8192,
    pad_buckets: bool = True,
):
    """Compute ``child = bits[i] & bits[j]`` and/or ``counts = |child|``.

    Args:
      bits: (t, W) uint32 parent bitsets (numpy or jax array).
      pairs: (M, 2) integer row indices.
      write_children: False selects the count-only k=k_max path.
      engine: one of ``numpy`` / ``jnp`` / ``pallas``.
      interpret: Pallas interpret mode; None lets the backend decide
        (``repro.core.placement.resolve_interpret``).
      indexed: Pallas path — scalar-prefetch gather (True) vs pre-gathered.
    Returns:
      (child (M, W) uint32 | None, counts (M,) int64 numpy array)
    """
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    m = int(pairs.shape[0])
    if m == 0:
        W = bits.shape[1]
        empty = np.zeros((0, W), dtype=np.uint32) if write_children else None
        return empty, np.zeros(0, dtype=np.int64)

    if engine == "numpy":
        bits_np = np.asarray(bits)
        a = bits_np[pairs[:, 0]]
        b = bits_np[pairs[:, 1]]
        child = np.bitwise_and(a, b)
        counts = _popcount_rows(child)
        return (child if write_children else None), counts

    pairs = np.asarray(pairs, dtype=np.int32)
    bucket = next_bucket(m) if pad_buckets else m
    padded = _pad_pairs(pairs, bucket)
    bits_j = jnp.asarray(bits)
    pairs_j = jnp.asarray(padded)

    if engine == "jnp":
        if write_children:
            child, cnt = _JIT_PAIRS_REF(bits_j, pairs_j)
        else:
            child, cnt = None, _JIT_COUNT_REF(bits_j, pairs_j)
    else:  # pallas
        from ...core.placement import resolve_interpret  # core imports kernels

        interpret = resolve_interpret(interpret)
        bits_j = pad_words(bits_j)
        W = bits_j.shape[1]
        bw = _largest_divisor_tile(W, block_words)
        if indexed:
            if write_children:
                child, cnt = _k.intersect_write_indexed(
                    bits_j, pairs_j, block_words=bw, interpret=interpret
                )
            else:
                child = None
                cnt = _k.intersect_count_indexed(
                    bits_j, pairs_j, block_words=bw, interpret=interpret
                )
        else:
            a = bits_j[pairs_j[:, 0]]
            b = bits_j[pairs_j[:, 1]]
            bm = _pair_tile(bucket, block_pairs)
            if write_children:
                child, cnt = _k.intersect_write_gathered(
                    a, b, block_pairs=bm, block_words=bw, interpret=interpret
                )
            else:
                child = None
                cnt = _k.intersect_count_gathered(
                    a, b, block_pairs=bm, block_words=bw, interpret=interpret
                )

    counts = np.asarray(cnt)[:m].astype(np.int64)
    child_np = None
    if write_children:
        child_np = np.asarray(child)[:m, : bits.shape[1]]
    return child_np, counts


def intersect_classify(
    bits,
    pairs: np.ndarray,
    parent_counts: np.ndarray,
    *,
    tau: int,
    write_children: bool,
    engine: str = "numpy",
    interpret: bool | None = None,
    indexed: bool = True,
    block_pairs: int = 8,
    block_words: int = 8192,
    pad_buckets: bool = True,
    locality_sort: bool = True,
):
    """Fused intersect + classify: one-shot convenience over :class:`LevelPipeline`.

    Returns ``(child | None, counts (M,) int64, classes (M,) int32)`` with
    classes in {CLASS_SKIP, CLASS_EMIT, CLASS_STORE}.
    """
    pipe = LevelPipeline(
        bits,
        parent_counts,
        tau=tau,
        engine=engine,
        interpret=interpret,
        indexed=indexed,
        block_pairs=block_pairs,
        block_words=block_words,
        pad_buckets=pad_buckets,
        locality_sort=locality_sort,
        fused_classify=True,
    )
    return pipe.submit(pairs, write_children).result()


class BatchHandle:
    """Future-like handle for one dispatched batch.

    ``result()`` blocks (device->host transfer) and returns
    ``(child | None, counts int64, classes int32 | None)`` in the caller's
    original pair order. ``raw()`` returns the placement-native (still
    padded, possibly device-resident) ``(child, counts, classes)`` without
    any host transfer — the device frontier consumes batches this way so
    stored children never leave the device.
    """

    def __init__(self, materialize, raw=None):
        self._materialize = materialize
        self._raw = raw
        self._out = None
        self._done = False

    def result(self):
        if not self._done:
            self._out = self._materialize()
            self._materialize = None
            self._done = True
        return self._out

    def raw(self):
        if self._raw is None:
            raise ValueError("batch was not dispatched with raw outputs")
        return self._raw


def build_engine_dispatch(
    engine: str,
    *,
    indexed: bool,
    fused_classify: bool,
    write_children: bool,
    n_words: int,
    bucket: int,
    block_pairs: int,
    block_words: int,
    interpret: bool,
    donate: bool,
):
    """Bind one executable bucket for a single-device engine: a callable
    ``fn(bits, pairs_j, pc, tau) -> (child | None, cnt, cls | None)``.

    Everything static — engine branch, kernel variant, tile sizes — is
    resolved here, once per bucket shape; ``DevicePlacement`` shares the
    bound closure process-wide through :data:`EXEC_CACHE`.
    """
    if engine == "jnp":
        if fused_classify:
            if write_children:
                return lambda bits, pairs_j, pc, tau: _JIT_CLASSIFY_REF(
                    bits, pairs_j, pc, tau
                )
            return lambda bits, pairs_j, pc, tau: (
                None,
                *_JIT_CLASSIFY_COUNT_REF(bits, pairs_j, pc, tau),
            )
        if write_children:
            return lambda bits, pairs_j, pc, tau: (
                *_JIT_PAIRS_REF(bits, pairs_j),
                None,
            )
        return lambda bits, pairs_j, pc, tau: (
            None,
            _JIT_COUNT_REF(bits, pairs_j),
            None,
        )
    if engine != "pallas":
        raise ValueError(f"engine must be jnp|pallas, got {engine!r}")

    # pallas
    bw = _largest_divisor_tile(n_words, block_words)
    if indexed:
        if fused_classify:
            if write_children:
                return lambda bits, pairs_j, pc, tau: _k.intersect_classify_write_indexed(
                    bits, pairs_j, pc, tau, block_words=bw, interpret=interpret
                )
            return lambda bits, pairs_j, pc, tau: (
                None,
                *_k.intersect_classify_count_indexed(
                    bits, pairs_j, pc, tau, block_words=bw, interpret=interpret
                ),
            )
        if write_children:
            return lambda bits, pairs_j, pc, tau: (
                *_k.intersect_write_indexed(
                    bits, pairs_j, block_words=bw, interpret=interpret
                ),
                None,
            )
        return lambda bits, pairs_j, pc, tau: (
            None,
            _k.intersect_count_indexed(
                bits, pairs_j, block_words=bw, interpret=interpret
            ),
            None,
        )

    # gathered pallas path
    bm = _pair_tile(bucket, block_pairs)
    if fused_classify:
        if write_children:
            kern = (
                _k.intersect_classify_write_gathered_donating
                if donate
                else _k.intersect_classify_write_gathered
            )

            def dispatch(bits, pairs_j, pc, tau):
                a = bits[pairs_j[:, 0]]
                b = bits[pairs_j[:, 1]]
                minp = jnp.minimum(pc[pairs_j[:, 0]], pc[pairs_j[:, 1]])
                return kern(
                    a, b, minp, tau,
                    block_pairs=bm, block_words=bw, interpret=interpret,
                )

            return dispatch

        def dispatch(bits, pairs_j, pc, tau):
            a = bits[pairs_j[:, 0]]
            b = bits[pairs_j[:, 1]]
            minp = jnp.minimum(pc[pairs_j[:, 0]], pc[pairs_j[:, 1]])
            cnt, cls = _k.intersect_classify_count_gathered(
                a, b, minp, tau,
                block_pairs=bm, block_words=bw, interpret=interpret,
            )
            return None, cnt, cls

        return dispatch
    if write_children:

        def dispatch(bits, pairs_j, pc, tau):
            a = bits[pairs_j[:, 0]]
            b = bits[pairs_j[:, 1]]
            child, cnt = _k.intersect_write_gathered(
                a, b, block_pairs=bm, block_words=bw, interpret=interpret
            )
            return child, cnt, None

        return dispatch

    def dispatch(bits, pairs_j, pc, tau):
        a = bits[pairs_j[:, 0]]
        b = bits[pairs_j[:, 1]]
        cnt = _k.intersect_count_gathered(
            a, b, block_pairs=bm, block_words=bw, interpret=interpret
        )
        return None, cnt, None

    return dispatch


class LevelPipeline:
    """Placement-generic, bucket-padded batch dispatcher for one BFS level.

    Construction hands the parent bitsets and popcounts to the placement
    once (``placement.prepare``); every ``submit`` then ships only the
    (tiny) pair list. Device/mesh placements dispatch asynchronously, so
    the host can generate and support-test the next candidate batch while
    the device intersects the current one; ``BatchHandle.result()`` is the
    only synchronisation point. The host placement computes eagerly inside
    ``submit`` (same contract, no async).

    This class owns only placement-independent orchestration: the empty-batch
    shortcut, locality-aware pair scheduling (+ inverse permutation of the
    outputs), padding to the placement's executable bucket, and stripping
    padding on materialization. Where the bitsets live and how a padded
    batch executes is entirely the placement's business — there are no
    engine-string branches here.

    ``placement`` is any ``repro.core.placement.BitsetPlacement``; passing
    the legacy ``engine=...`` string instead resolves one through
    ``repro.core.placement.make_placement`` (kept so existing callers and
    the ``KyivConfig.engine`` path keep working unchanged).

    With ``fused_classify=True`` the per-pair class codes are produced by the
    placement itself (device classification for jnp/pallas/mesh); with
    ``False`` the handle returns ``classes=None`` and the caller re-derives
    the masks on the host, or needs only the counts: the incremental
    recount and delta-born counts (``service/incremental.py``) run this way.
    """

    def __init__(
        self,
        bits,
        parent_counts,
        *,
        tau: int,
        placement=None,
        engine: str | None = None,
        interpret: bool | None = None,
        indexed: bool = True,
        fused_classify: bool = True,
        locality_sort: bool = True,
        block_pairs: int = 8,
        block_words: int = 8192,
        pad_buckets: bool = True,
    ):
        if placement is None:
            # deferred import: core imports kernels, never the reverse at
            # module scope — this only runs for legacy engine-string callers
            from ...core.placement import make_placement

            placement = make_placement(
                engine or "numpy",
                interpret=interpret,
                indexed=indexed,
                block_pairs=block_pairs,
                block_words=block_words,
            )
        self.placement = placement
        self.tau = int(tau)
        self.fused_classify = fused_classify
        self.locality_sort = locality_sort
        self.pad_buckets = pad_buckets
        self.n_words = int(bits.shape[1])
        self._state = placement.prepare(
            bits, parent_counts, self.tau, fused_classify=fused_classify
        )

    def retire(self) -> None:
        """Eagerly drop this level's prepared residency (device buffers the
        placement uploaded itself — see ``BitsetPlacement.release``). The
        driver calls this once a level's last batch has been consumed, so
        peak device memory tracks the two live levels of a transition
        instead of every parent level mined so far."""
        state, self._state = self._state, None
        if state is not None:
            _LEVELS_RETIRED.inc()
            release = getattr(self.placement, "release", None)
            if release is not None:
                release(state)

    def _dispatch(self, padded, write_children: bool):
        """One placement dispatch per chunk of at most the placement's
        ``max_dispatch_pairs`` (the SMEM bound of the indexed kernels, see
        :data:`MAX_INDEXED_PAIRS`); chunk outputs are concatenated where
        they live, so callers see one padded batch."""
        cap = getattr(self.placement, "max_dispatch_pairs", None)
        n = int(padded.shape[0])
        if cap is None or n <= cap:
            return self.placement.dispatch(self._state, padded, write_children)
        parts = [
            self.placement.dispatch(self._state, padded[s : s + cap], write_children)
            for s in range(0, n, cap)
        ]
        cat = np.concatenate if isinstance(parts[0][1], np.ndarray) else jnp.concatenate
        return tuple(
            None if outs[0] is None else cat(outs, axis=0) for outs in zip(*parts)
        )

    def _padded_size(self, m: int) -> int:
        """Rows a batch of ``m`` pairs is padded to: the placement's bucket
        up to its ``max_dispatch_pairs``, whole chunks of that bound past it
        (each chunk is one bucket, so the pad is under one chunk rather than
        up to the next power of two)."""
        size = self.placement.padded_size(m, pad_buckets=self.pad_buckets)
        cap = getattr(self.placement, "max_dispatch_pairs", None)
        if cap is not None and self.pad_buckets and size > cap:
            return -(-m // cap) * cap
        return size

    def _materializer(self, child_d, cnt_d, cls_d, m: int, inverse=None):
        n_words = self.n_words

        def materialize():
            counts = np.asarray(cnt_d)[:m].astype(np.int64)
            child = None
            if child_d is not None:
                # row-layout (M, 1, W) children flatten for free on the host;
                # lane/shard pad words are sliced off
                child = np.asarray(child_d)
                child = child.reshape(child.shape[0], -1)[:m, :n_words]
            classes = np.asarray(cls_d)[:m].astype(np.int32) if cls_d is not None else None
            if inverse is not None:
                counts = counts[inverse]
                if child is not None:
                    child = child[inverse]
                if classes is not None:
                    classes = classes[inverse]
            return child, counts, classes

        return materialize

    def submit_padded(self, pairs, m: int, write_children: bool) -> BatchHandle:
        """Dispatch one *pre-padded* batch of device-generated pair indices.

        The device frontier hands bucket-padded, locality-ordered pair
        arrays straight from candidate generation — no host ``np.stack``,
        no locality sort, no re-padding. ``m`` is the true pair count for
        ``result()``'s strip; ``raw()`` exposes the padded placement-native
        outputs for device-side partitioning.
        """
        _PIPE_BATCHES.inc(mode="padded")
        _PIPE_PAIRS.inc(int(pairs.shape[0]), mode="padded")
        raw = self._dispatch(pairs, write_children)
        return BatchHandle(self._materializer(*raw, m), raw=raw)

    def submit(self, pairs: np.ndarray, write_children: bool) -> BatchHandle:
        """Dispatch one batch of pair intersections; non-blocking on device
        placements. ``raw()`` of the handle holds the padded outputs when the
        pairs were dispatched in the order given (no locality sort)."""
        m = int(pairs.shape[0])
        if m == 0:
            W = self.n_words
            child = np.zeros((0, W), dtype=np.uint32) if write_children else None
            classes = np.zeros(0, dtype=np.int32) if self.fused_classify else None
            out = (child, np.zeros(0, dtype=np.int64), classes)
            return BatchHandle(lambda: out)

        _PIPE_BATCHES.inc(mode="host")
        _PIPE_PAIRS.inc(m, mode="host")
        pairs = np.ascontiguousarray(pairs, dtype=np.int32)
        order = inverse = None
        if self.locality_sort:
            order, inverse = locality_order(pairs)
            if order is not None:
                pairs = pairs[order]

        padded = _pad_pairs(pairs, self._padded_size(m))
        raw = self._dispatch(padded, write_children)
        # raw outputs are in submission order only when nothing was sorted
        return BatchHandle(
            self._materializer(*raw, m, inverse), raw=raw if order is None else None
        )


class LegacyIntersectPipeline:
    """Adapter: wrap an ``intersect_fn(bits, pairs, write_children)`` callable
    (the pre-pipeline injection contract, still used by the sharded tests) in
    the pipeline interface. Classification stays on the host
    (``classes=None``)."""

    def __init__(self, intersect_fn, bits):
        self._fn = intersect_fn
        self._bits = bits

    def submit(self, pairs: np.ndarray, write_children: bool) -> BatchHandle:
        child, counts = self._fn(self._bits, pairs, write_children)
        out = (child, counts, None)
        return BatchHandle(lambda: out)


# EXEC_CACHE binds at the module *bottom*: importing ``repro.core.exec_cache``
# runs ``repro.core.__init__``, which re-enters this (still-executing) module
# for LevelPipeline and friends — by this line every name core needs is
# already defined. Keep this import below every definition, and keep
# ``core/exec_cache.py`` itself a stdlib-only leaf (see its import
# discipline note).
from ...core.exec_cache import FamilyCache as ExecutableCache  # noqa: E402
from ...core.exec_cache import exec_family as _exec_family  # noqa: E402

EXEC_CACHE = _exec_family("intersect")
