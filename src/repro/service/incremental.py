"""Incremental re-mining after row-block appends (support monotonicity).

Appending rows can only *grow* an itemset's support. That single fact pins
down exactly how the answer set evolves between a cached base result and the
current store version:

* A minimal τ-infrequent itemset of the base table stays minimal as long as
  its own support stays ≤ τ — its proper subsets were frequent and frequency
  is append-monotone. So every cached result only needs a **recount on the
  appended rows** (``DatasetStore.delta_bits``): new support = old support +
  delta support, at a cost proportional to the delta block, not the table.
  On a device placement the recounted itemsets take their whole support
  from the placement's intersect kernels over the current version's
  resident bitsets instead, the kernels that count the delta-born pool.
* A cached result whose support crossed τ is **promoted** to frequent. Any
  *new* minimal itemset ``S`` (one not in the base answer) was τ-infrequent
  in the base table too (monotonicity), hence contained a base-minimal
  subset; that subset is a proper subset of ``S``, is frequent now, and was
  therefore promoted. New items (values first seen in the delta) are the one
  exception — they had no base support at all; frequent new singletons seed
  the same way. So the full frontier of change is::

      seeds = promoted base results  ∪  frequent brand-new singleton items

  and every new minimal itemset is a strict superset of a seed.
* Seeds sit *near the τ boundary by construction*: a promoted itemset has
  new support ≤ τ + d (d = appended rows), so its frequent supersets live in
  the thin band (τ, τ + d] — the expansion work shrinks with the delta.
* One family has no base-minimal subset to seed from: itemsets that were
  **absent** (support 0) in the base table. Cold Kyiv skips absent
  candidates, so nothing about them is cached. But support 0 at the base
  means their entire support lies in the delta block — every such itemset
  is a subset of some appended row's items, so ``_delta_born`` enumerates
  the ≤kmax column combinations of each appended row (cost per row is a
  function of table *width*, not history) and classifies them directly,
  their full-table supports counted by the placement's intersect kernels
  over the store's resident bitsets on a device placement.

``_expand_seeds`` explores exactly that band: a BFS over supersets of each
seed within the frequent item universe, pruning any infrequent node (an
infrequent proper subset disqualifies every superset from minimality) and
verifying minimality of emitted sets directly against the store bitsets.
Mirror items need no special casing — the BFS enumerates concrete item ids,
which is precisely the ``expansion="full"`` closure the cold miner produces
(incremental mining therefore requires ``KyivConfig.expansion == "full"``,
the default).

Past a configurable delta fraction — or if the boundary band turns out not
to be thin (expansion budget exhausted) — ``mine_incremental`` signals the
caller to fall back to a cold ``mine()``; the result is bit-identical either
way (property-tested against cold mining in ``tests/test_incremental.py``).

Two refinements ride on top of the base scheme:

* **Near-boundary bands** (:class:`ResultBands`): the result cache persists
  per-arity, count-sorted matrices of the cached itemsets. At recount time
  the per-item delta frequencies bound each cached itemset's delta support
  from above (``ub = min dfreq over members``); ``ub == 0`` proves the delta
  support is exactly 0, so only itemsets whose *every* member actually
  appears in the appended rows pay the bitset AND — the recount floor is
  delta-proportional instead of O(|cached results|), and the promotion scan
  is confined to the ``(τ - d, τ]`` band the sorted counts expose.
* **Fleet mode**: when the store is process-sharded and ``placement`` is a
  :class:`~repro.core.fleet.FleetPlacement`, every popcount in this module
  is a partial sum over local word stripes. All count vectors funnel
  through one ``allreduce_sum`` per stage (recount, expansion minimality,
  delta-born classification), delta-born candidates are unioned by one
  all-gather (each process only sees its own delta rows), and budget
  decisions are taken on the *global* pool so every process falls back —
  or doesn't — in lockstep.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time

import numpy as np

from ..core.bitops import popcount_rows
from ..core.items import ItemTable
from ..core.kyiv import KyivConfig, LevelStats, MiningResult
from ..core.preprocess import Preprocessed
from ..obs import metrics as _om
from ..obs.trace import current_span as _obs_current_span
from ..obs.trace import span as _obs_span
from .store import DatasetStore, mask_delta_words, mask_delta_words_local

__all__ = ["IncrementalConfig", "ResultBands", "mine_incremental", "delta_support"]

_FALLBACKS = _om.counter(
    "repro_incremental_fallbacks_total",
    "Incremental mines handed back to a cold mine, by reason.",
    ("reason",),
)


@dataclasses.dataclass
class IncrementalConfig:
    """Knobs for the append-delta mining path."""

    # past this appended-rows fraction of the current table, recounting +
    # boundary expansion loses to simply re-mining cold
    max_delta_fraction: float = 0.25
    # frontier-node limit for the boundary expansion BFS; exhausted => the
    # boundary band is not thin, fall back to cold mining
    expansion_budget: int = 4096
    # cap on deduplicated delta-born candidate itemsets (subsets of appended
    # rows); exhausted => fall back to cold mining
    delta_candidate_budget: int = 262_144
    enabled: bool = True


def _delta_bits_of(
    table: ItemTable, base_rows: int, word_map: np.ndarray | None = None
) -> np.ndarray:
    """Delta-row bitsets derived from an immutable snapshot table (same
    contract as ``DatasetStore.delta_bits``, but safe against appends that
    land while this mining request is running). ``word_map`` marks the
    snapshot as process-sharded: delta words are scattered round-robin, so
    the full local width is kept and pre-existing rows are zeroed in place."""
    if word_map is not None:
        return mask_delta_words_local(table.bits, base_rows, word_map)
    return mask_delta_words(table.bits, base_rows, table.n_rows)[0]


def delta_support(
    dbits: np.ndarray, itemsets: list[tuple[int, ...]]
) -> np.ndarray:
    """Support of each itemset restricted to the delta rows.

    ``dbits`` is the ``DatasetStore.delta_bits`` slice: (n_items, W_delta).
    Itemsets are grouped by arity and AND-reduced vectorised; total cost is
    O(sum_k r_k * k * W_delta).
    """
    out = np.zeros(len(itemsets), dtype=np.int64)
    by_k: dict[int, list[int]] = {}
    for idx, ids in enumerate(itemsets):
        by_k.setdefault(len(ids), []).append(idx)
    for k, idxs in by_k.items():
        mat = np.asarray([itemsets[i] for i in idxs], dtype=np.int64)  # (r, k)
        inter = np.bitwise_and.reduce(dbits[mat], axis=1)  # (r, Wd)
        out[idxs] = popcount_rows(inter)
    return out


@dataclasses.dataclass
class ResultBands:
    """Per-arity, count-sorted views of a cached result set.

    Built once when a mining result enters the cache and persisted beside
    it (``CacheEntry.bands``), so an append burst pays only the recount this
    structure admits: ``recount`` bounds each itemset's delta support by the
    minimum delta frequency of its members and runs the exact bitset AND
    only where that bound is non-zero; the ascending base counts confine
    promotion candidates to the thin ``(τ - d, τ]`` boundary band.
    """

    mats: dict[int, np.ndarray]  # arity -> (r, k) int64 ids, count-ascending
    counts: dict[int, np.ndarray]  # arity -> (r,) int64 base counts, ascending
    index: dict[int, np.ndarray]  # arity -> (r,) position in cached order

    @classmethod
    def from_result(cls, itemsets: list[tuple[tuple[int, ...], int]]) -> "ResultBands":
        by_k: dict[int, list[tuple[int, tuple[int, ...], int]]] = {}
        for pos, (ids, cnt) in enumerate(itemsets):
            by_k.setdefault(len(ids), []).append((pos, ids, cnt))
        mats, counts, index = {}, {}, {}
        for k, rows in by_k.items():
            cnt = np.asarray([c for _, _, c in rows], dtype=np.int64)
            order = np.argsort(cnt, kind="stable")
            mats[k] = np.asarray([ids for _, ids, _ in rows], dtype=np.int64)[order]
            counts[k] = cnt[order]
            index[k] = np.asarray([p for p, _, _ in rows], dtype=np.int64)[order]
        return cls(mats=mats, counts=counts, index=index)

    def nbytes(self) -> int:
        return sum(
            a.nbytes
            for d in (self.mats, self.counts, self.index)
            for a in d.values()
        )

    def recount(
        self,
        dbits: np.ndarray,
        dfreq: np.ndarray,
        tau: int,
        d: int,
        reduce_fn=None,
        supports=None,
    ) -> tuple[np.ndarray, dict]:
        """New (base + delta) support of every cached itemset, in cached
        order, touching bitsets only where the ``dfreq`` upper bound admits a
        non-zero delta. ``dfreq`` must be the *global* per-item delta
        frequency; under a fleet ``reduce_fn`` sums the partial popcounts
        (one collective for all arities — the upper-bound filter is computed
        from global values, so every process recounts the identical rows).
        ``supports`` (``{k: (r, k) ids} -> {k: full-table supports}``, the
        device counts on a device placement) replaces base + delta for the
        recounted itemsets with their whole support on the current version.
        Returns ``(new_counts, stats)``."""
        total = sum(len(c) for c in self.counts.values())
        new = np.zeros(total, dtype=np.int64)
        n_recounted = 0
        n_band = 0
        chunks: list[tuple[int, np.ndarray, np.ndarray]] = []
        full: dict[int, np.ndarray] = {}
        for k in sorted(self.mats):
            mat, cnt = self.mats[k], self.counts[k]
            if len(cnt) == 0:
                continue
            # ascending base counts: everything past this point could cross τ
            n_band += len(cnt) - int(np.searchsorted(cnt, tau - d, side="right"))
            if k == 1:
                # singleton delta support IS the delta frequency — no AND
                new[self.index[k]] = cnt + dfreq[mat[:, 0]]
                continue
            ub = dfreq[mat].min(axis=1)
            need = np.nonzero(ub > 0)[0]
            n_recounted += len(need)
            new[self.index[k]] = cnt  # ub == 0 rows are exact as-is
            if len(need) and supports is not None:
                full[k] = need
            elif len(need):
                inter = np.bitwise_and.reduce(dbits[mat[need]], axis=1)
                chunks.append((k, need, popcount_rows(inter).astype(np.int64)))
        if full:
            counts = supports({k: self.mats[k][need] for k, need in full.items()})
            for k, need in full.items():
                new[self.index[k][need]] = counts[k]
        if chunks:
            flat = np.concatenate([c for _, _, c in chunks])
            if reduce_fn is not None:
                flat = reduce_fn(flat)
            off = 0
            for k, need, c in chunks:
                new[self.index[k][need]] += flat[off : off + len(need)]
                off += len(need)
        stats = {
            "n_recounted": n_recounted,
            "n_recount_skipped": total - n_recounted,
            "n_promotion_band": n_band,
        }
        return new, stats


def _host_supports(bits: np.ndarray, cands: dict[int, np.ndarray]) -> dict[int, np.ndarray]:
    """Support of each ``(r, k)`` id-matrix row: host ANDs over ``bits``."""
    return {
        k: popcount_rows(np.bitwise_and.reduce(bits[mat], axis=1))
        for k, mat in cands.items()
    }


def _filter_minimal(
    table: ItemTable,
    cands: dict[frozenset, int],
    tau: int,
    reduce_fn=None,
    supports=None,
) -> dict[frozenset, int]:
    """Keep the minimal members of a τ-infrequent candidate pool, batched.

    Every distinct (|S|-1)-subset across all arity ≥ 3 candidates is counted
    once in one vectorised pass — under a fleet that is a single partial-
    popcount all-reduce instead of one per leave-one-out probe.
    ``supports`` counts ``{k: (r, k) ids}`` (host ANDs over the table's
    bitsets when omitted; the device counts on a device placement).
    Arity 1 is minimal by definition; arity 2 checks global frequencies.
    """
    freq = table.freq
    sub_index: dict[tuple[int, ...], int] = {}
    sub_list: list[tuple[int, ...]] = []
    refs_of: dict[frozenset, list[int]] = {}
    for cs in cands:
        if len(cs) <= 2:
            continue
        ids = tuple(sorted(cs))
        refs = []
        for drop in range(len(ids)):
            sub = ids[:drop] + ids[drop + 1 :]
            ix = sub_index.get(sub)
            if ix is None:
                ix = len(sub_list)
                sub_index[sub] = ix
                sub_list.append(sub)
            refs.append(ix)
        refs_of[cs] = refs
    sup = np.zeros(len(sub_list), dtype=np.int64)
    if sub_list:
        by_k: dict[int, list[int]] = {}
        for ix, sub in enumerate(sub_list):
            by_k.setdefault(len(sub), []).append(ix)
        mats = {
            kk: np.asarray([sub_list[i] for i in idxs], dtype=np.int64)
            for kk, idxs in by_k.items()
        }
        counts = (supports or (lambda m: _host_supports(table.bits, m)))(mats)
        parts = [(by_k[kk], counts[kk].astype(np.int64)) for kk in sorted(by_k)]
        flat = np.concatenate([p for _, p in parts])
        if reduce_fn is not None:
            flat = reduce_fn(flat)
        off = 0
        for idxs, p in parts:
            sup[idxs] = flat[off : off + len(p)]
            off += len(p)
    out: dict[frozenset, int] = {}
    for cs, cnt in cands.items():
        if len(cs) == 1:
            ok = True
        elif len(cs) == 2:
            a, b = tuple(cs)
            ok = bool(freq[a] > tau and freq[b] > tau)
        else:
            ok = all(sup[ix] > tau for ix in refs_of[cs])
        if ok:
            out[cs] = cnt
    return out


def _expand_seeds(
    table: ItemTable,
    seeds: list[tuple[int, ...]],
    tau: int,
    kmax: int,
    budget: int,
    *,
    placement=None,
    resident_bits=None,
    reduce_fn=None,
) -> dict[frozenset, int] | None:
    """All minimal τ-infrequent strict supersets of any seed, up to kmax.

    Level-synchronous BFS over the **resident frontier**: each wave's
    (node × extension-item) support counts are one batched intersect
    dispatch through the service's :class:`~repro.kernels.intersect.ops.LevelPipeline`
    — the extension items gather from the store's placement-resident bitset
    matrix (``resident_bits``) instead of re-gathering host levels, so the
    hot popcount loop runs wherever mining itself runs (host numpy, one
    device, or the mesh). Only surviving nodes' bitsets are re-derived on
    the host (two-row ANDs) to seed the next wave.

    Returns None when the explored node count exceeds ``budget`` (caller
    re-mines cold). Every wave node is a *frequent* superset of a seed; an
    infrequent node is classified once (emit if minimal) and never extended,
    because its supersets all contain an infrequent proper subset.
    """
    from ..kernels.intersect.ops import LevelPipeline, next_bucket

    n = table.n_rows
    freq = table.freq
    bits = table.bits
    ext_universe = np.nonzero((freq > tau) & (freq < n))[0].astype(np.int64)
    found: dict[frozenset, int] = {}
    if len(ext_universe) == 0:
        return found
    visited: set[frozenset] = set()
    wave: list[tuple[frozenset, np.ndarray]] = []
    for ids in seeds:
        fs = frozenset(int(i) for i in ids)
        if len(fs) >= kmax or fs in visited:
            continue
        visited.add(fs)
        wave.append((fs, np.bitwise_and.reduce(bits[list(fs)], axis=0)))
    if not wave:
        return found

    if placement is None:
        from ..core.placement import HostPlacement

        placement = HostPlacement()
    on_device = (
        getattr(placement, "kind", "host") != "host" and resident_bits is not None
    )
    ext_host = bits[ext_universe]  # host copy: seeds the next wave's bits
    if on_device and resident_bits is not None:
        import jax.numpy as jnp

        ext_res = jnp.asarray(resident_bits)[jnp.asarray(ext_universe)]
    else:
        ext_res = ext_host

    e_count, w_words = ext_host.shape
    # two budgets: nodes whose bitsets join the resident matrix per segment
    # (the extension block is re-placed once per *segment* — usually once
    # per wave; placing it exactly once per call would need a two-block
    # pair addressing scheme the placement API doesn't speak, and waves are
    # shallow by the thin-boundary-band premise), and rows per submit
    # bounding the dispatch working set: the host placement materialises
    # both gathered operands plus the AND, ~3 * pairs * W words per submit
    seg_nodes = max(1, (1 << 24) // max(w_words, 1))
    rows_per_submit = max(1, (1 << 23) // max(e_count * max(w_words, 1), 1))
    popped = 0
    while wave:
        popped += len(wave)
        if popped > budget:
            return None
        next_wave: list[tuple[frozenset, np.ndarray]] = []
        for s0 in range(0, len(wave), seg_nodes):
            seg = wave[s0 : s0 + seg_nodes]
            f_count = len(seg)
            wave_bits = np.stack([wb for _, wb in seg])
            if on_device:
                import jax.numpy as jnp

                # rows padded to a power of two: one executable per size
                # class of waves, not one per wave
                pad = next_bucket(f_count, 8) - f_count
                wave_bits = np.pad(wave_bits, ((0, pad), (0, 0)))
                mat = jnp.concatenate([ext_res, jnp.asarray(wave_bits)], axis=0)
            else:
                mat = np.concatenate([ext_res, wave_bits], axis=0)
            pipe = LevelPipeline(
                mat,
                np.zeros(int(mat.shape[0]), dtype=np.int64),
                tau=0,
                placement=placement,
                fused_classify=False,
                locality_sort=False,
            )
            for s in range(0, f_count, rows_per_submit):
                chunk = seg[s : s + rows_per_submit]
                c_count = len(chunk)
                fi = (
                    np.repeat(np.arange(s, s + c_count, dtype=np.int64), e_count)
                    + e_count
                )
                ei = np.tile(np.arange(e_count, dtype=np.int64), c_count)
                pairs = np.stack([fi, ei], axis=1).astype(np.int32)
                handle = pipe.submit(pairs, False)
                with _obs_span("device.wait", site="expand_counts"):
                    _, counts, _ = handle.result()
                counts = counts.reshape(c_count, e_count)
                for fidx, (fs, fb) in enumerate(chunk):
                    # absent extensions (the overwhelming majority in sparse
                    # data) die before any set building
                    for eidx in np.nonzero(counts[fidx])[0]:
                        x = int(ext_universe[eidx])
                        if x in fs:
                            continue
                        cs = fs | {x}
                        if cs in visited:
                            continue
                        visited.add(cs)
                        cnt = int(counts[fidx, eidx])
                        if cnt > tau:
                            if len(cs) < kmax:
                                next_wave.append((cs, fb & ext_host[eidx]))
                        else:
                            # minimality is deferred: one batched subset-
                            # support pass after the BFS (a single collective
                            # under a fleet) replaces per-emission probes
                            found[cs] = cnt
            pipe.retire()
        wave = next_wave
    supports = None
    if on_device and reduce_fn is None:  # a fleet dispatch returns global counts
        supports = functools.partial(
            _device_supports, resident=resident_bits, placement=placement,
            site="filter_counts",
        )
    return _filter_minimal(table, found, tau, reduce_fn, supports)


def _unique_rows(
    mat: np.ndarray, base: int, *, inverse: bool = False
):
    """Distinct rows of an ``(r, k)`` id matrix (ids < ``base``), in
    lexicographic order; with ``inverse``, also each input row's index into
    them. Rows are packed into one int64 key when ``base ** k`` fits."""
    k = mat.shape[1]
    if base**k >= 2**63:
        res = np.unique(mat, axis=0, return_inverse=inverse)
        return (res[0], res[1].reshape(-1)) if inverse else res
    key = np.zeros(len(mat), dtype=np.int64)
    for j in range(k):
        key = key * base + mat[:, j]
    res = np.unique(key, return_inverse=inverse)
    key = res[0] if inverse else res
    out = np.empty((len(key), k), dtype=np.int64)
    for j in range(k - 1, -1, -1):
        key, out[:, j] = np.divmod(key, base)
    return (out, res[1].reshape(-1)) if inverse else out


def _row_combinations(
    row_items: np.ndarray, keep: np.ndarray, kmax: int, budget: int
) -> tuple[dict[int, np.ndarray], bool]:
    """Distinct 2..kmax-combinations of each row's kept items, by arity:
    ``{k: (r, k) int64}``, each row ascending, rows in lexicographic order.

    ``row_items`` is ``(n_items, rows)`` 0/1. Rows with the same number of
    kept items form one ``(rows, m)`` item matrix whose column-index
    combinations are gathered at once. The flag is True (and the pools are
    partial) as soon as the distinct candidates, over all arities, exceed
    ``budget``."""
    base = len(keep)
    kept = np.flatnonzero(keep)
    sub = row_items[kept]
    per_row = sub.sum(axis=0)
    pools = {k: np.zeros((0, k), dtype=np.int64) for k in range(2, kmax + 1)}
    for m in np.unique(per_row).tolist():
        if m < 2:
            continue
        rows = np.flatnonzero(per_row == m)
        _, pos = np.nonzero(sub[:, rows].T)  # row-major: each row's items, ascending
        items = kept[pos].reshape(len(rows), m)
        for k in range(2, min(kmax, m) + 1):
            cols = np.asarray(list(itertools.combinations(range(m), k)), dtype=np.intp)
            step = max(1, (1 << 22) // (len(cols) * k))  # bound the gather
            for r0 in range(0, len(rows), step):
                combos = items[r0 : r0 + step][:, cols].reshape(-1, k)
                pools[k] = _unique_rows(np.concatenate([pools[k], combos]), base)
                if sum(len(p) for p in pools.values()) > budget:
                    return pools, True
    return {k: p for k, p in pools.items() if len(p)}, False


def _device_supports(
    cands: dict[int, np.ndarray],
    *,
    resident,
    placement,
    site: str,
    stats: dict | None = None,
) -> dict[int, np.ndarray]:
    """Full-table supports of candidate itemsets, counted by the placement's
    intersect kernels over the store's resident bitsets.

    A pair is one intersection of two item rows. An arity-k candidate is
    the intersection of its (k-1)-prefix's child with its last item row; the
    distinct prefixes are written as children first (recursively, so a
    triple's prefixes are item pairs). Every batch is dispatched before the
    single host fetch of the counts, a ``device.wait`` span named ``site``.
    Returns the supports by arity; ``stats``, when given, gains the
    intersections dispatched (``pairs``), the distinct rows each level's
    dispatches read, summed (``rows_read``), and the rows written
    (``children``)."""
    import jax
    import jax.numpy as jnp

    from ..kernels.intersect.ops import LevelPipeline

    if not cands:
        return {}
    n_items = int(resident.shape[0])
    stats = {"pairs": 0, "rows_read": 0, "children": 0} if stats is None else stats
    pipes: dict[int, LevelPipeline] = {}

    def pipeline(mat):
        if id(mat) not in pipes:
            pipes[id(mat)] = LevelPipeline(
                mat,
                np.zeros(int(mat.shape[0]), dtype=np.int64),
                tau=0,
                placement=placement,
                fused_classify=False,
                locality_sort=False,
            )
        return pipes[id(mat)]

    def operands(sets):
        if sets.shape[1] == 2:
            mat, left = resident, sets[:, 0]
        else:
            prefixes, inv = _unique_rows(sets[:, :-1], n_items, inverse=True)
            child = write(prefixes)
            if child.sharding != resident.sharding:  # a mesh shards pairs too
                child = jax.device_put(child, resident.sharding)
            items = resident.reshape((n_items,) + tuple(child.shape[1:]))
            mat, left = jnp.concatenate([items, child]), n_items + inv
        stats["rows_read"] += len(np.unique(np.concatenate([left, sets[:, -1]])))
        stats["pairs"] += len(sets)
        return pipeline(mat), np.stack([left, sets[:, -1]], axis=1).astype(np.int32)

    def write(prefixes):
        pipe, pairs = operands(prefixes)
        stats["children"] += len(pairs)
        return pipe.submit(pairs, True).raw()[0]

    handles = {}
    for k, sets in sorted(cands.items()):
        pipe, pairs = operands(sets)
        handles[k] = pipe.submit(pairs, False)
    with _obs_span("device.wait", site=site):
        out = {k: h.result()[1] for k, h in handles.items()}
    for pipe in pipes.values():
        pipe.retire()
    return out


def _delta_born(
    table: ItemTable,
    dbits: np.ndarray,
    base_rows: int,
    tau: int,
    kmax: int,
    budget: int,
    *,
    word_map: np.ndarray | None = None,
    coll=None,
    device=None,
) -> tuple[dict[frozenset, int], dict] | None:
    """Minimal τ-infrequent itemsets whose base support was 0.

    Their whole support lies in the appended rows, so every one is a subset
    of the items of at least one delta row. Delta rows are reconstructed
    from the item-major delta bitsets, each row's items are filtered to the
    frequent non-uniform universe (an infrequent or uniform member disquali-
    fies minimality immediately), and the surviving ≤kmax combinations are
    counted against the full table and checked for minimality directly.
    ``device`` (:func:`_device_supports` bound to a device placement and the
    store's resident bitsets) counts the full-table supports and the
    minimality probes on the device; without it they are host ANDs over the
    full-width bitsets. Delta supports are host ANDs over
    the few delta words either way. Returns ``(born, stats)``, or None when
    the deduplicated candidate pool exceeds ``budget``.

    Under a fleet (``word_map`` + ``coll``) each process reconstructs only
    the delta rows living in its own word stripes, so the candidate pools
    are unioned by one all-gather and the budget verdict is taken on the
    *global* pool — either every process falls back to cold mining or none
    does. Support counts and the minimality filter reduce partial popcounts.
    """
    n = table.n_rows
    freq = table.freq
    bits = table.bits
    d = n - base_rows
    stats = {"candidates": {}, "pairs": 0, "rows_read": 0, "children": 0}
    if d <= 0 or kmax < 2:
        return {}, stats
    # item-major delta bits -> per-row item lists (delta-scaled unpack)
    flat = np.unpackbits(
        np.ascontiguousarray(dbits).view(np.uint8), axis=1, bitorder="little"
    )  # (n_items, W*32); column j of word w = that word's row (w*32 + j)
    if word_map is None:
        lo = (base_rows // 32) * 32
        row_items = flat[:, base_rows - lo : n - lo]  # (n_items, d)
    else:
        # sharded width: column c covers global row word_map[c // 32]*32 +
        # c % 32; keep this process's columns inside the delta row range
        wm = np.asarray(word_map, dtype=np.int64)
        grow = wm.repeat(32) * 32 + np.tile(np.arange(32, dtype=np.int64), len(wm))
        row_items = flat[:, (grow >= base_rows) & (grow < n)]
    keep = (freq > tau) & (freq < n)

    cands, overflow = _row_combinations(row_items, keep, kmax, budget)
    if overflow and coll is None:
        return None
    if coll is not None:
        # verdict deferred to the global union
        pools = coll.allgather_obj((cands, overflow))
        if any(o for _, o in pools):
            return None
        cands = {
            k: _unique_rows(np.concatenate(parts), len(keep))
            for k in range(2, kmax + 1)
            if (parts := [p[k] for p, _ in pools if k in p])
        }
        if sum(len(c) for c in cands.values()) > budget:
            return None
    stats["candidates"] = {k: len(c) for k, c in cands.items()}

    reduce_fn = coll.allreduce_sum if coll is not None else None
    supports = None
    if device is not None:
        supports = functools.partial(device, site="filter_counts")
        counts = device(cands, site="born_counts", stats=stats)
    else:
        counts = _host_supports(bits, cands)
    parts = []
    for k, mat in sorted(cands.items()):
        dcounts = popcount_rows(np.bitwise_and.reduce(dbits[mat], axis=1))
        parts.append((mat, counts[k].astype(np.int64), dcounts.astype(np.int64)))
    if parts and reduce_fn is not None:
        # one collective for all arities: [counts | dcounts] concatenated
        flat_counts = reduce_fn(
            np.concatenate([np.concatenate([c, dc]) for _, c, dc in parts])
        )
        off = 0
        fixed = []
        for mat, c, dc in parts:
            r = len(mat)
            fixed.append((mat, flat_counts[off : off + r], flat_counts[off + r : off + 2 * r]))
            off += 2 * r
        parts = fixed
    pre: dict[frozenset, int] = {}
    for mat, counts_k, dcounts in parts:
        # cnt == dcnt <=> base support 0: itemsets present at the base are
        # exactly the family already covered by recount + seed expansion
        sel = np.flatnonzero((counts_k >= 1) & (counts_k <= tau) & (counts_k == dcounts))
        for ids, cnt in zip(mat[sel].tolist(), counts_k[sel].tolist()):
            pre[frozenset(ids)] = int(cnt)
    return _filter_minimal(table, pre, tau, reduce_fn, supports), stats


def _light_prep(table: ItemTable, tau: int) -> Preprocessed:
    """A Preprocessed container for incremental results: correct item
    partitions and ordering metadata, but no mirror hashing and no l_bits
    gather — the incremental path never re-enters the level miner, and
    skipping the O(items * W) work keeps its cost delta-dominated."""
    freq = table.freq
    n = table.n_rows
    uniform = np.nonzero(freq == n)[0]
    infrequent = np.nonzero(freq <= tau)[0]
    keep = np.nonzero((freq > tau) & (freq < n))[0]
    order = np.lexsort((table.min_row[keep], table.col[keep], freq[keep]))
    l_items = keep[order]
    return Preprocessed(
        table=table,
        tau=tau,
        uniform_items=uniform,
        infrequent_items=infrequent,
        l_items=l_items,
        mirror_of={},
        l_bits=np.zeros((0, table.n_words), dtype=np.uint32),
        l_freq=freq[l_items].astype(np.int64),
    )


def _fallback(reason: str) -> None:
    _FALLBACKS.inc(reason=reason)
    _obs_current_span().set(fallback=reason)
    return None


def mine_incremental(
    store: DatasetStore,
    base_result: MiningResult,
    base_version: int,
    config: KyivConfig,
    inc_config: IncrementalConfig | None = None,
    *,
    table: ItemTable | None = None,
    placement=None,
    resident_bits=None,
    bands: "ResultBands | None" = None,
) -> tuple[MiningResult, dict] | None:
    """Delta-mine the store against a cached base result.

    ``table`` is an optional immutable snapshot (``DatasetStore.item_table``)
    to mine; when omitted one is taken now. Only the historical watermarks of
    ``store`` are consulted otherwise, so concurrent appends cannot skew the
    delta. ``placement``/``resident_bits`` route the promoted/new-item seed
    expansion and the delta-born full-table counts through the service's
    placement and the store's device-resident bitsets
    (``DatasetStore.device_bits``) instead of host numpy; omitted, both run
    on the host — results are bit-identical either way. ``bands`` is the cached
    :class:`ResultBands` companion of ``base_result`` (built on the fly when
    absent, so callers without a cache still get the shrunken recount). A
    ``FleetPlacement`` switches every stage into its collective form (see
    module docstring). Returns ``(result, info)`` or ``None`` when the
    caller should fall back to a cold mine (delta too large, expansion
    budget exhausted, or a config the incremental invariants don't cover);
    each fallback counts in ``repro_incremental_fallbacks_total`` under its
    ``reason``, which also lands on the open span (``mine.incremental``).
    """
    inc = inc_config or IncrementalConfig()
    if not inc.enabled or config.expansion != "full" or config.kmax < 1:
        return _fallback("config")
    try:
        base_rows = store.rows_at(base_version)
        base_items = store.items_at(base_version)
    except KeyError:
        return _fallback("compacted")  # base watermark gone -> cold remine
    if base_rows == 0:
        return _fallback("empty_base")
    t0 = time.perf_counter()
    if table is None:
        table = store.item_table()
    n = table.n_rows
    delta_rows = n - base_rows
    if delta_rows <= 0:
        return _fallback("no_delta")
    if delta_rows > inc.max_delta_fraction * n:
        return _fallback("delta_fraction")

    tau, kmax = config.tau, config.kmax

    # fleet mode: partial popcounts over local word stripes, reduced through
    # the placement's collective; every budget/branch decision below is a
    # function of global values so the processes stay in lockstep
    if placement is None:
        placement = getattr(config, "placement", None)
    fleet = getattr(placement, "kind", None) == "fleet"
    coll = placement.collective if fleet else None
    reduce_fn = coll.allreduce_sum if fleet else None
    shard = tuple(getattr(store, "shard", (0, 1)))
    word_map = store.word_map(table.n_words) if shard[1] > 1 else None
    device = None
    if not fleet and resident_bits is not None and getattr(placement, "kind", "host") != "host":
        device = functools.partial(
            _device_supports, resident=resident_bits, placement=placement
        )

    # 1. recount cached results on the appended rows — only where the
    # per-item delta-frequency bound admits a non-zero delta support. On a
    # device placement those itemsets' whole supports are the device's
    # counts over the current version's resident bitsets
    recount_stats = {"pairs": 0, "rows_read": 0, "children": 0}
    with _obs_span("incremental.recount") as sp:
        dbits = _delta_bits_of(table, base_rows, word_map)
        dfreq = popcount_rows(dbits).astype(np.int64)
        if reduce_fn is not None:
            dfreq = reduce_fn(dfreq)
        if bands is None:
            bands = ResultBands.from_result(base_result.itemsets)
        old_sets = [ids for ids, _ in base_result.itemsets]
        supports = None
        if device is not None:
            supports = functools.partial(device, site="recount_counts", stats=recount_stats)
        new_counts, band_stats = bands.recount(
            dbits, dfreq, tau, delta_rows, reduce_fn, supports
        )
        sp.set(**band_stats, pairs_counted=recount_stats["pairs"])

    results: list[tuple[tuple[int, ...], int]] = []
    seeds: list[tuple[int, ...]] = []
    for ids, cnt in zip(old_sets, new_counts):
        if cnt <= tau:
            results.append((ids, int(cnt)))
        else:
            seeds.append(ids)
    n_promoted = len(seeds)

    # 2. brand-new items (values first seen in the delta)
    freq = table.freq
    n_new_items = table.n_items - base_items
    for a in range(base_items, table.n_items):
        if freq[a] <= tau:
            results.append(((a,), int(freq[a])))
        elif freq[a] < n:
            seeds.append((a,))

    # 3. boundary expansion: previously-present new minimal itemsets are
    # strict supersets of a seed, explored through the resident frontier
    with _obs_span("incremental.expand", seeds=len(seeds)) as sp:
        expanded = _expand_seeds(
            table,
            seeds,
            tau,
            kmax,
            inc.expansion_budget,
            placement=placement,
            resident_bits=resident_bits,
            reduce_fn=reduce_fn,
        )
        sp.set(expanded=None if expanded is None else len(expanded))
    if expanded is None:
        return _fallback("budget_expansion")

    # 4. delta-born itemsets: absent at the base (support 0 is never cached),
    # supported entirely inside the appended block
    with _obs_span("incremental.delta_born") as sp:
        out = _delta_born(
            table,
            dbits,
            base_rows,
            tau,
            kmax,
            inc.delta_candidate_budget,
            word_map=word_map,
            coll=coll,
            device=device,
        )
        if out is not None:
            born, born_stats = out
            sp.set(
                candidates=born_stats["candidates"],
                pairs_counted=born_stats["pairs"],
                born=len(born),
            )
    if out is None:
        return _fallback("budget_candidates")
    n_expanded = len(expanded)
    expanded.update(born)

    # no dedup needed: kept results had base support >= 1 and support <= tau,
    # expansion finds only sets with a base-infrequent (promoted) proper
    # subset, and delta-born sets had base support 0 — the families are
    # pairwise disjoint (expansion/delta-born overlap merged in `expanded`)
    for cs, cnt in sorted(expanded.items(), key=lambda e: (len(e[0]), sorted(e[0]))):
        results.append((tuple(sorted(cs)), cnt))

    stats = []
    by_size: dict[int, int] = {}
    for ids, _ in results:
        by_size[len(ids)] = by_size.get(len(ids), 0) + 1
    for k in range(1, kmax + 1):
        stats.append(LevelStats(k=k, emitted=by_size.get(k, 0)))
    elapsed = time.perf_counter() - t0
    stats[0].time_total = elapsed

    result = MiningResult(
        itemsets=results,
        stats=stats,
        prep=_light_prep(table, tau),
        config=config,
        wall_time=elapsed,
    )
    info = {
        "delta_rows": int(delta_rows),
        "n_promoted": n_promoted,
        "n_new_items": int(n_new_items),
        "n_seeds": len(seeds),
        "n_expanded": n_expanded,
        "n_delta_born": len(born),
        "n_cached": len(old_sets),
        **band_stats,
        # the delta-born counts' device work, from which a reader reckons
        # the bytes they move: rows read and children written per dispatch
        "born_candidates": {str(k): v for k, v in born_stats["candidates"].items()},
        "born_pairs_counted": born_stats["pairs"],
        "born_rows_read": born_stats["rows_read"],
        "born_children_written": born_stats["children"],
        # and the recount's, when its supports are the device's counts
        "recount_pairs_counted": recount_stats["pairs"],
        "recount_rows_read": recount_stats["rows_read"],
        "recount_children_written": recount_stats["children"],
        "device_words": int(resident_bits.shape[-1]) if device is not None else 0,
    }
    if fleet:
        info["fleet"] = coll.stats()
    return result, info
