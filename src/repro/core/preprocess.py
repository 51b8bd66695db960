"""Pre-processing of the item table (paper §4.1) and item ordering (Def. 4.5).

Steps, exactly as the paper prescribes:
  1. Uniform items ``U_A`` (``|R_a| = n``) are dropped — they cannot belong to
     a minimal τ-infrequent itemset.
  2. τ-infrequent single items ``r_{A,τ}`` (``|R_a| <= τ``) are emitted
     directly — items are trivially minimal.
  3. The remaining items ``I'_{A,τ}`` are partitioned into a canonical set
     ``L_{A,τ}`` with pairwise-distinct row sets and a mirror set ``L̄`` of
     duplicates (Propositions 4.1/4.2): mining runs on ``L`` only and every
     result involving a canonical item ``w`` expands to results for every
     mirror ``w'`` with ``R_w = R_{w'}``.
  4. ``L`` is sorted ascending (Def. 4.5): by ``(|R_a|, j_a, min R_a)``.

Duplicate row-set detection hashes bitset rows (exact: hash, then verify
within hash buckets) — O(items × W), in blocks of items × words.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .items import ItemTable

__all__ = ["Preprocessed", "preprocess", "set_row_group_collective", "ORDERINGS"]

ORDERINGS = ("ascending", "descending", "random")

# Fleet rendezvous for duplicate-row-set detection: with process-sharded
# bitsets each process sees only its word stripes, so neither the hashes nor
# the exact verification are decidable locally. When a collective is
# installed, `_row_set_groups` combines all-gathered per-item hashes into a
# global hash and AND-reduces the within-bucket equality flags — two
# collective rounds per preprocess, after which every process holds the
# identical canonical/mirror partition.
_ROW_GROUP_COLLECTIVE = None

# Block of the row hash: the rows are read this many items by this many
# words at a time (4 MiB of uint32), so hashing a wide store never doubles it.
_HASH_BLOCK_ITEMS = 256
_HASH_BLOCK_WORDS = 4096


def set_row_group_collective(coll):
    """Install the fleet collective (``repro.core.collective``) used to agree
    on duplicate row sets; ``None`` restores local-only grouping. Returns the
    previous value so callers can restore it."""
    global _ROW_GROUP_COLLECTIVE
    prev, _ROW_GROUP_COLLECTIVE = _ROW_GROUP_COLLECTIVE, coll
    return prev


@dataclasses.dataclass
class Preprocessed:
    """Output of §4.1 pre-processing.

    Attributes:
      table: the original item table.
      tau: threshold used.
      uniform_items: ids in ``U_A``.
      infrequent_items: ids in ``r_{A,τ}`` (emitted as 1-itemsets).
      l_items: ids of ``L_{A,τ}`` in the chosen order (``L^<`` when ascending).
      mirror_of: dict canonical item id -> list of duplicate item ids (``L̄``).
      l_bits: (|L|, W) uint32 bitsets of ``L`` rows, ordered like ``l_items``.
      l_freq: (|L|,) frequencies, same order.
      verified_buckets: hash buckets of more than one item that went through
        the exact row check of the duplicate-row-set detection.
    """

    table: ItemTable
    tau: int
    uniform_items: np.ndarray
    infrequent_items: np.ndarray
    l_items: np.ndarray
    mirror_of: dict[int, list[int]]
    l_bits: np.ndarray
    l_freq: np.ndarray
    verified_buckets: int = 0

    @property
    def n_l(self) -> int:
        return int(self.l_items.shape[0])


def _hash_coefficients(w0: int, w1: int) -> np.ndarray:
    """Fixed odd 64-bit multipliers of words ``w0 .. w1 - 1`` of a row: the
    splitmix64 finaliser of the word index, so every word position has its
    own multiplier and no block of the row can trade places with another."""
    x = np.arange(w0, w1, dtype=np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return (x ^ (x >> np.uint64(31))) | np.uint64(1)


def _row_hashes(bits: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Multilinear hash of the bitset rows ``bits[ids]``: the sum over words
    of word × ``_hash_coefficients``, mod 2**64.

    Equal rows hash equal, and the multiplier depends only on the word's
    position in ``bits``, so under the fleet collective equal stripes hash
    equal on every process. The rows are read straight from ``bits`` in
    ``(_HASH_BLOCK_ITEMS, _HASH_BLOCK_WORDS)`` blocks: no ``(g, W)`` copy or
    uint64 temporary of the whole selection is made, whatever the width.
    """
    h = np.zeros(len(ids), dtype=np.uint64)
    n_words = bits.shape[1]
    for w0 in range(0, n_words, _HASH_BLOCK_WORDS):
        w1 = min(w0 + _HASH_BLOCK_WORDS, n_words)
        coef = _hash_coefficients(w0, w1)
        for i0 in range(0, len(ids), _HASH_BLOCK_ITEMS):
            i1 = i0 + _HASH_BLOCK_ITEMS
            h[i0:i1] += np.dot(bits[ids[i0:i1], w0:w1], coef)
    return h


def _row_set_groups(table: ItemTable, ids: np.ndarray) -> tuple[list[np.ndarray], int]:
    """Group item ids by identical row sets (bitset rows). Exact.

    Returns ``(groups, verified_buckets)``: a list of arrays, each holding
    the ids sharing one row set in ascending item-id order, the groups in
    ascending order of their first id; and the number of hash buckets of
    more than one item that went through the exact row check.
    """
    if len(ids) == 0:
        return [], 0
    # Hash each row, then verify within buckets to keep exactness.
    h = _row_hashes(table.bits, ids)
    coll = _ROW_GROUP_COLLECTIVE
    if coll is not None:
        # round 1: fold every process's local hashes (pid order is fixed by
        # the all-gather) into one global hash — equal rows hash equal
        # everywhere, so the buckets below agree across the fleet
        mix2 = np.uint64(0xBF58476D1CE4E5B9)
        combined = np.zeros_like(h)
        for payload in coll.allgather(np.ascontiguousarray(h).tobytes()):
            ph = np.frombuffer(payload, dtype=np.uint64)
            combined = (combined ^ ph) * mix2
            combined ^= combined >> np.uint64(31)
        h = combined
    order = np.argsort(h, kind="stable")
    hs = h[order]
    starts = np.flatnonzero(hs[1:] != hs[:-1]) + 1
    buckets = np.split(ids[order], starts)
    # exact verification within each multi-element bucket: all pairwise
    # equality flags in one flat vector. Locally that is just array_equal;
    # under a collective the flags AND-reduce (round 2: sum == nproc) so a
    # pair is grouped only when its rows agree on *every* process's stripes.
    multis = [b for b in buckets if len(b) > 1]
    eq_of: dict[int, np.ndarray] = {}
    if multis:
        flags = []
        for b in multis:
            rows = table.bits[b]  # (g, W)
            eq = (rows[:, None, :] == rows[None, :, :]).all(axis=2)
            flags.append(eq[np.triu_indices(len(b), 1)])
        flat = np.concatenate(flags).astype(np.int64)
        if coll is not None:
            flat = coll.allreduce_sum(flat) == coll.nproc
        else:
            flat = flat.astype(bool)
        off = 0
        for bi, b in enumerate(multis):
            g = len(b)
            npairs = g * (g - 1) // 2
            eq = np.eye(g, dtype=bool)
            iu = np.triu_indices(g, 1)
            eq[iu] = flat[off : off + npairs]
            eq.T[iu] = flat[off : off + npairs]
            eq_of[bi] = eq
            off += npairs
    groups: list[np.ndarray] = []
    bi = 0
    for bucket in buckets:
        if len(bucket) == 1:
            groups.append(bucket)
            continue
        eq = eq_of[bi]
        bi += 1
        rem = list(range(len(bucket)))
        while rem:
            head = rem[0]
            same = [r for r in rem if eq[head, r]]
            groups.append(np.asarray(sorted(int(bucket[r]) for r in same), dtype=np.int64))
            rem = [r for r in rem if r not in same]
    groups.sort(key=lambda g: int(g[0]))
    return groups, len(multis)


def preprocess(
    table: ItemTable,
    tau: int,
    ordering: str = "ascending",
    seed: int = 0,
) -> Preprocessed:
    """Run §4.1 pre-processing + Def. 4.5 ordering on an item table."""
    if tau <= 0:
        raise ValueError(f"tau must be positive (Def. 3.3 usage), got {tau}")
    if ordering not in ORDERINGS:
        raise ValueError(f"ordering must be one of {ORDERINGS}, got {ordering!r}")

    n = table.n_rows
    freq = table.freq
    uniform = np.nonzero(freq == n)[0]
    infrequent = np.nonzero(freq <= tau)[0]
    # Uniform items with n <= tau would satisfy both; the paper confines τ < n.
    keep_mask = (freq > tau) & (freq < n)
    remaining = np.nonzero(keep_mask)[0]

    groups, verified_buckets = _row_set_groups(table, remaining)
    canonical = np.asarray([int(g[0]) for g in groups], dtype=np.int64)
    mirror_of = {int(g[0]): [int(x) for x in g[1:]] for g in groups if len(g) > 1}

    if ordering == "ascending":
        order = np.lexsort(
            (table.min_row[canonical], table.col[canonical], table.freq[canonical])
        )
    elif ordering == "descending":
        order = np.lexsort(
            (table.min_row[canonical], table.col[canonical], table.freq[canonical])
        )[::-1]
    else:  # random
        rng = np.random.default_rng(seed)
        order = rng.permutation(len(canonical))
    l_items = canonical[order]

    return Preprocessed(
        table=table,
        tau=tau,
        uniform_items=uniform,
        infrequent_items=infrequent,
        l_items=l_items,
        mirror_of=mirror_of,
        l_bits=np.ascontiguousarray(table.bits[l_items]),
        l_freq=table.freq[l_items].astype(np.int64),
        verified_buckets=verified_buckets,
    )
