"""Plain reference: every minimal τ-infrequent itemset up to size ``kmax``.

An itemset is a set of (column, value) items from distinct columns. Its
support is the number of rows that hold all of its items. It is
τ-infrequent when its support is at least 1 and at most τ, and minimal when
every subset with one item fewer has support above τ. Items with support 0
never co-occur, so they are no answer (the paper's Alg. 1, line 32).

Level by level, and once for all the τ a run asks about: the candidates of
size k are the sets of distinct-column items whose every (k-1)-subset is
frequent at the smallest τ; each candidate's support is counted exactly;
then an itemset is an answer at τ when its support is in [1, τ] and the
least support among its (k-1)-subsets is above τ. Supports are counted by
whichever of two plain methods reads less: enumerating the k-subsets of
every row and counting them, or AND-ing the items' row bitsets and counting
the bits.

This module imports numpy alone, and nothing of the program under test.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass

import numpy as np

__all__ = ["Answer", "Reference"]

# Answer: k-itemsets as an (n, k) array of reference item ids, ascending in
# each row and rows in lexicographic order, with their supports.
Answer = dict  # {k: (ids (n, k) int64, supports (n,) int64)}


@dataclass
class _Level:
    ids: np.ndarray  # (c, k) candidate item ids, ascending in each row
    support: np.ndarray  # (c,)
    min_sub: np.ndarray  # (c,) least support among the (k-1)-subsets


class Reference:
    """Supports of every candidate itemset of ``table`` that can be an
    answer at some τ >= ``tau_min``, then the answer for each τ."""

    def __init__(self, table: np.ndarray, kmax: int, tau_min: int):
        table = np.asarray(table)
        self.n_rows, self.n_cols = table.shape
        self.kmax = int(kmax)
        self.tau_min = int(tau_min)
        # item ids: column by column, values ascending within a column
        self.item_col, self.item_value, cols = [], [], []
        base = 0
        for j in range(self.n_cols):
            values, inverse = np.unique(table[:, j], return_inverse=True)
            cols.append(inverse.astype(np.int64) + base)
            self.item_col += [j] * len(values)
            self.item_value += values.tolist()
            base += len(values)
        self.n_items = base
        self.item_col = np.asarray(self.item_col, dtype=np.int64)
        self.item_value = np.asarray(self.item_value, dtype=np.int64)
        self._items = np.stack(cols, axis=1)  # (rows, cols) item ids
        self._bits: np.ndarray | None = None
        self.levels: list[_Level] = []
        self._build()

    # -- counting -----------------------------------------------------------

    def _count_rows(self, cand: np.ndarray, frequent: np.ndarray) -> np.ndarray:
        """Supports by enumerating the k-subsets of every row: each row holds
        one item per column, so its k-subsets are its items at each
        combination of k columns."""
        k = cand.shape[1]
        fid = np.full(self.n_items, -1, dtype=np.int64)
        fid[frequent] = np.arange(len(frequent))
        f = len(frequent)
        counts = np.zeros(f**k, dtype=np.int64)
        cols = fid[self._items].T.copy()  # frequent-item index or -1, per column
        keys, held = [], 0
        for combo in itertools.combinations(range(self.n_cols), k):
            key = cols[combo[0]].copy()
            for c in combo[1:]:
                key = key * f + cols[c]
            ok = (cols[list(combo)] >= 0).all(axis=0)
            keys.append(key if ok.all() else key[ok])
            held += len(keys[-1])
            if held >= 1 << 24:
                counts += np.bincount(np.concatenate(keys), minlength=f**k)
                keys, held = [], 0
        if keys:
            counts += np.bincount(np.concatenate(keys), minlength=f**k)
        key = np.zeros(len(cand), dtype=np.int64)
        for c in range(k):
            key = key * f + fid[cand[:, c]]
        return counts[key]

    def _item_bits(self) -> np.ndarray:
        """(items, ceil(rows / 64)) uint64 row bitsets."""
        if self._bits is None:
            n_words = -(-self.n_rows // 64)
            dense = np.zeros((self.n_items, n_words * 64), dtype=bool)
            dense[self._items, np.arange(self.n_rows)[:, None]] = True
            self._bits = np.packbits(dense, axis=1, bitorder="little").view(np.uint64)
        return self._bits

    def _count_bits(self, cand: np.ndarray) -> np.ndarray:
        """Supports by AND-ing the candidates' item bitsets and counting bits."""
        bits = self._item_bits()
        out = np.empty(len(cand), dtype=np.int64)
        step = max(1, (1 << 24) // bits.shape[1])
        for lo in range(0, len(cand), step):
            c = cand[lo : lo + step]
            acc = bits[c[:, 0]]
            for j in range(1, c.shape[1]):
                acc &= bits[c[:, j]]
            out[lo : lo + step] = np.bitwise_count(acc).sum(axis=1, dtype=np.int64)
        return out

    def _count(self, cand: np.ndarray, frequent: np.ndarray) -> np.ndarray:
        k = cand.shape[1]
        f = len(frequent)
        by_rows = self.n_rows * math.comb(self.n_cols, k)
        by_bits = len(cand) * (k + 1) * -(-self.n_rows // 64)
        if f**k <= (1 << 26) and by_rows <= by_bits:
            return self._count_rows(cand, frequent)
        return self._count_bits(cand)

    # -- levels -------------------------------------------------------------

    def _build(self) -> None:
        support = np.bincount(self._items.ravel(), minlength=self.n_items)
        ids = np.arange(self.n_items, dtype=np.int64)[:, None]
        self.levels.append(_Level(ids, support, np.full(self.n_items, np.iinfo(np.int64).max)))
        for k in range(2, self.kmax + 1):
            prev = self.levels[-1]
            keep = prev.support > self.tau_min
            parents = prev.ids[keep]
            parent_support = prev.support[keep]
            frequent = np.nonzero(self.levels[0].support > self.tau_min)[0]
            cand = self._join(parents)
            if len(cand) == 0:
                break
            min_sub = self._min_subset_support(cand, parents, parent_support)
            ok = min_sub >= 0  # every (k-1)-subset is a frequent parent
            cand, min_sub = cand[ok], min_sub[ok]
            self.levels.append(_Level(cand, self._count(cand, frequent), min_sub))

    def _join(self, parents: np.ndarray) -> np.ndarray:
        """k-sets from two (k-1)-sets that share their first k-2 items and
        end in items of different columns."""
        if len(parents) < 2:
            return np.zeros((0, parents.shape[1] + 1), dtype=np.int64)
        k1 = parents.shape[1]
        out = []
        prefix = parents[:, :-1]
        starts = np.flatnonzero(np.r_[True, (prefix[1:] != prefix[:-1]).any(axis=1)])
        ends = np.r_[starts[1:], len(parents)]
        for s, e in zip(starts, ends):
            if e - s < 2:
                continue
            a, b = np.triu_indices(e - s, 1)
            last = parents[s:e, -1]
            differ = self.item_col[last[a]] != self.item_col[last[b]]
            a, b = a[differ], b[differ]
            out.append(np.concatenate([parents[s + a], last[b][:, None]], axis=1))
        if not out:
            return np.zeros((0, k1 + 1), dtype=np.int64)
        return np.concatenate(out, axis=0)

    def _key(self, ids: np.ndarray) -> np.ndarray:
        key = np.zeros(len(ids), dtype=np.int64)
        for c in range(ids.shape[1]):
            key = key * self.n_items + ids[:, c]
        return key

    def _min_subset_support(self, cand, parents, parent_support) -> np.ndarray:
        """Least support among each candidate's (k-1)-subsets; -1 where a
        subset is not among the frequent parents."""
        pkey = self._key(parents)
        order = np.argsort(pkey)
        pkey, psup = pkey[order], parent_support[order]
        k = cand.shape[1]
        out = np.full(len(cand), np.iinfo(np.int64).max, dtype=np.int64)
        for drop in range(k):
            sub = np.delete(cand, drop, axis=1)
            key = self._key(sub)
            at = np.minimum(np.searchsorted(pkey, key), len(pkey) - 1)
            found = pkey[at] == key
            out = np.where(found & (out >= 0), np.minimum(out, psup[at]), -1)
        return out

    # -- answers ------------------------------------------------------------

    def answer(self, tau: int) -> Answer:
        """The minimal τ-infrequent itemsets, by size."""
        tau = int(tau)
        if tau < self.tau_min:
            raise ValueError(f"reference built for tau >= {self.tau_min}, asked {tau}")
        out: Answer = {}
        for lv in self.levels:
            hit = (lv.support >= 1) & (lv.support <= tau) & (lv.min_sub > tau)
            if hit.any():
                out[lv.ids.shape[1]] = (lv.ids[hit], lv.support[hit])
        return out

    def ids_of(self, items) -> np.ndarray:
        """Reference item ids of ``[[column, value], ...]`` rows; -1 where
        the item is not in the table."""
        items = np.asarray(items, dtype=np.int64).reshape(-1, 2)
        out = np.full(len(items), -1, dtype=np.int64)
        for j in np.unique(items[:, 0]):
            sel = items[:, 0] == j
            pos = np.flatnonzero(self.item_col == j)
            if len(pos) == 0:
                continue
            vals = self.item_value[pos]
            at = np.minimum(np.searchsorted(vals, items[sel, 1]), len(vals) - 1)
            out[sel] = np.where(vals[at] == items[sel, 1], pos[at], -1)
        return out
