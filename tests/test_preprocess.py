"""Pre-processing (§4.1): partition properties, Example 4.3, orderings,
exact duplicate-row-set grouping."""

import importlib
from types import SimpleNamespace

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import itemize, preprocess
from repro.obs.trace import TRACER

_pre = importlib.import_module("repro.core.preprocess")
BW = _pre._HASH_BLOCK_WORDS
BI = _pre._HASH_BLOCK_ITEMS


def paper_example_43():
    return np.array(
        [[1, 2, 3, 4, 8], [1, 2, 7, 4, 8], [1, 6, 3, 4, 8], [5, 2, 3, 4, 9]]
    )


def test_example_43_partition():
    t = itemize(paper_example_43())
    prep = preprocess(t, tau=1)
    # r_{A,tau} = the four unique items; U_A = {(4, col4)}
    assert len(prep.infrequent_items) == 4
    assert len(prep.uniform_items) == 1
    # L has 3 canonical items; item (8, col5) duplicates (1, col1)'s rows
    assert prep.n_l == 3
    mirrors = sum(len(v) for v in prep.mirror_of.values())
    assert mirrors == 1
    (canon,) = [c for c, v in prep.mirror_of.items() if v]
    v, j = t.describe(canon)
    assert (v, j) == (1, 0)
    mv, mj = t.describe(prep.mirror_of[canon][0])
    assert (mv, mj) == (8, 4)


dataset_st = st.integers(4, 40).flatmap(
    lambda n: st.integers(2, 6).flatmap(
        lambda m: st.lists(
            st.lists(st.integers(0, 4), min_size=m, max_size=m),
            min_size=n, max_size=n,
        )
    )
)


@given(dataset_st, st.integers(1, 3))
@settings(max_examples=50, deadline=None)
def test_partition_properties(rows, tau):
    D = np.asarray(rows)
    t = itemize(D)
    prep = preprocess(t, tau=tau)
    n = t.n_rows
    # (i) canonical rows pairwise distinct
    seen = set()
    for i, it in enumerate(prep.l_items):
        key = prep.l_bits[i].tobytes()
        assert key not in seen
        seen.add(key)
        # L items are neither uniform nor tau-infrequent
        assert tau < t.freq[it] < n
    # (ii) every dropped duplicate maps to a canonical with identical rows
    for canon, dups in prep.mirror_of.items():
        for d in dups:
            assert np.array_equal(t.bits[canon], t.bits[d])
    # partition covers everything exactly once
    covered = (
        set(prep.l_items.tolist())
        | {d for v in prep.mirror_of.values() for d in v}
        | set(prep.uniform_items.tolist())
        | set(prep.infrequent_items.tolist())
    )
    assert covered == set(range(t.n_items))


@given(dataset_st)
@settings(max_examples=20, deadline=None)
def test_ascending_order(rows):
    D = np.asarray(rows)
    t = itemize(D)
    prep = preprocess(t, tau=1, ordering="ascending")
    f = t.freq[prep.l_items]
    assert np.all(np.diff(f) >= 0)  # Def 4.5(i)
    desc = preprocess(t, tau=1, ordering="descending")
    assert np.all(np.diff(t.freq[desc.l_items]) <= 0)
    rnd1 = preprocess(t, tau=1, ordering="random", seed=1)
    rnd2 = preprocess(t, tau=1, ordering="random", seed=1)
    assert np.array_equal(rnd1.l_items, rnd2.l_items)  # deterministic per seed


def _brute_groups(bits, ids):
    """Reference grouping: ids keyed by their rows' bytes, each group in
    ascending id order, the groups in ascending order of their first id."""
    by_row: dict[bytes, list[int]] = {}
    for i in ids.tolist():
        by_row.setdefault(bits[i].tobytes(), []).append(i)
    return sorted((sorted(g) for g in by_row.values()), key=lambda g: g[0])


def _planted_bits(seed, n_items, n_words, last_word_twins=False):
    """Sparse random rows with planted duplicate groups; with
    ``last_word_twins`` also pairs that differ only in the last word."""
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2**32, size=(n_items, n_words), dtype=np.uint32)
    bits &= rng.integers(0, 2**32, size=(n_items, n_words), dtype=np.uint32)
    bits[rng.random(n_items) < 0.1] = 0  # a few empty rows: one more group
    for _ in range(max(1, n_items // 6)):
        src, *dups = rng.choice(n_items, size=int(rng.integers(2, 5)), replace=False)
        bits[dups] = bits[src]
    if last_word_twins:
        for src, twin in rng.choice(n_items, size=(3, 2), replace=False):
            bits[twin] = bits[src]
            bits[twin, -1] ^= np.uint32(1) << np.uint32(31)
    return bits


@pytest.mark.parametrize(
    "n_items, n_words, subset, last_word_twins",
    [
        (40, 1, False, False),
        (40, 3, False, False),
        (40, 130, False, False),
        (40, 130, True, False),
        (24, BW + 77, False, False),  # W not a multiple of the word block
        (24, 2 * BW + 5, True, True),  # twins differ in the last block's last word
        (2 * BI + 50, 3, True, False),  # several item blocks
    ],
)
def test_row_set_groups_match_brute_force(n_items, n_words, subset, last_word_twins):
    bits = _planted_bits(n_items * 1000 + n_words, n_items, n_words, last_word_twins)
    ids = np.arange(n_items, dtype=np.int64)
    if subset:  # a strict, unsorted subset of the items
        ids = np.random.default_rng(n_words).permutation(ids)[: n_items * 3 // 4]
    groups, verified = _pre._row_set_groups(SimpleNamespace(bits=bits), ids)
    want = _brute_groups(bits, ids)
    assert [g.tolist() for g in groups] == want
    assert any(len(g) > 1 for g in want)
    # without a hash collision the verified buckets are the duplicate groups
    assert verified == sum(len(g) > 1 for g in want)
    if last_word_twins:
        assert len(want) > len(_brute_groups(bits[:, :-1], ids))


def test_forced_hash_collision_stays_exact(monkeypatch):
    t = itemize(np.random.default_rng(5).integers(0, 4, size=(300, 5)))
    plain = preprocess(t, tau=3)
    monkeypatch.setattr(
        _pre, "_row_hashes", lambda bits, ids: np.zeros(len(ids), dtype=np.uint64)
    )
    ids = np.arange(t.n_items, dtype=np.int64)
    groups, verified = _pre._row_set_groups(t, ids)
    assert [g.tolist() for g in groups] == _brute_groups(t.bits, ids)
    assert verified == 1  # every item in the one bucket
    collided = preprocess(t, tau=3)
    assert collided.verified_buckets == 1 and plain.verified_buckets == 0
    assert np.array_equal(collided.l_items, plain.l_items)
    assert collided.mirror_of == plain.mirror_of


def test_preprocess_span_counts_hashed_items():
    from repro.service import MiningService

    rng = np.random.default_rng(8)
    D = rng.integers(0, 3, size=(500, 4))
    D[:, 3] = D[:, 0] + 10  # column 3 mirrors column 0: three duplicate pairs
    tau = 2
    svc = MiningService.from_dataset(D, engine="numpy")
    TRACER.reset()
    try:
        table = svc.store.item_table()
        assert svc.mine(tau=tau, kmax=2).source == "cold"
        (trace,) = TRACER.last(1)
        (span,) = trace.find("mine.preprocess")
    finally:
        svc.close()
        TRACER.reset()
    kept = (table.freq > tau) & (table.freq < table.n_rows)
    assert span.attrs["items"] == int(kept.sum()) == 12
    assert span.attrs["words"] == table.bits.shape[1]
    assert span.attrs["verified_buckets"] == 3
