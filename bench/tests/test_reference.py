"""The plain reference against the program's brute-force oracle, and the
table generators' shapes."""

import numpy as np
import pytest

from reference import Reference
from tables import columns, deal


def _value_sets(ref, answer):
    return {
        (tuple(sorted((int(ref.item_col[i]), int(ref.item_value[i])) for i in row)), int(c))
        for ids, sup in answer.values()
        for row, c in zip(ids, sup)
    }


@pytest.mark.parametrize("case", range(24))
def test_reference_matches_brute_force(case):
    from repro.core.items import itemize
    from repro.core.oracle import brute_force_minimal_infrequent

    rng = np.random.default_rng(case)
    n, m = int(rng.integers(20, 120)), int(rng.integers(2, 6))
    table = rng.integers(0, int(rng.integers(2, 5)), size=(n, m))
    tau, kmax = int(rng.integers(1, 8)), int(rng.integers(1, 4))
    items = itemize(table)
    want = {
        tuple(sorted((int(items.col[i]), int(items.value[i])) for i in s))
        for s in brute_force_minimal_infrequent(table, tau, kmax)
    }
    ref = Reference(table, kmax, tau_min=max(1, tau - 2))
    got = _value_sets(ref, ref.answer(tau))
    assert {s for s, _ in got} == want
    for s, count in got:
        rows = np.ones(n, dtype=bool)
        for c, v in s:
            rows &= table[:, c] == v
        assert count == rows.sum()


def test_counting_methods_agree():
    table = columns.generate({"domains": [5, 7, 3, 9, 4, 6], "zipf": 1.1}, 3000,
                             np.random.default_rng(3))
    ref = Reference(table, 3, 20)
    for level in ref.levels[1:]:
        frequent = np.nonzero(ref.levels[0].support > 20)[0]
        assert np.array_equal(ref._count_rows(level.ids, frequent), ref._count_bits(level.ids))


def test_reference_rejects_tau_below_its_floor():
    ref = Reference(np.zeros((4, 2), dtype=np.int64), 2, 3)
    with pytest.raises(ValueError):
        ref.answer(2)


def test_ids_of_maps_items_and_flags_unknown():
    table = np.array([[1, 5], [2, 5], [1, 7]])
    ref = Reference(table, 2, 1)
    assert ref.ids_of([[0, 2], [1, 7], [1, 6]]).tolist() == [1, 3, -1]


def test_deal_gives_distinct_cards_uniformly():
    t = deal.generate({"suits": 4, "ranks": 13, "hand": 5}, 52_000, np.random.default_rng(1))
    assert t.shape == (52_000, 10)
    cards = np.sort((t[:, 0::2] - 1) * 13 + t[:, 1::2] - 1, axis=1)
    assert (cards[:, 1:] != cards[:, :-1]).all()
    per_card = np.bincount(cards.ravel(), minlength=52)
    assert per_card.min() > 0.9 * 5_000 and per_card.max() < 1.1 * 5_000


def test_columns_use_every_value_with_zipf_marginals():
    t = columns.generate({"domains": [4, 9], "zipf": 1.1}, 20_000, np.random.default_rng(2))
    assert [len(np.unique(t[:, j])) for j in range(2)] == [4, 9]
    counts = np.bincount(t[:, 1])
    assert (np.diff(counts) < 0).all()
