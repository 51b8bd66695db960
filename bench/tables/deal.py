"""Card hands: each row deals ``hand`` distinct cards from a deck of
``suits`` x ``ranks`` and lays them out as (suit, rank) column pairs, suits
and ranks counted from 1 — the layout of the UCI Poker Hand attributes.

Cards are dealt without replacement by drawing the j-th card's position
among the ``suits * ranks - j`` cards still in the deck and stepping past the
cards already dealt, so every hand of distinct cards is equally likely.
"""

from __future__ import annotations

import numpy as np


def generate(spec: dict, n_rows: int, rng: np.random.Generator) -> np.ndarray:
    suits, ranks, hand = int(spec["suits"]), int(spec["ranks"]), int(spec["hand"])
    deck = suits * ranks
    dealt = np.empty((n_rows, hand), dtype=np.int64)
    for j in range(hand):
        card = rng.integers(0, deck - j, size=n_rows)
        # step past the cards already dealt, in ascending order
        for prev in np.sort(dealt[:, :j], axis=1).T:
            card += card >= prev
        dealt[:, j] = card
    out = np.empty((n_rows, 2 * hand), dtype=np.int64)
    out[:, 0::2] = dealt // ranks + 1
    out[:, 1::2] = dealt % ranks + 1
    return out
