"""Bytes the intersect kernels have to move for one mine, from its level
counts.

A level k reads its parent level's rows (the itemsets stored at k-1) and
writes the children it stores. Counting each operand row read once and each
stored child written once, at the width the placement holds (``n_words``
32-bit words), is the least any kernel that reads the materialised level can
move: a kernel that keeps operand rows in fast memory moves no fewer, so a
share of the bandwidth peak taken on this count cannot pass 100%. A design
that recomputes children from the base items instead of reading a
materialised level needs this count revised.

``per_pair`` counts what a kernel that reads both operands of every
intersection from HBM moves: two rows per intersection, plus the stored
children.
"""

from __future__ import annotations


def intersect_bytes(stats: list, n_words: int) -> dict:
    """``stats``: one mine's levels, ``[{"k", "stored", "intersections"}]``
    in order of k, level 1 first."""
    row = 4 * int(n_words)
    lower = per_pair = 0
    for parent, level in zip(stats, stats[1:]):
        lower += (parent["stored"] + level["stored"]) * row
        per_pair += (2 * level["intersections"] + level["stored"]) * row
    return {"lower_bound": lower, "per_pair": per_pair}
