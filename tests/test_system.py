"""End-to-end behaviour tests: the full pipelines a user would run."""

import numpy as np
import pytest

from repro.core import KyivConfig, brute_force_minimal_infrequent, mine
from repro.data.synth import randomized_dataset
from repro.sdc.quasi import find_quasi_identifiers, k_anonymize_columns


def test_mining_pipeline_randomized():
    """Paper §5.2-style run (scaled): dataset -> Kyiv -> verified results."""
    D = randomized_dataset(n=400, m=6, seed=0)
    res = mine(D, KyivConfig(tau=1, kmax=3))
    assert len(res.itemsets) > 0
    # spot-verify against brute force on a slice of the data
    Ds = D[:60, :4]
    oracle = brute_force_minimal_infrequent(Ds, 1, 3)
    got = mine(Ds, KyivConfig(tau=1, kmax=3)).canonical_set()
    assert got == oracle
    # stats are coherent
    for s in res.stats:
        if s.k > 1:
            assert s.candidates == s.type_a + s.type_b + s.type_c + s.skipped_absent_uniform + (
                s.stored
            ) or s.candidates >= s.intersections


def test_sdc_pipeline():
    """§1.1 scenario: anonymise, re-mine, risk decreases."""
    rng = np.random.default_rng(0)
    table = np.stack(
        [rng.zipf(1.3, 800).clip(max=500), rng.integers(0, 8, 800),
         rng.integers(0, 2, 800)], axis=1)
    before = find_quasi_identifiers(table, tau=1, kmax=2)
    anon = k_anonymize_columns(table, k=5)
    after = find_quasi_identifiers(anon, tau=1, kmax=2)
    # single-column uniques must be (nearly) eliminated
    assert after.by_size().get(1, 0) <= max(1, before.by_size().get(1, 0) // 10)

