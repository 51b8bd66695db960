"""Fused intersect-classify pipeline: device class codes vs host
classification, locality scheduling round-trips, and driver equivalence.

Deterministic (no hypothesis) so this file runs on minimal installs; every
check is an exact integer comparison."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import KyivConfig, mine
from repro.core.bitops import popcount_rows, popcount_unpackbits
from repro.kernels.intersect import (
    CLASS_EMIT,
    CLASS_SKIP,
    CLASS_STORE,
    LevelPipeline,
    classify_counts_host,
    intersect_classify,
    locality_order,
)
from repro.kernels.intersect.ops import _largest_divisor_tile

RNG = np.random.default_rng(42)

ENGINES = ("numpy", "jnp", "pallas")


def _mk_level(t, W, M, density=0.08):
    """Random sparse parent level + pairs: sparse so every class occurs."""
    bits = (
        RNG.integers(0, 2**32, size=(t, W), dtype=np.uint32)
        & RNG.integers(0, 2**32, size=(t, W), dtype=np.uint32)
        & (RNG.random(size=(t, W)) < density * 8).astype(np.uint32) * np.uint32(0xFFFFFFFF)
    )
    bits[0] = 0  # an absent parent: every pair with it classifies SKIP
    bits[1] = bits[2]  # identical parents: uniform pair -> SKIP
    pairs = RNG.integers(0, t, size=(M, 2)).astype(np.int32)
    pairs[0] = (1, 2)
    pairs[1] = (0, 3)
    pc = popcount_rows(bits)
    return bits, pairs, pc


def _host_reference(bits, pairs, pc, tau):
    child = bits[pairs[:, 0]] & bits[pairs[:, 1]]
    counts = popcount_rows(child)
    minp = np.minimum(pc[pairs[:, 0]], pc[pairs[:, 1]])
    return child, counts, classify_counts_host(counts, minp, tau)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("write", [True, False])
@pytest.mark.parametrize("t,W,M", [(16, 128, 37), (32, 256, 300), (8, 384, 11)])
def test_fused_classify_matches_host(engine, write, t, W, M):
    """Fused class codes == host classification, incl. padded-bucket tails
    (M=37, 300, 11 are all non-bucket sizes)."""
    bits, pairs, pc = _mk_level(t, W, M)
    tau = 6
    ref_child, ref_counts, ref_cls = _host_reference(bits, pairs, pc, tau)
    assert {CLASS_SKIP, CLASS_STORE} <= set(ref_cls.tolist())  # classes exercised
    child, counts, classes = intersect_classify(
        bits, pairs, pc, tau=tau, write_children=write, engine=engine, interpret=True
    )
    assert np.array_equal(counts, ref_counts)
    assert np.array_equal(classes, ref_cls)
    if write:
        assert np.array_equal(child, ref_child)
    else:
        assert child is None


@pytest.mark.parametrize("write", [True, False])
def test_fused_classify_pallas_gathered(write):
    """The gathered (indexed=False) Pallas path classifies identically."""
    bits, pairs, pc = _mk_level(16, 256, 64)
    tau = 4
    _, ref_counts, ref_cls = _host_reference(bits, pairs, pc, tau)
    child, counts, classes = intersect_classify(
        bits, pairs, pc, tau=tau, write_children=write, engine="pallas",
        interpret=True, indexed=False,
    )
    assert np.array_equal(counts, ref_counts)
    assert np.array_equal(classes, ref_cls)


def test_emit_class_occurs():
    """A construction where CLASS_EMIT must appear, on every engine."""
    W = 128
    bits = np.zeros((4, W), dtype=np.uint32)
    bits[0, 0] = 0b11110000
    bits[1, 0] = 0b00110011
    bits[2, 0] = 0xFFFF
    bits[3, 0] = 0xFF00FF00
    pairs = np.array([[0, 1], [2, 3]], dtype=np.int32)
    pc = popcount_rows(bits)
    for engine in ENGINES:
        _, counts, classes = intersect_classify(
            bits, pairs, pc, tau=2, write_children=True, engine=engine, interpret=True
        )
        assert counts.tolist() == [2, 8]
        assert classes.tolist() == [CLASS_EMIT, CLASS_STORE]


def test_locality_order_roundtrip():
    """The pair-locality permutation round-trips exactly."""
    pairs = RNG.integers(0, 50, size=(1000, 2)).astype(np.int32)
    order, inverse = locality_order(pairs)
    assert order is not None  # random pairs are not i-monotone
    sorted_pairs = pairs[order]
    i = sorted_pairs[:, 0]
    assert np.all(i[1:] >= i[:-1])  # scheduled: parent runs are contiguous
    # within an i-run, j ascending (stable (i, j) order)
    same_i = i[1:] == i[:-1]
    assert np.all(sorted_pairs[1:][same_i, 1] >= sorted_pairs[:-1][same_i, 1])
    assert np.array_equal(sorted_pairs[inverse], pairs)  # exact round-trip
    payload = np.arange(len(pairs))
    assert np.array_equal(payload[order][inverse], payload)


def test_locality_order_sorted_is_noop():
    """i-monotone batches (the prefix-join generator's output) skip the sort."""
    pairs = np.stack(
        [np.repeat(np.arange(10), 3), np.tile(np.arange(3), 10)], axis=1
    ).astype(np.int32)
    order, inverse = locality_order(pairs)
    assert order is None and inverse is None


@pytest.mark.parametrize("engine", ENGINES)
def test_locality_sort_does_not_change_outputs(engine):
    bits, pairs, pc = _mk_level(24, 128, 111)
    for write in (True, False):
        a = intersect_classify(
            bits, pairs, pc, tau=3, write_children=write, engine=engine,
            interpret=True, locality_sort=True,
        )
        b = intersect_classify(
            bits, pairs, pc, tau=3, write_children=write, engine=engine,
            interpret=True, locality_sort=False,
        )
        assert np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])
        if write:
            assert np.array_equal(a[0], b[0])


@pytest.mark.parametrize("engine", ENGINES)
def test_mine_fused_equals_host_classified(engine):
    """KyivConfig.fused_classify flips the classification location, never the
    mining result or the per-level counters."""
    rng = np.random.default_rng(7)
    for trial in range(3):
        D = rng.integers(0, 4, size=(60, 5))
        fused = mine(D, KyivConfig(tau=2, kmax=4, engine=engine, fused_classify=True))
        host = mine(D, KyivConfig(tau=2, kmax=4, engine=engine, fused_classify=False))
        assert fused.canonical_set() == host.canonical_set()
        assert sorted(fused.itemsets) == sorted(host.itemsets)
        for sf, sh in zip(fused.stats, host.stats):
            assert (sf.k, sf.candidates, sf.support_pruned, sf.bound_pruned,
                    sf.intersections, sf.emitted, sf.skipped_absent_uniform,
                    sf.stored) == \
                   (sh.k, sh.candidates, sh.support_pruned, sh.bound_pruned,
                    sh.intersections, sh.emitted, sh.skipped_absent_uniform,
                    sh.stored)


def test_mine_double_buffer_equivalence():
    rng = np.random.default_rng(13)
    D = rng.integers(0, 5, size=(80, 6))
    base = mine(D, KyivConfig(tau=1, kmax=4, double_buffer=False))
    dbuf = mine(D, KyivConfig(tau=1, kmax=4, double_buffer=True))
    assert base.canonical_set() == dbuf.canonical_set()
    # small chunks force many in-flight batches per level
    tiny = mine(D, KyivConfig(tau=1, kmax=4, max_pairs_per_chunk=8))
    assert base.canonical_set() == tiny.canonical_set()


def test_level_pipeline_empty_submit():
    bits = np.zeros((4, 128), dtype=np.uint32)
    pipe = LevelPipeline(bits, np.zeros(4, dtype=np.int64), tau=1, engine="numpy")
    child, counts, classes = pipe.submit(np.zeros((0, 2), np.int32), True).result()
    assert child.shape == (0, 128) and counts.shape == (0,) and classes.shape == (0,)


def test_largest_divisor_tile():
    """Word tiles are lane multiples: the O(sqrt) search agrees with the
    brute-force definition, and unaligned word counts are refused."""

    def brute(dim, preferred):
        t = max(preferred, 128) // 128 * 128
        while dim % t:
            t -= 128
        return t

    cases = [(512, 512), (384, 512), (128, 8), (896, 512), (1536, 1024),
             (128 * 997, 512), (128 * 2 * 3 * 5 * 7 * 11, 4096), (1 << 20, 512),
             (31360, 8192), (31360, 512)]
    for dim, preferred in cases:
        got = _largest_divisor_tile(dim, preferred)
        assert got == brute(dim, preferred) and got % 128 == 0, (dim, preferred)
    for unaligned in (1, 7, 31250, 31256):
        with pytest.raises(ValueError):
            _largest_divisor_tile(unaligned, 512)
    # pathological prime lane counts: exact and instant
    import time
    big_prime = 1_000_003
    t0 = time.perf_counter()
    assert _largest_divisor_tile(128 * big_prime, 512) == 128
    assert time.perf_counter() - t0 < 0.05


def test_popcount_fallback_matches_ufunc():
    """unpackbits fallback (numpy<2.0 path) is exact for every word dtype."""
    for dtype in (np.uint8, np.uint16, np.uint32, np.uint64):
        words = RNG.integers(0, np.iinfo(dtype).max, size=(13, 17), dtype=dtype)
        ref = np.bitwise_count(words)
        assert np.array_equal(popcount_unpackbits(words), ref)
