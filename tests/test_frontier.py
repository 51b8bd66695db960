"""The device-resident level frontier: kernel parity, driver bit-identity,
eager retirement, and the unified executable cache.

The host reference path (``HostPlacement`` frontier methods) is the oracle:
every test asserts the device/mesh frontier produces identical results *and*
identical per-level counters. The 8-device mesh runs in a subprocess (XLA
device count must pre-date jax init); the hypothesis sweeps live in
tests/test_frontier_prop.py.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import KyivConfig, exec_cache, mine
from repro.core.frontier import LevelFrontier
from repro.core.placement import DevicePlacement, make_placement
from repro.core.prefix import (
    Level,
    generate_candidates,
    group_reps,
    iter_group_spans,
    prefix_group_sizes,
)
from repro.core.support import ItemsetIndex, support_test
from repro.kernels.frontier import ops as fops
from repro.kernels.frontier import ref as fref
from repro.kernels.intersect import LevelPipeline

RNG = np.random.default_rng(77)


def _rand_level(t_target, k, n_symbols, seed):
    """A lex-sorted level table with realistic prefix groups (itemset rows
    are strictly increasing, as the prefix-tree invariant requires)."""
    rng = np.random.default_rng(seed)
    rows: set[tuple] = set()
    tries = 0
    while len(rows) < t_target and tries < 50 * t_target:
        tries += 1
        if k == 1:
            rows.add((int(rng.integers(0, n_symbols)),))
            continue
        prefix = tuple(sorted(int(x) for x in rng.choice(n_symbols, size=k - 1, replace=False)))
        for last in rng.choice(n_symbols, size=int(rng.integers(1, 6)), replace=False):
            if int(last) > prefix[-1]:
                rows.add(prefix + (int(last),))
    its = np.asarray(sorted(rows), dtype=np.int32)
    counts = rng.integers(1, 50, size=len(its)).astype(np.int64)
    return its, counts


def _stat_tuple(s):
    return (s.k, s.candidates, s.support_pruned, s.bound_pruned,
            s.intersections, s.emitted, s.skipped_absent_uniform, s.stored)


# ---------------------------------------------------------------------------
# kernel-level parity: gen / support / mask / partition
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k,n_symbols", [(2, 40), (3, 300), (4, 70_000)])
def test_gen_support_matches_host(k, n_symbols):
    its, counts = _rand_level(60, k, n_symbols, seed=k)
    if its.shape[0] < 2:
        pytest.skip("degenerate level")
    level = Level(k=k, itemsets=its, counts=counts, bits=None)
    cand = generate_candidates(level)
    idx = ItemsetIndex(its, counts, n_symbols=n_symbols)
    ok_host = support_test(cand.itemsets, idx)

    dev = make_placement("jnp")
    state = dev.prepare_frontier(its, counts, n_symbols)
    sizes = prefix_group_sizes(its)
    got_i, got_j, got_ok = [], [], []
    for lo, hi, n_pairs in iter_group_spans(sizes, 1 << 22):
        if n_pairs == 0:
            continue
        pairs, ok = dev.frontier_dispatch(state, lo, hi, n_pairs)
        pairs, ok = np.asarray(pairs), np.asarray(ok)
        got_i.append(pairs[:n_pairs, 0])
        got_j.append(pairs[:n_pairs, 1])
        got_ok.append(ok[:n_pairs])
        assert not ok[n_pairs:].any(), "padding rows must be not-ok"
    dev.release(state)
    assert np.array_equal(np.concatenate(got_i), cand.i_idx)
    assert np.array_equal(np.concatenate(got_j), cand.j_idx)
    assert np.array_equal(np.concatenate(got_ok), ok_host)


def test_packed_key_lookup_matches_itemset_index():
    for n_symbols, k in ((17, 2), (1000, 3), (90_000, 4)):
        its, _ = _rand_level(80, k, n_symbols, seed=n_symbols)
        idx = ItemsetIndex(its, None, n_symbols=n_symbols)
        table = fref.key_table_np(its, n_symbols, fops.table_pad(its.shape[0]))
        rng = np.random.default_rng(1)
        present = its[rng.integers(0, its.shape[0], size=30)]
        absent = present.copy()
        absent[:, -1] = (absent[:, -1] + 1) % n_symbols
        for q in (present, absent):
            want = idx.lookup(q) >= 0
            got_np = fref.lookup_np(table, fref.pack_rows_np(q, n_symbols))
            assert np.array_equal(got_np, want)
            b, ipw, _ = fops.pack_params(n_symbols, k)
            from repro.kernels.frontier.frontier import lookup_keys, pack_cols

            queries = pack_cols([jnp.asarray(q[:, c]) for c in range(k)], b, ipw)
            got_dev = np.asarray(
                lookup_keys(jnp.asarray(table), queries, t_pad=table.shape[0])
            )
            assert np.array_equal(got_dev, want)


def test_partition_is_stable_class_argsort():
    part = fops.partition
    for seed in range(3):
        rng = np.random.default_rng(seed)
        classes = rng.integers(0, 3, size=512).astype(np.int32)
        order, n_emit, n_store = part(jnp.asarray(classes))
        ref_order, ref_e, ref_s = fref.partition_np(classes)
        assert np.array_equal(np.asarray(order), ref_order)
        assert (int(n_emit), int(n_store)) == (ref_e, ref_s)


def test_mask_pruned_neutralises_without_reorder():
    mask = fops.mask_pruned
    rng = np.random.default_rng(3)
    pairs = rng.integers(0, 9, size=(64, 2)).astype(np.int32)
    ok = rng.random(64) < 0.5
    out, n_ok = mask(jnp.asarray(pairs), jnp.asarray(ok))
    out = np.asarray(out)
    assert int(n_ok) == ok.sum()
    assert np.array_equal(out[ok], pairs[ok])  # survivors untouched, in place
    assert np.all(out[~ok, 0] == out[~ok, 1])  # pruned -> CLASS_SKIP self-pairs


def test_group_reps_matches_generate_candidates():
    its, _ = _rand_level(50, 3, 200, seed=9)
    reps = group_reps(its)
    cand = generate_candidates(Level(k=3, itemsets=its, counts=np.zeros(len(its)), bits=None))
    assert reps.sum() == cand.m
    assert np.array_equal(np.repeat(np.arange(len(its)), reps), cand.i_idx)


# ---------------------------------------------------------------------------
# driver bit-identity: device frontier == host reference, results AND stats
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_mine_device_frontier_bit_identical(engine):
    D = RNG.integers(0, 5, size=(250, 7))
    for tau, kmax, use_bounds in ((1, 3, True), (2, 4, True), (2, 4, False)):
        ref = mine(D, KyivConfig(tau=tau, kmax=kmax, engine="numpy", use_bounds=use_bounds))
        got = mine(D, KyivConfig(tau=tau, kmax=kmax, engine=engine, use_bounds=use_bounds))
        off = mine(
            D,
            KyivConfig(
                tau=tau, kmax=kmax, engine=engine,
                use_bounds=use_bounds, device_frontier=False,
            ),
        )
        for other in (got, off):
            assert sorted(other.itemsets) == sorted(ref.itemsets)
            assert list(map(_stat_tuple, other.stats)) == list(map(_stat_tuple, ref.stats))


def test_mine_device_frontier_with_mirrors_and_paper_expansion():
    base = RNG.integers(0, 3, size=(60, 4))
    D = np.concatenate([base, base[:, :2]], axis=1)  # duplicate columns -> mirrors
    for expansion in ("full", "paper"):
        ref = mine(D, KyivConfig(tau=1, kmax=3, engine="numpy", expansion=expansion))
        got = mine(D, KyivConfig(tau=1, kmax=3, engine="jnp", expansion=expansion))
        assert sorted(got.itemsets) == sorted(ref.itemsets)
        assert list(map(_stat_tuple, got.stats)) == list(map(_stat_tuple, ref.stats))


def test_mine_resume_mid_run_with_device_frontier():
    D = RNG.integers(0, 5, size=(120, 7))
    cfg = KyivConfig(tau=2, kmax=4, engine="jnp")
    from repro.core import itemize, preprocess
    from repro.core.kyiv import mine_preprocessed

    prep = preprocess(itemize(D), cfg.tau)
    full = mine_preprocessed(prep, cfg)

    for kill_at in (2, 3):
        saved = {}

        class Stop(Exception):
            pass

        def hook(k, state):
            # checkpointed level bitsets are materialised host numpy even on
            # the device frontier (the states must stay picklable)
            assert state.level.bits is None or isinstance(state.level.bits, np.ndarray)
            if k == kill_at:
                saved.update(state)
                raise Stop

        with pytest.raises(Stop):
            mine_preprocessed(prep, cfg, on_level_end=hook)
        resumed = mine_preprocessed(prep, cfg, resume_state=saved)
        assert sorted(resumed.itemsets) == sorted(full.itemsets)
        assert list(map(_stat_tuple, resumed.stats)) == list(
            map(_stat_tuple, full.stats)
        )


def test_timing_breakdown_fields():
    D = RNG.integers(0, 4, size=(80, 5))
    res = mine(D, KyivConfig(tau=1, kmax=3, engine="jnp"))
    levels = res.timing_breakdown()
    # host stage clocks only: device time comes from the profiler's trace
    assert levels and set(levels[0]) == {
        "k", "total", "candidates", "intersect", "classify", "host_busy"
    }
    for lv in levels:
        assert lv["host_busy"] == lv["candidates"] + lv["classify"]
    assert res.total_candidate_time >= 0.0


# ---------------------------------------------------------------------------
# eager retirement
# ---------------------------------------------------------------------------


def test_level_pipeline_retire_releases_owned_buffers():
    bits = RNG.integers(0, 2**32, size=(10, 8), dtype=np.uint32)
    counts = np.ones(10, dtype=np.int64)
    pipe = LevelPipeline(bits, counts, tau=1, placement=make_placement("jnp"))
    state = pipe._state
    pipe.submit(np.asarray([[0, 1], [2, 3]], dtype=np.int32), True).result()
    pipe.retire()
    assert pipe._state is None
    assert state[0].is_deleted()  # numpy input -> placement-owned upload

    # resident (already-jax) bits are the caller's: never deleted
    dev_bits = jnp.asarray(bits)
    pipe2 = LevelPipeline(dev_bits, counts, tau=1, placement=make_placement("jnp"))
    pipe2.retire()
    assert not dev_bits.is_deleted()


def test_frontier_state_release():
    its, counts = _rand_level(30, 2, 50, seed=4)
    dev = DevicePlacement("jnp")
    state = dev.prepare_frontier(its, counts, 50)
    ids, keys = state["ids"], state["keys"]
    dev.release(state)
    assert ids.is_deleted() and keys.is_deleted()


def test_frontier_owns_bits_retire():
    f = LevelFrontier(
        k=2,
        itemsets=np.zeros((2, 2), np.int32),
        counts=np.zeros(2, np.int64),
        bits=jnp.zeros((2, 4), jnp.uint32),
        owns_bits=True,
    )
    arr = f.bits
    f.retire()
    assert f.bits is None and arr.is_deleted()
    # borrowed bits (store caches, resume states) stay alive
    borrowed = jnp.zeros((2, 4), jnp.uint32)
    f2 = LevelFrontier(
        k=2,
        itemsets=np.zeros((2, 2), np.int32),
        counts=np.zeros(2, np.int64),
        bits=borrowed,
        owns_bits=False,
    )
    f2.retire()
    assert not borrowed.is_deleted()


# ---------------------------------------------------------------------------
# unified executable cache
# ---------------------------------------------------------------------------


def test_unified_exec_cache_families():
    mine(RNG.integers(0, 4, size=(60, 4)), KyivConfig(tau=1, kmax=3, engine="jnp"))
    stats = exec_cache.stats()
    assert "frontier" in stats["families"] and "intersect" in stats["families"]
    assert stats["entries"] == sum(f["entries"] for f in stats["families"].values())
    fam = exec_cache.exec_family("frontier")
    assert fam.stats()["entries"] == stats["families"]["frontier"]["entries"]


def test_family_clear_is_isolated():
    from repro.kernels.frontier.ops import frontier_cache_stats, reset_frontier_cache
    from repro.kernels.intersect.ops import executable_cache_stats

    mine(RNG.integers(0, 4, size=(50, 4)), KyivConfig(tau=1, kmax=2, engine="jnp"))
    assert executable_cache_stats()["entries"] >= 1
    before_intersect = executable_cache_stats()["entries"]
    reset_frontier_cache()
    assert frontier_cache_stats()["entries"] == 0
    assert executable_cache_stats()["entries"] == before_intersect


# ---------------------------------------------------------------------------
# 8-device mesh frontier (subprocess — XLA device count must pre-date jax init)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import numpy as np
import jax
from repro.core import KyivConfig, MeshPlacement, mine

def tup(s):
    return (s.k, s.candidates, s.support_pruned, s.bound_pruned,
            s.intersections, s.emitted, s.skipped_absent_uniform, s.stored)

rng = np.random.default_rng(13)
D = rng.integers(0, 5, size=(200, 7))
ref = mine(D, KyivConfig(tau=2, kmax=4, engine="numpy"))
for shape, axes, word in (((2, 4), ("data", "model"), "model"),
                          ((8,), ("data",), None)):
    mesh = jax.make_mesh(shape, axes)
    # device_frontier=True: opt in on the CPU mesh (off by default there —
    # emulated collectives stall; tpu/gpu default on)
    p = MeshPlacement(mesh, pair_axes=("data",), word_axis=word,
                      device_frontier=True)
    got = mine(D, KyivConfig(tau=2, kmax=4, placement=p))
    assert sorted(got.itemsets) == sorted(ref.itemsets), (shape, word)
    assert list(map(tup, got.stats)) == list(map(tup, ref.stats)), (shape, word)
    off = mine(D, KyivConfig(tau=2, kmax=4, placement=p, device_frontier=False))
    assert sorted(off.itemsets) == sorted(ref.itemsets)
    assert not MeshPlacement(mesh, pair_axes=("data",), word_axis=word).use_device_frontier, \
        "CPU mesh must default to the host frontier path"
from repro.kernels.frontier.ops import frontier_cache_stats
assert frontier_cache_stats()["entries"] > 0, "mesh frontier never engaged"
print("MESH_FRONTIER_OK")
"""


@pytest.mark.slow
def test_mesh_frontier_bit_identical_8dev():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT, src],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH_FRONTIER_OK" in proc.stdout
