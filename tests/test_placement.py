"""The placement layer: one factory, three placements, bit-identical mining.

Single-device coverage lives here (host + device placements, the factory,
store word-tile alignment, executable-bucket sharing); the mesh placement's
multi-device behaviour is exercised in subprocesses by
tests/test_sharded_driver.py and tests/test_mesh_service.py.
"""

import numpy as np
import pytest

from repro.core import (
    DevicePlacement,
    HostPlacement,
    KyivConfig,
    MeshPlacement,
    make_placement,
    mine,
    resolve_placement,
)
from repro.kernels.intersect import LevelPipeline, reset_executable_cache
from repro.kernels.intersect.ops import EXEC_CACHE
from repro.service import DatasetStore

RNG = np.random.default_rng(21)


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------


def test_make_placement_kinds():
    assert make_placement("numpy").kind == "host"
    assert make_placement("host").kind == "host"
    for eng in ("jnp", "pallas"):
        p = make_placement(eng, interpret=True, indexed=False)
        assert (p.kind, p.engine, p.indexed) == ("device", eng, False)
    with pytest.raises(ValueError):
        make_placement("mesh")
    with pytest.raises(ValueError):
        DevicePlacement("numpy")


def test_resolve_placement_precedence():
    # engine string drives the default...
    assert resolve_placement(KyivConfig(engine="numpy")).kind == "host"
    assert resolve_placement(KyivConfig(engine="pallas")).engine == "pallas"
    # ...an explicit placement object wins over the engine...
    p = HostPlacement()
    assert resolve_placement(KyivConfig(engine="pallas", placement=p)) is p
    # ...and a placement *string* resolves through the same factory
    assert resolve_placement(KyivConfig(engine="numpy", placement="jnp")).engine == "jnp"


def test_describe_is_json_friendly():
    import json

    for p in (HostPlacement(), make_placement("jnp"), make_placement("pallas")):
        d = p.describe()
        assert d["kind"] in ("host", "device")
        json.dumps(d)  # /stats serialises this


def test_interpret_resolves_from_backend(monkeypatch):
    """Interpret mode is the backend's call: on CPU the Pallas kernels are
    interpreted; on a TPU an explicit interpret=True is refused, never
    quietly honoured."""
    import jax

    from repro.core import resolve_interpret

    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True and resolve_interpret(True) is True
    assert DevicePlacement("pallas").interpret is True
    assert make_placement("pallas").describe()["interpret"] is True
    with pytest.raises(ValueError):
        resolve_interpret(False)  # Mosaic kernels cannot run on the CPU

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    assert DevicePlacement("pallas").describe()["interpret"] is False
    with pytest.raises(ValueError, match="interpret"):
        DevicePlacement("pallas", interpret=True)
    with pytest.raises(ValueError, match="interpret"):
        resolve_placement(KyivConfig(engine="pallas", interpret=True))


@pytest.mark.parametrize("env_dir", [None, "set"], ids=["checkout", "env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """With JAX_COMPILATION_CACHE_DIR set nothing is configured in code;
    without it the cache goes to one fixed, git-ignored checkout path."""
    import os

    import jax

    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    calls = []
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        assert calls == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = compile_cache.configure_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", got)]
        assert compile_cache.configure_compile_cache() == got  # fixed, not per call
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    assert jax.config.jax_compilation_cache_dir == prev


def test_pallas_placement_lane_pads_and_bounds_dispatch():
    """The Pallas placement pads words to 128 lanes (the store tiles for it),
    keeps levels in the kernels' dense row layout, and LevelPipeline splits
    batches above the SMEM bound into chunks — results unchanged."""
    from repro.kernels.intersect.ops import MAX_INDEXED_PAIRS

    p = DevicePlacement("pallas")
    assert p.store_word_tile == 128 and p.max_dispatch_pairs == MAX_INDEXED_PAIRS
    assert DevicePlacement("jnp").max_dispatch_pairs is None
    assert DevicePlacement("pallas", indexed=False).max_dispatch_pairs is None
    store = DatasetStore(3, word_tile=8, placement=p)
    assert store.word_tile == 128

    bits = RNG.integers(0, 2**32, size=(6, 5), dtype=np.uint32)  # 5 words
    pc = np.bitwise_count(bits).sum(1)
    pairs = RNG.integers(0, 6, size=(300, 2)).astype(np.int32)
    want_child = bits[pairs[:, 0]] & bits[pairs[:, 1]]
    p.max_dispatch_pairs = 128  # 300 pairs pad to 384 -> three dispatches
    pipe = LevelPipeline(bits, pc, tau=40, placement=p)
    assert pipe._state[0].shape == (6, 8, 128)  # 5 words pad to one (8, 128) tile
    child, counts, classes = pipe.submit(pairs, True).result()
    assert np.array_equal(child, want_child)
    assert np.array_equal(counts, np.bitwise_count(want_child).sum(1))
    host = LevelPipeline(bits, pc, tau=40, placement=HostPlacement())
    assert np.array_equal(classes, host.submit(pairs, True).result()[2])


@pytest.mark.parametrize("m,sizes", [(100, [128]), (128, [128]), (129, [128, 128]),
                                     (300, [128, 128, 128])])
def test_submit_pads_past_the_dispatch_bound_to_whole_chunks(m, sizes):
    """Up to the placement's dispatch bound a batch pads to its bucket; past
    it, to whole chunks of the bound (one bucket each), not to the next
    power of two. The raw outputs of an unsorted batch are in its order."""
    p = DevicePlacement("pallas")
    p.max_dispatch_pairs = 128
    seen = []
    dispatch = p.dispatch

    def recording(state, padded, write_children):
        seen.append(int(padded.shape[0]))
        return dispatch(state, padded, write_children)

    p.dispatch = recording
    bits = RNG.integers(0, 2**32, size=(6, 5), dtype=np.uint32)
    pairs = RNG.integers(0, 6, size=(m, 2)).astype(np.int32)
    pipe = LevelPipeline(bits, np.bitwise_count(bits).sum(1), tau=0, placement=p,
                         fused_classify=False, locality_sort=False)
    handle = pipe.submit(pairs, True)
    assert seen == sizes
    want = bits[pairs[:, 0]] & bits[pairs[:, 1]]
    assert np.array_equal(handle.result()[1], np.bitwise_count(want).sum(1))
    child = np.asarray(handle.raw()[0])
    assert child.shape[0] == sum(sizes)
    assert np.array_equal(child.reshape(child.shape[0], -1)[:m, :5], want)


# ---------------------------------------------------------------------------
# mining equivalence: every placement is bit-identical to the host reference
# ---------------------------------------------------------------------------


def _stat_tuple(s):
    return (s.k, s.candidates, s.support_pruned, s.bound_pruned,
            s.intersections, s.emitted, s.skipped_absent_uniform, s.stored)


@pytest.mark.parametrize("engine", ["numpy", "jnp", "pallas"])
def test_mine_with_explicit_placement_matches_engine_string(engine):
    D = RNG.integers(0, 4, size=(70, 5))
    cfg = KyivConfig(tau=2, kmax=3, engine=engine)
    via_engine = mine(D, cfg)
    via_placement = mine(D, KyivConfig(tau=2, kmax=3, placement=make_placement(engine)))
    assert sorted(via_engine.itemsets) == sorted(via_placement.itemsets)
    assert list(map(_stat_tuple, via_engine.stats)) == list(
        map(_stat_tuple, via_placement.stats)
    )


def test_placement_string_in_config():
    D = RNG.integers(0, 4, size=(60, 4))
    ref = mine(D, KyivConfig(tau=1, kmax=3))
    got = mine(D, KyivConfig(tau=1, kmax=3, placement="pallas"))
    assert sorted(ref.itemsets) == sorted(got.itemsets)


# ---------------------------------------------------------------------------
# LevelPipeline is placement-generic
# ---------------------------------------------------------------------------


def _mk_level(t=12, W=64, M=33):
    bits = RNG.integers(0, 2**32, size=(t, W), dtype=np.uint32) & RNG.integers(
        0, 2**32, size=(t, W), dtype=np.uint32
    )
    pairs = RNG.integers(0, t, size=(M, 2)).astype(np.int32)
    from repro.core.bitops import popcount_rows

    return bits, pairs, popcount_rows(bits)


def test_level_pipeline_placement_vs_engine_kwarg():
    """The legacy engine= kwarg and an explicit placement give identical
    batches (the compat path resolves through the same factory)."""
    bits, pairs, pc = _mk_level()
    for engine in ("numpy", "jnp", "pallas"):
        a = LevelPipeline(bits, pc, tau=3, engine=engine).submit(pairs, True).result()
        b = (
            LevelPipeline(bits, pc, tau=3, placement=make_placement(engine))
            .submit(pairs, True)
            .result()
        )
        assert np.array_equal(a[0], b[0])
        assert np.array_equal(a[1], b[1])
        assert np.array_equal(a[2], b[2])


def test_level_pipeline_has_no_engine_branches():
    """The pipeline's orchestration is placement-blind: everything
    engine-specific is reachable only through the placement object."""
    import inspect

    src = inspect.getsource(LevelPipeline)
    for needle in ('== "numpy"', '== "jnp"', '== "pallas"', "self.engine"):
        assert needle not in src, f"engine branch {needle} back in LevelPipeline"


def test_device_placement_shares_executable_buckets():
    """Two pipelines over same-shaped levels share EXEC_CACHE entries."""
    reset_executable_cache()
    bits, pairs, pc = _mk_level()
    LevelPipeline(bits, pc, tau=2, placement=make_placement("jnp")).submit(
        pairs, True
    ).result()
    first = EXEC_CACHE.stats()
    assert first["misses"] >= 1
    LevelPipeline(bits, pc, tau=2, placement=make_placement("jnp")).submit(
        pairs, True
    ).result()
    second = EXEC_CACHE.stats()
    assert second["hits"] > first["hits"]
    assert second["entries"] == first["entries"]


# ---------------------------------------------------------------------------
# store word-tile alignment
# ---------------------------------------------------------------------------


class _FakeShardedPlacement(HostPlacement):
    """Host semantics but a mesh-like word tile, so alignment is testable
    without multiple devices."""

    store_word_tile = 12


def test_store_aligns_word_tile_to_placement():
    store = DatasetStore(3, word_tile=8, placement=_FakeShardedPlacement())
    assert store.word_tile == 24  # lcm(8, 12)
    store.append(RNG.integers(0, 4, size=(40, 3)))
    assert store.n_words % 24 == 0
    # the resident copy is produced by the placement (host: numpy passthrough)
    dev = store.device_bits()
    assert isinstance(dev, np.ndarray) and dev.shape[1] == store.n_words


def test_store_device_bits_version_pinning():
    store = DatasetStore(3, placement=HostPlacement())
    store.append(RNG.integers(0, 4, size=(10, 3)))
    v = store.version
    assert store.device_bits(v) is not None
    store.append(RNG.integers(0, 4, size=(5, 3)))
    assert store.device_bits(v) is None  # stale pin -> caller re-snapshots


def test_mesh_from_spec_parsing():
    from repro.launch.mesh import mesh_from_spec

    assert dict(mesh_from_spec("1x1").shape) == {"data": 1, "model": 1}
    assert dict(mesh_from_spec("1").shape) == {"data": 1, "model": 1}
    for bad in ("4x", "x4", "0x1", "1x2x3", "", "axb"):
        with pytest.raises(ValueError):
            mesh_from_spec(bad)


def test_mesh_placement_describe_without_devices():
    """MeshPlacement metadata works on however many devices exist (1 here)."""
    import jax

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    p = MeshPlacement(mesh, pair_axes=("data",), word_axis="model")
    d = p.describe()
    assert d["kind"] == "mesh" and d["word_shards"] == 1 and d["pair_shards"] == 1
    assert p.store_word_tile == 1
    # degenerate 1x1 mesh still mines correctly through the generic pipeline
    D = RNG.integers(0, 4, size=(50, 4))
    ref = mine(D, KyivConfig(tau=1, kmax=3))
    got = mine(D, KyivConfig(tau=1, kmax=3, placement=p))
    assert sorted(ref.itemsets) == sorted(got.itemsets)
