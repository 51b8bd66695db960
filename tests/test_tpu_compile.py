"""Compile every main-path Pallas kernel for a described TPU v5e.

Nothing runs: the chip's compiler, which ships with libtpu, compiles for a
described ``v5e:2x2`` topology and refuses what the chip would refuse —
block shapes off the (8, 128) tiling, scalar-prefetch tables that overflow
SMEM, VMEM tiles that do not fit. Shapes are the 1,000,000-row table the
chip smoke serves: 31,250 words per bitset, lane-padded by the store to
31,360; the indexed kernels read it as dense (t, 248, 128) rows, the
gathered and coverage kernels in tiles of the placement's default word
block, with pair buckets the level batch cap picks at that width
(``core/frontier.py``). The append cell's store, grown once to 35,328
words, is compiled too. Interpret-mode tests cannot see any of this.

The topology is described inside a module fixture (never at import): only
one process may load libtpu at a time, and the worker that runs this file
keeps it until it exits.
"""

import inspect

import pytest

import jax
import jax.numpy as jnp

from repro.core.placement import DevicePlacement
from repro.kernels.coverage import coverage as _cov
from repro.kernels.intersect import intersect as _k
from repro.kernels.intersect import tiled as _tiled
from repro.kernels.intersect.ops import (
    LANES,
    MAX_INDEXED_PAIRS,
    SMEM_PREFETCH_WORDS,
    _largest_divisor_tile,
    next_bucket,
)

N_ROWS = 1_000_000
N_ITEMS = 85  # poker-like: 5 cards x (4 suits + 13 ranks)
LEVEL2 = 4096  # stored level-2 children of 85 items, bucket-padded
WORDS = -(-N_ROWS // 32)  # 31,250
W = -(-WORDS // LANES) * LANES  # as the store pads it for the Pallas engine
BW = _largest_divisor_tile(
    W, inspect.signature(DevicePlacement).parameters["block_words"].default
)
S = _k.dense_sublanes(W)  # 248 sublanes: 31,744 words, a 1.2% pad
# the append cell's store after its one growth (9/8 of the rows, tiled)
W_APPEND = 35_328
S_APPEND = _k.dense_sublanes(W_APPEND)  # 280
# pairs per level batch at this width (core/frontier.py), as a bucket
PAIR_BUCKET = min(next_bucket(max(4096, (1 << 28) // W)), MAX_INDEXED_PAIRS)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no topology support
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_real_width_is_lane_tiled():
    assert W == 31_360 and W % LANES == 0
    assert BW % LANES == 0 and W % BW == 0
    assert (S, S_APPEND) == (248, 280)
    assert PAIR_BUCKET * 3 <= SMEM_PREFETCH_WORDS  # pairs + per-pair min count


def _pallas_grids(fn, shapes):
    """The grid of every ``pallas_call`` that tracing ``fn`` reaches."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for p in eqn.params.values():
                sub = getattr(p, "jaxpr", p)
                if hasattr(sub, "eqns"):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*[jax.ShapeDtypeStruct(s, d) for s, d in shapes]).jaxpr)
    return grids


def _assert_dense_one_step_per_pair(fn, compiled, shapes):
    """The level parameter is laid out in (8, 128) tiles of one row each,
    and the kernel's grid takes one step per pair."""
    (t, s, _), _ = shapes[0]
    m = shapes[1][0][0]
    layout = compiled.as_text().split("entry_computation_layout={", 1)[1].split("}}", 1)[0]
    assert f"u32[{t},{s},128]{{2,1,0:T(8,128)" in layout, layout
    assert _pallas_grids(fn, shapes) == [(m, 1)]


@pytest.mark.parametrize("write", [True, False], ids=["write", "count"])
def test_fused_indexed_kernel_compiles(one_chip, write):
    u32, i32 = jnp.uint32, jnp.int32
    if write:  # level 1 -> 2: the 85 item rows, write the children
        t, m = N_ITEMS, LEVEL2
        fn = _k.intersect_classify_write_indexed
    else:  # level 2 -> 3 (k = kmax): count-only over the stored children
        t, m = LEVEL2, PAIR_BUCKET
        fn = _k.intersect_classify_count_indexed
    shapes = (((t, S, LANES), u32), ((m, 2), i32), ((t,), i32), ((), i32))
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()
    _assert_dense_one_step_per_pair(fn, compiled, shapes)


@pytest.mark.parametrize("write", [True, False], ids=["write", "count"])
def test_append_indexed_kernel_compiles(one_chip, write):
    """The append cell's unfused kernels over the store grown to 35,328
    words: item rows plus the triples' prefix children, one dispatch chunk."""
    u32, i32 = jnp.uint32, jnp.int32
    fn = _k.intersect_write_indexed if write else _k.intersect_count_indexed
    shapes = (((N_ITEMS + LEVEL2, S_APPEND, LANES), u32), ((PAIR_BUCKET, 2), i32))
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()
    _assert_dense_one_step_per_pair(fn, compiled, shapes)


@pytest.mark.parametrize("write", [True, False], ids=["write", "count"])
def test_gathered_kernel_compiles(one_chip, write):
    u32, i32 = jnp.uint32, jnp.int32
    kern = (
        _k.intersect_classify_write_gathered
        if write
        else _k.intersect_classify_count_gathered
    )
    fn = lambda a, b, mp, tau: kern(a, b, mp, tau, block_pairs=8, block_words=BW)  # noqa: E731
    compiled = _compile(
        fn, one_chip, ((LEVEL2, W), u32), ((LEVEL2, W), u32), ((LEVEL2,), i32), ((), i32)
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_coverage_kernel_compiles(one_chip):
    i32 = jnp.int32
    kmax = 3
    fit = SMEM_PREFETCH_WORDS // (kmax + 1)  # CoverageEngine's bound on sets
    m = 1 << (fit.bit_length() - 1)
    fn = lambda b, s, w: _cov.coverage_accumulate_indexed(b, s, w, block_words=BW)  # noqa: E731
    compiled = _compile(
        fn, one_chip, ((N_ITEMS, 1, W), jnp.uint32), ((m, kmax), i32), ((m,), i32)
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_oversized_pair_table_is_a_compile_refusal(one_chip):
    """An SMEM overflow is refused at compile time, and the service's
    failure classifier calls it a bug — never a device fault to degrade."""
    from repro.core.placement import is_compile_refusal, is_device_failure

    m = 1 << 18  # 2 MiB of flattened pairs against 1 MiB of SMEM
    with pytest.raises(jax.errors.JaxRuntimeError) as err:
        _compile(
            _k.intersect_count_indexed, one_chip,
            ((64, S, LANES), jnp.uint32), ((m, 2), jnp.int32),
        )
    assert "RESOURCE_EXHAUSTED" in str(err.value)
    assert is_compile_refusal(err.value) and not is_device_failure(err.value)


def _kernel_cases():
    u32, i32 = jnp.uint32, jnp.int32
    w, bw, t, m = 8 * LANES, 4 * LANES, 64, 128
    dense, rows = ((t, w // LANES, LANES), u32), ((t, 1, w), u32)
    flat, pairs = ((m, w), u32), ((m, 2), i32)
    cnt, tau = ((t,), i32), ((), i32)
    minp = ((m,), i32)
    return [
        ("intersect_write_indexed", _k.intersect_write_indexed, (dense, pairs)),
        ("intersect_count_indexed", _k.intersect_count_indexed, (dense, pairs)),
        ("intersect_classify_write_indexed", _k.intersect_classify_write_indexed,
         (dense, pairs, cnt, tau)),
        ("intersect_classify_count_indexed", _k.intersect_classify_count_indexed,
         (dense, pairs, cnt, tau)),
        ("intersect_write_gathered",
         lambda a, b: _k.intersect_write_gathered(a, b, block_words=bw), (flat, flat)),
        ("intersect_count_gathered",
         lambda a, b: _k.intersect_count_gathered(a, b, block_words=bw), (flat, flat)),
        ("intersect_classify_write_gathered",
         lambda a, b, mp, x: _k.intersect_classify_write_gathered(a, b, mp, x, block_words=bw),
         (flat, flat, minp, tau)),
        ("intersect_classify_count_gathered",
         lambda a, b, mp, x: _k.intersect_classify_count_gathered(a, b, mp, x, block_words=bw),
         (flat, flat, minp, tau)),
        ("intersect_count_tiled",
         lambda b, i, j: _tiled.intersect_count_tiled(b, i, j, block_words=bw),
         (((t, w), u32), ((16,), i32), ((16,), i32))),
        ("coverage_accumulate_indexed",
         lambda b, s, wt: _cov.coverage_accumulate_indexed(b, s, wt, block_words=bw),
         (rows, ((m, 3), i32), ((m,), i32))),
    ]


@pytest.mark.parametrize(
    "name,fn,shapes", _kernel_cases(), ids=[c[0] for c in _kernel_cases()]
)
def test_kernel_trace_names_are_pinned(one_chip, name, fn, shapes):
    """The profiler's trace names a kernel by its HLO instruction; each
    ``pallas_call`` names itself, so the name survives a refactor of the
    jitted wrappers (the benchmark matches ``intersect_*``)."""
    lowered = jax.jit(fn).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    )
    assert f'kernel_name = "{name}"' in lowered.as_text()
    calls = [
        line.split(" = ", 1)[0].split()[-1].lstrip("%")
        for line in lowered.compile().as_text().splitlines()
        if "tpu_custom_call" in line and " = " in line
    ]
    assert calls and all(c.rsplit(".", 1)[0] == name for c in calls), calls
