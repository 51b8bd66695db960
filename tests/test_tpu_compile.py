"""Compile every main-path Pallas kernel for a described TPU v5e.

Nothing runs: the chip's compiler, which ships with libtpu, compiles for a
described ``v5e:2x2`` topology and refuses what the chip would refuse —
block shapes off the (8, 128) tiling, scalar-prefetch tables that overflow
SMEM, VMEM tiles that do not fit. Shapes are the 1,000,000-row table the
chip smoke serves: 31,250 words per bitset, lane-padded by the store to
31,360, tiled at the placement's default word block, with pair buckets the
level batch cap picks at that width (``core/frontier.py``). Interpret-mode
tests cannot see any of this.

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
    assert PAIR_BUCKET * 3 <= SMEM_PREFETCH_WORDS  # pairs + per-pair min count


@pytest.mark.parametrize("write", [True, False], ids=["write", "count"])
def test_fused_indexed_kernel_compiles(one_chip, write):
    u32, i32 = jnp.uint32, jnp.int32
    if write:  # level 1 -> 2: the 85 item rows, write the children
        t, m = N_ITEMS, LEVEL2
        fn = lambda b, p, c, tau: _k.intersect_classify_write_indexed(  # noqa: E731
            b, p, c, tau, block_words=BW
        )
    else:  # level 2 -> 3 (k = kmax): count-only over the stored children
        t, m = LEVEL2, PAIR_BUCKET
        fn = lambda b, p, c, tau: _k.intersect_classify_count_indexed(  # noqa: E731
            b, p, c, tau, block_words=BW
        )
    compiled = _compile(
        fn, one_chip, ((t, 1, W), u32), ((m, 2), i32), ((t,), i32), ((), i32)
    )
    assert "tpu_custom_call" in compiled.as_text()


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
    fn = lambda b, p: _k.intersect_count_indexed(b, p, block_words=BW)  # noqa: E731
    with pytest.raises(jax.errors.JaxRuntimeError) as err:
        _compile(fn, one_chip, ((64, 1, W), jnp.uint32), ((m, 2), jnp.int32))
    assert "RESOURCE_EXHAUSTED" in str(err.value)
    assert is_compile_refusal(err.value) and not is_device_failure(err.value)
