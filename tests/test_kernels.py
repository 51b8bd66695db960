"""Pallas intersection kernels vs the jnp oracle: shape/dtype sweeps in
interpret mode (CPU container; kernels target TPU BlockSpecs). Exact integer
op — zero tolerance."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels.intersect import (
    CLASS_EMIT,
    CLASS_SKIP,
    CLASS_STORE,
    intersect_and_count,
    intersect_count_gathered,
    intersect_count_indexed,
    intersect_count_ref,
    intersect_pairs_ref,
    intersect_write_gathered,
    intersect_write_indexed,
    next_bucket,
)

RNG = np.random.default_rng(0)


def _mk(t, W, M, dtype=np.uint32):
    bits = RNG.integers(0, np.iinfo(dtype).max, size=(t, W), dtype=dtype)
    pairs = RNG.integers(0, t, size=(M, 2)).astype(np.int32)
    return bits, pairs


@pytest.mark.parametrize("t,W,M", [(4, 128, 8), (16, 256, 32), (64, 512, 128), (8, 1024, 16)])
def test_indexed_kernels_match_ref(t, W, M):
    bits, pairs = _mk(t, W, M)
    ref_child = bits[pairs[:, 0]] & bits[pairs[:, 1]]
    ref_cnt = np.bitwise_count(ref_child).sum(1)
    child, cnt = intersect_write_indexed(jnp.asarray(bits), jnp.asarray(pairs),
                                         interpret=True)
    assert np.array_equal(np.asarray(child), ref_child)
    assert np.array_equal(np.asarray(cnt), ref_cnt)
    cnt2 = intersect_count_indexed(jnp.asarray(bits), jnp.asarray(pairs), interpret=True)
    assert np.array_equal(np.asarray(cnt2), ref_cnt)


@pytest.mark.parametrize("bm,bw", [(1, 128), (8, 128), (4, 256), (8, 512)])
def test_gathered_kernels_block_sweep(bm, bw):
    M, W = 16, 512
    a = RNG.integers(0, 2**32, size=(M, W), dtype=np.uint32)
    b = RNG.integers(0, 2**32, size=(M, W), dtype=np.uint32)
    ref_child = a & b
    ref_cnt = np.bitwise_count(ref_child).sum(1)
    child, cnt = intersect_write_gathered(
        jnp.asarray(a), jnp.asarray(b), block_pairs=bm, block_words=bw, interpret=True
    )
    assert np.array_equal(np.asarray(child), ref_child)
    assert np.array_equal(np.asarray(cnt), ref_cnt)
    cnt2 = intersect_count_gathered(
        jnp.asarray(a), jnp.asarray(b), block_pairs=bm, block_words=bw, interpret=True
    )
    assert np.array_equal(np.asarray(cnt2), ref_cnt)


def test_ref_oracle_consistency():
    bits, pairs = _mk(10, 128, 20)
    child, cnt = intersect_pairs_ref(jnp.asarray(bits), jnp.asarray(pairs))
    assert np.array_equal(np.asarray(child), bits[pairs[:, 0]] & bits[pairs[:, 1]])
    cnt2 = intersect_count_ref(jnp.asarray(bits), jnp.asarray(pairs))
    assert np.array_equal(np.asarray(cnt), np.asarray(cnt2))


@pytest.mark.parametrize("engine", ["numpy", "jnp", "pallas"])
@pytest.mark.parametrize("write", [True, False])
def test_ops_wrapper_engines(engine, write):
    bits, pairs = _mk(12, 128, 37)  # non-power-of-2 M exercises padding
    child, cnt = intersect_and_count(
        bits, pairs, write_children=write, engine=engine, interpret=True
    )
    ref_child = bits[pairs[:, 0]] & bits[pairs[:, 1]]
    assert np.array_equal(cnt, np.bitwise_count(ref_child).sum(1))
    if write:
        assert np.array_equal(child, ref_child)
    else:
        assert child is None


def test_empty_pairs():
    bits, _ = _mk(4, 128, 1)
    child, cnt = intersect_and_count(
        bits, np.zeros((0, 2), np.int32), write_children=True, engine="numpy"
    )
    assert child.shape == (0, 128) and cnt.shape == (0,)


def test_next_bucket():
    assert next_bucket(1) == 256
    assert next_bucket(256) == 256
    assert next_bucket(257) == 512
    assert next_bucket(1 << 20) == 1 << 20


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.uint32])
def test_kernel_dtype_sweep(dtype):
    """Kernels are word-size agnostic: AND+popcount over u8/u16/u32 words."""
    t, W, M = 8, 128, 16
    bits = RNG.integers(0, np.iinfo(dtype).max, size=(t, W), dtype=dtype)
    pairs = RNG.integers(0, t, size=(M, 2)).astype(np.int32)
    ref_child = bits[pairs[:, 0]] & bits[pairs[:, 1]]
    ref_cnt = np.bitwise_count(ref_child).sum(1).astype(np.int32)
    child, cnt = intersect_write_indexed(jnp.asarray(bits), jnp.asarray(pairs),
                                         interpret=True)
    assert child.dtype == jnp.asarray(bits).dtype
    assert np.array_equal(np.asarray(child), ref_child)
    assert np.array_equal(np.asarray(cnt), ref_cnt)


@pytest.mark.parametrize("W", [128, 256, 384, 1024])
def test_kernel_word_width_sweep(W):
    t, M = 6, 12
    bits = RNG.integers(0, 2**32, size=(t, W), dtype=np.uint32)
    pairs = RNG.integers(0, t, size=(M, 2)).astype(np.int32)
    ref = np.bitwise_count(bits[pairs[:, 0]] & bits[pairs[:, 1]]).sum(1)
    cnt = intersect_count_indexed(jnp.asarray(bits), jnp.asarray(pairs), interpret=True)
    assert np.array_equal(np.asarray(cnt), ref)


# The dense (t, S, 128) row layout at the cells' widths: PUMSB (1,536 words,
# S 12 -> 16), poker (31,360, S 245 -> 248) and poker's append store grown
# once (35,328, S 276 -> 280).
DENSE_WIDTHS = [1536, 31360, 35328]
INDEXED_VARIANTS = [
    ("intersect_write_indexed", True, False),
    ("intersect_count_indexed", False, False),
    ("intersect_classify_write_indexed", True, True),
    ("intersect_classify_count_indexed", False, True),
]


def _dense_level(W):
    """Eight rows of mixed density, among them a subset of row 0 (uniform)
    and its complement (absent); pairs in runs of equal I, then three
    padding pairs (row 0 twice)."""
    rng = np.random.default_rng(W)
    bits = rng.integers(0, 2**32, size=(8, W), dtype=np.uint32)
    for r in range(2, 8):  # density 1/2 down to 1/64
        for _ in range(r // 2):
            bits[r] &= rng.integers(0, 2**32, size=W, dtype=np.uint32)
    bits[1] = bits[0] & rng.integers(0, 2**32, size=W, dtype=np.uint32)
    bits[2] = ~bits[0]
    i = np.repeat([0, 0, 2, 3, 5, 7], [4, 1, 3, 1, 5, 2])
    j = rng.integers(0, 8, size=len(i))
    j[:2] = [1, 2]  # uniform, absent
    pairs = np.stack([np.concatenate([i, [0, 0, 0]]), np.concatenate([j, [0, 0, 0]])], 1)
    return bits, pairs.astype(np.int32)


@pytest.mark.parametrize("split", [False, True], ids=["one-block", "split"])
@pytest.mark.parametrize("name,write,classify", INDEXED_VARIANTS,
                         ids=[v[0] for v in INDEXED_VARIANTS])
@pytest.mark.parametrize("W", DENSE_WIDTHS)
def test_dense_indexed_variants_match_ref(W, name, write, classify, split):
    """Counts, children (pad stripped) and classes of the four indexed
    variants over the dense layout equal kernels/intersect/ref.py. ``split``
    passes a VMEM budget of one (8, 128) tile per buffer, so each row is
    read in blocks of 8 sublanes across a second grid axis."""
    from repro.kernels.intersect import intersect as _k
    from repro.kernels.intersect import ref as _ref

    bits, pairs = _dense_level(W)
    S = _k.dense_sublanes(W)
    assert S % 8 == 0 and S * 128 - W < 1024
    rows = _k.as_dense_rows(jnp.asarray(bits))
    assert rows.shape == (8, S, 128)
    assert np.array_equal(np.asarray(rows).reshape(8, -1)[:, :W], bits)
    pc = np.bitwise_count(bits).sum(1).astype(np.int32)
    want_child, want_cnt = _ref.intersect_pairs_ref(jnp.asarray(bits), jnp.asarray(pairs))
    tau = int(np.median(np.asarray(want_cnt)))
    want_cls = _ref.classify_counts_ref(
        want_cnt, np.minimum(pc[pairs[:, 0]], pc[pairs[:, 1]]), tau
    )

    tail = (jnp.asarray(pc), jnp.int32(tau)) if classify else ()
    if split:
        budget = (6 if write else 4) * 1024 * 4
        assert _k.row_blocks(S, write, budget) == S // 8
        minp = _k._pair_minp(tail[0], jnp.asarray(pairs)) if classify else None
        got = _k._indexed_call(
            rows, jnp.asarray(pairs), minp, tail[1] if classify else None,
            name=name, write=write, interpret=True, vmem_bytes=budget,
        )
    else:
        assert _k.row_blocks(S, write) == 1
        got = getattr(_k, name)(rows, jnp.asarray(pairs), *tail, interpret=True)
        got = got if isinstance(got, tuple) else (got,)
    got = [np.asarray(g) for g in got]
    if write:
        child, *got = got
        assert child.shape == (len(pairs), S, 128)
        flat = child.reshape(len(pairs), -1)
        assert not flat[:, W:].any()
        assert np.array_equal(flat[:, :W], np.asarray(want_child))
    assert np.array_equal(got[0], np.asarray(want_cnt))
    if classify:
        cls = got[1]
        assert np.array_equal(cls, np.asarray(want_cls))
        assert cls[0] == CLASS_SKIP and cls[1] == CLASS_SKIP  # uniform, absent
        assert (cls[-3:] == CLASS_SKIP).all()  # padding
        assert {CLASS_EMIT, CLASS_STORE} <= set(cls.tolist())
