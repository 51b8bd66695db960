"""The incremental delta path on a device placement, at small sizes.

Delta-born candidates are enumerated from the appended rows with arrays and
their full-table supports counted by the placement's intersect kernels over
the store's resident bitsets; those counts must equal the host's, the
recount takes its supports from the same device counts, and the served
answers after each append must equal the brute-force oracle. The
store's width grows in steps of at least an eighth, only on overflow, and a
grown store answers exactly as a snug one. A served append and incremental
mine leave their span tree, and every fallback to a cold mine is counted
under its reason.
"""

import functools
import itertools
import json
import threading
import urllib.request

import numpy as np
import pytest

from repro.core import KyivConfig, brute_force_minimal_infrequent, mine
from repro.core.items import itemize
from repro.core.placement import make_placement
from repro.obs.trace import TRACER
from repro.service import DatasetStore, IncrementalConfig, MiningService, mine_incremental
from repro.service import incremental as inc

SUITS, RANKS, HAND = 2, 6, 3  # a small deck: 6 columns, 24 items


def _deal(n, seed):
    """``n`` hands of ``HAND`` distinct cards as (suit, rank) column pairs."""
    rng = np.random.default_rng(seed)
    cards = np.argsort(rng.random((n, SUITS * RANKS)), axis=1)[:, :HAND]
    out = np.empty((n, 2 * HAND), dtype=np.int64)
    out[:, 0::2] = cards // RANKS + 1
    out[:, 1::2] = cards % RANKS + 1
    return out


def _oracle_value_sets(rows, tau, kmax):
    table = itemize(rows)
    return {
        frozenset((int(table.col[i]), int(table.value[i])) for i in ids)
        for ids in brute_force_minimal_infrequent(rows, tau, kmax)
    }


def _delta_inputs(engine, n, d, tau):
    placement = make_placement(engine)
    store = DatasetStore(2 * HAND, placement=placement)
    store.append(_deal(n, 1))
    base_rows = store.n_rows
    store.append(_deal(d, 2))
    table = store.item_table()
    dbits = inc._delta_bits_of(table, base_rows)
    row_items = np.unpackbits(dbits.view(np.uint8), axis=1, bitorder="little")
    lo = (base_rows // 32) * 32
    row_items = row_items[:, base_rows - lo : table.n_rows - lo]
    keep = (table.freq > tau) & (table.freq < table.n_rows)
    return placement, store, table, row_items, keep


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
@pytest.mark.parametrize("delta", [1, 40, 300])
def test_delta_born_device_counts_equal_host(engine, delta):
    placement, store, table, row_items, keep = _delta_inputs(engine, 400, delta, 3)
    cands, overflow = inc._row_combinations(row_items, keep, 3, 1 << 20)
    assert not overflow and 2 in cands
    stats = {"pairs": 0, "rows_read": 0, "children": 0}
    got = inc._device_supports(cands, resident=store.device_bits(), placement=placement,
                               site="born_counts", stats=stats)
    want = inc._host_supports(table.bits, cands)
    assert sorted(got) == sorted(want)
    for k in want:
        assert np.array_equal(got[k], want[k]), k
    # pairs, the triples' prefix children, and the triples
    n_prefix = len(np.unique(cands[3][:, :2], axis=0)) if 3 in cands else 0
    assert stats["children"] == n_prefix
    assert stats["pairs"] == sum(len(c) for c in cands.values()) + n_prefix


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_recount_takes_the_device_counts(engine):
    """On a device placement the recounted itemsets' supports are the
    device's full-table counts: equal to base + delta when sound, and
    whatever the counts say when they are not."""
    placement, store, table, _, _ = _delta_inputs(engine, 400, 40, 3)
    base = mine(_deal(400, 1), KyivConfig(tau=3, kmax=3, engine="numpy"))
    bands = inc.ResultBands.from_result(base.itemsets)
    dbits = inc._delta_bits_of(table, 400)
    dfreq = np.bitwise_count(dbits).sum(axis=1).astype(np.int64)
    host, host_stats = bands.recount(dbits, dfreq, 3, 40)
    stats = {"pairs": 0, "rows_read": 0, "children": 0}
    supports = functools.partial(inc._device_supports, resident=store.device_bits(),
                                 placement=placement, site="recount_counts", stats=stats)
    device, device_stats = bands.recount(dbits, dfreq, 3, 40, supports=supports)
    assert np.array_equal(device, host) and device_stats == host_stats
    assert host_stats["n_recounted"] > 0 and stats["pairs"] >= host_stats["n_recounted"]

    def zeros(cands):
        return {k: np.zeros(len(m), dtype=np.int64) for k, m in cands.items()}

    stale, _ = bands.recount(dbits, dfreq, 3, 40, supports=zeros)
    assert (stale == 0).sum() == host_stats["n_recounted"]


@pytest.mark.parametrize("delta", [1, 40, 300])
def test_row_combinations_match_per_row_enumeration(delta):
    _, _, _, row_items, keep = _delta_inputs("jnp", 400, delta, 3)
    want: set[tuple[int, ...]] = set()
    for r in range(row_items.shape[1]):
        items = np.nonzero(row_items[:, r])[0]
        items = items[keep[items]].tolist()
        for k in range(2, min(3, len(items)) + 1):
            want.update(itertools.combinations(items, k))
    cands, overflow = inc._row_combinations(row_items, keep, 3, 1 << 20)
    assert not overflow
    got = [tuple(c) for k in sorted(cands) for c in cands[k].tolist()]
    assert len(got) == len(want) and set(got) == want
    for k, c in cands.items():
        assert [tuple(r) for r in c.tolist()] == sorted(tuple(r) for r in c.tolist())
    _, overflow = inc._row_combinations(row_items, keep, 3, len(want) - 1)
    assert overflow


@pytest.mark.parametrize(
    "engine,base,deltas,absent_at_base",
    [
        ("jnp", 480, [20, 40, 70], True),  # 512-row width overflows on the second
        ("jnp", 480, [150, 150], True),
        # 4,096-row width overflows on the second; every card triple is dealt
        ("pallas", 4000, [60, 100], False),
    ],
)
def test_served_appends_equal_brute_force(engine, base, deltas, absent_at_base):
    tau, kmax = 3, 3
    rows = _deal(base, 5)
    svc = MiningService(engine=engine)
    try:
        svc.append(rows)
        assert svc.mine(tau=tau, kmax=kmax).source == "cold"
        widths = [svc.store.n_words]
        born = 0
        for i, d in enumerate(deltas):
            delta = _deal(d, 10 + i)
            svc.append(delta)
            rows = np.concatenate([rows, delta])
            widths.append(svc.store.n_words)
            r = svc.mine(tau=tau, kmax=kmax)
            assert r.source == "incremental"
            assert r.info["born_pairs_counted"] > 0
            assert r.info["device_words"] == svc.store.n_words
            born += r.info["n_delta_born"]
            got = {frozenset(ids) for ids, _ in r.result.as_value_sets()}
            assert got == _oracle_value_sets(rows, tau, kmax)
        assert widths[-1] > widths[0]
        assert (born > 0) == absent_at_base  # answers holding itemsets absent at the base
    finally:
        svc.close()


def test_width_grows_by_an_eighth_on_overflow_only():
    store = DatasetStore(2 * HAND, word_tile=8)
    store.append(_deal(1000, 0))
    assert store.n_words == 32  # snug: 1,000 rows in 32 words, a tile multiple
    width = store.n_words
    grown = 0
    for i in range(60):
        store.append(_deal(25 + 7 * i, 100 + i))
        if store.n_words != width:
            grown += 1
            assert width * 32 < store.n_rows  # only on overflow
            assert store.n_words * 32 >= 9 * store.n_rows / 8
            assert store.n_words % store.word_tile == 0
            assert store.n_words >= width * 9 / 8
            width = store.n_words
        assert store.n_rows <= store.n_words * 32
    assert 1 <= grown <= 20

    # fleet stripes keep word_tile * nproc divisibility
    shard = DatasetStore(2 * HAND, word_tile=8, shard=(1, 2))
    for i in range(12):
        shard.append(_deal(300, 200 + i))
        assert shard.stats()["n_words_global"] % 16 == 0


@pytest.mark.parametrize("engine", ["numpy", "jnp"])
def test_grown_store_answers_equal_a_snug_store(engine):
    # 2,000 rows fill 64 words snugly; 2,100 overflow them and take 80 words
    # (9/8 of the rows), which 2,200 rows, snug, would not need (72)
    blocks = [_deal(2000, 7), _deal(100, 8), _deal(100, 9)]
    rows = np.concatenate(blocks)
    grown = MiningService(engine=engine)
    snug = MiningService.from_dataset(rows, engine=engine)
    try:
        for b in blocks:
            grown.append(b)
        gt, st = grown.store.item_table(), snug.store.item_table()
        assert (gt.n_words, st.n_words) == (80, 72)
        assert np.array_equal(gt.col, st.col) and np.array_equal(gt.value, st.value)
        assert np.array_equal(gt.bits[:, : st.n_words], st.bits)
        assert not gt.bits[:, st.n_words :].any()
        for tau in (2, 6):
            a = grown.mine(tau=tau, kmax=3)
            b = snug.mine(tau=tau, kmax=3)
            assert a.source == b.source == "cold"
            assert a.result.itemsets == b.result.itemsets
    finally:
        grown.close()
        snug.close()


@pytest.fixture()
def served():
    from repro.launch.serve_miner import make_server

    TRACER.reset()
    svc = MiningService.from_dataset(_deal(480, 3), engine="jnp")
    server = make_server(svc, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server.server_address[1]
    server.shutdown()
    server.server_close()
    svc.close()
    TRACER.reset()


def _post(port, route, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def _trace(trace_id):
    import time

    deadline = time.monotonic() + 10.0
    while (trace := TRACER.get(trace_id)) is None and time.monotonic() < deadline:
        time.sleep(0.005)
    assert trace is not None, trace_id
    return trace


def test_served_append_and_incremental_mine_span_tree(served):
    port = served
    assert _post(port, "/mine", {"tau": 3, "kmax": 3})["source"] == "cold"
    appended = _post(port, "/append", {"rows": _deal(60, 4).tolist()})
    mined = _post(port, "/mine", {"tau": 3, "kmax": 3})
    assert mined["source"] == "incremental"
    assert mined["info"]["born_candidates"]["3"] > 0

    trace = _trace(appended["trace_id"])
    (store_append,) = trace.find("store.append")
    parent = {s.span_id: s for s in trace.spans}[store_append.parent_id]
    assert parent.name == "service.append"
    assert store_append.attrs["rows"] == 60
    assert store_append.attrs["words"] == 24  # 540 rows overflow 16 words
    assert store_append.attrs["grew"] is True

    trace = _trace(mined["trace_id"])
    by_id = {s.span_id: s for s in trace.spans}
    (root,) = trace.find("mine.incremental")
    names = [s.name for s in trace.children_of(root) if s.name.startswith("incremental.")]
    assert names == ["incremental.recount", "incremental.expand", "incremental.delta_born"]
    (born,) = trace.find("incremental.delta_born")
    assert born.attrs["pairs_counted"] == mined["info"]["born_pairs_counted"] > 0
    assert born.attrs["born"] == mined["info"]["n_delta_born"]
    waits = [s for s in trace.find("device.wait") if s.attrs.get("site") == "born_counts"]
    assert waits and all(by_id[w.parent_id] is born for w in waits)
    (recount,) = trace.find("incremental.recount")
    assert recount.attrs["pairs_counted"] == mined["info"]["recount_pairs_counted"] > 0
    waits = [s for s in trace.find("device.wait") if s.attrs.get("site") == "recount_counts"]
    assert len(waits) == 1 and by_id[waits[0].parent_id] is recount
    assert trace.find("store.device_put")[0].attrs["bytes"] > 0


def _promoting_case():
    base, delta = _deal(300, 21), _deal(40, 22)
    cfg = KyivConfig(tau=3, kmax=3, engine="numpy")
    store = DatasetStore.from_dataset(base)
    base_res = mine(base, cfg)  # one load: the store's item ids are mine()'s
    version = store.version
    store.append(delta)
    return store, base_res, version, cfg


@pytest.mark.parametrize(
    "reason",
    ["config", "compacted", "empty_base", "no_delta", "delta_fraction",
     "budget_expansion", "budget_candidates"],
)
def test_each_fallback_is_counted_under_its_reason(reason):
    store, base_res, version, cfg = _promoting_case()
    loose = dict(max_delta_fraction=1.0)
    kw = {
        "config": (version, IncrementalConfig(enabled=False)),
        "compacted": (version + 99, IncrementalConfig(**loose)),
        "empty_base": (0, IncrementalConfig(**loose)),
        "no_delta": (store.version, IncrementalConfig(**loose)),
        "delta_fraction": (version, IncrementalConfig(max_delta_fraction=0.01)),
        "budget_expansion": (version, IncrementalConfig(expansion_budget=0, **loose)),
        "budget_candidates": (version, IncrementalConfig(delta_candidate_budget=0, **loose)),
    }[reason]
    before = inc._FALLBACKS.value(reason=reason)
    with TRACER.start("test") as root:
        out = mine_incremental(store, base_res, kw[0], cfg, kw[1])
    assert out is None
    assert inc._FALLBACKS.value(reason=reason) == before + 1
    assert root.attrs["fallback"] == reason
    # the sound call on the same state does not fall back
    assert mine_incremental(store, base_res, version, cfg, IncrementalConfig(**loose))
