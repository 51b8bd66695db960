"""Resident mining service: store/cache/scheduler/API/HTTP behaviour.

The incremental-vs-cold equivalence property test lives in
tests/test_incremental.py (hypothesis); here are the deterministic
subsystem contracts plus targeted incremental edge cases.
"""

import dataclasses
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core import KyivConfig, bits_to_rows, itemize, mine
from repro.data.loaders import read_csv
from repro.kernels.intersect import executable_cache_stats
from repro.service import (
    DatasetStore,
    IncrementalConfig,
    MineResponse,
    MiningService,
    RequestScheduler,
    ResultCache,
    make_key,
    mine_incremental,
)
from repro.service.cache import CacheEntry


def _value_sets(result):
    return {(frozenset(ids), c) for ids, c in result.as_value_sets()}


def _rand(seed, n, m, dom):
    return np.random.default_rng(seed).integers(0, dom, size=(n, m))


# ---------------------------------------------------------------------------
# DatasetStore
# ---------------------------------------------------------------------------


def test_store_incremental_itemization_matches_itemize():
    """Appending in blocks must produce the same items/supports/row sets as
    one-shot itemization of the concatenated table."""
    blocks = [_rand(s, 37, 4, 5) for s in range(3)]
    store = DatasetStore(4)
    for b in blocks:
        store.append(b)
    table = store.item_table()
    ref = itemize(np.concatenate(blocks))

    got = {
        (int(table.col[i]), int(table.value[i])): (
            int(table.freq[i]),
            int(table.min_row[i]),
            tuple(bits_to_rows(table.bits[i]).tolist()),
        )
        for i in range(table.n_items)
    }
    want = {
        (int(ref.col[i]), int(ref.value[i])): (
            int(ref.freq[i]),
            int(ref.min_row[i]),
            tuple(ref.rows_of(i).tolist()),
        )
        for i in range(ref.n_items)
    }
    assert got == want


def test_store_versioning_and_word_tile():
    store = DatasetStore(3, word_tile=8)
    assert store.version == 0 and store.n_rows == 0
    v1 = store.append(_rand(0, 10, 3, 4))
    v2 = store.append(_rand(1, 300, 3, 4))
    assert (v1, v2) == (1, 2)
    assert store.rows_at(1) == 10 and store.rows_at(2) == 310
    assert store.n_words % 8 == 0
    assert store.n_words >= (310 + 31) // 32
    # appending zero rows does not bump the version
    assert store.append(np.zeros((0, 3), dtype=np.int64)) == 2


def test_store_delta_bits_exact():
    a, b = _rand(0, 45, 3, 4), _rand(1, 21, 3, 4)
    store = DatasetStore.from_dataset(a)
    base = store.version
    store.append(b)
    dbits, word_lo = store.delta_bits(base)
    table = store.item_table()
    # delta support per item == support of the item within the appended rows
    ref = itemize(np.concatenate([a, b]))
    for i in range(table.n_items):
        key = (int(table.col[i]), int(table.value[i]))
        j = next(
            r
            for r in range(ref.n_items)
            if (int(ref.col[r]), int(ref.value[r])) == key
        )
        delta_rows = [r for r in ref.rows_of(j) if r >= 45]
        got_rows = [word_lo * 32 + r for r in bits_to_rows(dbits[i])]
        assert got_rows == delta_rows


def test_store_snapshot_immune_to_later_appends():
    store = DatasetStore.from_dataset(_rand(0, 20, 3, 4))
    version, table = store.snapshot()
    before = table.bits.copy()
    store.append(_rand(1, 40, 3, 4))
    assert version == 1
    np.testing.assert_array_equal(table.bits, before)


# ---------------------------------------------------------------------------
# read_csv
# ---------------------------------------------------------------------------


def test_read_csv_header_and_codebooks(tmp_path):
    p = tmp_path / "t.csv"
    p.write_text("city,plan\nkyiv,a\nlviv,b\nkyiv,a\nodesa,b\n")
    table, names, books = read_csv(str(p))
    assert names == ["city", "plan"]
    assert table.shape == (4, 2)
    decoded = [books[0][i] for i in table[:, 0]]
    assert decoded == ["kyiv", "lviv", "kyiv", "odesa"]
    # feeds the service directly
    svc = MiningService.from_dataset(table)
    assert svc.mine(tau=1, kmax=2).n_itemsets >= 1
    svc.close()


def test_read_csv_headerless(tmp_path):
    p = tmp_path / "t.csv"
    p.write_text("1,2\n1,3\n2,2\n")
    table, names, _ = read_csv(str(p), header=False)
    assert names == ["col0", "col1"]
    assert table.shape == (3, 2)


# ---------------------------------------------------------------------------
# ResultCache / RequestScheduler
# ---------------------------------------------------------------------------


def _entry(version, tau=1, kmax=3):
    key = make_key(version, tau, kmax, "ascending")
    return CacheEntry(key=key, result=None, source="cold", info={})


def test_cache_lru_eviction_and_latest_base():
    cache = ResultCache(capacity=2)
    cache.put(_entry(1))
    cache.put(_entry(2))
    assert cache.get(make_key(1, 1, 3, "ascending")) is not None  # 1 now MRU
    cache.put(_entry(3))  # evicts version 2
    assert cache.get(make_key(2, 1, 3, "ascending")) is None
    assert cache.get(make_key(1, 1, 3, "ascending")) is not None
    base = cache.latest_base(1, 3, "ascending", before_version=3)
    assert base is not None and base.version == 1
    # different mining params never serve as a base
    assert cache.latest_base(2, 3, "ascending", before_version=99) is None


def test_scheduler_coalesces_identical_requests():
    sched = RequestScheduler(max_workers=2)
    calls = []
    release = threading.Event()

    def work():
        calls.append(1)
        release.wait(timeout=5)
        return "done"

    f1 = sched.submit(("k",), work)
    f2 = sched.submit(("k",), work)  # coalesced onto f1
    assert f2 is f1
    release.set()
    assert f1.result(timeout=5) == "done"
    assert len(calls) == 1
    assert sched.stats()["coalesced"] == 1
    # after completion the key is free again
    f3 = sched.submit(("k",), lambda: "again")
    assert f3.result(timeout=5) == "again"
    sched.shutdown()


# ---------------------------------------------------------------------------
# MiningService: cold -> cache -> incremental
# ---------------------------------------------------------------------------


def test_service_cold_cache_incremental_equivalence():
    base, delta = _rand(0, 300, 5, 6), _rand(1, 25, 5, 6)
    svc = MiningService.from_dataset(base)
    r1 = svc.mine(tau=2, kmax=3)
    r2 = svc.mine(tau=2, kmax=3)
    assert (r1.source, r2.source) == ("cold", "cache")
    assert r2.result is r1.result

    svc.append(delta)
    r3 = svc.mine(tau=2, kmax=3)
    assert r3.source == "incremental"
    cold = mine(np.concatenate([base, delta]), KyivConfig(tau=2, kmax=3))
    assert _value_sets(r3.result) == _value_sets(cold)

    # and the incremental result is itself cached
    assert svc.mine(tau=2, kmax=3).source == "cache"
    svc.close()


def test_service_incremental_new_values_and_mirrors():
    """Delta introduces brand-new values, promotes old rare ones, and breaks
    a mirror pair (two columns identical in the base diverge in the delta)."""
    base = np.stack(
        [
            np.array([1, 1, 1, 1, 2, 2, 2, 3]),
            np.array([1, 1, 1, 1, 2, 2, 2, 3]),  # mirror of col 0 in the base
            np.array([5, 5, 6, 6, 5, 5, 6, 6]),
        ],
        axis=1,
    )
    delta = np.array(
        [
            [3, 1, 5],  # promotes value 3 in col 0; breaks the col0/col1 mirror
            [9, 9, 7],  # brand-new values 9 (cols 0, 1) and 7 (col 2)
            [3, 2, 6],
        ]
    )
    svc = MiningService.from_dataset(
        base, incremental=IncrementalConfig(max_delta_fraction=0.5)
    )
    svc.mine(tau=1, kmax=3)
    svc.append(delta)
    r = svc.mine(tau=1, kmax=3)
    assert r.source == "incremental"
    assert r.info["n_new_items"] >= 3
    cold = mine(np.concatenate([base, delta]), KyivConfig(tau=1, kmax=3))
    assert _value_sets(r.result) == _value_sets(cold)
    svc.close()


def test_service_fallback_on_large_delta():
    base, delta = _rand(0, 60, 4, 5), _rand(1, 60, 4, 5)
    svc = MiningService.from_dataset(base)
    svc.mine(tau=1, kmax=3)
    svc.append(delta)
    r = svc.mine(tau=1, kmax=3)  # delta = 50% > max_delta_fraction
    assert r.source == "cold"
    cold = mine(np.concatenate([base, delta]), KyivConfig(tau=1, kmax=3))
    assert _value_sets(r.result) == _value_sets(cold)
    svc.close()


def test_mine_incremental_direct_kmax1():
    base, delta = _rand(0, 80, 4, 4), _rand(3, 10, 4, 4)
    store = DatasetStore.from_dataset(base)
    cfg = KyivConfig(tau=2, kmax=1)
    base_res = mine(base, cfg)
    v1 = store.version
    store.append(delta)
    out = mine_incremental(store, base_res, v1, cfg, IncrementalConfig())
    assert out is not None
    result, _ = out
    cold = mine(np.concatenate([base, delta]), cfg)
    assert _value_sets(result) == _value_sets(cold)


def test_service_warm_executables_across_requests():
    """Repeated jnp mining requests reuse the process-wide executable
    buckets (the ops.EXEC_CACHE warm-start satellite) and mine through the
    store's device-resident bitsets — results must match the numpy engine."""
    a, b = _rand(0, 128, 4, 4), _rand(1, 128, 4, 4)
    svc = MiningService.from_dataset(a, engine="jnp")
    r1 = svc.mine(tau=1, kmax=3)
    before = executable_cache_stats()
    svc.append(b)  # doubles rows -> fallback cold remine
    r2 = svc.mine(tau=1, kmax=3)
    after = executable_cache_stats()
    assert after["hits"] > before["hits"]
    assert _value_sets(r1.result) == _value_sets(mine(a, KyivConfig(tau=1, kmax=3)))
    assert _value_sets(r2.result) == _value_sets(
        mine(np.concatenate([a, b]), KyivConfig(tau=1, kmax=3))
    )
    svc.close()


# ---------------------------------------------------------------------------
# /mine reply bytes
# ---------------------------------------------------------------------------


def _spec_bytes(resp, max_itemsets, trace_id):
    """What the HTTP layer sent before replies were built from arrays:
    ``json.dumps`` of ``to_json`` with the trace id set as ``_encode`` sets it."""
    doc = resp.to_json(max_itemsets)
    if trace_id:
        doc.setdefault("trace_id", trace_id)
    return json.dumps(doc).encode()


@pytest.fixture(scope="module")
def reply_cases():
    D = _rand(0, 200, 4, 5)
    D[0, 0] = 9  # one rare value: a 1-itemset beside the 2- and 3-itemsets
    svc = MiningService.from_dataset(D, engine="numpy")
    real = svc.mine(tau=3, kmax=3)
    partial = svc.mine(tau=1, kmax=4, deadline_s=0.0)
    svc.close()
    # column 4 relabels column 0: its items share their rowsets (mirrors)
    M = _rand(1, 120, 3, 4)
    svc = MiningService.from_dataset(np.concatenate([M, (M[:, :1] + 1) % 4], axis=1),
                                     engine="numpy")
    mirrored = svc.mine(tau=4, kmax=3)
    svc.close()

    res = real.result
    by_k = {len(ids): (ids, c) for ids, c in res.itemsets}
    # arities in non-monotone runs, with a duplicate kept in place
    runs = [by_k[3], by_k[3], by_k[1], res.itemsets[-1], by_k[2]]
    return {
        "empty": (dataclasses.replace(real, result=dataclasses.replace(res, itemsets=[])), None),
        "mine_k123": (real, None),
        "runs_3_1_3_2": (dataclasses.replace(
            real, result=dataclasses.replace(res, itemsets=runs)), None),
        "max_0": (real, 0),
        "max_5": (real, 5),
        "max_over": (real, len(res.itemsets) + 1),
        "mirrored": (mirrored, None),
        "partial": (partial, None),
        "nested_info": (dataclasses.replace(
            real, latency_s=1 / 3,
            info={"cost": {"wall_s": 0.1 + 0.2, "path": "cold"}, "ratio": 1e-9,
                  "levels": [1.5, {"k": 2}], "name": "caf\u00e9"}), 2),
    }


@pytest.mark.parametrize("trace_id", [None, "a1b2c3d4e5f60718"])
@pytest.mark.parametrize("case", ["empty", "mine_k123", "runs_3_1_3_2", "max_0", "max_5",
                                  "max_over", "mirrored", "partial", "nested_info"])
def test_mine_reply_bytes_equal_json_dumps_of_to_json(reply_cases, case, trace_id):
    resp, max_itemsets = reply_cases[case]
    assert isinstance(resp, MineResponse)
    if case == "mine_k123":
        assert {len(ids) for ids, _ in resp.result.itemsets} == {1, 2, 3}
    if case == "mirrored":
        assert resp.result.prep.mirror_of
    if case == "partial":
        assert resp.source == "partial" and resp.result.interrupted == "deadline"
    got = resp.to_json_bytes(max_itemsets, trace_id)
    assert got == _spec_bytes(resp, max_itemsets, trace_id)
    pieces = resp.reply_pieces(max_itemsets)
    assert pieces.n_itemsets == len(resp.to_json(max_itemsets)["itemsets"])


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------


@pytest.fixture()
def http_service():
    from repro.launch.serve_miner import make_server

    svc = MiningService.from_dataset(_rand(0, 200, 4, 5))
    server = make_server(svc, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield svc, server.server_address[1]
    server.shutdown()
    server.server_close()
    svc.close()


def _req(port, path, payload=None):
    url = f"http://127.0.0.1:{port}{path}"
    if payload is None:
        resp = urllib.request.urlopen(url, timeout=30)
    else:
        resp = urllib.request.urlopen(
            urllib.request.Request(
                url,
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
            ),
            timeout=30,
        )
    return resp.status, json.loads(resp.read())


def test_http_mine_append_report_cycle(http_service):
    svc, port = http_service
    assert _req(port, "/healthz")[1] == {"ok": True}

    code, m1 = _req(port, "/mine", {"tau": 1, "kmax": 3, "max_itemsets": 5})
    assert code == 200 and m1["source"] == "cold" and len(m1["itemsets"]) <= 5

    code, m2 = _req(port, "/mine?tau=1&kmax=3")
    assert m2["source"] == "cache" and m2["n_itemsets"] == m1["n_itemsets"]

    rows = _rand(7, 15, 4, 5).tolist()
    code, a = _req(port, "/append", {"rows": rows})
    assert code == 200 and a["appended"] == 15 and a["version"] == 2

    code, m3 = _req(port, "/mine", {"tau": 1, "kmax": 3})
    assert m3["source"] in ("incremental", "cold") and m3["version"] == 2

    code, rep = _req(port, "/report?tau=1&kmax=3")
    assert code == 200
    assert rep["n_quasi_identifiers"] == m3["n_itemsets"]
    assert rep["n_rows"] == 215

    code, stats = _req(port, "/stats")
    assert stats["store"]["n_rows"] == 215
    assert stats["cache"]["hits"] >= 1


@pytest.mark.parametrize("max_itemsets", [None, 5])
def test_http_mine_body_is_to_json_with_trace_id(http_service, monkeypatch, max_itemsets):
    svc, port = http_service
    served = []
    mine_fn = svc.mine

    def keep(**kw):
        served.append(mine_fn(**kw))
        return served[-1]

    monkeypatch.setattr(svc, "mine", keep)
    payload = {"tau": 1, "kmax": 3}
    if max_itemsets is not None:
        payload["max_itemsets"] = max_itemsets
    resp = urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/mine", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}), timeout=30)
    body = resp.read()
    (mined,) = served
    want = mined.to_json(max_itemsets)
    want["trace_id"] = resp.headers["X-Trace-Id"]
    assert want["itemsets"] and json.loads(body) == want
    assert body == json.dumps(want).encode()


def test_http_error_handling(http_service):
    _, port = http_service
    with pytest.raises(urllib.error.HTTPError) as e:
        _req(port, "/nope")
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _req(port, "/append", {"rows": []})
    assert e.value.code == 400


# ---------------------------------------------------------------------------
# Store compaction
# ---------------------------------------------------------------------------


def test_store_compaction_preserves_retained_versions():
    """Auto-compaction drops only old watermarks; every retained version's
    rows_at/delta_bits and the table content itself are unchanged."""
    blocks = [_rand(s, 30, 4, 5) for s in range(6)]
    store = DatasetStore(4, compact_threshold=4, keep_versions=2)
    ref = DatasetStore(4)
    for b in blocks:
        store.append(b)
        ref.append(b)
    assert store.compactions >= 1
    assert not store.has_version(1)  # consolidated into the base
    for v in range(store.version - 2, store.version + 1):  # retained window
        assert store.has_version(v)
        assert store.rows_at(v) == ref.rows_at(v)
        np.testing.assert_array_equal(store.delta_bits(v)[0], ref.delta_bits(v)[0])
    t, r = store.item_table(), itemize(np.concatenate(blocks))
    got = {
        (int(t.col[i]), int(t.value[i])): tuple(bits_to_rows(t.bits[i]).tolist())
        for i in range(t.n_items)
    }
    want = {
        (int(r.col[i]), int(r.value[i])): tuple(r.rows_of(i).tolist())
        for i in range(r.n_items)
    }
    assert got == want


def test_store_manual_compaction_trims_capacity():
    store = DatasetStore(3, word_tile=8)
    for s in range(5):
        store.append(_rand(s, 100, 3, 6))
    cap_before = store._bits.nbytes
    info = store.compact(keep_versions=2)
    assert info["dropped_versions"] >= 1
    assert store._bits.nbytes <= cap_before
    assert store.n_words % store.word_tile == 0
    t = store.item_table()
    assert t.n_rows == 500 and t.bits.shape[0] == t.n_items


def test_store_compaction_config_validation():
    # thrash guard: a threshold the retained watermarks can never get under
    with pytest.raises(ValueError):
        DatasetStore(3, compact_threshold=4, keep_versions=8)
    with pytest.raises(ValueError):
        DatasetStore(3).compact(keep_versions=0)


def test_store_auto_compaction_does_not_thrash():
    """Steady appends between compactions: each auto-compaction must drop
    something, not re-fire (and re-copy the matrix) on every append."""
    store = DatasetStore(4, compact_threshold=6, keep_versions=2)
    for s in range(20):
        store.append(_rand(s, 10, 4, 5))
    assert store.compactions <= 20 // (6 - (2 + 1)) + 1


def test_service_incremental_falls_back_cold_after_compaction():
    """A cached base whose version watermark was compacted away can no longer
    seed the delta miner — the service re-mines cold, bit-identically."""
    base, d1, d2 = _rand(0, 200, 4, 5), _rand(1, 10, 4, 5), _rand(2, 10, 4, 5)
    svc = MiningService.from_dataset(base)
    svc.mine(tau=1, kmax=3)  # cached at version 1
    svc.append(d1)
    svc.append(d2)
    svc.store.compact(keep_versions=1)  # drops versions 1 and 2
    assert not svc.store.has_version(1)
    r = svc.mine(tau=1, kmax=3)
    assert r.source == "cold"
    cold = mine(np.concatenate([base, d1, d2]), KyivConfig(tau=1, kmax=3))
    assert _value_sets(r.result) == _value_sets(cold)
    assert svc.stats()["store"]["compactions"] == 1
    svc.close()


# ---------------------------------------------------------------------------
# HTTP hardening: bearer auth + bounded in-flight queue
# ---------------------------------------------------------------------------


@pytest.fixture()
def hardened_http_service():
    from repro.launch.serve_miner import make_server

    svc = MiningService.from_dataset(_rand(0, 120, 4, 5))
    server = make_server(svc, port=0, auth_token="tok3n", max_inflight=2)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield svc, server
    server.shutdown()
    server.server_close()
    svc.close()


def _req_auth(port, path, token=None, payload=None):
    headers = {"Content-Type": "application/json"}
    if token is not None:
        headers["Authorization"] = f"Bearer {token}"
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode() if payload is not None else None,
        headers=headers,
    )
    resp = urllib.request.urlopen(req, timeout=30)
    return resp.status, json.loads(resp.read())


def test_http_bearer_auth(hardened_http_service):
    _, server = hardened_http_service
    port = server.server_address[1]
    # liveness is never gated
    assert _req_auth(port, "/healthz")[1] == {"ok": True}
    # missing, wrong, and non-ASCII tokens -> 401 (never a 500 leak)
    for token in (None, "wrong", "café"):
        with pytest.raises(urllib.error.HTTPError) as e:
            _req_auth(port, "/mine?tau=1&kmax=2", token=token)
        assert e.value.code == 401
    code, body = _req_auth(port, "/mine?tau=1&kmax=2", token="tok3n")
    assert code == 200 and body["source"] == "cold"
    code, stats = _req_auth(port, "/stats", token="tok3n")
    assert stats["http"]["auth"] is True
    assert stats["http"]["unauthorized"] == 3
    assert stats["http"]["served"] >= 2
    assert stats["placement"]["kind"] == "host"
    assert "hits" in stats["executables"] and "misses" in stats["executables"]


def test_http_bounded_queue_returns_429(hardened_http_service):
    _, server = hardened_http_service
    port = server.server_address[1]
    sem = server.RequestHandlerClass.inflight
    # saturate the in-flight bound as two stuck requests would
    assert sem.acquire(blocking=False) and sem.acquire(blocking=False)
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _req_auth(port, "/stats", token="tok3n")
        assert e.value.code == 429
        # liveness still answers while the queue is full
        assert _req_auth(port, "/healthz")[1] == {"ok": True}
    finally:
        sem.release()
        sem.release()
    code, stats = _req_auth(port, "/stats", token="tok3n")
    assert code == 200
    assert stats["http"]["rejected"] == 1
    assert stats["http"]["max_inflight"] == 2


def test_concurrent_http_requests_coalesce(http_service):
    svc, port = http_service
    svc.cache.clear()
    results = []

    def query():
        results.append(_req(port, "/mine", {"tau": 1, "kmax": 3})[1])

    threads = [threading.Thread(target=query) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(results) == 6
    assert len({r["n_itemsets"] for r in results}) == 1
    # exactly one cold run; everyone else hit the cache or coalesced onto it
    sched = svc.scheduler.stats()
    cache = svc.cache.stats()
    assert sched["scheduled"] + sched["coalesced"] + cache["hits"] >= 6
    assert sum(1 for r in results if r["source"] == "cold") >= 1


# ---------------------------------------------------------------------------
# Scheduler worker death + byte-bounded cache + readiness (robustness PR)
# ---------------------------------------------------------------------------


def test_scheduler_worker_death_fails_only_that_key():
    """A worker raising mid-job must deliver the exception to every waiter
    coalesced on that key — and nothing else: the key is released and
    subsequent requests (same or different key) run normally."""
    sched = RequestScheduler(max_workers=1)
    gate = threading.Event()

    def dies():
        gate.wait(5)
        raise RuntimeError("worker died")

    f1 = sched.submit(("k",), dies)
    f2 = sched.submit(("k",), dies)  # coalesces onto the doomed run
    assert f1 is f2
    gate.set()
    with pytest.raises(RuntimeError, match="worker died"):
        f1.result(timeout=10)
    with pytest.raises(RuntimeError, match="worker died"):
        f2.result(timeout=10)
    # the scheduler is not wedged: the same key runs again, fresh
    assert sched.submit(("k",), lambda: 42).result(timeout=10) == 42
    assert sched.submit(("other",), lambda: 7).result(timeout=10) == 7
    stats = sched.stats()
    assert stats["failed"] == 1 and stats["inflight"] == 0
    assert stats["scheduled"] == 3 and stats["coalesced"] == 1
    sched.shutdown()


def test_cache_bounded_by_bytes():
    data = _rand(0, 60, 4, 5)
    result = mine(data, KyivConfig(tau=1, kmax=2))
    per_entry = CacheEntry(
        key=make_key(1, 1, 2, "ascending"), result=result, source="cold", info={}
    ).nbytes()
    assert per_entry > 0
    cache = ResultCache(capacity=64, max_bytes=3 * per_entry)
    for v in range(1, 7):
        cache.put(
            CacheEntry(
                key=make_key(v, 1, 2, "ascending"),
                result=result,
                source="cold",
                info={},
            )
        )
    stats = cache.stats()
    assert stats["entries"] == 3  # byte bound, not the 64-entry capacity
    assert stats["bytes"] <= stats["max_bytes"]
    # LRU order: the newest versions survived
    assert cache.get(make_key(6, 1, 2, "ascending")) is not None
    assert cache.get(make_key(1, 1, 2, "ascending")) is None


def test_cache_oversized_entry_still_cached():
    data = _rand(0, 60, 4, 5)
    result = mine(data, KyivConfig(tau=1, kmax=2))
    cache = ResultCache(capacity=4, max_bytes=1)  # smaller than any entry
    entry = CacheEntry(
        key=make_key(1, 1, 2, "ascending"), result=result, source="cold", info={}
    )
    cache.put(entry)
    assert cache.get(entry.key) is entry  # newest is never evicted


def test_service_cache_bytes_in_stats():
    svc = MiningService.from_dataset(_rand(0, 80, 4, 5), cache_max_bytes=1 << 30)
    svc.mine(tau=1, kmax=2)
    stats = svc.stats()["cache"]
    assert stats["max_bytes"] == 1 << 30
    assert stats["bytes"] > 0
    svc.close()


def test_http_readyz_and_deadline(http_service):
    svc, port = http_service
    code, body = _req(port, "/readyz")
    assert code == 200 and body == {"ready": True, "reason": "ok"}
    # an already-expired deadline returns 499 with the partial body
    with pytest.raises(urllib.error.HTTPError) as e:
        _req(port, "/mine", {"tau": 1, "kmax": 4, "deadline_s": 0.0})
    assert e.value.code == 499
    body = json.loads(e.value.read())
    assert body["source"] == "partial" and body["info"]["interrupted"] == "deadline"
    # the failed deadline did not wedge anything
    code, m = _req(port, "/mine", {"tau": 1, "kmax": 4})
    assert code == 200 and m["source"] == "cold"
    code, c = _req(port, "/cancel", {"tau": 1, "kmax": 4})
    # data routes also carry the request-correlation trace_id
    assert code == 200 and c["cancelled"] == 0 and "trace_id" in c


def test_http_readyz_not_ready_returns_503():
    from repro.launch.serve_miner import make_server

    svc = MiningService(engine="numpy", defer_recovery=True)
    server = make_server(svc, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    port = server.server_address[1]
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _req(port, "/readyz")
        assert e.value.code == 503
        assert json.loads(e.value.read())["reason"] == "recovering"
        # liveness stays green while readiness is red
        assert _req(port, "/healthz")[1] == {"ok": True}
        # data routes 503 (retryable) instead of 500
        with pytest.raises(urllib.error.HTTPError) as e:
            _req(port, "/mine?tau=1&kmax=2")
        assert e.value.code == 503
        svc.recover()
        assert _req(port, "/readyz")[0] == 200
    finally:
        server.shutdown()
        server.server_close()
        svc.close()


def test_service_drain_counters():
    svc = MiningService.from_dataset(_rand(0, 80, 4, 5))
    svc.mine(tau=1, kmax=2)
    info = svc.drain(timeout=1.0)
    assert info == {"inflight": 0, "drained": 0, "abandoned": 0}
    stats = svc.stats()
    assert stats["drain"] == info and stats["served"] == 1
    svc.close()
