"""Durability layer: WAL framing, snapshot folding, crash recovery.

The contract under test is the one the README's Operations section
promises: an acknowledged append survives process death (fsync'd WAL
record), an unacknowledged torn tail is dropped, and a recovered store is
observably identical to the pre-crash one — same item ids, bitsets,
supports and version watermarks.
"""

import os
import pickle

import numpy as np
import pytest

from repro.core import bits_to_rows
from repro.service.checkpoint import CheckpointManager, save_pytree
from repro.service import (
    DatasetStore,
    DurableStore,
    FaultInjector,
    KillPoint,
    MiningService,
    WriteAheadLog,
)


def _rand(seed, n, m, dom=4):
    return np.random.default_rng(seed).integers(0, dom, size=(n, m))


def _store_fingerprint(store: DatasetStore):
    """Everything a client can observe about a store."""
    table = store.item_table()
    items = {
        (int(table.col[i]), int(table.value[i])): (
            int(table.freq[i]),
            int(table.min_row[i]),
            tuple(bits_to_rows(table.bits[i]).tolist()),
        )
        for i in range(table.n_items)
    }
    watermarks = {
        v: (store.rows_at(v), store.items_at(v))
        for v in range(1, store.version + 1)
        if store.has_version(v)
    }
    return (store.version, store.n_rows, store.n_items, items, watermarks)


# ---------------------------------------------------------------------------
# WriteAheadLog
# ---------------------------------------------------------------------------


def test_wal_roundtrip(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.log"))
    records = [{"version": i, "rows": _rand(i, 5, 3)} for i in range(1, 4)]
    for r in records:
        wal.append(r)
    got = wal.replay()
    assert len(got) == 3
    for want, have in zip(records, got):
        assert have["version"] == want["version"]
        np.testing.assert_array_equal(have["rows"], want["rows"])
    assert wal.truncated_bytes == 0


def test_wal_truncated_tail_dropped(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    wal.append({"version": 1, "rows": _rand(0, 5, 3)})
    wal.append({"version": 2, "rows": _rand(1, 5, 3)})
    wal.close()
    size = os.path.getsize(path)
    with open(path, "r+b") as f:  # tear the last frame mid-payload
        f.truncate(size - 7)
    wal2 = WriteAheadLog(path)
    got = wal2.replay()
    assert [r["version"] for r in got] == [1]
    assert wal2.truncated_bytes > 0
    # the torn tail is physically gone: a fresh append after recovery
    # produces a clean log
    wal2.append({"version": 2, "rows": _rand(1, 5, 3)})
    assert [r["version"] for r in wal2.replay()] == [1, 2]


def test_wal_corrupt_tail_bytes_dropped(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    wal.append({"version": 1, "rows": _rand(0, 5, 3)})
    wal.close()
    with open(path, "ab") as f:  # garbage after the good prefix
        f.write(b"\x00garbage-not-a-frame" * 3)
    wal2 = WriteAheadLog(path)
    assert [r["version"] for r in wal2.replay()] == [1]
    assert wal2.truncated_bytes > 0


def test_wal_flipped_bit_fails_crc(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    wal.append({"version": 1, "rows": _rand(0, 5, 3)})
    wal.close()
    data = bytearray(open(path, "rb").read())
    data[-3] ^= 0x40
    open(path, "wb").write(bytes(data))
    assert WriteAheadLog(path).replay() == []


def test_wal_reset(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.log"))
    wal.append({"version": 1, "rows": _rand(0, 5, 3)})
    wal.reset()
    assert wal.size() == 0 and wal.replay() == []
    wal.append({"version": 2, "rows": _rand(1, 5, 3)})
    assert [r["version"] for r in wal.replay()] == [2]


# ---------------------------------------------------------------------------
# DatasetStore state export
# ---------------------------------------------------------------------------


def test_export_from_state_identical():
    store = DatasetStore(4)
    for s in range(4):
        store.append(_rand(s, 30, 4, 5))
    rebuilt = DatasetStore.from_state(store.export_state())
    assert _store_fingerprint(rebuilt) == _store_fingerprint(store)
    # the rebuilt store keeps working: appends continue the version chain
    # and itemize against the recovered item-id table
    a, b = _rand(9, 20, 4, 5), _rand(9, 20, 4, 5)
    assert store.append(a) == rebuilt.append(b) == 5
    np.testing.assert_array_equal(a, b)
    assert _store_fingerprint(rebuilt) == _store_fingerprint(store)


def test_export_state_is_a_snapshot():
    store = DatasetStore(3)
    store.append(_rand(0, 25, 3, 4))
    state = store.export_state()
    store.append(_rand(1, 25, 3, 4))
    rebuilt = DatasetStore.from_state(state)
    assert rebuilt.version == 1 and rebuilt.n_rows == 25


# ---------------------------------------------------------------------------
# DurableStore: WAL + snapshots + recovery
# ---------------------------------------------------------------------------


def test_durable_store_recovers_from_wal_only(tmp_path):
    d = str(tmp_path / "wal")
    ds = DurableStore(d, snapshot_every=100)
    for s in range(3):
        ds.append(_rand(s, 20, 4, 5))
    want = _store_fingerprint(ds.store)
    ds.close()

    ds2 = DurableStore(d, snapshot_every=100)
    info = ds2.recover()
    assert info["replayed"] == 3 and info["snapshot_version"] == 0
    assert _store_fingerprint(ds2.store) == want


def test_durable_store_snapshot_folding(tmp_path):
    d = str(tmp_path / "wal")
    ds = DurableStore(d, snapshot_every=2)
    for s in range(5):
        ds.append(_rand(s, 20, 4, 5))
    assert ds.snapshots_taken == 2  # after appends 2 and 4
    assert ds.stats()["since_snapshot"] == 1
    want = _store_fingerprint(ds.store)
    ds.close()

    ds2 = DurableStore(d, snapshot_every=2)
    info = ds2.recover()
    assert info["snapshot_version"] == 4 and info["replayed"] == 1
    assert _store_fingerprint(ds2.store) == want


def test_kill_mid_append_recovers_to_last_ack(tmp_path):
    """The torn half-frame of a power cut mid-append is dropped: recovery
    lands on the last *acknowledged* version, exactly."""
    d = str(tmp_path / "wal")
    inj = FaultInjector()
    ds = DurableStore(d, snapshot_every=100, injector=inj)
    ds.append(_rand(0, 30, 4, 5))
    ds.append(_rand(1, 30, 4, 5))
    want = _store_fingerprint(ds.store)

    inj.arm("wal.append", action="partial")
    with pytest.raises(KillPoint):
        ds.append(_rand(2, 30, 4, 5))
    ds.close()

    ds2 = DurableStore(d, snapshot_every=100)
    info = ds2.recover()
    assert info["truncated_bytes"] > 0
    assert ds2.store.version == 2
    assert _store_fingerprint(ds2.store) == want
    # and the recovered store accepts the retried block normally
    assert ds2.append(_rand(2, 30, 4, 5)) == 3


def test_crash_between_snapshot_and_wal_reset_is_idempotent(tmp_path):
    """Records the snapshot already holds are skipped by version on replay —
    simulate the crash window by re-appending the WAL records the snapshot
    folded in."""
    d = str(tmp_path / "wal")
    ds = DurableStore(d, snapshot_every=2)
    blocks = [_rand(s, 20, 4, 5) for s in range(2)]
    for i, b in enumerate(blocks):
        ds.append(b)
    # snapshot at v2 just ran and reset the WAL; undo the reset
    for i, b in enumerate(blocks):
        ds.wal.append({"version": i + 1, "rows": b})
    want = _store_fingerprint(ds.store)
    ds.close()

    ds2 = DurableStore(d, snapshot_every=2)
    info = ds2.recover()
    assert info["skipped"] == 2 and info["replayed"] == 0
    assert _store_fingerprint(ds2.store) == want


# ---------------------------------------------------------------------------
# CheckpointManager hardening (restore fallback)
# ---------------------------------------------------------------------------


def test_manager_restore_falls_back_past_corrupt_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=3)
    mgr.save(1, {"x": np.arange(3)})
    mgr.save(2, {"x": np.arange(4)})
    # corrupt the newest checkpoint's arrays
    with open(os.path.join(mgr._step_dir(2), "arrays.npz"), "wb") as f:
        f.write(b"not an npz")
    tree, meta = mgr.restore()
    assert meta["step"] == 1
    np.testing.assert_array_equal(tree["x"], np.arange(3))
    # the corrupt dir is quarantined, not rediscovered
    assert mgr.steps() == [1]
    assert os.path.exists(mgr._step_dir(2) + ".corrupt")


def test_manager_restore_none_when_all_corrupt(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=3)
    mgr.save(1, {"x": np.arange(3)})
    with open(os.path.join(mgr._step_dir(1), "arrays.npz"), "wb") as f:
        f.write(b"junk")
    assert mgr.restore() == (None, None)


# ---------------------------------------------------------------------------
# MiningService over a durable store
# ---------------------------------------------------------------------------


def test_service_restart_recovers_store_and_serves(tmp_path):
    d = str(tmp_path / "wal")
    svc = MiningService(engine="numpy", wal_dir=d, snapshot_every=3)
    for s in range(5):
        svc.append(_rand(s, 25, 4, 5))
    want = _store_fingerprint(svc.store)
    ref = svc.mine(tau=2, kmax=3)
    svc.close()

    svc2 = MiningService(engine="numpy", wal_dir=d, snapshot_every=3)
    assert svc2.ready
    assert _store_fingerprint(svc2.store) == want
    got = svc2.mine(tau=2, kmax=3)
    assert got.result.canonical_set() == ref.result.canonical_set()
    stats = svc2.stats()
    assert stats["durability"]["last_recovery"]["version"] == 5
    svc2.close()


def test_service_not_ready_rejects_until_recovered(tmp_path):
    from repro.service import NotReadyError

    d = str(tmp_path / "wal")
    svc = MiningService(engine="numpy", wal_dir=d)
    svc.append(_rand(0, 25, 4, 5))
    svc.close()

    svc2 = MiningService(engine="numpy", wal_dir=d, defer_recovery=True)
    assert not svc2.ready
    assert svc2.readiness() == (False, "recovering")
    with pytest.raises(NotReadyError):
        svc2.mine(tau=1, kmax=2)
    with pytest.raises(NotReadyError):
        svc2.append(_rand(1, 5, 4, 5))
    svc2.recover()
    assert svc2.ready
    assert svc2.mine(tau=1, kmax=2).result is not None
    svc2.close()


def test_compact_snapshots_durable_state(tmp_path):
    d = str(tmp_path / "wal")
    svc = MiningService(engine="numpy", wal_dir=d, snapshot_every=100)
    for s in range(4):
        svc.append(_rand(s, 20, 4, 5))
    svc.compact(keep_versions=1)
    want = _store_fingerprint(svc.store)
    svc.close()

    svc2 = MiningService(engine="numpy", wal_dir=d, snapshot_every=100)
    assert _store_fingerprint(svc2.store) == want
    assert svc2.store.compactions == 1
    svc2.close()
