"""Multi-host mining fleet: sharded store, lockstep collectives, coordinator.

Three rings of coverage, innermost first:

* pure unit tests — stripe math of the process-sharded ``DatasetStore``,
  ``ResultBands`` near-boundary recounts, snapshot shard guards;
* in-process fleet simulation — N threads, each a "process" with its own
  sharded store and :class:`FleetPlacement`, joined by a barrier-backed
  collective. Mining, incremental mining and risk must be bit-identical to
  the single-process answer on every simulated process;
* real 2-process harness (``@pytest.mark.slow``) — ``jax.distributed``
  over localhost, the actual ``FleetCollective`` KV transport, the
  ``FleetFrontend``/peer-loop coordinator, and a peer-kill chaos case that
  must degrade to the shadow service with exact answers.
"""

import importlib
import json
import os
import socket
import subprocess
import sys
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.collective import Collective, FleetDesyncError, LoopbackCollective
from repro.core.fleet import FleetPlacement
from repro.core.kyiv import KyivConfig, mine, mine_preprocessed
from repro.core.placement import HostPlacement
from repro.service import (
    DatasetStore,
    FleetFrontend,
    IncrementalConfig,
    MiningService,
    ResultBands,
    mine_incremental,
)
from repro.service.incremental import delta_support
from repro.service.store import mask_delta_words_local

_pre = importlib.import_module("repro.core.preprocess")

NPROC = 2


# -- in-process fleet simulation ------------------------------------------


class ThreadCollective(Collective):
    """Barrier-backed collective for N threads posing as N processes."""

    def __init__(self, pid: int, shared: dict, nproc: int = NPROC):
        self.pid, self.nproc = pid, nproc
        self.sh = shared
        self._round = 0
        self.rounds = 0
        self.seconds = 0.0
        self.payload_bytes = 0

    def allgather(self, payload: bytes) -> list[bytes]:
        n = self._round
        self._round += 1
        self.sh["slots"][(n, self.pid)] = payload
        self.sh["barrier"].wait()
        out = [self.sh["slots"][(n, p)] for p in range(self.nproc)]
        self.sh["barrier"].wait()
        self.rounds += 1
        self.payload_bytes += sum(len(b) for b in out)
        return out


class _HookProxy:
    """Routes the module-global preprocess hook to each thread's collective."""

    def __init__(self, nproc: int = NPROC):
        self.by_thread: dict[int, ThreadCollective] = {}
        self.nproc = nproc

    def _mine(self) -> ThreadCollective:
        return self.by_thread[threading.get_ident()]

    def allgather(self, payload):
        return self._mine().allgather(payload)

    def allreduce_sum(self, arr):
        return self._mine().allreduce_sum(arr)


def _run_fleet(worker, nproc: int = NPROC):
    """Run ``worker(pid, collective)`` on nproc threads; returns results."""
    shared = {"slots": {}, "barrier": threading.Barrier(nproc)}
    proxy = _HookProxy(nproc)
    prev = _pre.set_row_group_collective(proxy)
    outs = [None] * nproc
    errs = [None] * nproc

    def run(p):
        try:
            tc = ThreadCollective(p, shared, nproc)
            proxy.by_thread[threading.get_ident()] = tc
            outs[p] = worker(p, tc)
        except Exception as exc:  # noqa: BLE001 - surfaced via errs
            errs[p] = exc
            try:
                shared["barrier"].abort()
            except Exception:
                pass

    threads = [threading.Thread(target=run, args=(p,)) for p in range(nproc)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    _pre.set_row_group_collective(prev)
    assert not any(errs), [e for e in errs if e]
    return outs


def _dataset(seed=3, n=400, d=130, cols=4, vals=5):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, vals, size=(n, cols)),
        rng.integers(0, vals, size=(d, cols)),
    )


# -- sharded store stripe math --------------------------------------------


def test_sharded_store_reconstructs_global_bits():
    rows, delta = _dataset(seed=9)
    full = DatasetStore(4, word_tile=8)
    full.append(rows)
    full.append(delta)
    shards = []
    for p in range(NPROC):
        s = DatasetStore(4, word_tile=8, shard=(p, NPROC))
        s.append(rows)
        s.append(delta)
        shards.append(s)
    t_full = full.item_table()
    n_words_global = shards[0].stats()["n_words_global"]
    assert n_words_global >= t_full.n_words
    rebuilt = np.zeros((t_full.n_items, n_words_global), dtype=np.uint32)
    for s in shards:
        t = s.item_table()
        wm = s.word_map(t.n_words)
        rebuilt[:, wm] = t.bits
    assert np.array_equal(rebuilt[:, : t_full.n_words], t_full.bits)
    # trailing global pad words hold no bits
    assert not rebuilt[:, t_full.n_words :].any()
    # global metadata is replicated, not sharded
    for s in shards:
        t = s.item_table()
        assert np.array_equal(t.freq, t_full.freq)
        assert np.array_equal(t.value, t_full.value)
        assert s.version == full.version
        assert s.n_rows == full.n_rows


def test_sharded_delta_popcounts_sum_to_global():
    rows, delta = _dataset(seed=21)
    base_rows = len(rows)
    full = DatasetStore(4, word_tile=8)
    v1 = full.append(rows)
    full.append(delta)
    fbits, _ = full.delta_bits(v1)
    want = np.unpackbits(fbits.view(np.uint8), axis=1).sum(axis=1).astype(np.int64)
    got = np.zeros_like(want)
    for p in range(NPROC):
        s = DatasetStore(4, word_tile=8, shard=(p, NPROC))
        s.append(rows)
        s.append(delta)
        t = s.item_table()
        dbits = mask_delta_words_local(t.bits, base_rows, s.word_map(t.n_words))
        got += (
            np.unpackbits(dbits.view(np.uint8), axis=1).sum(axis=1).astype(np.int64)
        )
    assert np.array_equal(got, want)


def test_sharded_snapshot_rejects_foreign_shard():
    rows, _ = _dataset()
    s = DatasetStore(4, word_tile=8, shard=(0, NPROC))
    s.append(rows)
    state = s.export_state()
    restored = DatasetStore.from_state(state)  # same shard: fine
    assert restored.shard == (0, NPROC)
    with pytest.raises(ValueError, match="not transferable"):
        DatasetStore.from_state(state, shard=(1, NPROC))


def test_identity_shard_is_unsharded():
    rows, _ = _dataset()
    a = DatasetStore(4, word_tile=8)
    b = DatasetStore(4, word_tile=8, shard=(0, 1))
    a.append(rows)
    b.append(rows)
    ta, tb = a.item_table(), b.item_table()
    assert np.array_equal(ta.bits, tb.bits)
    assert a.watermark_digest() == b.watermark_digest()


# -- ResultBands: near-boundary recounts ----------------------------------


def test_result_bands_recount_matches_brute_force():
    rng = np.random.default_rng(5)
    for trial in range(6):
        rows = rng.integers(0, 4, size=(250, 4))
        delta = rng.integers(0, 4, size=(30, 4))
        tau = int(rng.integers(4, 40))
        cfg = KyivConfig(tau=tau, kmax=3)
        store = DatasetStore(4, word_tile=8)
        v1 = store.append(rows)
        base = mine(rows, cfg)
        store.append(delta)
        table = store.item_table()
        dbits, _ = store.delta_bits(v1)
        dfreq = (
            np.unpackbits(dbits.view(np.uint8), axis=1).sum(axis=1).astype(np.int64)
        )
        bands = ResultBands.from_result(base.itemsets)
        new_counts, stats = bands.recount(dbits, dfreq, tau, len(delta))
        dsup = delta_support(dbits, [ids for ids, _ in base.itemsets])
        for (ids, old), new, ds in zip(base.itemsets, new_counts, dsup):
            assert new == old + ds
        assert stats["n_recounted"] + stats["n_recount_skipped"] == len(
            base.itemsets
        )
        # skipped sets are exactly those whose members all miss the delta
        if stats["n_recount_skipped"]:
            for (ids, old), new in zip(base.itemsets, new_counts):
                if all(dfreq[i] == 0 for i in ids) and len(ids) > 1:
                    assert new == old


def test_result_bands_skip_shrinks_recount_floor():
    # a delta touching few items must leave most multi-item recounts skipped
    rng = np.random.default_rng(8)
    rows = rng.integers(0, 3, size=(600, 5))
    delta = rows[:8].copy()  # delta reuses existing value patterns
    delta[:, 4] = rows[:8, 4]
    cfg = KyivConfig(tau=30, kmax=3)
    store = DatasetStore(5, word_tile=8)
    v1 = store.append(rows)
    base = mine(rows, cfg)
    store.append(delta)
    table = store.item_table()
    dbits, _ = store.delta_bits(v1)
    dfreq = np.unpackbits(dbits.view(np.uint8), axis=1).sum(axis=1).astype(np.int64)
    bands = ResultBands.from_result(base.itemsets)
    _, stats = bands.recount(dbits, dfreq, cfg.tau, len(delta))
    multi = sum(1 for ids, _ in base.itemsets if len(ids) > 1)
    zero_ub = sum(
        1
        for ids, _ in base.itemsets
        if len(ids) > 1 and min(dfreq[i] for i in ids) == 0
    )
    assert stats["n_recount_skipped"] == zero_ub
    assert stats["n_recounted"] == len(base.itemsets) - zero_ub
    if zero_ub:
        assert stats["n_recounted"] < len(base.itemsets)
    assert multi >= zero_ub


def test_incremental_with_cached_bands_is_identical():
    rows, delta = _dataset(seed=31)
    cfg = KyivConfig(tau=25, kmax=3)
    store = DatasetStore(4, word_tile=8)
    v1 = store.append(rows)
    base = mine(rows, cfg)
    store.append(delta)
    cold = mine(np.concatenate([rows, delta]), cfg)
    with_bands = mine_incremental(
        store, base, v1, cfg, IncrementalConfig(),
        bands=ResultBands.from_result(base.itemsets),
    )
    without = mine_incremental(store, base, v1, cfg, IncrementalConfig())
    assert with_bands is not None and without is not None
    assert sorted(with_bands[0].itemsets) == sorted(cold.itemsets)
    assert sorted(without[0].itemsets) == sorted(cold.itemsets)
    assert with_bands[1]["n_recounted"] == without[1]["n_recounted"]


# -- lockstep fleet mining (thread-simulated processes) -------------------


@pytest.mark.parametrize("cfg", [dict(tau=8, kmax=4), dict(tau=40, kmax=3)])
def test_fleet_mining_bit_identical(cfg):
    rows, delta = _dataset()
    baseline = mine(np.concatenate([rows, delta]), KyivConfig(**cfg))

    def worker(p, tc):
        store = DatasetStore(4, word_tile=8, shard=(p, NPROC))
        store.append(rows)
        store.append(delta)
        placement = FleetPlacement(HostPlacement(), collective=tc)
        config = KyivConfig(placement=placement, **cfg)
        prep = _pre.preprocess(
            store.item_table(), config.tau, ordering=config.ordering,
            seed=config.seed,
        )
        return mine_preprocessed(prep, config)

    for out in _run_fleet(worker):
        assert out.itemsets == baseline.itemsets
        assert [s.emitted for s in out.stats] == [
            s.emitted for s in baseline.stats
        ]


@pytest.mark.parametrize("forced_collision", [False, True])
def test_fleet_row_groups_need_every_stripe(monkeypatch, forced_collision):
    """Rows equal on one process's word stripes but not on the other's stay
    apart, and the fleet's partition is the single-process one."""
    if forced_collision:  # every row in one bucket: the AND-reduce decides
        monkeypatch.setattr(
            _pre, "_row_hashes",
            lambda bits, ids: np.zeros(len(ids), dtype=np.uint64),
        )
    rng = np.random.default_rng(4)
    tile, n_tiles = 4, 6
    bits = rng.integers(0, 2**32, size=(8, tile * n_tiles), dtype=np.uint32)
    stripes = [
        np.concatenate([np.arange(t * tile, (t + 1) * tile)
                        for t in range(p, n_tiles, NPROC)])
        for p in range(NPROC)
    ]
    bits[1] = bits[0]
    bits[1, stripes[1][-1]] ^= 1  # 0 and 1 agree on process 0's stripes only
    bits[3, stripes[1]] = bits[2, stripes[1]]  # 2 and 3 on process 1's only
    bits[5] = bits[4]  # 4 and 5 everywhere
    bits[7] = bits[4]
    ids = np.arange(8, dtype=np.int64)
    single, _ = _pre._row_set_groups(SimpleNamespace(bits=bits), ids)
    assert [g.tolist() for g in single] == [[0], [1], [2], [3], [4, 5, 7], [6]]

    def worker(p, tc):
        local = SimpleNamespace(bits=np.ascontiguousarray(bits[:, stripes[p]]))
        groups, verified = _pre._row_set_groups(local, ids)
        return [g.tolist() for g in groups], verified

    for groups, verified in _run_fleet(worker):
        assert groups == [g.tolist() for g in single]
        assert verified == 1


def test_fleet_incremental_bit_identical():
    rows, delta = _dataset(seed=11, n=420, d=60)
    cfg = dict(tau=12, kmax=4)
    cold = mine(np.concatenate([rows, delta]), KyivConfig(**cfg))

    def worker(p, tc):
        store = DatasetStore(4, word_tile=8, shard=(p, NPROC))
        v1 = store.append(rows)
        placement = FleetPlacement(HostPlacement(), collective=tc)
        config = KyivConfig(placement=placement, **cfg)
        prep = _pre.preprocess(
            store.item_table(), config.tau, ordering=config.ordering,
            seed=config.seed,
        )
        base = mine_preprocessed(prep, config)
        store.append(delta)
        out = mine_incremental(
            store, base, v1, config, IncrementalConfig(),
            bands=ResultBands.from_result(base.itemsets),
        )
        assert out is not None
        return out

    outs = _run_fleet(worker)
    for res, info in outs:
        assert sorted(res.itemsets) == sorted(cold.itemsets)
        assert info["fleet"]["nproc"] == NPROC
    assert outs[0][1]["n_recounted"] == outs[1][1]["n_recounted"]


def test_fleet_risk_profile_bit_identical():
    from repro.privacy.risk import risk_profile

    rows, delta = _dataset(seed=29)
    cfg = dict(tau=20, kmax=3)
    all_rows = np.concatenate([rows, delta])
    base = mine(all_rows, KyivConfig(**cfg))
    ref = risk_profile(base)

    def worker(p, tc):
        store = DatasetStore(4, word_tile=8, shard=(p, NPROC))
        store.append(rows)
        store.append(delta)
        placement = FleetPlacement(HostPlacement(), collective=tc)
        config = KyivConfig(placement=placement, **cfg)
        prep = _pre.preprocess(
            store.item_table(), config.tau, ordering=config.ordering,
            seed=config.seed,
        )
        result = mine_preprocessed(prep, config)
        table = store.item_table()
        return risk_profile(
            result, placement=placement, word_map=store.word_map(table.n_words)
        )

    for prof in _run_fleet(worker):
        assert np.array_equal(prof.counts_by_size, ref.counts_by_size)
        assert np.allclose(prof.risk, ref.risk)
        assert prof.records_at_risk == ref.records_at_risk


def test_collective_agree_raises_on_divergence():
    def worker(p, tc):
        with pytest.raises(FleetDesyncError):
            tc.agree(f"value-{p}".encode(), what="digest")
        return True

    assert all(_run_fleet(worker))


# -- loopback frontend: coordinator semantics without processes -----------


def test_loopback_frontend_matches_plain_service():
    rows, delta = _dataset(seed=5, n=300, d=40, cols=5, vals=4)
    tc = LoopbackCollective()
    svc = MiningService(placement=FleetPlacement(HostPlacement(), collective=tc))
    shadow = MiningService(engine="numpy")
    front = FleetFrontend(svc, tc, shadow=shadow)
    plain = MiningService(engine="numpy")

    front.append(rows)
    plain.append(rows)
    assert (
        front.mine(tau=10, kmax=3).result.itemsets
        == plain.mine(tau=10, kmax=3).result.itemsets
    )
    front.append(delta)
    plain.append(delta)
    r = front.mine(tau=10, kmax=3)
    p = plain.mine(tau=10, kmax=3)
    assert r.result.itemsets == p.result.itemsets
    assert r.source == "incremental"
    rf, rp = front.risk(tau=10, kmax=3), plain.risk(tau=10, kmax=3)
    for k in ("records_at_risk", "max_risk", "qi_total", "top_records"):
        assert rf[k] == rp[k]
    # shadow tracked every append
    assert shadow.store.n_rows == len(rows) + len(delta)
    st = front.stats()
    fl = st["resilience"]["fleet"]
    assert fl["degraded"] is False and fl["replicated_ops"] == 5


def test_frontend_rejects_fleet_incompatible_modes():
    tc = LoopbackCollective()
    svc = MiningService(placement=FleetPlacement(HostPlacement(), collective=tc))
    front = FleetFrontend(svc, tc, shadow=MiningService(engine="numpy"))
    front.append(np.zeros((64, 3), dtype=np.int64))
    with pytest.raises(ValueError, match="approx"):
        front.mine(tau=1, kmax=2, mode="approx")
    with pytest.raises(ValueError, match="deadline"):
        front.mine(tau=1, kmax=2, deadline_s=1.0)


# -- mesh warm-bucket registry (FleetPlacement delegates to it) -----------


def test_mesh_warm_buckets_records_dispatched_shapes():
    import jax

    from repro.core.placement import MeshPlacement

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    placement = MeshPlacement(mesh, pair_axes=("data",), word_axis="model")
    rng = np.random.default_rng(0)
    bits = rng.integers(0, 2**32, size=(6, 8), dtype=np.uint32)
    counts = np.full(6, 64, dtype=np.int64)
    n_words = bits.shape[1]
    before = placement.warm_buckets(n_words, fused=False, write_children=False)
    state = placement.prepare(bits, counts, 3, fused_classify=False)
    m = placement.padded_size(4)
    pairs = np.zeros((m, 2), dtype=np.int32)
    pairs[:4] = [[0, 1], [0, 2], [1, 2], [3, 4]]
    placement.dispatch(state, pairs, False)
    placement.release(state)
    after = placement.warm_buckets(n_words, fused=False, write_children=False)
    assert m in after
    assert set(before) <= set(after)
    # the fleet wrapper reports its inner placement's warm shapes
    fleet = FleetPlacement(placement, collective=LoopbackCollective())
    assert fleet.warm_buckets(n_words, fused=True, write_children=False) == after


# -- real processes over jax.distributed (slow ring) ----------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_WORKER = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, sys.argv[4])
pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
chaos = len(sys.argv) > 5 and sys.argv[5] == "chaos"
import jax
jax.distributed.initialize(f"localhost:{port}", nproc, pid)
from repro.core.collective import FleetCollective
from repro.core.fleet import FleetPlacement
from repro.core.placement import HostPlacement
from repro.core.preprocess import set_row_group_collective
from repro.service import FleetFrontend, MiningService, serve_fleet_peer

fc = FleetCollective(timeout_s=4.0 if chaos else 30.0)
set_row_group_collective(fc)
svc = MiningService(placement=FleetPlacement(HostPlacement(), collective=fc))
rng = np.random.default_rng(17)
rows = rng.integers(0, 5, size=(360, 5))
delta = rng.integers(0, 5, size=(50, 5))

if pid != 0:
    out = serve_fleet_peer(svc, fc)
    print(json.dumps({"pid": pid, **out}), flush=True)
    if chaos:
        os._exit(0)  # skip the poisoned shutdown barrier
    sys.exit(0)  # clean exit: jax's atexit disconnect keeps p0 healthy

shadow = MiningService(engine="numpy")
front = FleetFrontend(svc, fc, shadow=shadow)
front.append(rows)
r1 = front.mine(tau=18, kmax=3)
if chaos:
    print("READY", flush=True)  # harness kills the peer now
    import time; time.sleep(2.0)
front.append(delta)
r2 = front.mine(tau=18, kmax=3)
risk = front.risk(tau=18, kmax=3)
st = front.stats()
fl = st["resilience"]["fleet"]
if not chaos:
    front.close()
print(json.dumps({
    "pid": 0,
    "r1": sorted([[list(map(int, i)), int(c)] for i, c in r1.result.itemsets]),
    "r2": sorted([[list(map(int, i)), int(c)] for i, c in r2.result.itemsets]),
    "r2_source": r2.source,
    "risk": {k: risk[k] for k in ("records_at_risk", "max_risk", "qi_total")},
    "degraded": fl["degraded"],
    "reason": fl["degraded_reason"],
    "rounds": fl["collective"]["rounds"],
}), flush=True)
if chaos:
    # skip the jax.distributed atexit shutdown barrier: with the peer
    # killed it can only fail fatally; output is flushed above
    os._exit(0)
"""

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _spawn(pid: int, port: int, mode: str = "") -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.Popen(
        [sys.executable, "-c", _WORKER, str(pid), "2", str(port), _SRC, mode],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        env=env,
        text=True,
    )


def _single_process_baseline():
    rng = np.random.default_rng(17)
    rows = rng.integers(0, 5, size=(360, 5))
    delta = rng.integers(0, 5, size=(50, 5))
    svc = MiningService(engine="numpy")
    svc.append(rows)
    b1 = svc.mine(tau=18, kmax=3)
    svc.append(delta)
    b2 = svc.mine(tau=18, kmax=3)
    bk = svc.risk(tau=18, kmax=3)
    fmt = lambda r: sorted(
        [[list(map(int, i)), int(c)] for i, c in r.result.itemsets]
    )
    return fmt(b1), fmt(b2), bk


@pytest.mark.slow
def test_two_process_fleet_bit_identical_to_single():
    port = _free_port()
    procs = [_spawn(p, port) for p in range(2)]
    outs = []
    for p in procs:
        so, se = p.communicate(timeout=300)
        assert p.returncode == 0, se[-3000:]
        outs.append(json.loads(so.strip().splitlines()[-1]))
    o0 = next(o for o in outs if o["pid"] == 0)
    o1 = next(o for o in outs if o["pid"] == 1)
    base1, base2, bk = _single_process_baseline()
    assert o0["r1"] == base1
    assert o0["r2"] == base2
    assert o0["r2_source"] == "incremental"
    assert o0["risk"] == {
        k: bk[k] for k in ("records_at_risk", "max_risk", "qi_total")
    }
    assert o0["degraded"] is False
    assert o1["reason"] == "shutdown" and o1["executed"] == 5


@pytest.mark.slow
def test_two_process_peer_kill_degrades_to_shadow():
    port = _free_port()
    p0 = _spawn(0, port, "chaos")
    p1 = _spawn(1, port, "chaos")
    while True:
        line = p0.stdout.readline()
        if not line or line.startswith("READY"):
            break
    assert line.startswith("READY"), "frontend never reached READY"
    p1.kill()
    so, se = p0.communicate(timeout=300)
    p1.wait()
    assert p0.returncode == 0, se[-3000:]
    out = json.loads(so.strip().splitlines()[-1])
    assert out["degraded"] is True
    assert "FleetTimeout" in out["reason"]
    base1, base2, bk = _single_process_baseline()
    assert out["r1"] == base1  # mined by the healthy fleet
    assert out["r2"] == base2  # mined by the shadow after degradation
    assert out["risk"] == {
        k: bk[k] for k in ("records_at_risk", "max_risk", "qi_total")
    }
