#!/usr/bin/env python3
"""Chip smoke: serve a million-row mine from a TPU through the HTTP server.

    python chip_smoke.py             # one chip: DevicePlacement, Pallas kernels
    python chip_smoke.py --chips 4   # 2x2 host: word-sharded MeshPlacement

One process. It imports JAX itself, builds a ``MiningService`` (Pallas
engine, or the 2x2 mesh with ``--chips 4``), serves it through
``serve_miner.make_server`` on a thread and drives it over HTTP:

1. ``/append`` 1,000,000 rows of ``data.synth.poker_like`` (seed 0): the UCI
   Poker Hand shape of paper §5.3.1 — 10 columns, 85 items, 31,250 bitset
   words per item;
2. cold ``/mine``;
3. ``/append`` 1,000 more rows (seed 1), then ``/mine`` — served
   ``incremental``;
4. the same ``/mine`` again — served from ``cache``;
5. ``/risk`` — the record-coverage kernel over the served result.

Every answer is checked against a numpy (``HostPlacement``) mine of the same
rows: itemsets as value sets with counts, per-level stats of the cold mine,
the ``/risk`` summary and per-record coverage counts. The mines run at
``tau=250``, ``kmax=3``: at ``tau=1`` a million poker hands hold no minimal
infrequent itemset up to size 3 (the rarest 3-itemset, one rank on three
cards, occurs about 180 times), so the answer would be empty and the
coverage kernel would have no work. At ``tau=250`` those same-rank triples
are the quasi-identifiers.

The script fails (exit 1, no ``ok`` line) when the backend is not a TPU,
when any answer differs from the reference, when a mine was retried or
degraded to the host, when the circuit breaker is not closed, or when the
placement runs Pallas in interpret mode. Per-phase lines carry wall seconds
— a smoke timing, not a benchmark. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
N_ROWS = 1_000_000
N_COLS = 10
N_APPEND = 1_000
TAU = 250
KMAX = 3


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _http(port: int, method: str, route: str, payload: dict | None = None) -> dict:
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}",
        data=data,
        method=method,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=1200) as resp:
        return json.loads(resp.read())


def _value_sets(itemsets) -> set:
    """HTTP itemsets -> {(((col, value), ...), count)}, order-free."""
    return {
        (tuple(sorted((int(c), int(v)) for c, v in s["items"])), int(s["count"]))
        for s in itemsets
    }


def _reference_sets(result) -> set:
    return {(tuple(sorted(ids)), int(cnt)) for ids, cnt in result.as_value_sets()}


def _stat_tuple(s) -> tuple:
    return (s.k, s.candidates, s.support_pruned, s.bound_pruned,
            s.intersections, s.emitted, s.skipped_absent_uniform, s.stored)


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _word_split(store) -> dict:
    """Where the placed bitset words live: one entry per device holding a
    shard, with the word range it holds."""
    bits = store.device_bits()
    spans = {}
    for shard in bits.addressable_shards:
        words = shard.index[1]
        spans[str(shard.device.id)] = [words.start or 0, words.stop or bits.shape[1]]
    return {"shape": list(bits.shape), "words_by_device": spans}


def run_smoke(service, devices, *, n_rows: int, n_append: int, tau: int, kmax: int,
              cache_dir: str, log=print) -> None:
    """Drive the phases through the HTTP server and check every answer."""
    import numpy as np

    from repro.core import HostPlacement, KyivConfig, mine
    from repro.data.synth import poker_like
    from repro.launch.serve_miner import make_server
    from repro.privacy.risk import risk_profile

    t0 = time.perf_counter()
    base = poker_like(n=n_rows, m=N_COLS, seed=0)
    extra = poker_like(n=n_append, m=N_COLS, seed=1)
    log(f"data: poker_like {base.shape} + {extra.shape} in "
        f"{time.perf_counter() - t0:.2f}s (set-up)")

    refs = {}

    def reference(rows, key):
        if key not in refs:
            t = time.perf_counter()
            refs[key] = mine(rows, KyivConfig(tau=tau, kmax=kmax, engine="numpy"))
            log(f"reference[{key}]: numpy mine, {len(refs[key].itemsets)} itemsets "
                f"in {time.perf_counter() - t:.2f}s")
        return refs[key]

    server = make_server(service, "127.0.0.1", 0)
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        def phase(name, method, route, payload=None):
            t = time.perf_counter()
            out = _http(port, method, route, payload)
            wall = time.perf_counter() - t
            cost = out.get("info", {}).get("cost", {})
            log(json.dumps({
                "phase": name,
                "source": out.get("source"),
                "n_itemsets": out.get("n_itemsets"),
                "qi_total": out.get("qi_total"),
                "smoke_wall_s_not_a_benchmark": wall,
                "executables_compiled": cost.get("executables_compiled"),
                "executables_reused": cost.get("executables_reused"),
                "peak_bytes_in_use": _peak_bytes(devices),
                "compile_cache_dir": cache_dir,
            }))
            return out

        mine_q = {"tau": tau, "kmax": kmax}
        out = phase("append", "POST", "/append", {"rows": base.tolist()})
        _check(out["n_rows"] == n_rows, f"append stored {out['n_rows']} rows")

        out = phase("mine-cold", "POST", "/mine", mine_q)
        _check(out["source"] == "cold", f"first mine served {out['source']!r}")
        ref = reference(base, "base")
        _check(_value_sets(out["itemsets"]) == _reference_sets(ref),
               "cold mine differs from the numpy reference")
        served = service.mine(tau=tau, kmax=kmax).result
        _check([_stat_tuple(s) for s in served.stats] == [_stat_tuple(s) for s in ref.stats],
               "cold mine per-level stats differ from the numpy reference")

        out = phase("append-delta", "POST", "/append", {"rows": extra.tolist()})
        _check(out["n_rows"] == n_rows + n_append, f"append stored {out['n_rows']} rows")
        full = np.concatenate([base, extra], axis=0)
        ref = reference(full, "appended")

        out = phase("mine-incremental", "POST", "/mine", mine_q)
        _check(out["source"] == "incremental", f"mine after append served {out['source']!r}")
        _check(_value_sets(out["itemsets"]) == _reference_sets(ref),
               "incremental mine differs from the numpy reference")

        out = phase("mine-cache", "POST", "/mine", mine_q)
        _check(out["source"] == "cache", f"repeated mine served {out['source']!r}")
        _check(_value_sets(out["itemsets"]) == _reference_sets(ref),
               "cached mine differs from the numpy reference")

        out = phase("risk", "GET", f"/risk?tau={tau}&kmax={kmax}&top=10")
        host_profile = risk_profile(ref, placement=HostPlacement())
        want = host_profile.summary(top=10)
        for field in ("n_rows", "records_at_risk", "qi_total", "top_records", "histogram"):
            _check(out[field] == want[field], f"/risk {field} differs from the numpy reference")
        dev_profile = risk_profile(service.mine(tau=tau, kmax=kmax).result,
                                   placement=service.placement)
        _check(np.array_equal(dev_profile.counts_by_size, host_profile.counts_by_size),
               "per-record coverage counts differ from the numpy reference")
        _check(int(host_profile.counts_by_size.sum()) > 0,
               "the risk phase covered no record: the coverage kernel had no work")

        stats = _http(port, "GET", "/stats")
        res, placed = stats["resilience"], stats["placement"]
        log(json.dumps({"resilience": res, "placement": placed}))
        _check(res["degraded_mines"] == 0, f"{res['degraded_mines']} mines degraded to host")
        _check(res["device_retries"] == 0, f"{res['device_retries']} device retries")
        _check(res["state"] == "closed", f"circuit breaker is {res['state']!r}")
    finally:
        server.shutdown()
        server.server_close()
        thread.join()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the word-sharded 2x2 mesh path instead of one chip")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax

        from repro.core import MeshPlacement
        from repro.launch.compile_cache import configure_compile_cache
        from repro.launch.mesh import mesh_from_spec
        from repro.service import MiningService
    except ImportError as e:
        print(f"FAIL: the miner is not importable next to chip_smoke.py: {e}",
              file=sys.stderr)
        return 1

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"FAIL: no TPU: JAX reports platform {dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"FAIL: --chips {args.chips} but JAX sees {len(devices)} devices",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")

    if args.chips == 4:
        placement = MeshPlacement(
            mesh_from_spec("2x2"), pair_axes=("data",), word_axis="model"
        )
        service = MiningService(placement=placement)
        used = list(placement.mesh.devices.flat)
    else:
        service = MiningService(engine="pallas")
        used = [dev]
    print(json.dumps({"placement": service.placement.describe()}))
    try:
        _check(service.placement.describe().get("interpret") is not True,
               "placement runs Pallas interpreted")
        run_smoke(service, used, n_rows=N_ROWS, n_append=N_APPEND, tau=TAU,
                  kmax=KMAX, cache_dir=cache_dir)
        if args.chips == 4:
            placement = service.placement
            _check(placement.use_device_frontier, "mesh device frontier is off")
            split = _word_split(service.store)
            print(json.dumps({"placed_bits": split, "word_shards": placement.word_shards}))
            _check(len(split["words_by_device"]) == 4,
                   "placed bitsets do not reach all four devices")
            ranges = {tuple(r) for r in split["words_by_device"].values()}
            _check(len(ranges) == placement.word_shards
                   and all(hi - lo < split["shape"][1] for lo, hi in ranges),
                   "bitset words are not split across the word shards")
    except Exception as e:  # noqa: BLE001 — any phase failure fails the smoke
        print(f"FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    finally:
        service.close()

    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
