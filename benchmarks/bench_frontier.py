"""Frontier benchmark: host vs device candidate-gen + support-test per level.

Two measurements, both appended to ``BENCH_frontier.json``:

* **level micro-bench** — a synthetic prefix-grouped level table (sized like
  the wide levels of the paper-scale configs) is pushed through one full
  frontier stage per path: the host reference
  (``generate_candidates`` + packed-key ``support_test`` numpy) vs the
  device frontier (``repeat``/``cumsum`` pair gen + packed-key binary
  search + pruned-pair masking, jit-compiled, warmed). This isolates
  exactly the work the tentpole moved off the host.
* **end-to-end** — ``mine()`` on the randomized dataset config with
  ``device_frontier`` on vs off for each device engine, recording
  ``LevelStats.time_candidates`` (candidate gen + support + bounds) and the
  per-level host-busy / device-busy split.

Default is a container-sized config; ``--full`` selects the paper-scale
million-row config (the acceptance target: >=3x faster candidate-gen +
support-test per level on the device path, measured on a real accelerator
host — interpret-mode CPU numbers are recorded for trend only).
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.core import KyivConfig, mine  # noqa: E402
from repro.core.placement import make_placement  # noqa: E402
from repro.core.prefix import iter_group_spans, prefix_group_sizes  # noqa: E402
from repro.data.synth import randomized_dataset  # noqa: E402

try:  # package-relative when run via benchmarks.run
    from .common import FULL, QUICK, Row, emit
except ImportError:  # direct `python benchmarks/bench_frontier.py`
    from common import FULL, QUICK, Row, emit  # type: ignore

OUT_PATH = os.path.join(os.path.dirname(__file__), "BENCH_frontier.json")


def synth_level(t: int, group: int, n_symbols: int, seed: int = 0):
    """A lex-sorted (t, 2) level table of ~``t/group`` prefix groups, the
    shape of a wide level-2 frontier."""
    rng = np.random.default_rng(seed)
    rows = []
    n_prefix = max(1, t // group)
    prefixes = np.sort(rng.choice(n_symbols, size=n_prefix, replace=False))
    for p in prefixes:
        lasts = rng.choice(n_symbols, size=min(group, n_symbols - 1), replace=False)
        lasts = np.sort(lasts[lasts != p])
        for l in lasts:
            rows.append((int(p), int(l)))
    its = np.asarray(sorted(set(rows)), dtype=np.int32)[:t]
    counts = rng.integers(1, 1000, size=its.shape[0]).astype(np.int64)
    return its, counts


def bench_level_stage(t: int, group: int, n_symbols: int, max_pairs: int, reps: int):
    """Time one full candidate-gen + support-test pass over a level."""
    its, counts = synth_level(t, group, n_symbols)
    sizes = prefix_group_sizes(its)
    spans = [s for s in iter_group_spans(sizes, max_pairs) if s[2] > 0]
    n_pairs = sum(s[2] for s in spans)

    host = make_placement("numpy")
    dev = make_placement("jnp")

    def run_host():
        state = host.prepare_frontier(its, counts, n_symbols)
        pruned = 0
        for lo, hi, np_ in spans:
            cand, ok = host.frontier_dispatch(state, lo, hi, np_)
            pruned += int((~ok).sum())
        return pruned

    def run_device():
        state = dev.prepare_frontier(its, counts, n_symbols)
        n_ok_total = 0
        for lo, hi, np_ in spans:
            pairs, ok = dev.frontier_dispatch(state, lo, hi, np_)
            _, n_ok = dev.frontier_mask(state, pairs, ok)
            n_ok_total += int(n_ok)  # block: the host path is synchronous too
        dev.release(state)
        return n_ok_total

    host_pruned = run_host()
    dev_ok = run_device()  # warm the executables before timing
    assert n_pairs - host_pruned == dev_ok, "host/device support verdicts differ!"

    t_host = min(
        (lambda t0=time.perf_counter(): (run_host(), time.perf_counter() - t0)[1])()
        for _ in range(reps)
    )
    t_dev = min(
        (lambda t0=time.perf_counter(): (run_device(), time.perf_counter() - t0)[1])()
        for _ in range(reps)
    )
    return {
        "t": int(its.shape[0]),
        "n_pairs": int(n_pairs),
        "survivors": int(n_pairs - host_pruned),
        "host_s": t_host,
        "device_s": t_dev,
        "speedup": t_host / max(t_dev, 1e-12),
    }


def bench_end_to_end(D, engine: str, kmax: int, tau: int, reps: int = 2):
    out = {}
    for frontier_on in (False, True):
        # warm reps: executables bind through the process-wide cache, so the
        # steady-state (resident-service) cost is the min over repeats —
        # the first rep carries XLA compile time
        runs = [
            mine(
                D,
                KyivConfig(
                    tau=tau, kmax=kmax, engine=engine,
                    device_frontier=frontier_on,
                ),
            )
            for _ in range(max(1, reps))
        ]
        res = min(runs, key=lambda r: r.wall_time)
        out[frontier_on] = {
            "wall_time": res.wall_time,
            "time_candidates": res.total_candidate_time,
            "time_intersect": res.total_intersect_time,
            "per_level_timing": res.timing_breakdown(),
            "n_results": len(res.itemsets),
        }
    assert out[False]["n_results"] == out[True]["n_results"], "frontier changed results!"
    return {
        "engine": engine,
        "host_path": out[False],
        "device_frontier": out[True],
        "candidates_speedup": out[False]["time_candidates"]
        / max(out[True]["time_candidates"], 1e-12),
    }


def run(cfg=QUICK, *, engines=("jnp",), n=None, m=None, kmax=None, tau=1,
        reps=3, level_t=None, full=False):
    n = n or cfg["rand_n"]
    m = m or cfg["rand_m"]
    kmax = kmax or cfg["kmax"]
    # level micro-bench sized to the config: --full mimics the million-row
    # run's wide level (tens of thousands of stored itemsets)
    level_t = level_t or (50_000 if full else 4_000)
    rows: list[Row] = []
    micro = bench_level_stage(
        t=level_t, group=32, n_symbols=max(2 * level_t, 64),
        max_pairs=1 << 22, reps=reps,
    )
    rows.append(Row("frontier/level_stage_host", micro["host_s"] * 1e6,
                    f"pairs={micro['n_pairs']}"))
    rows.append(Row("frontier/level_stage_device", micro["device_s"] * 1e6,
                    f"speedup={micro['speedup']:.2f}x"))

    D = randomized_dataset(n, m, seed=0)
    e2e = []
    for engine in engines:
        r = bench_end_to_end(D, engine, kmax, tau, reps=min(reps, 3))
        e2e.append(r)
        rows.append(
            Row(
                f"frontier/e2e_{engine}_candidates",
                r["device_frontier"]["time_candidates"] * 1e6,
                f"host={r['host_path']['time_candidates']:.3f}s "
                f"speedup={r['candidates_speedup']:.2f}x",
            )
        )
    meta = {
        "n": n, "m": m, "kmax": kmax, "tau": tau, "level_t": level_t,
        "timestamp": time.time(), "platform": platform.platform(),
        "numpy": np.__version__, "full": full,
    }
    return rows, {"meta": meta, "level_stage": micro, "end_to_end": e2e}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale million-row config")
    ap.add_argument("--engines", default="jnp")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--m", type=int, default=None)
    ap.add_argument("--kmax", type=int, default=None)
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--level-t", type=int, default=None,
                    help="synthetic level size for the micro-bench")
    args = ap.parse_args()
    cfg = FULL if args.full else QUICK
    n = args.n or (cfg["scale_n"][-1] if args.full else None)  # 1M rows on --full
    rows, data = run(
        cfg,
        engines=tuple(args.engines.split(",")),
        n=n, m=args.m, kmax=args.kmax, tau=args.tau, reps=args.reps,
        level_t=args.level_t, full=args.full,
    )
    emit(rows)
    history = []
    if os.path.exists(OUT_PATH):
        with open(OUT_PATH) as f:
            history = json.load(f)
    history.append(data)
    with open(OUT_PATH, "w") as f:
        json.dump(history, f, indent=2)
    print(f"wrote {OUT_PATH} ({len(history)} run(s))")


if __name__ == "__main__":
    main()
