"""Fused intersect-classify pipeline benchmark: host-classified batch
dispatch vs the device-classified fused pipeline, per engine.

For each engine it mines the same synthetic randomized dataset twice —
``fused_classify=False`` (the pre-fusion baseline: counts come back to the
host and the absent/uniform/infrequent/store masks are re-derived in numpy
per batch) and ``fused_classify=True`` (class codes computed by the engine,
host only gathers) — and records wall time, intersect time, and the
per-level host classification time (``LevelStats.time_classify``, the
component that used to hide inside ``time_total - time_intersect``).

Results are appended to ``BENCH_fused.json`` next to this file (a list of
runs, one per invocation) so the perf trajectory is tracked across PRs.

Default is a container-sized config; ``--full`` selects the paper-scale
synthetic million-row config (FULL["scale_n"][-1] rows — hours on CPU,
intended for real TPU hosts).
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
from repro.data.synth import randomized_dataset  # noqa: E402

try:  # package-relative when run via benchmarks.run
    from .common import FULL, QUICK, Row, emit
except ImportError:  # direct `python benchmarks/bench_fused_pipeline.py`
    from common import FULL, QUICK, Row, emit  # type: ignore

OUT_PATH = os.path.join(os.path.dirname(__file__), "BENCH_fused.json")


def _mine_once(D, engine: str, fused: bool, kmax: int, tau: int) -> dict:
    res = mine(
        D,
        KyivConfig(
            tau=tau,
            kmax=kmax,
            engine=engine,
            fused_classify=fused,
            # pin the host candidate path so this bench keeps isolating
            # classification fusion (device frontier vs host candidate gen
            # is benchmarks/bench_frontier.py's comparison)
            device_frontier=False,
        ),
    )
    return {
        "engine": engine,
        "fused_classify": fused,
        "wall_time": res.wall_time,
        "time_intersect": res.total_intersect_time,
        "time_classify": res.total_classify_time,
        "time_candidates": res.total_candidate_time,
        "per_level_classify": [s.time_classify for s in res.stats],
        # per-level host-busy vs device-busy split (candidate gen + support
        # + classify vs dispatch + sync) — the frontier win at --full scale
        "per_level_timing": res.timing_breakdown(),
        "intersections": res.total_intersections,
        "n_results": len(res.itemsets),
    }


def run(cfg=QUICK, *, engines=("numpy", "jnp", "pallas"), n=None, m=None,
        kmax=None, tau=1, reps=1, full=False) -> tuple[list[Row], dict]:
    n = n or cfg["rand_n"]
    m = m or cfg["rand_m"]
    kmax = kmax or cfg["kmax"]
    D = randomized_dataset(n, m, seed=0)
    # interpret-mode pallas on CPU is a *validation* platform (the grid runs
    # interpreted); time it on a scaled-down dataset so the bench stays
    # runnable off-TPU. On real TPU (--full), pallas gets the full config.
    D_small = randomized_dataset(min(n, 300), min(m, 6), seed=0)
    kmax_small = min(kmax, 3)
    rows: list[Row] = []
    runs: list[dict] = []
    checks: dict[str, int] = {}
    for engine in engines:
        eng_D, eng_kmax = (D, kmax)
        if engine == "pallas" and not full and n > 300:
            eng_D, eng_kmax = D_small, kmax_small
        best: dict[bool, dict] = {}
        for fused in (False, True):
            recs = [_mine_once(eng_D, engine, fused, eng_kmax, tau) for _ in range(reps)]
            rec = min(recs, key=lambda r: r["wall_time"])
            rec["n_effective"] = int(eng_D.shape[0])
            rec["kmax_effective"] = eng_kmax
            best[fused] = rec
            runs.append(rec)
            checks.setdefault(engine, rec["n_results"])
            assert checks[engine] == rec["n_results"], "fused changed the result!"
        base, fus = best[False], best[True]
        speedup = base["time_classify"] / max(fus["time_classify"], 1e-12)
        rows.append(
            Row(
                f"fused/{engine}/classify_time_host", base["time_classify"] * 1e6,
                f"wall={base['wall_time']:.3f}s intersect={base['time_intersect']:.3f}s",
            )
        )
        rows.append(
            Row(
                f"fused/{engine}/classify_time_fused", fus["time_classify"] * 1e6,
                f"wall={fus['wall_time']:.3f}s speedup={speedup:.1f}x",
            )
        )
    meta = {
        "n": n, "m": m, "kmax": kmax, "tau": tau,
        "timestamp": time.time(),
        "platform": platform.platform(),
        "numpy": np.__version__,
    }
    return rows, {"meta": meta, "runs": runs}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale synthetic million-row config")
    ap.add_argument("--engines", default="numpy,jnp,pallas")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--m", type=int, default=None)
    ap.add_argument("--kmax", type=int, default=None)
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--reps", type=int, default=1)
    args = ap.parse_args()
    cfg = FULL if args.full else QUICK
    n = args.n or (cfg["scale_n"][-1] if args.full else None)  # 1M rows on --full
    rows, data = run(
        cfg,
        engines=tuple(args.engines.split(",")),
        n=n, m=args.m, kmax=args.kmax, tau=args.tau, reps=args.reps,
        full=args.full,
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
