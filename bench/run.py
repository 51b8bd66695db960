#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload poker_1m.tau_sweep --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``). With ``--trace 0`` the result carries the
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and the result carries the per-layer metrics, each read by
``bench/metrics/<metric>.py``. The numbers that decide ``correct`` follow on
standard error, each beside its limit, and in the result's ``check`` key.

Exits 2 with no result when there is no TPU, too few chips, the Pallas
kernels would run interpreted, or the program is not beside the benchmark.
JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("approx",), default=None,
                    help="send every /mine in the program's sampled mode: the "
                         "control run that the answer check has to fail")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"FAIL: the program (src/repro) is not beside {BENCH.name}/", file=sys.stderr)
        return 2
    # one fixed directory inside the checkout: the path is part of the key
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness

    try:
        cell = harness.load_cell(args.workload)
        result = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, control=args.control,
        )
    except harness.SetupError as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    check = result.pop("check")
    for name, c in check.items():
        print(f"check {name}: {c['value']} limit {c['limit']}", file=sys.stderr)
    result["check"] = check
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
