"""Mining driver: dataset -> Kyiv -> quasi-identifier report, with optional
multi-device sharding and level checkpointing.

  PYTHONPATH=src python -m repro.launch.mine --dataset randomized --n 2000 \
      --m 10 --tau 1 --kmax 4 --engine numpy
  PYTHONPATH=src python -m repro.launch.mine --fimi path/to/connect.dat ...
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from ..core import KyivConfig, itemize, mine, preprocess
from ..core.kyiv import mine_preprocessed
from ..data.loaders import read_fimi
from ..data.synth import DATASETS
from ..service.checkpoint import CheckpointManager
from .compile_cache import configure_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="randomized", choices=sorted(DATASETS))
    ap.add_argument("--fimi", default=None, help="path to a FIMI-format file")
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--m", type=int, default=10)
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--kmax", type=int, default=3)
    ap.add_argument("--ordering", default="ascending")
    ap.add_argument("--no-bounds", action="store_true")
    ap.add_argument("--engine", default="numpy", choices=["numpy", "jnp", "pallas"])
    ap.add_argument("--no-fused-classify", action="store_true",
                    help="classify on the host (pre-fusion baseline path)")
    ap.add_argument("--sharded", action="store_true", help="shard over local devices")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="write results JSON here")
    args = ap.parse_args()
    configure_compile_cache()

    if args.fimi:
        D = read_fimi(args.fimi)
    else:
        gen = DATASETS[args.dataset]
        if args.dataset == "randomized":
            D = gen(args.n, args.m, seed=args.seed)
        else:
            D = gen(n=args.n, seed=args.seed)

    cfg = KyivConfig(tau=args.tau, kmax=args.kmax, ordering=args.ordering,
                     use_bounds=not args.no_bounds, engine=args.engine,
                     fused_classify=not args.no_fused_classify)
    prep = preprocess(itemize(D), cfg.tau, ordering=cfg.ordering, seed=cfg.seed)

    pipeline_factory = None
    if args.sharded:
        from ..core.sharded import make_sharded_pipeline
        from .mesh import make_host_mesh

        mesh = make_host_mesh()
        pipeline_factory = make_sharded_pipeline(mesh, pair_axes=("data",),
                                                 word_axis="model",
                                                 fused_classify=cfg.fused_classify)
        print(f"sharded over mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    hook = None
    if args.ckpt_dir:
        cm = CheckpointManager(args.ckpt_dir)

        def hook(k, state):
            lvl = state["level"]
            cm.save(k, {"itemsets": lvl.itemsets, "counts": lvl.counts,
                        "bits": lvl.bits, "next_k": state["next_k"]},
                    {"tau": cfg.tau, "kmax": cfg.kmax})

    res = mine_preprocessed(prep, cfg, pipeline_factory=pipeline_factory,
                            on_level_end=hook)

    print(f"dataset {D.shape}, |L| = {prep.n_l}, tau={cfg.tau}, kmax={cfg.kmax}")
    print(f"minimal tau-infrequent itemsets: {len(res.itemsets)}")
    for s in res.stats:
        print(f"  k={s.k}: candidates={s.candidates} B={s.type_b} "
              f"intersections={s.intersections} emitted={s.emitted} "
              f"stored={s.stored} t={s.time_total:.3f}s")
    print(f"wall time {res.wall_time:.3f}s "
          f"(intersect {res.total_intersect_time:.3f}s = "
          f"{res.total_intersect_time / max(res.wall_time, 1e-9):.0%})")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(
                {"itemsets": [{"items": list(ids), "count": c} for ids, c in res.itemsets],
                 "stats": [vars(s) for s in res.stats]},
                f, indent=1, default=str)


if __name__ == "__main__":
    main()
