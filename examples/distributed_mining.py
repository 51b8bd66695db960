"""Distributed mining end-to-end: the Kyiv level step sharded over an 8-device
mesh (pairs over 'data', bitset words over 'model'), with level checkpointing
and a simulated mid-run failure + elastic restart on a smaller mesh.

  PYTHONPATH=src python examples/distributed_mining.py
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import tempfile

import numpy as np
import jax

from repro.core import KyivConfig, itemize, preprocess
from repro.core.kyiv import mine_preprocessed
from repro.core.sharded import make_sharded_pipeline
from repro.data.synth import randomized_dataset
from repro.service.checkpoint import CheckpointManager


def main() -> None:
    D = randomized_dataset(n=4000, m=9, seed=1)
    cfg = KyivConfig(tau=1, kmax=4)
    prep = preprocess(itemize(D), cfg.tau)

    # --- 8-device run: pairs over data(4), words over model(2) -------------
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    factory = make_sharded_pipeline(mesh, pair_axes=("data",), word_axis="model")
    with tempfile.TemporaryDirectory() as ckdir:
        cm = CheckpointManager(ckdir)

        class SimulatedFailure(Exception):
            pass

        state_store = {}

        def hook(k, state):
            lvl = state["level"]
            cm.save(k, {"itemsets": lvl.itemsets, "counts": lvl.counts,
                        "bits": lvl.bits, "next_k": state["next_k"]})
            state_store[k] = state
            if k == 2:
                raise SimulatedFailure  # "node died" after level 2

        try:
            mine_preprocessed(prep, cfg, pipeline_factory=factory, on_level_end=hook)
        except SimulatedFailure:
            print(f"node failure simulated after level 2 "
                  f"(checkpoints: steps {cm.steps()})")

        # --- elastic restart: resume on a smaller (2, 2) mesh --------------
        mesh2 = jax.make_mesh((2, 2), ("data", "model"))
        factory2 = make_sharded_pipeline(mesh2, pair_axes=("data",), word_axis="model")
        res = mine_preprocessed(prep, cfg, pipeline_factory=factory2,
                                resume_state=state_store[2])
        print(f"resumed on 2x2 mesh -> {len(res.itemsets)} minimal "
              f"tau-infrequent itemsets")

    # cross-check against a fresh sequential run
    seq = mine_preprocessed(prep, cfg)
    assert res.canonical_set() == seq.canonical_set()
    print("distributed + elastic-restart result == sequential result ✓")


if __name__ == "__main__":
    main()
