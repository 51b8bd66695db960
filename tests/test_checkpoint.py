"""Fault tolerance: checkpoint manager semantics + mining resume."""

import os

import numpy as np
import pytest

from repro.core import KyivConfig, itemize, mine, preprocess
from repro.core.kyiv import mine_preprocessed
from repro.service.checkpoint import CheckpointManager, load_pytree, save_pytree


def test_pytree_roundtrip(tmp_path):
    tree = {
        "params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
        "step": 7,
        "lst": [np.ones(3), 2.5],
        "tup": (1, np.zeros(2, np.int64)),
        "name": "adamw",
    }
    p = str(tmp_path / "ck")
    save_pytree(p, tree, {"note": "x"})
    restored, meta = load_pytree(p)
    assert meta["note"] == "x"
    assert np.array_equal(restored["params"]["w"], tree["params"]["w"])
    assert isinstance(restored["lst"], list) and restored["lst"][1] == 2.5
    assert isinstance(restored["tup"], tuple) and restored["tup"][0] == 1
    assert restored["tup"][1].dtype == np.int64
    assert restored["name"] == "adamw"
    assert restored["step"] == 7


def test_corruption_detected(tmp_path):
    p = str(tmp_path / "ck")
    save_pytree(p, {"w": np.ones(4)})
    # flip a byte in the array payload
    npz = os.path.join(p, "arrays.npz")
    data = bytearray(open(npz, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(npz, "wb").write(bytes(data))
    with pytest.raises(Exception):
        load_pytree(p)


def test_manager_retention_and_async(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    t = {"x": np.ones(3)}
    cm.save(1, t, blocking=False)
    cm.save(2, t)
    cm.save(5, t)
    cm.wait()
    assert cm.steps() == [2, 5]
    restored, meta = cm.restore()
    assert meta["step"] == 5
    restored2, meta2 = cm.restore(step=2)
    assert meta2["step"] == 2


def test_mining_resume_equivalence():
    """Kill after each level boundary; resume must reproduce the full run."""
    rng = np.random.default_rng(5)
    D = rng.integers(0, 5, size=(100, 7))
    cfg = KyivConfig(tau=2, kmax=4)
    full = mine(D, cfg).canonical_set()
    prep = preprocess(itemize(D), cfg.tau)

    for kill_at in (2, 3):
        saved = {}

        class Stop(Exception):
            pass

        def hook(k, state):
            if k == kill_at:
                saved.update(state)
                raise Stop

        with pytest.raises(Stop):
            mine_preprocessed(prep, cfg, on_level_end=hook)
        resumed = mine_preprocessed(prep, cfg, resume_state=saved).canonical_set()
        assert resumed == full, f"resume at level {kill_at} diverged"


def test_mining_resume_through_disk(tmp_path):
    """Same, but the state round-trips through the checkpoint files
    (simulating a node failure + restart)."""
    from repro.core.prefix import Level
    from repro.core.support import ItemsetIndex

    rng = np.random.default_rng(9)
    D = rng.integers(0, 4, size=(60, 6))
    cfg = KyivConfig(tau=1, kmax=3)
    prep = preprocess(itemize(D), cfg.tau)
    full = mine_preprocessed(prep, cfg).canonical_set()

    cm = CheckpointManager(str(tmp_path))

    class Stop(Exception):
        pass

    def hook(k, state):
        lvl = state["level"]
        cm.save(
            k,
            {
                "itemsets": lvl.itemsets,
                "counts": lvl.counts,
                "bits": lvl.bits,
                "results": [list(ids) for ids, _ in state["results"]],
                "result_counts": np.asarray([c for _, c in state["results"]], np.int64),
                "next_k": state["next_k"],
                "k": lvl.k,
            },
        )
        if k == 2:
            raise Stop

    with pytest.raises(Stop):
        mine_preprocessed(prep, cfg, on_level_end=hook)

    tree, meta = cm.restore()
    lvl = Level(k=int(tree["k"]), itemsets=tree["itemsets"], counts=tree["counts"],
                bits=tree["bits"])
    results = [
        (tuple(int(x) for x in ids), int(c))
        for ids, c in zip(tree["results"], tree["result_counts"])
    ]
    # rebuild grandparent index (level 1 = singletons) for bounds at kmax
    gp = ItemsetIndex(
        np.arange(prep.n_l, dtype=np.int32)[:, None], prep.l_freq, n_symbols=prep.n_l
    )
    state = {
        "results": results,
        "stats": [],
        "level": lvl,
        "grandparent_index": gp,
        "next_k": int(tree["next_k"]),
    }
    resumed = mine_preprocessed(prep, cfg, resume_state=state).canonical_set()
    assert resumed == full
