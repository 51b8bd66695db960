"""Whole runs on the CPU.

``run.py`` refuses to measure off the chip. Below that, the harness is
driven at a tiny size with its look for a chip skipped, once sound and once
with each fault that a cell of this benchmark can have planted in the timed
path: every fault and the control (the program's sampled ``mode=approx``
answers, which give up exactness) must come out not ``correct``.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import harness

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(cwd / "bench" / "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600,
    )


def _has_result(stdout):
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "correct" in json.loads(lines[-1])
    except ValueError:
        return False


def test_run_refuses_without_a_chip():
    p = _run_py(ROOT, "--workload", "poker_1m.tau_sweep", "--seed", "5",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, "--workload", "poker_1m.tau_sweep", "--seed", "5",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and not _has_result(p.stdout)


def _tiny_cell(rows=1500):
    cell = harness.load_cell("poker_1m.tau_sweep")
    # the jnp engine runs the same service, level loop and LevelPipeline as
    # the Pallas kernels, without interpreting them
    cell.config.update(rows=rows, tau_range=[rows // 75, rows // 25], engine="jnp")
    return cell


def _run(rows=1500, **kw):
    return harness.run_cell(_tiny_cell(rows), 2**33 + 11, 1.0, False,
                            t_start=time.perf_counter(), require_chip=False,
                            log=lambda msg: None, **kw)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"] and out["attempted"] >= 1 and out["failed"] == 0
    assert out["check"]["itemsets_wrong"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"answer_s", "setup_s"}


def test_control_sampled_answers_fail():
    # the sampler keeps about 1,600 rows of a table of 10 columns
    out = _run(rows=8000, control="approx")
    assert not out["correct"]
    assert out["check"]["itemsets_wrong"]["value"] > 0


def test_answer_altered_where_produced_fails(monkeypatch):
    from repro.service.api import MineResponse

    to_json = MineResponse.to_json

    def altered(self, *a, **kw):
        out = to_json(self, *a, **kw)
        if out["itemsets"]:
            out["itemsets"][0]["count"] += 1
        return out

    monkeypatch.setattr(MineResponse, "to_json", altered)
    out = _run()
    assert not out["correct"] and out["check"]["itemsets_wrong"]["value"] > 0


def test_half_of_each_batch_left_out_fails(monkeypatch):
    from repro.kernels.intersect import ops
    from repro.kernels.intersect.ref import CLASS_SKIP

    dispatch = ops.LevelPipeline._dispatch

    def half(self, padded, write_children):
        child, cnt, cls = dispatch(self, padded, write_children)
        m = int(cnt.shape[0]) // 2
        cnt = cnt.at[m:].set(0)
        if cls is not None:
            cls = cls.at[m:].set(CLASS_SKIP)
        return child, cnt, cls

    monkeypatch.setattr(ops.LevelPipeline, "_dispatch", half)
    out = _run()
    assert not out["correct"] and out["check"]["itemsets_wrong"]["value"] > 0


def test_a_reply_from_another_source_fails():
    cell = _tiny_cell()
    cell.mix["answer"][0]["source"] = "cache"
    out = harness.run_cell(cell, 3, 1.0, False, t_start=time.perf_counter(),
                           require_chip=False, log=lambda msg: None)
    assert not out["correct"] and out["failed"] == out["attempted"] > 0


def test_an_append_mix_is_data_alone():
    """The generator and the check already carry an append -> incremental
    mix (section 7 of PERF.md): set-up mines cold, each answer appends rows
    and mines again."""
    cell = _tiny_cell()
    cell.mix = {
        "loop": "closed", "clients": 1,
        "setup": [{"route": "/mine", "body": {"tau": 30, "kmax": "$kmax"}, "source": "cold"}],
        "answer": [
            {"route": "/append", "body": {"rows": "$rows"}},
            {"route": "/mine", "body": {"tau": "$tau", "kmax": "$kmax"}, "source": "incremental"},
        ],
        "draws": {"rows": {"kind": "rows", "size": 40}, "tau": {"kind": "fixed", "value": 30}},
    }
    out = harness.run_cell(cell, 17, 1.0, False, t_start=time.perf_counter(),
                           require_chip=False, log=lambda msg: None)
    assert out["correct"], out
    assert out["check"]["answers_checked"]["value"] == out["attempted"] > 0
