"""Whole runs of the append cell on the CPU.

A tiny copy of ``poker_1m_appends`` (1,500 dealt hands, batches of 100,
τ = 12, where delta-born itemsets, promotions and a width growth all occur)
runs through the harness with its look for a chip skipped: sound, every
answer is incremental and equal to the per-version reference. Each fault
the device counts can have comes out not ``correct``: counts read on the
base version's resident bitsets (the appended rows missed), half of each
intersect batch left out, or no counts at all. So does the control, every
``/mine`` sampled (on 8,000 hands, where the sampler keeps a part of the
table). The metric readers of the cell are checked on a hand-made run
record.
"""

import json
import time

import harness

CELL = "poker_1m_appends.append_mine"


def _tiny_cell(batches=6, rows=1500, tau=12):
    cell = harness.load_cell(CELL)
    # the jnp engine runs the same service, placement and LevelPipeline as
    # the Pallas kernels, without interpreting them
    cell.config.update(rows=rows, tau=tau, delta_rows=100, batches=batches, engine="jnp")
    return cell


def _run(cell, seed=2**33 + 5, **kw):
    return harness.run_cell(cell, seed, 120.0, False, t_start=time.perf_counter(),
                            require_chip=False, log=lambda msg: None, **kw)


def test_append_feed_is_correct_and_incremental():
    out = _run(_tiny_cell())
    assert out["correct"], out
    assert out["attempted"] == 6 and out["failed"] == 0
    assert out["check"]["answers_checked"] == {"value": 6, "limit": 6}
    assert out["check"]["itemsets_wrong"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"answer_s", "setup_s"}


def test_device_counts_on_the_base_version_fail(monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    from repro.service import incremental

    delta_bits_of, device_supports = incremental._delta_bits_of, incremental._device_supports
    base = {}

    def delta_bits(table, base_rows, *args):
        base["rows"] = base_rows
        return delta_bits_of(table, base_rows, *args)

    def on_base_version(cands, *, resident, **kw):
        # the resident bitsets as the base version held them: the rows
        # appended since are missing from every full-table count
        rows, bits = base["rows"], np.array(resident)
        bits[:, rows // 32] &= np.uint32((1 << (rows % 32)) - 1)
        bits[:, rows // 32 + 1 :] = 0
        return device_supports(cands, resident=jnp.asarray(bits), **kw)

    monkeypatch.setattr(incremental, "_delta_bits_of", delta_bits)
    monkeypatch.setattr(incremental, "_device_supports", on_base_version)
    out = _run(_tiny_cell())
    assert not out["correct"]
    assert out["check"]["itemsets_wrong"]["value"] > 0


def test_half_of_each_batch_left_out_fails(monkeypatch):
    from repro.kernels.intersect import ops
    from repro.service import incremental

    dispatch, device_supports = ops.LevelPipeline._dispatch, incremental._device_supports

    def half(self, padded, write_children):
        child, cnt, cls = dispatch(self, padded, write_children)
        return child, cnt.at[int(cnt.shape[0]) // 2 :].set(0), cls

    def halved(cands, **kw):
        # only the incremental path's counts: the set-up's cold mine is sound
        with monkeypatch.context() as m:
            m.setattr(ops.LevelPipeline, "_dispatch", half)
            return device_supports(cands, **kw)

    monkeypatch.setattr(incremental, "_device_supports", halved)
    out = _run(_tiny_cell())
    assert not out["correct"] and out["check"]["itemsets_wrong"]["value"] > 0


def test_device_counts_left_out_fail(monkeypatch):
    import numpy as np

    from repro.service import incremental

    def none(cands, **kw):
        return {k: np.zeros(len(m), dtype=np.int64) for k, m in cands.items()}

    monkeypatch.setattr(incremental, "_device_supports", none)
    out = _run(_tiny_cell())
    assert not out["correct"] and out["check"]["itemsets_wrong"]["value"] > 0


def test_control_sampled_answers_fail():
    out = _run(_tiny_cell(rows=8000, tau=64), control="approx")
    assert not out["correct"]
    assert out["check"]["itemsets_wrong"]["value"] > 0


class _Answer:
    def __init__(self, trace_ids, info):
        self.trace_ids = trace_ids
        self.requests = [{"route": "/append"}, {"route": "/mine"}]
        self.replies = [b"{}", json.dumps({"info": info}).encode()]


def _span(name, t0, t1, sid, parent=None):
    return {"name": name, "t0": t0, "t1": t1, "span_id": sid, "parent_id": parent}


def _record():
    info = {"born_rows_read": 8, "born_children_written": 4, "born_pairs_counted": 16,
            "recount_rows_read": 2, "recount_children_written": 1,
            "recount_pairs_counted": 4, "device_words": 256}
    spans = {
        "a1": [_span("http /append", 0.0, 1.0, "1"),
               _span("service.append", 0.2, 0.7, "2", "1"),
               _span("store.append", 0.3, 0.6, "3", "2")],
        "m1": [_span("http /mine", 1.0, 5.0, "4"),
               _span("mine.incremental", 1.5, 4.5, "5", "4"),
               _span("incremental.delta_born", 2.0, 4.0, "6", "5"),
               _span("device.wait", 3.0, 3.5, "7", "6"),
               _span("device.wait", 4.6, 4.7, "8", "4")],
    }
    ops = [("%intersect_count_indexed.1 = u32[] custom-call()", 10.0, 10.25),
           ("%fusion.2 = u32[] fusion()", 10.25, 11.0)]
    return harness.RunRecord(answers=[_Answer(["a1", "m1"], info)] * 2, spans=spans,
                             window=(0.0, 40.0), n_words=0, device_ops=ops,
                             device_kind="TPU v5 lite")


def test_append_cell_readers():
    from pathlib import Path

    metrics = Path(harness.BENCH) / "metrics"
    read = {name: harness.load_module(metrics / f"{name}.py").read
            for name in ("append.apply_s", "incremental.born_s", "incremental.wait_s",
                         "incremental.count_device_s", "incremental.count_roofline")}
    run = _record()
    assert abs(read["append.apply_s"](run) - 0.5) < 1e-9
    assert abs(read["incremental.born_s"](run) - 1.5) < 1e-9
    # the wait outside mine.incremental is not the incremental path's
    assert abs(read["incremental.wait_s"](run) - 0.5) < 1e-9
    assert abs(read["incremental.count_device_s"](run) - 0.125) < 1e-9
    # (10 rows read + 5 written) x 1 KiB, twice, over 0.25 s at 819 GB/s
    want = 100.0 * 2 * 15 * 1024 / (0.25 * 819e9)
    assert abs(read["incremental.count_roofline"](run) - want) < 1e-12


def test_append_cell_readers_are_silent_without_the_spans():
    from pathlib import Path

    metrics = Path(harness.BENCH) / "metrics"
    run = harness.RunRecord(answers=[_Answer(["m1"], {})], spans={"m1": []},
                            window=(0.0, 1.0), n_words=0, device_ops=[],
                            device_kind="TPU v5 lite")
    for name in ("append.apply_s", "incremental.born_s", "incremental.wait_s",
                 "incremental.count_device_s", "incremental.count_roofline"):
        assert harness.load_module(metrics / f"{name}.py").read(run) is None, name
