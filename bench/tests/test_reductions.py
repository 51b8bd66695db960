"""Trace and span reductions, the byte count and the traffic generator, on
hand-worked inputs."""

import numpy as np

import traffic
from intersect_ops import kernel_seconds
from trace_reduce import breakdown, busy_seconds, self_time, union
from work import intersect_bytes


def test_union_merges_and_clips():
    assert union([(3, 4), (0, 2), (1, 2.5)], (0.5, 3.5)) == [(0.5, 2.5), (3, 3.5)]


def test_busy_and_idle_share_on_overlapping_ops():
    ops = [("a", 0.0, 1.0), ("b", 0.5, 1.5), ("c", 3.0, 4.0)]
    assert busy_seconds(ops, (0.0, 10.0)) == 2.5


def test_kernel_seconds_counts_only_the_intersect_kernels():
    ops = [("%intersect_classify_count_indexed.1 = (s32[8]) custom-call(%p)", 0.0, 0.25),
           ("%slice_reduce_fusion = s32[8] fusion(%intersect_classify_count_indexed.1)", 0.25, 1.0),
           ("%intersect_classify_write_indexed.1 = (u32[4,1,128]) custom-call(%p)", 2.0, 2.5)]
    assert kernel_seconds(ops, (0.0, 10.0)) == 0.75


def test_self_time_subtracts_direct_children():
    spans = [
        {"name": "root", "t0": 0.0, "t1": 10.0, "span_id": "1", "parent_id": None},
        {"name": "frontier.candidates", "t0": 1.0, "t1": 5.0, "span_id": "2", "parent_id": "1"},
        {"name": "x", "t0": 2.0, "t1": 3.0, "span_id": "3", "parent_id": "2"},
        {"name": "frontier.candidates", "t0": 6.0, "t1": 7.0, "span_id": "4", "parent_id": "1"},
    ]
    assert self_time(spans, "frontier.candidates") == 4.0


def test_breakdown_names_ops_and_idle_gaps():
    ops = [("k", 1.0, 2.0), ("k", 5.0, 6.0), ("g", 6.0, 6.5)]
    spans = {"t": [
        {"name": "mine.preprocess", "t0": 2.0, "t1": 5.0, "span_id": "1", "parent_id": None},
    ]}
    out = breakdown(ops, spans, (0.0, 8.0))
    assert out["device_ops"] == [["k", 2.0], ["g", 0.5]]
    assert out["idle_gaps"] == [["mine.preprocess", 3.0], ["client", 2.5]]


def test_intersect_bytes_on_a_hand_worked_mine():
    # level 1 keeps 4 items; level 2 intersects 6 pairs and stores 5; level 3
    # intersects 7 candidates and stores none (k = kmax)
    stats = [
        {"k": 1, "stored": 4, "intersections": 0},
        {"k": 2, "stored": 5, "intersections": 6},
        {"k": 3, "stored": 0, "intersections": 7},
    ]
    out = intersect_bytes(stats, n_words=128)
    row = 512
    assert out["lower_bound"] == (4 + 5) * row + (5 + 0) * row
    assert out["per_pair"] == (2 * 6 + 5) * row + (2 * 7 + 0) * row


def test_sweep_visits_each_tau_once_and_spreads_every_prefix():
    mix = traffic.load_mix("tau_sweep")
    config = {"kmax": 3, "tau_range": [200, 600], "tau_step": 1}
    taus = [a[0]["body"]["tau"] for a in traffic.answers(mix, config, 2**33 + 5, None)]
    assert sorted(taus) == list(range(200, 601))
    for n in (5, 10, 20):
        # a prefix's mean lies near the range's middle whatever the seed
        assert abs(np.mean(taus[:n]) - 400) < 400 / n + 20
    other = [a[0]["body"]["tau"] for a in traffic.answers(mix, config, 7, None)]
    assert other[:10] != taus[:10]
    assert all(a[0]["source"] == "cold" and a[0]["body"]["kmax"] == 3
               for a in traffic.answers(mix, config, 7, None))


def test_sweep_keeps_to_its_grid():
    mix = traffic.load_mix("tau_sweep")
    config = {"kmax": 3, "tau_range": [2000, 4000], "tau_step": 50}
    taus = [a[0]["body"]["tau"] for a in traffic.answers(mix, config, 99, None)]
    assert sorted(taus) == list(range(2000, 4001, 50))


def test_op_name_strips_the_instruction_text():
    from trace_reduce import op_name

    assert op_name("%intersect_classify_count_indexed.1 = (s32[8192,1,128]) custom-call(x)") \
        == "intersect_classify_count_indexed"
    assert op_name("%fusion = u32[4096,31360] fusion(a)") == "fusion"
    assert op_name("jit_body(9784262318788621257)") == "jit_body(9784262318788621257)"


def test_reductions_on_a_trace_recorded_on_the_chip():
    """``data/small_trace``: three calls of the count-only intersect kernel
    and of a small XLA reduction on one TPU v5e chip, inside a 37.7 ms
    ``bench.window`` annotation."""
    import importlib.util
    from pathlib import Path

    from harness import RunRecord
    from intersect_ops import is_kernel
    from trace_reduce import device_ops, op_name

    window = (0.0, 0.03772196899999969)
    ops = device_ops(str(Path(__file__).parent / "data" / "small_trace"), window,
                     "bench.window")
    kernels = [o for o in ops if is_kernel(o[0])]
    assert [op_name(n) for n, _, _ in kernels] == ["intersect_classify_count_indexed"] * 3
    assert sum(op_name(n) == "xor_reduce_fusion" for n, _, _ in ops) == 3
    busy = busy_seconds(ops, window)
    assert 0 < kernel_seconds(ops, window) < busy < window[1]
    path = Path(__file__).parents[1] / "metrics" / "device.idle_share.py"
    spec = importlib.util.spec_from_file_location("idle_share", path)
    idle = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(idle)
    record = RunRecord(answers=[], spans={}, window=window, n_words=0, device_ops=ops)
    assert idle.read(record) == 100.0 * (1 - busy / window[1])
