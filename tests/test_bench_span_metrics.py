"""The benchmark's readers of the level loop's and the reply's spans
(``bench/metrics``), on a hand-made run record: two answers, one mine each."""

import importlib.util
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"


@pytest.fixture()
def bench_modules(monkeypatch):
    """The benchmark's harness and readers, imported as ``bench/run.py``
    imports them (``bench/`` on the path); its modules leave with the test."""
    before = set(sys.modules)
    monkeypatch.syspath_prepend(str(BENCH))
    spec = importlib.util.spec_from_file_location("bench_harness", BENCH / "harness.py")
    harness = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, harness)
    spec.loader.exec_module(harness)

    def reader(metric):
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric}", BENCH / "metrics" / f"{metric}.py"
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    yield harness, reader
    for name in set(sys.modules) - before:
        if (getattr(sys.modules[name], "__file__", None) or "").startswith(str(BENCH)):
            del sys.modules[name]


def _span(sid, parent, name, t0, t1):
    return {"name": name, "t0": t0, "t1": t1, "span_id": sid, "parent_id": parent}


def _answer_spans(off):
    """One /mine trace, times from ``off``: a level with two device waits, a
    classify and an emission (with a nested compile); the reply's three
    children under http.respond."""
    return [
        _span("r", None, "http /mine", off + 0.0, off + 10.0),
        _span("l", "r", "mine.level", off + 0.0, off + 5.0),
        _span("w1", "l", "device.wait", off + 0.5, off + 0.75),
        _span("c", "l", "level.classify", off + 1.0, off + 1.5),
        _span("w2", "l", "device.wait", off + 1.5, off + 2.0),
        _span("e", "l", "level.emit", off + 2.0, off + 3.0),
        _span("j", "e", "jax.compile", off + 2.25, off + 2.5),
        _span("h", "r", "http.respond", off + 5.0, off + 10.0),
        _span("d", "h", "http.decode", off + 5.0, off + 7.0),
        _span("n", "h", "http.encode", off + 7.0, off + 8.5),
        _span("o", "h", "http.write", off + 8.5, off + 9.0),
    ]


def _record(harness, spans):
    answers = [harness.Answer(requests=[], t_send=0.0, trace_ids=[t]) for t in ("a", "b")]
    return harness.RunRecord(answers=answers, spans=spans, window=(0.0, 40.0), n_words=0)


@pytest.mark.parametrize(
    "metric,per_answer",
    [
        ("device.wait_s", 0.75),
        ("level.emit_s", 0.75),  # the compile under it is not emission
        ("http.decode_s", 2.0),
        ("http.encode_s", 1.5),
        ("http.write_s", 0.5),
    ],
)
def test_span_reader_on_a_hand_made_run(bench_modules, metric, per_answer):
    harness, reader = bench_modules
    read = reader(metric)
    spans = {"a": _answer_spans(0.0), "b": _answer_spans(20.0)}
    assert read(_record(harness, spans)) == pytest.approx(per_answer)
    # a program without the span (the parent of this change) reads nothing
    bare = {t: [s for s in v if s["name"] != metric[:-2]] for t, v in spans.items()}
    assert read(_record(harness, bare)) is None
    assert read(_record(harness, {})) is None
