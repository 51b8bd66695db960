"""Incremental (``service/incremental.py``): seconds per answer in which
the incremental mine blocks on a device value, the summed durations of the
``device.wait`` spans under ``mine.incremental`` (``site`` ``born_counts``:
the delta-born counts; ``expand_counts``: a seed expansion wave)."""


def _under(span, by_id, name):
    parent = by_id.get(span["parent_id"])
    while parent is not None:
        if parent["name"] == name:
            return True
        parent = by_id.get(parent["parent_id"])
    return False


def read(run):
    answers = [[run.spans[t] for t in a.trace_ids if t in run.spans] for a in run.answers]
    answers = [a for a in answers if a]
    waits = []
    for a in answers:
        for spans in a:
            by_id = {s["span_id"]: s for s in spans}
            waits += [s["t1"] - s["t0"] for s in spans
                      if s["name"] == "device.wait" and s["t1"] is not None
                      and _under(s, by_id, "mine.incremental")]
    if not waits:
        return None
    return sum(waits) / len(answers)
