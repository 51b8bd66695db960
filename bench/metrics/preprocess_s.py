"""Preprocess (``core/preprocess.py`` via ``service/api.py``): self time of
the program's ``mine.preprocess`` spans, per mine."""

from trace_reduce import self_time


def read(run):
    mines = [t for a in run.answers for t in a.trace_ids if t in run.spans]
    if not mines:
        return None
    return sum(self_time(run.spans[t], "mine.preprocess") for t in mines) / len(mines)
