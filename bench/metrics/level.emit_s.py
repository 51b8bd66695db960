"""Level loop (``core/frontier.py``): self time of the program's
``level.emit`` spans (each batch's emitted itemsets turned into Python
tuples), per mine."""

from trace_reduce import self_time


def read(run):
    mines = [t for a in run.answers for t in a.trace_ids if t in run.spans]
    if not any(s["name"] == "level.emit" for t in mines for s in run.spans[t]):
        return None
    return sum(self_time(run.spans[t], "level.emit") for t in mines) / len(mines)
