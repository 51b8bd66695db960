"""Incremental (``service/incremental.py``): self time of the program's
``incremental.delta_born`` spans per answer: the host's enumeration of the
appended rows' candidate itemsets, the dispatch of their counts, their
delta counts and the minimality filter, less the ``device.wait`` spans in
which the host fetches the counts."""

from trace_reduce import self_time


def read(run):
    answers = [[run.spans[t] for t in a.trace_ids if t in run.spans] for a in run.answers]
    answers = [a for a in answers if a]
    if not any(s["name"] == "incremental.delta_born"
               for a in answers for spans in a for s in spans):
        return None
    return sum(self_time(spans, "incremental.delta_born")
               for a in answers for spans in a) / len(answers)
