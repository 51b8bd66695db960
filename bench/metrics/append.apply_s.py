"""Store (``service/store.py``): seconds per answer the service spends
applying an appended block, the summed durations of the program's
``service.append`` spans (the block itemized into the store's bitsets; the
``store.append`` span inside it says whether the width grew)."""


def read(run):
    answers = [[run.spans[t] for t in a.trace_ids if t in run.spans] for a in run.answers]
    answers = [a for a in answers if a]
    spans = [s for a in answers for spans in a for s in spans
             if s["name"] == "service.append" and s["t1"] is not None]
    if not spans:
        return None
    return sum(s["t1"] - s["t0"] for s in spans) / len(answers)
