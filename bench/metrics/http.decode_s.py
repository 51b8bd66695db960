"""HTTP layer (``launch/serve_miner.py``): self time of the program's
``http.decode`` spans under ``http.respond`` (``MineResponse.to_json``,
one Python dict per itemset), per answer."""

from trace_reduce import self_time


def read(run):
    answers = [[run.spans[t] for t in a.trace_ids if t in run.spans] for a in run.answers]
    answers = [a for a in answers if a]
    if not any(s["name"] == "http.decode" for a in answers for spans in a for s in spans):
        return None
    return sum(self_time(spans, "http.decode") for a in answers for spans in a) / len(answers)
