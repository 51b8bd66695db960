"""Kernels (``kernels/intersect``): percent of the HBM bandwidth peak the
intersect kernels reach in an append cell, on the least bytes the
recount's and the delta-born pool's device counts make them move over the
kernels' device time.

Each incremental ``/mine`` reply's ``info`` says how the counts ran, for
the delta-born pool (``born_*``) and the recount (``recount_*``, absent
where the program recounts on the host): the distinct operand rows each
dispatch reads (``*_rows_read``, summed over dispatches), the prefix
children written (``*_children_written``), the intersections dispatched
(``*_pairs_counted``) and the width of the resident rows (``device_words``
32-bit words). Every operand row read once and every child written once is
the least any kernel that reads those rows can move, so the share cannot
pass 100%; ``per_pair`` (both operands of every intersection read,
children written) is logged beside it. Seed expansions run the same
kernels and are left out of the bytes, so they can only lower the share."""

import json
import sys

from intersect_ops import kernel_seconds
from peaks import peak


def count_bytes(info: dict) -> dict:
    """Bytes of one answer's device counts, from its reply's ``info``."""
    row = 4 * int(info.get("device_words", 0))

    def total(key):
        return sum(int(info.get(f"{part}_{key}", 0)) for part in ("born", "recount"))

    written = total("children_written")
    return {
        "lower_bound": (total("rows_read") + written) * row,
        "per_pair": (2 * total("pairs_counted") + written) * row,
    }


def read(run):
    seconds = kernel_seconds(run.device_ops, run.window)
    infos = [json.loads(raw).get("info", {})
             for a in run.answers for req, raw in zip(a.requests, a.replies)
             if req["route"] == "/mine"]
    moved = [count_bytes(i) for i in infos if "born_rows_read" in i]
    if not seconds or not moved:
        return None
    print(json.dumps({"count_bytes_per_answer": {
        key: sum(m[key] for m in moved) / len(moved) for key in ("lower_bound", "per_pair")}}),
        file=sys.stderr, flush=True)
    total = sum(m["lower_bound"] for m in moved)
    return 100.0 * total / (seconds * peak(run.device_kind)["hbm_bytes_per_s"])
