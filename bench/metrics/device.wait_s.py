"""Level loop (``core/frontier.py``): seconds per mine in which the host
blocks on a device value, the summed durations of the program's
``device.wait`` spans (one per fetch: survivor count, partition order,
pairs, counts, bounds-phase survivors, a host batch's result)."""


def read(run):
    mines = [t for a in run.answers for t in a.trace_ids if t in run.spans]
    waits = [s["t1"] - s["t0"] for t in mines for s in run.spans[t]
             if s["name"] == "device.wait" and s["t1"] is not None]
    if not waits:
        return None
    return sum(waits) / len(mines)
