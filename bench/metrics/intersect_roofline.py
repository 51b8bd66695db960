"""Kernels (``kernels/intersect``): percent of the HBM bandwidth peak the
intersect kernels reach, on the least bytes a mine's levels make them move
(``bench/work.py``) over their device time. AND + popcount does a few
integer operations per byte read, so bytes bound it."""

from intersect_ops import kernel_seconds
from peaks import peak
from work import intersect_bytes


def read(run):
    seconds = kernel_seconds(run.device_ops, run.window)
    if not seconds:
        return None
    moved = sum(
        intersect_bytes(stats, run.n_words)["lower_bound"]
        for a in run.answers
        for stats in a.stats
    )
    if not moved:
        return None
    return 100.0 * moved / (seconds * peak(run.device_kind)["hbm_bytes_per_s"])
