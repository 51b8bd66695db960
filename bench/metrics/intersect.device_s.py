"""Kernels (``kernels/intersect``): device seconds of the intersect kernels
per mine, summed over their events in the profiler's trace."""

from intersect_ops import kernel_seconds


def read(run):
    mines = sum(len(a.stats) for a in run.answers)
    seconds = kernel_seconds(run.device_ops, run.window)
    if not mines or not seconds:
        return None
    return seconds / mines
