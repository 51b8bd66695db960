"""Kernels (``kernels/intersect``): device seconds of the intersect kernels
per answer, summed over their events in the profiler's trace. The window
of an append cell runs no cold mine, so all of it is the incremental
path's: the delta-born counts and any seed expansion."""

from intersect_ops import kernel_seconds


def read(run):
    seconds = kernel_seconds(run.device_ops, run.window)
    if not run.answers or not seconds:
        return None
    return seconds / len(run.answers)
