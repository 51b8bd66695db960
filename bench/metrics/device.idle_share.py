"""Device: percent of the window in which no operation ran on the device,
from the profiler's trace."""

from trace_reduce import busy_seconds


def read(run):
    length = run.window[1] - run.window[0]
    if not run.device_ops or length <= 0:
        return None
    return 100.0 * (1.0 - busy_seconds(run.device_ops, run.window) / length)
