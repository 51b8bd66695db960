"""Which device operations are the intersect kernels, by the names they
carry in the profiler's trace."""

from __future__ import annotations

from trace_reduce import op_name, union

# The trace names a device operation by its HLO instruction,
# "%intersect_classify_count_indexed.1 = (...) custom-call(...)": the
# Pallas kernels of kernels/intersect are the instructions whose name starts
# so (intersect_{,classify_}{write,count}_{indexed,gathered}).
KERNEL_PREFIX = "intersect_"


def is_kernel(name: str) -> bool:
    return op_name(name).startswith(KERNEL_PREFIX)


def kernel_seconds(ops: list, window: tuple) -> float:
    return sum(b - a for a, b in union([(s, e) for n, s, e in ops if is_kernel(n)], window))
