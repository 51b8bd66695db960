"""Reductions from the profiler's trace and the program's spans to numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. Device operations are the events of
the ``XLA Ops`` line of each ``/device:`` plane. Times are put on the host's
``perf_counter`` clock through the window's own annotation on the host
plane, which opens when the window's clock starts.
"""

from __future__ import annotations

import glob
import os

DEVICE_LINE = "XLA Ops"


def _xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, found {len(files)}")
    return files[0]


def device_ops(trace_dir: str, window: tuple, annotation: str) -> list:
    """``[(name, start, end)]`` of every device operation, in seconds on the
    host clock, from the trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_xplane(trace_dir))
    anchor = None
    device_planes = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == annotation:
                        anchor = ev.start_ns
    if anchor is None:
        raise RuntimeError(f"no {annotation!r} annotation in the trace")
    if not device_planes:
        raise RuntimeError("the trace holds no device plane")
    w0 = window[0]
    ops = []
    for plane in device_planes:
        for line in plane.lines:
            if line.name != DEVICE_LINE:
                continue
            for ev in line.events:
                start = w0 + (ev.start_ns - anchor) / 1e9
                ops.append((ev.name, start, start + ev.duration_ns / 1e9))
    ops.sort(key=lambda o: o[1])
    return ops


def op_name(event_name: str) -> str:
    """``"%copy_bitcast_fusion.2 = u32[...] fusion(...)"`` -> ``"copy_bitcast_fusion"``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def union(intervals: list, window: tuple | None = None) -> list:
    """Sorted, merged ``[(start, end)]``, clipped to ``window``."""
    out = []
    for a, b in sorted(intervals):
        if window is not None:
            a, b = max(a, window[0]), min(b, window[1])
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_seconds(ops: list, window: tuple | None = None) -> float:
    """Seconds in which some operation ran on the device."""
    return sum(b - a for a, b in union([(s, e) for _, s, e in ops], window))


def self_time(spans: list, name: str) -> float:
    """Sum over spans called ``name`` of their duration less the part their
    direct children cover."""
    kids: dict = {}
    for s in spans:
        kids.setdefault(s["parent_id"], []).append(s)
    total = 0.0
    for s in spans:
        if s["name"] != name or s["t1"] is None:
            continue
        covered = union([(c["t0"], c["t1"]) for c in kids.get(s["span_id"], []) if c["t1"]],
                        (s["t0"], s["t1"]))
        total += (s["t1"] - s["t0"]) - sum(b - a for a, b in covered)
    return total


def breakdown(ops: list, spans: dict, window: tuple, top: int = 10) -> dict:
    """The device operations that took most time, and the device's idle
    time by the innermost program span open on the host at each gap's
    midpoint (``client`` where none is: the HTTP client and the harness)."""
    per_op: dict = {}
    for name, s, e in ops:
        key = op_name(name)
        per_op[key] = per_op.get(key, 0.0) + (e - s)
    flat = [s for trace in spans.values() for s in trace if s["t1"] is not None]
    busy = union([(s, e) for _, s, e in ops], window)
    gaps, t = [], window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < window[1]:
        gaps.append((t, window[1]))
    idle: dict = {}
    for a, b in gaps:
        mid = (a + b) / 2
        open_ = [s for s in flat if s["t0"] <= mid <= s["t1"]]
        name = max(open_, key=lambda s: s["t0"])["name"] if open_ else "client"
        idle[name] = idle.get(name, 0.0) + (b - a)

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": ranked(per_op), "idle_gaps": ranked(idle)}
