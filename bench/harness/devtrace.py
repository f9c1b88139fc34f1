"""Reduction of a JAX profiler trace to the device's busy time and its gaps.

``extract`` reads an ``.xplane.pb`` into plain lists: the benchmark's own
host annotations (names starting ``bench.``) and, per device plane, the
operations of its ``XLA Ops`` line (named by their HLO instruction), each as
``[name, start_ns, end_ns]`` on the profiler's one clock. ``reduce`` then works on those lists alone:

  * the window is the ``bench.window`` annotation;
  * busy time is the union of a device's operation intervals inside the
    window, averaged over the devices that ran any;
  * ``device_ops``: the operations with the most time inside the window;
  * ``idle_gaps``: the device's idle time inside the window, split by the
    host annotation open during each part of it (``other`` where none was).
"""
from __future__ import annotations

import bisect
import glob
import os

__all__ = ["extract", "find_xplane", "reduce"]

WINDOW = "bench.window"
_OPS_LINE = "XLA Ops"
_TOP = 10


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [[e.name.split(" = ", 1)[0], int(e.start_ns), int(e.end_ns)]
                   for line in plane.lines if line.name == _OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host += [[e.name, int(e.start_ns), int(e.end_ns)]
                     for line in plane.lines for e in line.events
                     if e.name.startswith("bench.")]
    return {"host": host, "devices": devices}


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events: dict) -> dict | None:
    """Busy and window seconds, top operations and attributed gaps; None
    when the trace holds no window or no device operation in it."""
    windows = [(s, e) for n, s, e in events["host"] if n == WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0]
    # the annotations are made one after another on the main thread
    marks = sorted((s, e, n) for n, s, e in events["host"] if n != WINDOW)
    ends = [m[1] for m in marks]

    def attribute(s, e, into):
        """Add [s, e) to ``into`` by the annotations it overlaps."""
        covered = 0
        for ms, me, name in marks[bisect.bisect_right(ends, s):]:
            if ms >= e:
                break
            part = min(e, me) - max(s, ms)
            if part > 0:
                into[name] = into.get(name, 0) + part
                covered += part
        if e - s > covered:
            into["other"] = into.get("other", 0) + (e - s - covered)

    busy, op_time, gap_time = [], {}, {}
    for name in sorted(events["devices"]):
        clipped = [(max(s, w0), min(e, w1), n) for n, s, e in events["devices"][name]
                   if e > w0 and s < w1]
        if not clipped:
            continue
        for s, e, n in clipped:
            op_time[n] = op_time.get(n, 0) + (e - s)
        merged = _union([[s, e] for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                attribute(s, e, gap_time)
    if not busy:
        return None
    n = len(busy)

    def top(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:_TOP]]

    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": top(op_time),
        "idle_gaps": top(gap_time),
    }
