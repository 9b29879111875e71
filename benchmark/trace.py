"""From a profiler trace (`.xplane.pb`, read with nothing but
`jax.profiler.ProfileData`) to the numbers the per-layer readers use:
device busy time and span, time per device operation, the idle gaps
labelled by what the host was doing, and kernels' summed device time.

Two stages, so that the arithmetic is testable on a recorded trace:
`read_xplane` turns the file into plain lists, `reduce` turns those into
numbers."""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
HOST_SPANS = ("bench.make_feed", "bench.executor_run", "bench.read_loss")
TOP = 10
#: characters of an operation's name kept in the result line
NAME_CHARS = 120


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("the profiler wrote no .xplane.pb under %s"
                                % trace_dir)
    return found[-1]


def read_xplane(path):
    """{"devices": {ordinal: [(name, start_ns, dur_ns)]},
        "host": [(name, start_ns, dur_ns)] of the benchmark's own spans}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.name in HOST_SPANS)
    return {"devices": devices, "host": host}


def union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(s, e, spans):
    best, name = 0.0, "no benchmark span (inside the library or idle host)"
    for n, s2, d2 in spans:
        o = min(e, s2 + d2) - max(s, s2)
        if o > best:
            best, name = o, n
    return name


def self_times(events):
    """{name: seconds in that operation and in none it contains}. The
    ops line nests: a `while` holds every operation of its body, so the
    plain sum of durations counts a loop's work twice."""
    out = {}
    for name, _, _, own in _nested(events):
        out[name] = out.get(name, 0.0) + own
    return out


def _nested(events):
    """[(name, start, duration, self time)], a self time being the
    duration less that of the operations nested directly inside."""
    rows, stack = [], []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            rows[stack[-1][0]][3] -= dur
        stack.append((len(rows), start + dur))
        rows.append([name, start, dur, dur])
    return rows


def short(name):
    """An operation's name as the trace gives it, cut to one line of the
    result: `%fusion.986 = (f32[512,128]{...}, ...) fusion(...)` keeps
    its left-hand side and the start of its shape."""
    return name if len(name) <= NAME_CHARS else name[:NAME_CHARS - 3] + "..."


def reduce(raw, chips):
    """Busy seconds and traced span averaged over the chips used, the
    operations that took most device time (self time: a loop's body is
    counted in its operations, not again in the loop), the longest idle
    gaps by the host span that covered most of each, and every
    operation's summed self time (for the kernel readers)."""
    devices = raw["devices"]
    if not devices:
        raise ValueError("the trace holds no device operation")
    busy = span = 0.0
    by_name, gaps = {}, []
    for ordinal in sorted(devices)[:chips]:
        events = devices[ordinal]
        merged = union((s, s + d) for _, s, d in events)
        busy += sum(e - s for s, e in merged)
        span += merged[-1][1] - merged[0][0]
        for name, d in self_times(events).items():
            by_name[name] = by_name.get(name, 0.0) + d
        if ordinal == min(devices):
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    n = min(chips, len(devices))
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = {}
    for s, e in gaps[:200]:
        name = _overlap(s, e, raw["host"])
        labelled[name] = labelled.get(name, 0.0) + (e - s)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy / n / 1e9,
        "window_s": span / n / 1e9,
        "op_seconds": {k: v / n / 1e9 for k, v in by_name.items()},
        "breakdown": {
            "device_ops": [[short(k), v / n / 1e9] for k, v in top],
            "idle_gaps": [[k, v / 1e9] for k, v in sorted(
                labelled.items(), key=lambda kv: -kv[1])[:TOP]],
        },
    }


def reduce_file(path, chips):
    return reduce(read_xplane(path), chips)
