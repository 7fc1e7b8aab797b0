"""From a profiler trace (.xplane.pb) to device busy time, top operations and
idle gaps labelled by what the host was doing.

The arithmetic (`reduce_intervals`) works on plain lists, so it is checked on
a hand-built fixture (selftest/xplane_check.py); `read_trace` only pulls those
lists out of jax's ProfileData. Definitions:

  window   the span of the benchmark's "bench:window" TraceAnnotation on the
           host plane (it brackets the measured window exactly);
  busy     per device, the union of the intervals in which an operation ran
           on it (line "XLA Ops"), clipped to the window; `busy_s` is the mean
           over the devices that ran anything;
  top ops  durations summed by program name (line "XLA Modules": today's
           `jit_<function>` names, trailing "(id)" dropped), first device;
  gaps     the complement of busy inside the window on the first device; each
           gap goes to the innermost benchmark span open at its midpoint, and
           the seconds are summed by label.
"""

from __future__ import annotations

import re
from bisect import bisect_right
from pathlib import Path

WINDOW = "bench:window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def newest_xplane(directory) -> Path | None:
    found = sorted(Path(directory).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def union(intervals: list) -> list:
    """Merged, sorted [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce_intervals(devices: dict, window: tuple, host_spans: list, top: int = 10) -> dict:
    """`devices`: {plane name: {"ops": [(name, start_ns, end_ns)], "modules":
    [...]}}; `window`: (start_ns, end_ns); `host_spans`: [(label, start_ns,
    end_ns)] on the same clock."""
    lo, hi = window
    busy = {}
    for name, lines in devices.items():
        merged = union(clip([(s, e) for _, s, e in lines["ops"]], lo, hi))
        if merged:
            busy[name] = merged
    if not busy:
        return {"window_s": (hi - lo) / 1e9, "busy_s": 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": [], "events": 0}
    first = sorted(busy)[0]
    by_name: dict = {}
    named = devices[first]["modules"] or devices[first]["ops"]
    for name, s, e in named:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            key = re.sub(r"\(\d+\)$", "", name)
            by_name[key] = by_name.get(key, 0) + (e - s)
    gaps: dict = {}
    spans = sorted(host_spans, key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    edge = lo
    for s, e in busy[first] + [[hi, hi]]:
        if s > edge:
            mid = (edge + s) // 2
            # innermost = the latest-started span still open at the midpoint
            label = next((spans[k][0] for k in range(bisect_right(starts, mid) - 1, -1, -1)
                          if spans[k][2] > mid), "no benchmark span open")
            gaps[label] = gaps.get(label, 0) + (s - edge)
        edge = max(edge, e)
    rank = lambda d: [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(sum(e - s for s, e in m) for m in busy.values()) / len(busy) / 1e9,
        "devices": len(busy),
        "device_ops": rank(by_name),
        "idle_gaps": rank(gaps),
        "events": sum(len(lines["ops"]) for lines in devices.values()),
    }


def read_trace(path) -> tuple:
    from jax.profiler import ProfileData

    return extract(ProfileData.from_file(str(path)))


def extract(data) -> tuple:
    """(devices, window or None) out of jax's ProfileData, as reduce_intervals
    takes them. Device planes are those named /device:TPU:<n>."""
    devices: dict = {}
    window = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    lines[key] = [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                                  for ev in line.events]
            devices[plane.name] = lines
        elif window is None:
            ev = next((ev for line in plane.lines for ev in line.events if ev.name == WINDOW), None)
            if ev is not None:
                window = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
    return devices, window


def describe(path) -> list:
    """Planes, lines and event counts of a trace, with each line's first event:
    what to look at by hand before trusting the reduction on a new runtime."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            events = list(line.events)
            first = events[0] if events else None
            out.append({
                "plane": plane.name, "line": line.name, "events": len(events),
                "first": None if first is None else [first.name, int(first.start_ns), int(first.duration_ns)],
            })
    return out
