"""From the profiler's ``.xplane.pb`` to intervals, and from intervals
to numbers.  ``load`` needs jax (``jax.profiler.ProfileData``); every
reduction below works on the plain structure it returns, so the tests
build that structure by hand:

    {"planes": [{"name": str, "lines": {line name: [(op name, start_ns,
                                                      duration_ns), ...]}}]}
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return {"planes": []}
    data = ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events)
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(data: dict) -> list:
    return [p for p in data["planes"] if DEVICE_PLANE.match(p["name"])]


def union_ns(intervals) -> int:
    """Total length of the union of (start, duration) intervals."""
    total, end = 0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def span_ns(intervals):
    """(first start, last end) or None."""
    iv = list(intervals)
    if not iv:
        return None
    return min(s for s, _ in iv), max(s + d for s, d in iv)


def ops(plane: dict, line: str = OPS_LINE) -> list:
    return plane["lines"].get(line, [])


def busy_and_window(data: dict):
    """(busy seconds, window seconds) averaged over the device planes:
    busy is the union of the intervals in which an operation ran, the
    window runs from the first operation's start to the last one's end
    on that plane.  (0.0, 0.0) when no device plane has an operation."""
    busy, window, n = 0.0, 0.0, 0
    for p in device_planes(data):
        iv = [(s, d) for _, s, d in ops(p)]
        sp = span_ns(iv)
        if sp is None:
            continue
        busy += union_ns(iv) / 1e9
        window += (sp[1] - sp[0]) / 1e9
        n += 1
    return (busy / n, window / n) if n else (0.0, 0.0)


def matching_seconds(data: dict, pattern: str, line: str = OPS_LINE):
    """(seconds, events) of the events whose name matches ``pattern`` on
    ``line``, averaged over the device planes that have the line."""
    rx = re.compile(pattern)
    tot, cnt, n = 0.0, 0, 0
    for p in device_planes(data):
        if line not in p["lines"]:
            continue
        n += 1
        for name, _s, d in p["lines"][line]:
            if rx.search(name):
                tot += d / 1e9
                cnt += 1
    return (tot / n, cnt // n) if n else (0.0, 0)


def durations_ms(data: dict, pattern: str, line: str) -> list:
    rx = re.compile(pattern)
    return [d / 1e6 for p in device_planes(data)
            for name, _s, d in p["lines"].get(line, []) if rx.search(name)]


_HLO = re.compile(r"^%?([A-Za-z_\-]+)[.\d]* = \(?([a-z0-9]+\[[\d,]*\])")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """An op's name in the trace is its whole HLO instruction; keep the
    instruction's base name (numbering dropped, so the same op of every
    layer falls together), its first result shape and, of a custom
    call, the target."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    t = _TARGET.search(name)
    return " ".join(filter(None, [m.group(1), m.group(2),
                                  t.group(1) if t else None]))


def top_ops(data: dict, k: int = 10) -> list:
    """[[short name, seconds]] of the device operations with most time
    (first device plane that has any)."""
    for p in device_planes(data):
        tot = {}
        for name, _s, d in ops(p):
            name = short_name(name)
            tot[name] = tot.get(name, 0) + d
        if tot:
            best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
            return [[n, d / 1e9] for n, d in best]
    return []


def longest_gaps(data: dict, k: int = 5) -> list:
    """[[label, seconds]] of the longest idle gaps on the first device
    plane.  Engine spans are not on the profiler's clock yet, so every
    gap is ``unattributed`` (what ran before and after is named)."""
    for p in device_planes(data):
        evs = sorted((s, d, n) for n, s, d in ops(p))
        if not evs:
            continue
        gaps, end, last = [], None, None
        for s, d, n in evs:
            if end is not None and s > end:
                gaps.append((s - end, last, n))
            if end is None or s + d > end:
                end, last = s + d, n
        gaps.sort(key=lambda g: -g[0])
        return [[f"unattributed:{short_name(a)}>{short_name(b)}", g / 1e9]
                for g, a, b in gaps[:k]]
    return []


def describe(data: dict, k: int = 25) -> str:
    """What a trace holds, for a reader who writes a regex against it."""
    out = []
    for p in data["planes"]:
        out.append(f"plane {p['name']!r}")
        for line, evs in p["lines"].items():
            tot = {}
            for n, _s, d in evs:
                c = tot.setdefault(n, [0, 0])
                c[0] += d
                c[1] += 1
            out.append(f"  line {line!r}: {len(evs)} events, "
                       f"{len(tot)} names")
            for n, (d, c) in sorted(tot.items(), key=lambda kv: -kv[1][0])[:k]:
                out.append(f"    {d / 1e6:12.3f} ms {c:7d} x  {n[:140]}")
    return "\n".join(out)
