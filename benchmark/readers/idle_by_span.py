"""The device's idle time by what the host was doing in it, in % of the
traced window: the complement of the operations' union on the first
device plane that has operations (over ``xplane_busy``'s window: first
operation's start to the last one's end), cut by the host events whose
name matches a pattern.

``args``: ``phases`` lists the pattern of every phase the program may
emit; ``pattern`` — idle time inside the host events matching it;
without ``pattern`` — idle time inside no event of any phase.  With no
phase in the trace (a program without these spans) there is nothing to
read, whichever of the two is asked for.  The host planes'
events are on the trace's own time base (``monitor.span`` opens a
``jax.profiler.TraceAnnotation``), all threads' lines taken together;
events of one pattern are merged, so a span nested in another of its
name, or in ``engine/step <index>``, counts once.

``owner`` names the phase under most of one gap, for whoever labels
single gaps."""
import re

import xplane

HOST_PLANE = re.compile(r"^/host:")


def merged(intervals):
    """Sorted disjoint (start, end) from (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap_ns(a, b):
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(data):
    """(disjoint idle (start, end) inside the window, window ns) of the
    first device plane that has operations; ([], 0) without one."""
    for p in xplane.device_planes(data):
        busy = merged((s, s + d) for _, s, d in xplane.ops(p))
        if not busy:
            continue
        gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
        return gaps, busy[-1][1] - busy[0][0]
    return [], 0


def host_spans(data, pattern):
    """Merged (start, end) of the host planes' events matching
    ``pattern``, every line of every host plane."""
    rx = re.compile(pattern)
    return merged((s, s + d) for p in data["planes"]
                  if HOST_PLANE.match(p["name"])
                  for evs in p["lines"].values()
                  for name, s, d in evs if rx.search(name))


def owner(gap, data, phases):
    """The pattern of ``phases`` whose host events cover most of the
    gap ``(start, end)``, or ``"unattributed"`` when none covers any."""
    best, most = "unattributed", 0
    for pattern in phases:
        ns = overlap_ns([gap], host_spans(data, pattern))
        if ns > most:
            best, most = pattern, ns
    return best


def read(args, src):
    data = src["trace"]
    idle, window = idle_intervals(data)
    if window <= 0:
        return None
    under = merged(iv for p in args["phases"] for iv in host_spans(data, p))
    if not under:
        return None
    if "pattern" in args:
        ns = overlap_ns(idle, host_spans(data, args["pattern"]))
    else:
        ns = sum(e - s for s, e in idle) - overlap_ns(idle, under)
    return 100.0 * ns / window
