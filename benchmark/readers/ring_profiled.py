"""One field of the step ring's ``dispatch`` records, split by whether
the record's step stood under the profiler: the ring is captured over
the whole window, the profiler runs for a few seconds in its middle, and
a record's ``index`` is the ``<index>`` of the host span ``engine/step
<index>`` of the iteration the record describes (the tracer's join).  A
record whose index the trace holds is PROFILED, the others are not: the
same program, the same run, the same traffic, with and without the
profiler's hooks on every Python call.

``args``: ``field`` (``host_work_ns``: the work phases of that
iteration, nanoseconds); ``stat``: ``max_unprofiled`` — the largest
value among the unprofiled records, in ms: the longest iteration of the
untraced seconds, which says whether an untraced run stalls;
``median_ratio`` — the median of the profiled records over the median of
the unprofiled.  ``guard_s``: unprofiled records that end less than this
many seconds before the first profiled record starts, or start less
than this after the last one ends, are left out (the profiler starting
and writing its file holds the interpreter; neither state).  Nothing to
read without the field (the parent), without a trace that holds a step,
or with either side empty."""
import re
import statistics

from readers.idle_by_span import HOST_PLANE

STEP = re.compile(r"^engine/step (\d+)$")


def traced_indices(data):
    """The ``<index>`` of every ``engine/step <index>`` on a host plane."""
    out = set()
    for p in data["planes"]:
        if not HOST_PLANE.match(p["name"]):
            continue
        for evs in p["lines"].values():
            for name, _s, _d in evs:
                m = STEP.match(name)
                if m:
                    out.add(int(m.group(1)))
    return out


def split(steps, data, field, guard_s=0.0):
    """(profiled, unprofiled) ``dispatch`` records that carry ``field``."""
    recs = [r for r in steps if r["kind"] == "dispatch" and field in r]
    held = traced_indices(data)
    inside = [r for r in recs if r["index"] in held]
    if not inside:
        return [], []
    guard = int(guard_s * 1e9)
    lo = min(r["start_ns"] for r in inside) - guard
    hi = max(r["end_ns"] for r in inside) + guard
    outside = [r for r in recs if r["index"] not in held
               and (r["end_ns"] <= lo or r["start_ns"] >= hi)]
    return inside, outside


def read(args, src):
    field = args["field"]
    inside, outside = split(src.get("steps") or [], src["trace"], field,
                            float(args.get("guard_s", 0.0)))
    if not inside or not outside:
        return None
    stat = args["stat"]
    if stat == "max_unprofiled":
        return max(r[field] for r in outside) / 1e6
    if stat == "median_ratio":
        base = statistics.median(r[field] for r in outside)
        if base <= 0:
            return None
        return statistics.median(r[field] for r in inside) / base
    raise ValueError(f"ring_profiled: unknown stat {stat!r}")
