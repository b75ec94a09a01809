"""As ``xplane_scope_share`` where SEVERAL compiled programs share the
trace (a serving engine runs one a (rows, span) bucket, and two programs
number their instructions alike): the driver hands
``hlo_scopes_by_program``, a list of {instruction name: scope path} maps,
one a program, holding EVERY instruction of the program's text ('' for
one outside the scopes).  Each execution on the modules line is given the
map whose instructions match best what ran inside executions of that
module name (Jaccard; none if under nine tenths of what ran is known to
it), and an operation counts under the scope its own execution's map
gives it.  Own time only (a ``while`` less its body).  Time of the
operations under the scopes matching ``scope``, as a share of the
device's busy time, in %.  Nothing to read without the maps."""
import bisect
import re

import xplane

_NAME = re.compile(r"^%?([\w.\-]+)")


def own_times(events):
    """[(name, start, own ns)] of (name, start, duration) events that
    nest."""
    out, stack = [], []              # stack of [end, index into out]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= d
        stack.append([s + d, len(out)])
        out.append([name, s, d])
    return [(n, s, max(d, 0)) for n, s, d in out]


def scoped_ops(plane, maps):
    """[(scope, own ns)] of the plane's operations that ran inside an
    execution of a module one of ``maps`` describes."""
    mods = sorted((s, s + d, name) for name, s, d in
                  plane["lines"].get(xplane.MODULES_LINE, []))
    starts = [m[0] for m in mods]
    inside = {}                      # module name -> [(instr, own ns)]
    for name, s, own in own_times(xplane.ops(plane)):
        at = bisect.bisect_right(starts, s) - 1
        m = _NAME.match(name)
        if at >= 0 and s < mods[at][1] and m:
            inside.setdefault(mods[at][2], []).append((m.group(1), own))
    out = []
    for ran in inside.values():
        seen = {n for n, _ in ran}
        best = max(maps, key=lambda mp: len(seen & mp.keys())
                   / len(seen | mp.keys()))
        if len(seen & best.keys()) >= 0.9 * len(seen):
            out += [(best.get(n, ""), own) for n, own in ran]
    return out


def scope_seconds(src, scope):
    """Seconds of own time under the scopes matching ``scope``, averaged
    over the device planes; None without maps or operations."""
    maps = src.get("hlo_scopes_by_program")
    planes = [p for p in xplane.device_planes(src["trace"]) if xplane.ops(p)]
    if not maps or not planes:
        return None
    rx = re.compile(scope)
    ns = sum(own for p in planes for sc, own in scoped_ops(p, maps)
             if rx.search(sc))
    return ns / len(planes) / 1e9


def read(args, src):
    busy, _ = xplane.busy_and_window(src["trace"])
    secs = scope_seconds(src, args["scope"])
    if secs is None or busy <= 0:
        return None
    return 100.0 * secs / busy
