"""Time of the device operations that came from the ``jax.named_scope``
paths matching ``scope``, as a share of the device's busy time, in %.

The trace names HLO instructions, the scopes are in the compiled
program's metadata: the driver hands ``hlo_scopes`` ({instruction name:
scope path}, from the compiled step's text).  A ``while`` (a scan) is on
the ops line over all of its body's operations, so an event counts its
OWN time only: its duration less that of the events inside it.  An
operation whose instruction is not in the map (another program's, or
one the compiler made without metadata) belongs to no scope.  Nothing to
read without the map."""
import re

import xplane

_NAME = re.compile(r"^%?([\w.\-]+)")


def own_times(events):
    """[(name, own ns)] of (name, start, duration) events that nest."""
    out, stack = [], []              # stack of [end, index into out]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= d
        stack.append([s + d, len(out)])
        out.append([name, d])
    return [(n, max(d, 0)) for n, d in out]


def read(args, src):
    scopes = src.get("hlo_scopes")
    busy, _ = xplane.busy_and_window(src["trace"])
    if not scopes or busy <= 0:
        return None
    rx = re.compile(args["scope"])
    planes = [p for p in xplane.device_planes(src["trace"]) if xplane.ops(p)]
    ns = 0
    for p in planes:
        for name, own in own_times(xplane.ops(p)):
            m = _NAME.match(name)
            if m and rx.search(scopes.get(m.group(1), "")):
                ns += own
    return 100.0 * (ns / len(planes) / 1e9) / busy
