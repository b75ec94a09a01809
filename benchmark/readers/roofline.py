"""A kernel's share of its roofline, in %: the least time the chip
could take for the work (``work`` names a function below that counts
one step's from the shapes) over the time of the device operations
whose name matches ``pattern``.  Only whole steps count: the executions
of the modules matching ``step_module`` that lie inside the traced
operations and last as long as the median one (a trace that starts or
stops inside a step holds a part of that step's operations), and of the
operations those that start inside one of them.  ``bound`` says which
peak bounds it."""
import re
import statistics

import flops
import xplane


def flash_attention_train(src):
    """FLOPs of causal attention, forward + backward, in every layer,
    for ONE training step."""
    t = src["traffic"]
    return (src["config"]["num_hidden_layers"]
            * flops.attention_flops(src["config"], int(t["batch"]),
                                    int(t["seq"]), True))


WORK = {"flash_attention_train": flash_attention_train}


def whole_steps(plane, step_module):
    """[(start, duration)] in ns of the plane's whole step executions."""
    span = xplane.span_ns([(s, d) for _, s, d in xplane.ops(plane)])
    rx = re.compile(step_module)
    mods = [(s, d) for name, s, d in plane["lines"].get(xplane.MODULES_LINE, [])
            if rx.search(name)]
    if span is None or not mods:
        return []
    full = statistics.median(d for _, d in mods)
    return [(s, d) for s, d in mods
            if d >= 0.98 * full and s >= span[0] and s + d <= span[1]]


def read(args, src):
    if args["bound"] != "compute":
        raise ValueError("roofline: only compute-bound work is counted here")
    rx = re.compile(args["pattern"])
    steps, ns = 0, 0
    for p in xplane.device_planes(src["trace"]):
        whole = whole_steps(p, args["step_module"])
        steps += len(whole)
        ns += sum(d for name, s, d in xplane.ops(p) if rx.search(name)
                  and any(a <= s < a + b for a, b in whole))
    if not steps or not ns or not src.get("peak"):
        return None
    least = WORK[args["work"]](src) * steps / src["peak"]["bf16_flops_per_s"]
    return 100.0 * least / (ns / 1e9)
