"""Named kernels' share of their roofline, in %, where one step calls
several kernels a different number of times: the least time the chip
could take for the calls it made over the time those calls took.

``calls`` lists [pattern of a device operation's name, name of a
function below that counts ONE such call's work from the shapes, or
null]: every matching operation inside a whole step (``roofline.
whole_steps`` of the modules matching ``step_module``) adds its time,
and its work unless the entry says null (a kernel whose products are
counted with its sibling's).  A call made again by recomputation is a
call: the kernel did the work.  ``bound`` says which peak bounds it."""
import re

import flops_kimi_linear as kimi
import xplane
from readers.roofline import whole_steps
from reference import kimi_linear_plain as plain


def _mla_flash(src, backward):
    """One MLA layer's causal attention: the forward kernel's products,
    or the two backward kernels' together (dV, dP, S again, dQ, dK)."""
    cfg, t = plain.model_cfg(src["config"]), src["traffic"]
    batch, seq = int(t["batch"]), int(t["seq"])
    fwd = kimi.mla_attention_flops(cfg, batch, seq, False)
    return (kimi.mla_attention_flops(cfg, batch, seq, True) - fwd
            if backward else fwd)


WORK = {"mla_flash_fwd": lambda src: _mla_flash(src, False),
        "mla_flash_bwd": lambda src: _mla_flash(src, True)}


def read(args, src):
    if args["bound"] != "compute":
        raise ValueError("roofline_named: only compute-bound work is "
                         "counted here")
    calls = [(re.compile(p), w) for p, w in args["calls"]]
    work, ns = 0.0, 0
    for p in xplane.device_planes(src["trace"]):
        whole = whole_steps(p, args["step_module"])
        for name, s, d in xplane.ops(p):
            if not any(a <= s < a + b for a, b in whole):
                continue
            for rx, fn in calls:
                if rx.search(name):
                    ns += d
                    work += WORK[fn](src) if fn else 0.0
                    break
    if not work or not ns or not src.get("peak"):
        return None
    return 100.0 * (work / src["peak"]["bf16_flops_per_s"]) / (ns / 1e9)
