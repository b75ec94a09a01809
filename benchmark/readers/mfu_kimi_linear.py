"""Model-FLOP utilization of the Kimi-Linear training cell, in %:
``flops_kimi_linear.train_flops_per_token`` (routed experts by the held
slots the program counted during set-up) times the tokens a second of
the window's MEDIAN step (``group_step_ms``; a traced run's window
holds the seconds the profiler takes to start and stop, its median step
does not), over the chip's bf16 peak.  Nothing to read without the
program's ``moe_*`` counters."""
import statistics

import flops_kimi_linear as flops
from reference import kimi_linear_plain as plain


def read(args, src):
    steps = src.get("group_step_ms") or []
    c0 = src.get("counters0") or {}
    if not steps or not src.get("peak") or not c0.get("moe_slots_total"):
        return None
    cfg = plain.model_cfg(src["config"])
    t = src["traffic"]
    rate = int(t["batch"]) * int(t["seq"]) / (statistics.median(steps) / 1e3)
    held = (cfg["num_experts_per_token"] * c0["moe_held_slots_total"]
            / c0["moe_slots_total"])
    per_token = flops.train_flops_per_token(cfg, int(t["seq"]), held)
    return 100.0 * per_token * rate / src["peak"]["bf16_flops_per_s"]
