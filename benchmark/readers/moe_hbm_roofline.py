"""The routed experts' share of their memory roofline, in %: the bytes of
the weights of the experts the steps' tokens CHOSE
(``flops_laguna.expert_bytes`` of the ring's ``moe_experts_touched``, of
the ``dispatch`` records that lie inside ``trace_window_ns``, the host's
stamps around the profiler on the ring's clock) over the chip's memory
bandwidth, over the device time ``time`` names: {"scope": a pattern of
``xplane_scope_share_programs``} or {"op": a pattern of operation
names}.  Memory-bound: the products are a few rows an expert.  The count
is of touched experts only, so the share cannot pass 100 % by weights
read for nothing.  Nothing to read without the records, the stamps or
the time."""
import flops_laguna
import xplane
from readers.xplane_scope_share_programs import scope_seconds
from reference import laguna_plain as plain


def read(args, src):
    span, peak = src.get("trace_window_ns"), src.get("peak")
    recs = [r for r in src.get("steps") or [] if r["kind"] == "dispatch"
            and "moe_experts_touched" in r]
    if not span or not peak or not recs:
        return None
    touched = sum(r["moe_experts_touched"] for r in recs
                  if span[0] <= r["start_ns"] and r["end_ns"] <= span[1])
    if "scope" in args["time"]:
        secs = scope_seconds(src, args["time"]["scope"])
    else:
        secs, _ = xplane.matching_seconds(src["trace"], args["time"]["op"])
    if not touched or not secs:
        return None
    least = flops_laguna.expert_bytes(plain.model_cfg(src["config"]),
                                      touched) / peak["hbm_bytes_per_s"]
    return 100.0 * least / secs
