"""The ZAYA experts' share of their memory roofline, in %: the bytes of the
weights of the experts the steps' tokens CHOSE
(``flops_zaya.expert_bytes`` of the ring's ``moe_experts_touched``, of the
``dispatch`` records that lie inside ``trace_window_ns``, the host's stamps
around the profiler on the ring's clock) over the chip's memory bandwidth,
over the device time of the operations whose names match ``op``.  As
``moe_hbm_roofline``, which is bound to ``flops_laguna`` and its
configuration's keys.  Memory-bound: at 64 decode rows an expert multiplies
four rows.  The count is of touched experts only, so the share cannot pass
100 % by weights read for nothing (a run of blocks of one expert fetches
each weight tile once).  Nothing to read without the records, the stamps or
the time (a program without the counter or the kernel: the parent)."""
import flops_zaya
import xplane
from reference import zaya_plain as plain


def read(args, src):
    span, peak = src.get("trace_window_ns"), src.get("peak")
    recs = [r for r in src.get("steps") or [] if r["kind"] == "dispatch"
            and "moe_experts_touched" in r]
    if not span or not peak or not recs or not src.get("trace"):
        return None
    touched = sum(r["moe_experts_touched"] for r in recs
                  if span[0] <= r["start_ns"] and r["end_ns"] <= span[1])
    secs, _ = xplane.matching_seconds(src["trace"], args["op"])
    if not touched or not secs:
        return None
    least = flops_zaya.expert_bytes(plain.model_cfg(src["config"]),
                                    touched) / peak["hbm_bytes_per_s"]
    return 100.0 * least / secs
