"""A memory-bound layer's share of its HBM roofline in a serving window, in
%: the bytes the step ring's ``dispatch`` records say the layer had to read
(the sum of ``fields`` over the records that lie inside ``trace_window_ns``,
the host's stamps around the profiler on the ring's clock; ``per``
"expert": a count of touched experts, times ``flops_mimo.expert_bytes`` of
one) over the chip's memory bandwidth, over the layer's device time: the
operations whose names match ``op``, or with ``scope`` the own time of the
operations under the scopes matching it (``xplane_scope_share_programs``:
whatever implements the layer under that scope, the bytes counted are the
same).  The bytes are what the algorithm needs (a page once a kv head, a
touched expert once), the time holds everything the operations did besides,
and the device trace runs a little past the host's stamps: the share can
only read low.  Nothing to read without the fields (the parent, another
model), the stamps, the peak or the time."""
import flops_mimo
import xplane
from readers.xplane_scope_share_programs import scope_seconds
from reference import mimo_v2_flash_plain as plain


def read(args, src):
    span, peak = src.get("trace_window_ns"), src.get("peak")
    fields = list(args["fields"])
    recs = [r for r in src.get("steps") or [] if r["kind"] == "dispatch"
            and all(f in r for f in fields)]
    if not span or not peak or not recs or not src.get("trace"):
        return None
    amount = sum(r[f] for r in recs for f in fields
                 if span[0] <= r["start_ns"] and r["end_ns"] <= span[1])
    if args.get("per") == "expert":
        try:
            cfg = plain.model_cfg(src["config"])
        except KeyError:            # another model's configuration
            return None
        amount = flops_mimo.expert_bytes(cfg, amount)
    secs = (scope_seconds(src, args["scope"]) if "scope" in args
            else xplane.matching_seconds(src["trace"], args["op"])[0])
    if not amount or not secs:
        return None
    return 100.0 * amount / peak["hbm_bytes_per_s"] / secs
