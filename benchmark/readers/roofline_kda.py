"""The chunkwise-KDA kernels' share of their roofline, in %: the least
time the chip could take for the calls it made over the time those calls
took.

``calls`` lists [pattern of a device operation's name, "fwd" or "bwd"]:
every matching operation inside a whole step (``roofline.whole_steps``
of the modules matching ``step_module``) adds its time and ONE call's
least time, the larger of its products over the chip's bfloat16 peak
(``flops_kimi_linear.kda_chunk_flops``: the forward's for a forward
call, twice that for a backward call) and of the bytes of the op's own
inputs and outputs over the chip's memory bandwidth (forward: q, k, v
in, o out, the log-decay in float32; backward: those, do, and the four
gradients).  A call made again by recomputation is a call: the kernel
did the work.  What a kernel does or moves beyond that count (the
triangular inverse, the diagonal blocks, the states it keeps for the
backward) is in the time alone, so the share can only read low.  None
where no such operation ran: a program without the kernels."""
import re

import flops_kimi_linear as kimi
import xplane
from readers.roofline import whole_steps
from reference import kimi_linear_plain as plain

ITEM_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def call_floor_s(config, traffic, backward, peak):
    """Least seconds of ONE layer's call at the cell's batch and length."""
    cfg = plain.model_cfg(config)
    la = cfg["linear_attn_config"]
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    fwd_flops = kimi.kda_chunk_flops(cfg, batch, seq, False)
    # q, k, v, o (and do, dq, dk, dv) in the model's dtype; a (and da)
    # in float32; all [batch, seq, heads, head_dim]
    stream = batch * seq * la["num_heads"] * la["head_dim"]
    item = ITEM_BYTES[config.get("torch_dtype", "bfloat16")]
    if backward:
        flops, nbytes = 2.0 * fwd_flops, stream * (7 * item + 2 * 4)
    else:
        flops, nbytes = fwd_flops, stream * (4 * item + 4)
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def read(args, src):
    peak = src.get("peak")
    if not peak:
        return None
    calls = [(re.compile(p), kind == "bwd") for p, kind in args["calls"]]
    floors = {}
    least, ns = 0.0, 0
    for p in xplane.device_planes(src["trace"]):
        whole = whole_steps(p, args["step_module"])
        for name, s, d in xplane.ops(p):
            if not any(a <= s < a + b for a, b in whole):
                continue
            for rx, backward in calls:
                if rx.search(name):
                    if backward not in floors:
                        floors[backward] = call_floor_s(
                            src["config"], src["traffic"], backward, peak)
                    least += floors[backward]
                    ns += d
                    break
    if not ns:
        return None
    return 100.0 * least / (ns / 1e9)
