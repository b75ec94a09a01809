"""The Mamba state ops' share of their roofline in a serving window, in %,
from the ``dispatch`` records the program writes (``state_bytes``: the
state the step's rows read and wrote, each real row's ``h`` and tail once
in and once out, a layer; ``tokens``: the step's tokens, each of which goes
through every Mamba layer's convolution and scan).

Each record's least time is the larger of its operations
(``flops_phi4_flash.state_token_flops`` a token a layer) over the chip's
bfloat16 peak and of its bytes (``state_bytes`` and
``flops_phi4_flash.state_token_bytes`` a token a layer) over the chip's
memory bandwidth, summed over the records inside ``trace_window_ns`` (the
host's stamps around the profiler, on the ring's clock), over the own
device time of the scopes ``time`` names (``xplane_scope_share_programs``):
whatever implements the ops under that scope, the work counted is the
same.  The device trace runs a little past the host's stamps, so the share
can only read low.

Nothing to read on a program without the fields or the scope, or without
the stamps or the peak."""
import flops_phi4_flash as flops
from readers.xplane_scope_share_programs import scope_seconds
from reference import phi4_flash_plain as plain


def read(args, src):
    recs = [r for r in src.get("steps") or [] if r["kind"] == "dispatch"
            and "state_bytes" in r]
    span, peak = src.get("trace_window_ns"), src.get("peak")
    if not recs or not span or not peak:
        return None
    try:
        cfg = plain.model_cfg(src["config"])
    except KeyError:            # another model's configuration
        return None
    layers = flops.mamba_layers(cfg)
    least = 0.0
    for r in recs:
        if not (span[0] <= r["start_ns"] and r["end_ns"] <= span[1]):
            continue
        tokens = layers * r["tokens"]
        least += max(
            tokens * flops.state_token_flops(cfg) / peak["bf16_flops_per_s"],
            (r["state_bytes"] + tokens * flops.state_token_bytes(cfg))
            / peak["hbm_bytes_per_s"])
    secs = scope_seconds(src, args["scope"])
    if not least or not secs:
        return None
    return 100.0 * least / secs
