"""The recurrent state of a retention model in a serving window, from the
``dispatch`` records the program writes (``state_rows``, ``state_bytes``,
``state_chunk_rows``, ``state_chunk_tokens``: the rows that carried a
token, the state they read and wrote as the equations count it, and the
rows of several tokens with their tokens).  ``stat``:

``bytes_per_token`` — sum of ``state_bytes`` over the sum of ``tokens``:
what a token the step processes costs in state traffic.

``roofline`` — the state ops' share of their roofline, in %: each
record's least time, the larger of its operations over the chip's
bfloat16 peak (``flops_brumby.one_token_flops`` a row of one token,
``chunk_flops`` of the other rows' tokens, a layer) and of its
``state_bytes`` over the chip's memory bandwidth, summed over the records
inside ``trace_window_ns`` (the host's stamps around the profiler, on the
ring's clock), over the device time ``time`` names: {"scope": a pattern
of ``xplane_scope_share_programs``} or {"op": a pattern of operation
names}.  The count is of rows that carried a token and of the symmetric
state only, and the device trace runs a little past the host's stamps, so
the share can only read low.

Nothing to read on a program without the fields, or without the stamps,
the peak or the time."""
import flops_brumby
import xplane
from readers.xplane_scope_share_programs import scope_seconds
from reference import brumby_plain as plain


def read(args, src):
    recs = [r for r in src.get("steps") or [] if r["kind"] == "dispatch"
            and "state_bytes" in r]
    if not recs:
        return None
    if args["stat"] == "bytes_per_token":
        tokens = sum(r["tokens"] for r in recs)
        return sum(r["state_bytes"] for r in recs) / tokens if tokens else None
    if args["stat"] != "roofline":
        raise ValueError(f"retention_state: unknown stat {args['stat']!r}")
    span, peak = src.get("trace_window_ns"), src.get("peak")
    if not span or not peak:
        return None
    cfg = plain.model_cfg(src["config"])
    layers = cfg["num_hidden_layers"]
    least = 0.0
    for r in recs:
        if not (span[0] <= r["start_ns"] and r["end_ns"] <= span[1]):
            continue
        ones = r["state_rows"] - r["state_chunk_rows"]
        flops = layers * (ones * flops_brumby.one_token_flops(cfg)
                          + flops_brumby.chunk_flops(
                              cfg, r["state_chunk_tokens"]))
        least += max(flops / peak["bf16_flops_per_s"],
                     r["state_bytes"] / peak["hbm_bytes_per_s"])
    if "scope" in args["time"]:
        secs = scope_seconds(src, args["time"]["scope"])
    else:
        secs, _ = xplane.matching_seconds(src["trace"], args["time"]["op"])
    if not least or not secs:
        return None
    return 100.0 * least / secs
