"""Numbers from the engine's step ring (``monitor.get_tracer()
.step_records()`` captured over the window): host time and counts.

``stat``: ``occupancy`` — mean ``batch`` of the ``decode`` records over
``max_batch``, in %; ``chunk_steps`` — share of step indices that carry
a ``prefill_chunk`` record, in %; ``step_host_ms`` — median over step
indices of the longest ``end_ns - start_ns`` among the index's records
(the records of one ragged dispatch share its interval)."""
import statistics


def read(args, src):
    steps = src.get("steps") or []
    if not steps:
        return None
    stat = args["stat"]
    if stat == "occupancy":
        b = [r["batch"] for r in steps if r["kind"] == "decode"]
        return 100.0 * statistics.fmean(b) / src["max_batch"] if b else None
    by_index = {}
    for r in steps:
        by_index.setdefault(r["index"], []).append(r)
    if stat == "chunk_steps":
        n = sum(any(r["kind"] == "prefill_chunk" for r in rs)
                for rs in by_index.values())
        return 100.0 * n / len(by_index)
    if stat == "step_host_ms":
        return statistics.median(
            max(r["end_ns"] - r["start_ns"] for r in rs) / 1e6
            for rs in by_index.values())
    raise ValueError(f"step_ring: unknown stat {stat!r}")
