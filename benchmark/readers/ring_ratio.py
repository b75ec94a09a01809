"""A ratio of sums over the step ring's records of one ``kind``, in %:
100 x (sum over records of the product of the ``numerator`` fields) /
(sum of the product of the ``denominator`` fields); with ``complement``
100 minus that.  The records are written by the program from what it
dispatched (``dispatch``: ``rows_padded`` x ``span_padded`` computed
against ``tokens`` asked for), so the bucket rule is not re-derived
here.  Nothing to read without such records."""
import math


def read(args, src):
    recs = [r for r in src.get("steps") or [] if r["kind"] == args["kind"]]
    fields = list(args["numerator"]) + list(args["denominator"])
    if not recs or any(f not in r for r in recs for f in fields):
        return None
    num = sum(math.prod(r[f] for f in args["numerator"]) for r in recs)
    den = sum(math.prod(r[f] for f in args["denominator"]) for r in recs)
    if den <= 0:
        return None
    share = 100.0 * num / den
    return 100.0 - share if args.get("complement") else share
