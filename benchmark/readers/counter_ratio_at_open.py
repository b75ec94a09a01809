"""A ratio of sums of the program's counters when the window opens, in
%: 100 x (sum of ``numerator``) / (sum of ``denominator``); with
``complement`` 100 minus that.  Nothing to read where the program keeps
none of the denominator's counters."""


def read(args, src):
    c0 = src.get("counters0")
    if c0 is None:
        return None
    den = sum(c0.get(n, 0) for n in args["denominator"])
    if den <= 0:
        return None
    share = 100.0 * sum(c0.get(n, 0) for n in args["numerator"]) / den
    return 100.0 - share if args.get("complement") else share
