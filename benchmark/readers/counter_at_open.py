"""The total of the named counters of the program's registry when the
window opens (``counters0``): what set-up added to them.  Nothing to
read where the program keeps none of them."""


def read(args, src):
    c0 = src.get("counters0")
    if c0 is None or not any(n in c0 for n in args["counters"]):
        return None
    return float(sum(c0.get(n, 0) for n in args["counters"]))
