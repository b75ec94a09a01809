"""A ratio of how far two sets of the program's counters moved over the
window: ``scale`` x (sum of the ``numerator`` counters' moves) / (sum of
the ``denominator`` counters' moves); ``scale`` defaults to 1 (100 for a
share in %, 1000 for milliseconds a step from seconds and steps).  A
counter's value is the total over its series (``counters_now``), so a
labelled counter is summed over its labels.  Nothing to read where the
program keeps one of the named counters not at all (the parent), or
where the denominator did not move."""


def read(args, src):
    c0, c1 = src.get("counters0"), src.get("counters1")
    if c0 is None or c1 is None:
        return None
    names = list(args["numerator"]) + list(args["denominator"])
    if any(n not in c1 for n in names):
        return None
    moved = {n: c1[n] - c0.get(n, 0) for n in names}
    den = sum(moved[n] for n in args["denominator"])
    if den <= 0:
        return None
    num = sum(moved[n] for n in args["numerator"])
    return float(args.get("scale", 1)) * num / den
