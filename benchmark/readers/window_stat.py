"""A statistic over a list of host-clock readings the driver took
inside the window (``key`` names the list): ``median`` or ``p90``
(which wants ten samples beyond it: a hundred readings)."""
import statistics

import stats


def read(args, src):
    xs = src.get(args["key"]) or []
    if not xs:
        return None
    if args["stat"] == "p90":
        return stats.percentile(xs, 90)[0]
    if args["stat"] == "median":
        return statistics.median(xs)
    raise ValueError(f"window_stat: unknown stat {args['stat']!r}")
