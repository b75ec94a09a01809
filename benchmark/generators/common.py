"""What both generators share: a fixed pool of sizes per traffic file."""
from __future__ import annotations

import math
import statistics

import numpy as np


def lognormal_pool(spec, n):
    """``n`` sizes at the (i + 0.5) / n quantiles of
    clip(lognormal(ln median, sigma), lo, hi), as ints.  The pool is a
    property of the traffic file, not of the seed: every seed sends the
    same set of sizes, in another order, so the seed does not change
    the work."""
    nd = statistics.NormalDist()
    q = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    x = [math.exp(math.log(spec["median"]) + spec["sigma"] * z) for z in q]
    return np.clip(np.rint(x), spec["lo"], spec["hi"]).astype(np.int64)
