"""Median device duration, in ms, of the executions of the compiled
programs ("XLA Modules" line) whose name matches ``pattern``."""
import statistics

import xplane


def read(args, src):
    ds = xplane.durations_ms(src["trace"], args["pattern"],
                             xplane.MODULES_LINE)
    return statistics.median(ds) if ds else None
