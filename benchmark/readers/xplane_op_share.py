"""Time of the device operations whose name matches ``pattern``, as a
share of the device's busy time, in %."""
import xplane


def read(args, src):
    busy, _ = xplane.busy_and_window(src["trace"])
    secs, n = xplane.matching_seconds(src["trace"], args["pattern"])
    if busy <= 0 or n == 0:
        return None
    return 100.0 * secs / busy
