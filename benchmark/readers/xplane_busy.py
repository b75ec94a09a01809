"""The device's idle share of the traced window, in %: 1 - union of the
operations' intervals over the window."""
import xplane


def read(args, src):
    busy, window = xplane.busy_and_window(src["trace"])
    if window <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
