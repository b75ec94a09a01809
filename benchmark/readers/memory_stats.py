"""The device's peak bytes in use over its limit, in %, read after the
window and before the reference runs."""


def read(args, src):
    m = src["memory"]
    if not m.get("bytes_limit"):
        return None
    return 100.0 * m["peak_bytes_in_use"] / m["bytes_limit"]
