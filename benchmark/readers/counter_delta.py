"""How far a counter of the program's registry moved over the window."""


def read(args, src):
    c0, c1 = src.get("counters0"), src.get("counters1")
    if c0 is None or c1 is None:
        return None
    name = args["counter"]
    return float(c1.get(name, 0) - c0.get(name, 0))
