"""Share of the traced window in which no operation ran on the device; the
largest over the cell's chips."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    return 100.0 * max(1.0 - c["busy_ns"] / t["window_ns"] for c in t["chips"])
