"""Mean host milliseconds of one Network Monitor refresh (the loop's
``monitor`` span: collect, step and the repair of P) in the traced window."""


def read(ctx):
    s = getattr(ctx, "spans", None)
    if ctx.trace is None or s is None or "monitor" not in s["host"]:
        return None
    n, secs = s["host"]["monitor"]
    return 1e3 * secs / n
