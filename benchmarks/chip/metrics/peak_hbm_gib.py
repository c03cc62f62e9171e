"""Peak device memory in use after the window (``peak_bytes_in_use``), GiB,
on the fullest of the cell's chips."""


def read(ctx):
    if ctx.memory_peak_bytes is None:
        return None
    return ctx.memory_peak_bytes / 2**30
