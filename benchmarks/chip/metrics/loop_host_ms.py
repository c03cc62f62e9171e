"""Host milliseconds a round of the launcher loop's own work: its stage,
draw, dispatch, readback, ema and log spans in the traced window, over the
window's rounds."""

from spans import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "host", ("stage", "draw", "dispatch", "readback", "ema", "log"))
