"""Tokens trained by all workers in the window, per second and per chip."""


def read(ctx):
    return len(ctx.rounds_s) * ctx.tokens_per_round / ctx.window_s / ctx.chips
