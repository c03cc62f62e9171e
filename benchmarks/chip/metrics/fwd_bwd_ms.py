"""Device milliseconds a round of the round program's self time under the
``forward_backward`` scope (the microbatch loop of forward and backward
passes), in the traced window."""

from spans import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "scopes", ("forward_backward",))
