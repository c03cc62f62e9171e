"""Device milliseconds a round of the round program's self time under the
``gossip_pull`` and ``gossip_mix`` scopes, in the traced window."""

from spans import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "scopes", ("gossip_pull", "gossip_mix"))
