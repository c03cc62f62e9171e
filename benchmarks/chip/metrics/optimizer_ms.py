"""Device milliseconds a round of the round program's self time under the
``optimizer`` scope (grad transform, update, apply), in the traced window;
its share of the fusions it shares with the pull and mix counts here."""

from spans import per_round_ms


def read(ctx):
    return per_round_ms(ctx, "scopes", ("optimizer",))
