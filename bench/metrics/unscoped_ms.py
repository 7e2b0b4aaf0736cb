"""Device self time of the ``other/rest`` part, per simulated round: the ops
that neither their own scope nor the fallback of bench/parts.py places."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "other/rest")
