"""Device self time of the ``schedule/pdd`` part, per simulated round: the
edge scheduler: PDD (or the fastest-edges baseline)."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "schedule/pdd")
