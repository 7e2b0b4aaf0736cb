"""Device self time of the ``train/agg`` part, per simulated round: the
aggregation: edge aggregation, the broadcast back to the lanes, the
scatter of the lanes into the client parameters and the cloud aggregation."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "train/agg")
