"""Device self time of the ``train/sgd`` part, per simulated round: the local
SGD steps: the cohort loss's gradient and the update."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "train/sgd")
