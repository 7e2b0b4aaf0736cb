"""Device self time of the ``train/cohort`` part, per simulated round: the
cohort's data moved: the admitted clients' selection, the gathers of their
data slabs, counts and lane parameters, the minibatch index lattice and
each step's minibatch gather (with the fallback: the layout copies of the
client data)."""
from bench import parts


def read(ctx):
    return parts.per_round_ms(ctx, "train/cohort")
