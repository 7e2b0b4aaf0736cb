"""Device self time of the cross-chip collectives (all-reduce, all-gather,
all-to-all, reduce-scatter, collective-permute) per simulated round,
averaged over the chips (``tracing.reduce_events``).  On one chip there is
nothing to read."""


def read(ctx):
    if ctx["unit"] != "rounds" or not ctx["units"] or ctx["chips"] < 2:
        return None
    return ctx["collective_s"] / ctx["units"] * 1e3
