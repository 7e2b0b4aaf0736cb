"""Device self time of the round program's ops under none of its stage
scopes, per simulated round: layout copies of the client data, the cohort
pad, dynamic-update-slices and whatever else carries no stage."""


def read(ctx):
    if ctx["unit"] != "rounds" or not ctx["units"] \
            or "other" not in ctx["stage_s"]:
        return None
    return ctx["stage_s"]["other"] / ctx["units"] * 1e3
