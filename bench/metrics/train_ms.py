"""Device self time of the ops under the program's ``train`` scope, per
simulated round (rounds summed over the fleet's lanes)."""


def read(ctx):
    if ctx["unit"] != "rounds" or not ctx["units"] \
            or "train" not in ctx["stage_s"]:
        return None
    return ctx["stage_s"]["train"] / ctx["units"] * 1e3
