"""Share of the traced window in which no op ran on the device (one minus
the union of op intervals over the window), averaged over the chips."""


def read(ctx):
    if ctx["unit"] != "steps" or ctx["window_s"] <= 0:
        return None
    return (1.0 - ctx["busy_s"] / ctx["window_s"]) * 100.0
