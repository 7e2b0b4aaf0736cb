"""DDPG network FLOPs of the steps completed in the traced window (acting,
critic and actor updates, target forwards; ``bench/flops.py``) over the
window and the chips' bf16 peak."""


def read(ctx):
    if ctx["unit"] != "steps" or not ctx["units"] or ctx["window_s"] <= 0:
        return None
    done = ctx["flops_per_op"] * ctx["units"]
    peak = ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]
    return done / ctx["window_s"] / peak * 100.0
