"""Model FLOPs of the rounds completed in the traced window (training at
6·|theta| a sample, evaluation at 2·|theta| a test sample) over the
window and the chips' bf16 peak."""


def read(ctx):
    if ctx["unit"] != "rounds" or not ctx["units"] or ctx["window_s"] <= 0:
        return None
    done = ctx["flops_per_op"] * ctx["units"]
    peak = ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]
    return done / ctx["window_s"] / peak * 100.0
