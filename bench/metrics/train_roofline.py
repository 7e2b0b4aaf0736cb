"""The training stage's share of its roofline: the least time the chip
needs for the SGD work the algorithm asks for (the larger of its FLOPs
over peak and its bytes over HBM bandwidth, counted from shapes by
``bench/flops.py``) over the device time of the ``train`` scope."""
from bench import flops


def read(ctx):
    t = ctx["stage_s"].get("train", 0.0)
    if ctx["unit"] != "rounds" or not ctx["units"] or t <= 0.0:
        return None
    least, _ = flops.least_time(ctx["train_flops"], ctx["train_bytes"],
                                ctx["peak"])
    return least * ctx["units"] / t * 100.0
