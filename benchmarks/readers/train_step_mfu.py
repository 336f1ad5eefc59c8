"""The whole train step's share of the chip's peak over the window:
forward and backward operations per step (`flops.py`; recomputation and the
optimizer do not count) times steps, over window x peak."""

from .. import flops


def read(ctx, args):
    w = ctx.window
    if not w.get("steps"):
        return None
    total = w["steps"] * flops.train_flops_per_step(ctx.cfg, w["batch"], w["seqlen"])
    return 100.0 * total / (w["seconds"] * ctx.peaks["flops_per_s"]["bfloat16"])
