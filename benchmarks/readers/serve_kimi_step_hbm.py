"""The decode steps' share of the chip's memory bandwidth over the window, for
a Kimi-Linear configuration: the bytes its decode steps must move
(`flops_kimi_linear.decode_bytes`: the weights outside the routed experts once
a step; the held experts each step actually hit, from the program's
`moe_summary()`; the state the live slots read and wrote, from its
`linear_attn_summary()`; the latent rows in reach, 1,280 B each, from its
`latent_walk_summary()`; all three counted inside the compiled step) over the
host's time in decode steps x peak bytes/s.  A program without those counters
gives nothing to read."""

from .. import flops_kimi_linear as flops
from ..weights_kimi_linear import model_cfg


def read(ctx, args):
    moe, linear, walk = (ctx.counters.get(k) for k in ("moe", "linear_attn", "latent_walk"))
    busy = ctx.counters.get("decode_busy_s")
    if not moe or not linear or not walk or not busy:
        return None
    nbytes = flops.decode_bytes(model_cfg(ctx.cfg), moe["steps"], moe["experts_hit"],
                                linear["state_bytes_read"] + linear["state_bytes_written"],
                                walk["rows_in_reach"])
    return 100.0 * nbytes / (busy * ctx.peaks["hbm_bytes_per_s"])
