"""The decode steps' share of the chip's memory bandwidth over the window, for
a Ling-3.0-flash configuration: the bytes its decode steps must move
(`flops_ling3.decode_bytes`: the weights outside the routed experts once a
step; the held experts each step actually hit, from the program's
`moe_summary()`; the state the live slots read and wrote, from its
`linear_attn_summary()`, both counted inside the compiled step; the latent
rows in context, from the positions of the tokens streamed in the window)
over the host's time in decode steps x peak bytes/s.  A program without those
counters gives nothing to read."""

from .. import flops_ling3 as flops
from ..weights_ling3 import model_cfg


def read(ctx, args):
    moe, linear = ctx.counters.get("moe"), ctx.counters.get("linear_attn")
    busy = ctx.counters.get("decode_busy_s")
    if not moe or not linear or not busy or not ctx.window.get("records"):
        return None
    t0, t1 = ctx.window["t0"], ctx.window["t1"]
    context = sum(len(r.prompt) + i for r in ctx.window["records"]
                  for i, t in enumerate(r.times) if i and t0 <= t < t1)
    nbytes = flops.decode_bytes(model_cfg(ctx.cfg), moe["steps"], moe["experts_hit"],
                                linear["state_bytes_read"] + linear["state_bytes_written"], context)
    return 100.0 * nbytes / (busy * ctx.peaks["hbm_bytes_per_s"])
