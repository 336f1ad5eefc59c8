"""The decode steps' share of the chip's memory bandwidth over the window, for
an LFM2 configuration: the bytes its decode steps must move
(`flops_lfm2.decode_bytes`: the weights outside the experts once a step; the
experts each step actually hit, from the program's `moe_summary()`; the
convolution tails the live slots read and wrote, from its
`short_conv_summary()`; the packed K/V rows in reach, 2,048 B a row a layer,
from its `kv_walk_summary()`; all three counted inside the compiled step)
over the host's time in decode steps x peak bytes/s.  A program without
those counters gives nothing to read."""

from .. import flops_lfm2 as flops
from ..weights_lfm2 import model_cfg


def read(ctx, args):
    moe, conv, walk = (ctx.counters.get(k) for k in ("moe", "short_conv", "kv_walk"))
    busy = ctx.counters.get("decode_busy_s")
    if not moe or not conv or not walk or not busy:
        return None
    nbytes = flops.decode_bytes(model_cfg(ctx.cfg), moe["steps"], moe["experts_hit"],
                                conv["tail_bytes_read"] + conv["tail_bytes_written"], walk["rows_in_reach"])
    return 100.0 * nbytes / (busy * ctx.peaks["hbm_bytes_per_s"])
