"""The decode steps' share of the chip's memory bandwidth over the window, for
a Mellum2 configuration: the bytes its decode steps must move
(`flops_mellum2.decode_bytes`: the weights outside the experts once a step;
the experts each step actually hit, from the program's `moe_summary()`; the K
and V rows in reach of the live slots, `pos + 1` in a full layer and `min(pos
+ 1, sliding_window)` in a sliding one, from its `window_cache_summary()`, all
counted inside the compiled step) over the host's time in decode steps x peak
bytes/s.  A program without those counters gives nothing to read."""

from .. import flops_mellum2 as flops


def read(ctx, args):
    moe = ctx.counters.get("moe")
    rows = (ctx.counters.get("window_cache") or {}).get("decode")
    busy = ctx.counters.get("decode_busy_s")
    if not moe or not rows or not rows.get("steps") or not busy:
        return None
    nbytes = flops.decode_bytes(ctx.cfg, moe["steps"], moe["experts_hit"],
                                rows["rows_in_reach_full"], rows["rows_in_reach_window"])
    return 100.0 * nbytes / (busy * ctx.peaks["hbm_bytes_per_s"])
