"""The whole serving step's share of the chip's peak over the window: the
operations every prompt prefilled and every token decoded in the window
needs (`flops.py`, from the configuration and the traffic; padding and
recomputation do not count) over window x peak."""

from .. import flops


def read(ctx, args):
    t0, t1 = ctx.window["t0"], ctx.window["t1"]
    total = 0
    for r in ctx.window["records"]:
        n = len(r.prompt)
        for i, t in enumerate(r.times):
            if t0 <= t < t1:
                # the first token comes out of the prompt's prefill; token i
                # after it from a decode step over n + i tokens of context
                total += (flops.forward_flops_prompt(ctx.cfg, n) if i == 0
                          else flops.forward_flops_decode(ctx.cfg, n + i))
    if not total:
        return None
    return 100.0 * total / ((t1 - t0) * ctx.peaks["flops_per_s"]["bfloat16"])
