"""The whole serving step's share of the chip's peak over the window, for a
Ling-3.0-flash configuration: the operations every prompt prefilled and every
token decoded in the window needs (`flops_ling3.py`: the KDA recurrence a
token, MLA over every key in context, the routed picks expected on the
experts held here; padding and the chunked scan's extra work do not count)
over window x peak.  A resumed prompt counts whole, as it was prefilled."""

from .. import flops_ling3 as flops
from ..weights_ling3 import model_cfg


def read(ctx, args):
    if not ctx.window.get("records"):
        return None
    cfg = model_cfg(ctx.cfg)
    t0, t1 = ctx.window["t0"], ctx.window["t1"]
    total = 0
    for r in ctx.window["records"]:
        n = len(r.prompt)
        for i, t in enumerate(r.times):
            if t0 <= t < t1:
                # the first token comes out of the prompt's prefill; token i
                # after it from a decode step over n + i tokens of context
                total += (flops.forward_flops_prompt(cfg, n) if i == 0
                          else flops.forward_flops_decode(cfg, n + i))
    if not total:
        return None
    return 100.0 * total / ((t1 - t0) * ctx.peaks["flops_per_s"]["bfloat16"])
