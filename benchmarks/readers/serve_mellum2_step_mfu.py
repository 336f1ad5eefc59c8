"""The whole serving step's share of the chip's peak over the window, for a
Mellum2 configuration: the operations every prompt prefilled and every token
decoded in the window needs (`flops_mellum2.py`: attention over the keys a
query may SEE, so the last `sliding_window` of them in a sliding layer; the
routed picks as made, 8 a token; padding does not count) over window x peak.
A resumed prompt counts whole, as it was prefilled."""

from .. import flops_mellum2 as flops


def read(ctx, args):
    if not ctx.window.get("records"):
        return None
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
