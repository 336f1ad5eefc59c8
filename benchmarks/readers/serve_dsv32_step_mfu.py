"""The whole serving step's share of the chip's peak over the window, for a
DeepSeek-V3.2 configuration: the operations every prompt prefilled and every
token decoded in the window needs (`flops_deepseek_v32.py`: selected keys
only, the routed picks expected on the experts held here; padding and the
dense-masked prefill's unselected pairs do not count) over window x peak."""

from .. import flops_deepseek_v32 as flops
from ..weights_deepseek_v32 import model_cfg


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
