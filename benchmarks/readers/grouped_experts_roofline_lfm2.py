"""The grouped-expert kernel's share of its roofline in the traced seconds,
for an LFM2 configuration: `grouped_experts_roofline`'s reading with the
expert's size from this configuration (`flops_lfm2.param_counts(...)[
"expert"]`: three 2048 x 1792 matrices, 22,020,096 B in bfloat16), the experts
a step hit and the picks they took from the program's own `moe_summary()`,
one call an expert layer, against the MEAN device time of the events whose
OWN name holds one of `match`; guarded as there by the events whose own name
holds one of `step_marks` (the page walk: one a decode step an attention
layer), to within two steps' worth.

args: match, step_marks (substrings of the device operations' own names)."""

from .. import flops, flops_lfm2
from ..weights_lfm2 import model_cfg
from .grouped_experts_roofline import named


def read(ctx, args):
    moe = ctx.counters.get("moe")
    if not ctx.trace or not moe or not moe.get("steps"):
        return None
    calls = named(ctx.trace["op_counts"], args["match"])
    seconds = named(ctx.trace["ops"], args["match"])
    marks = named(ctx.trace["op_counts"], args["step_marks"])
    if not calls or not seconds or not marks:
        return None
    p = flops_lfm2.param_counts(model_cfg(ctx.cfg))
    want = p["moe_layers"] * marks / p["attn_layers"]
    ctx.log(f"grouped_experts_roofline: {calls} calls in {seconds:.4f}s, {marks} step marks "
            f"({want} calls expected); {moe['experts_hit'] / moe['steps']:.1f} experts hit a step")
    if abs(calls - want) > 2 * p["moe_layers"]:
        ctx.log("grouped_experts_roofline: the match holds another kernel, nothing is reported")
        return None
    per_call = lambda total: total / moe["steps"] / p["moe_layers"]
    least, _bound = flops.roofline_seconds(2 * p["expert"] * per_call(moe["picks_held"]),
                                           2 * p["expert"] * per_call(moe["experts_hit"]), ctx.peaks)
    return 100.0 * least * calls / seconds if least else None
