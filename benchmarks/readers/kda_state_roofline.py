"""The KDA state kernel's share of its roofline in the traced seconds, for a
Ling-3.0-flash configuration: the bytes a KDA layer's decode step NEEDS to move
of its float32 state (each live slot's `[heads, head_dim, head_dim]` read once
and written once; the window's mean live slots a step from the program's own
`linear_attn_summary()`, counted inside the compiled step), one call a KDA
layer, against the MEAN device time of the operations whose OWN name holds
one of `match`.  The convolution tail is left out: it is part of a slot's
state but the kernel does not move it.

The guard is the trace's own, as `grouped_experts_roofline`'s: the matched
events have to number `kda_layers` for every event whose name holds one of
`step_marks` (the latent walk: one a decode step an MLA layer), to within two
steps' worth.  Another count means another kernel has joined or left the
match, and nothing is reported.  A program without the kernel, the walk or
the counters gives nothing to read.

args: match, step_marks (substrings of the device operations' own names)."""

from .. import flops, flops_ling3
from ..weights_ling3 import model_cfg
from .grouped_experts_roofline import named


def read(ctx, args):
    linear = ctx.counters.get("linear_attn")
    if not ctx.trace or not linear or not linear.get("steps"):
        return None
    calls = named(ctx.trace["op_counts"], args["match"])
    seconds = named(ctx.trace["ops"], args["match"])
    marks = named(ctx.trace["op_counts"], args["step_marks"])
    if not calls or not seconds or not marks:
        return None
    cfg = model_cfg(ctx.cfg)
    p = flops_ling3.param_counts(cfg)
    want = p["kda_layers"] * marks / p["mla_layers"]
    live = linear["live_slots"] / linear["steps"]
    ctx.log(f"kda_state_roofline: {calls} calls in {seconds:.4f}s, {marks} step marks ({want} calls "
            f"expected); {live:.1f} live slots a step")
    if abs(calls - want) > 2 * p["kda_layers"]:
        ctx.log("kda_state_roofline: the match holds another kernel, nothing is reported")
        return None
    H, d = cfg["num_attention_heads"], cfg["head_dim"]
    least, _bound = flops.roofline_seconds(7 * live * H * d * d, 2 * live * H * d * d * 4, ctx.peaks)
    return 100.0 * least * calls / seconds if least else None
