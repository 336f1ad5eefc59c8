"""The page walk's share of its roofline in the traced seconds, for an LFM2
configuration: the MEAN work of a walk call the program counted inside those
seconds (the packed K/V rows in reach of the live slots, `pos + 1` a slot,
summed over the attention layers of a decode step: `traced_decode` of the
kind's counters, from `kv_walk_summary()`; rows in reach, never pages
copied), `flops_lfm2.walk_bytes` (a row is 2,048 B a layer) and `walk_flops`,
against the MEAN device time of the events whose OWN name holds one of
`match`.

The guard is `latent_walk_roofline`'s: the matched events have to number one
an attention layer a counted step, to within two steps' worth (the trace's
edges cut steps); another count means another kernel has joined or left the
match, and nothing is reported.  A program without the walk or the counters
gives nothing to read.

args: match (substrings of the device operations' own names)."""

from .. import flops, flops_lfm2
from ..weights_lfm2 import model_cfg
from .grouped_experts_roofline import named


def read(ctx, args):
    work = ctx.counters.get("traced_decode")
    if not ctx.trace or not work or not work.get("steps"):
        return None
    cfg = model_cfg(ctx.cfg)
    per_step = flops_lfm2.param_counts(cfg)["attn_layers"]
    calls = named(ctx.trace["op_counts"], args["match"])
    seconds = named(ctx.trace["ops"], args["match"])
    ctx.log(f"kv_walk_roofline: {calls} calls in {seconds:.4f}s over {work['steps']} counted steps "
            f"({per_step * work['steps']} calls expected), {work['rows_in_reach'] / work['steps']:.0f} rows "
            "in reach a step")
    if not calls or not seconds or abs(calls - per_step * work["steps"]) > 2 * per_step:
        ctx.log("kv_walk_roofline: the match holds another kernel, nothing is reported")
        return None
    rows = work["rows_in_reach"] / work["steps"] / per_step  # a call's
    least, _bound = flops.roofline_seconds(flops_lfm2.walk_flops(cfg, rows),
                                           flops_lfm2.walk_bytes(cfg, rows), ctx.peaks)
    return 100.0 * least * calls / seconds if least else None
