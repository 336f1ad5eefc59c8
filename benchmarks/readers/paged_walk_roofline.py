"""The page walk's share of its roofline in the traced seconds, where the
number of decode steps the trace holds is not the host's to know (the device
runs a step behind the dispatch, and the trace's edges cut steps in two):
the MEAN work of a walk call the program counted inside those seconds (the K
and V rows in reach of the live slots, summed over layers, a decode step:
`traced_decode` of the kind's counters; rows in reach, never pages copied)
against the MEAN device time of the operations whose names hold one of
`match`.  `flops_mellum2.walk_bytes` / `walk_flops` give a row's bytes and
operations, `flops.roofline_seconds` the bound.

`calls_per_layer_step` guards the match as `kernel_roofline`'s does: the
matched events have to number that many a layer a counted step, to within
two steps' worth (the edges); another count means another kernel has joined
or left the match, and nothing is reported.

args: match (substrings of the device operations' names), calls_per_layer_step."""

from .. import flops, flops_mellum2
from ..trace_reduce import ops_matching


def read(ctx, args):
    work = ctx.counters.get("traced_decode")
    if not ctx.trace or not work or not work.get("steps"):
        return None
    calls = ops_matching(ctx.trace["op_counts"], args["match"])
    seconds = ops_matching(ctx.trace["ops"], args["match"])
    per_step = args["calls_per_layer_step"] * ctx.cfg["num_hidden_layers"]
    by_name = {n: (ctx.trace["op_counts"][n], t) for n, t in ctx.trace["ops"].items()
               if any(s in n for s in args["match"])}
    ctx.log(f"paged_walk_roofline: {calls} calls in {seconds:.4f}s over {work['steps']} counted steps; "
            f"by operation (calls, seconds): {sorted(by_name.items())}")
    if not calls or abs(calls - per_step * work["steps"]) > 2 * per_step:
        ctx.log(f"paged_walk_roofline: {per_step * work['steps']} calls expected: the match holds "
                "another kernel, nothing is reported")
        return None
    rows = (work["rows_in_reach_full"] + work["rows_in_reach_window"]) / work["steps"] * (calls / per_step)
    least, _bound = flops.roofline_seconds(
        flops_mellum2.walk_flops(ctx.cfg, rows), flops_mellum2.walk_bytes(ctx.cfg, rows), ctx.peaks)
    return 100.0 * least / seconds if seconds and least else None
