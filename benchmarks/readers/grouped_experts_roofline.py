"""The grouped-expert kernel's share of its roofline in the traced seconds,
for a Ling-3.0-flash configuration: the work an expert layer of a decode step
NEEDS (the held experts the step's tokens hit, three matrices each, once;
`flops_ling3.param_counts(...)["expert"]` parameters an expert, two
operations a parameter a pick), the window's mean a step from the program's
own `moe_summary()` (hits and picks counted inside the compiled step, never
what the kernel copied), one call an expert layer, against the MEAN device
time of the operations whose names hold one of `match`.

The kind counts no decode steps inside the traced seconds, so the guard is the
trace's own: the matched events have to number `moe_layers` for every event
whose name holds one of `step_marks` (the latent walk: one a decode step an
MLA layer), to within two steps' worth (the trace's edges cut steps).  Another
count means another kernel has joined or left the match, and nothing is
reported.  A program without the kernel, the walk or the counters gives
nothing to read.

An event of the trace is named by its whole HLO instruction, operands and
all, so the operation that READS a kernel's output holds the kernel's name
too: only the instruction's own name (what stands before ` = `) is matched.

args: match, step_marks (substrings of the device operations' own names)."""

from .. import flops, flops_ling3
from ..weights_ling3 import model_cfg


def named(ops, needles):
    """Seconds (or events) of every operation whose OWN name holds one of `needles`."""
    return sum(v for n, v in ops.items() if any(s in n.split(" = ", 1)[0] for s in needles))


def read(ctx, args):
    moe = ctx.counters.get("moe")
    if not ctx.trace or not moe or not moe.get("steps"):
        return None
    calls = named(ctx.trace["op_counts"], args["match"])
    seconds = named(ctx.trace["ops"], args["match"])
    marks = named(ctx.trace["op_counts"], args["step_marks"])
    if not calls or not seconds or not marks:
        return None
    p = flops_ling3.param_counts(model_cfg(ctx.cfg))
    want = p["moe_layers"] * marks / p["mla_layers"]
    ctx.log(f"grouped_experts_roofline: {calls} calls in {seconds:.4f}s, {marks} step marks "
            f"({want} calls expected); {moe['experts_hit'] / moe['steps']:.1f} experts hit a step")
    if abs(calls - want) > 2 * p["moe_layers"]:
        ctx.log("grouped_experts_roofline: the match holds another kernel, nothing is reported")
        return None
    per_call = lambda total: total / moe["steps"] / p["moe_layers"]
    least, _bound = flops.roofline_seconds(2 * p["expert"] * per_call(moe["picks_held"]),
                                           2 * p["expert"] * per_call(moe["experts_hit"]), ctx.peaks)
    return 100.0 * least * calls / seconds if least else None
