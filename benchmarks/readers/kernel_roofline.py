"""A kernel's share of its roofline in the traced window: the least time the
chip could take for the work the kind counted (`flops.py`: the larger of
operations over peak FLOP/s and bytes over peak bytes/s) over the device
time of the operations whose names hold one of `match`.

The names hold no kernel's identity (every Pallas kernel is a
`tpu_custom_call`), so `calls_per_layer_step` guards the match: the matched
events have to number that many a layer a traced step.  Another count means
another kernel has joined or left the match, its time would pass for this
kernel's, and the reader returns nothing.

args: work (a key of the kind's `traced_work`, with its `steps`), match
(substrings of the device operations' names), calls_per_layer_step."""

from .. import flops
from ..trace_reduce import ops_matching


def read(ctx, args):
    work = ctx.counters.get("traced_work", {}).get(args["work"])
    if not ctx.trace or not work:
        return None
    calls = ops_matching(ctx.trace["op_counts"], args["match"])
    want = args["calls_per_layer_step"] * ctx.cfg["num_hidden_layers"] * work["steps"]
    if calls != want:
        ctx.log(f"kernel_roofline {args['work']}: {calls} matched calls, {want} expected: "
                "the match holds another kernel, nothing is reported")
        return None
    seconds = ops_matching(ctx.trace["ops"], args["match"])
    least, _bound = flops.roofline_seconds(work["flops"], work["bytes"], ctx.peaks)
    if not seconds or not least:
        return None
    return 100.0 * least / seconds
