"""The decode steps' share of the chip's memory bandwidth over the window, for
a DeepSeek-V3.2 configuration: the bytes its decode steps must read
(`flops_deepseek_v32.decode_bytes`: the weights outside the routed experts
once a step, the held experts each step actually hit, the indexer's keys of
every row in context, the selected latent rows; the last three from the
program's `moe_summary()` and `sparse_attn_summary()`, counted inside the
compiled step) over the host's time in decode steps x peak bytes/s.  The
clients stream, so a step's tokens are fetched before the next is sent and
its host time holds its device time.  A program without those counters gives
nothing to read."""

from .. import flops_deepseek_v32 as flops
from ..weights_deepseek_v32 import model_cfg


def read(ctx, args):
    moe, sparse = ctx.counters.get("moe"), ctx.counters.get("sparse_attn")
    busy = ctx.counters.get("decode_busy_s")
    if not moe or not sparse or not busy:
        return None
    nbytes = flops.decode_bytes(model_cfg(ctx.cfg), moe["steps"], moe["experts_hit"],
                                sparse["context"], sparse["selected"])
    return 100.0 * nbytes / (busy * ctx.peaks["hbm_bytes_per_s"])
