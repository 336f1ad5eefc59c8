"""The share of the traced window in which no operation ran on the device:
1 - union of the device-operation intervals over the window."""


def read(ctx, args):
    if not ctx.trace or not ctx.trace["window_s"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
