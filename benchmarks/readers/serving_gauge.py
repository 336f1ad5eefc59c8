"""A gauge of `profiler.serving_summary()` over the window (the kind resets
it at window open).  `times_slots` turns the occupied share into slots."""


def read(ctx, args):
    value = ctx.counters.get("serving", {}).get(args["key"])
    if value is None:
        return None
    return value * ctx.counters["slots"] if args.get("times_slots") else value
