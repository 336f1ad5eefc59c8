"""A gauge of one page group from `profiler.window_cache_summary()` over the
window (the kind resets it at window open): `slot_pages_peak`, the most pages
one DECODING slot held in the group, which stays at `reach / page_size + 1` while
pages are released behind the window and grows with the context if they are
not.  A program without page groups gives nothing to read.

args: group (`window`, `full`), key."""


def read(ctx, args):
    return ((ctx.counters.get("window_cache") or {}).get(args["group"]) or {}).get(args["key"])
