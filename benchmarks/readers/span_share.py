"""The share of the traced window that lies under the program's spans of the
given names (their union, clipped to the window).

args: spans (names of `obs/trace.py` spans)."""


def read(ctx, args):
    if not ctx.trace_window or not ctx.spans:
        return None
    lo, hi = ctx.trace_window
    cuts = sorted((max(a, lo), min(b, hi)) for name, a, b in ctx.spans
                  if name in args["spans"] and b > lo and a < hi)
    covered, end = 0.0, lo
    for a, b in cuts:
        if b > end:
            covered += b - max(a, end)
            end = b
    return 100.0 * covered / (hi - lo)
