"""The host's part of a scheduler tick, in ms a device step: the wall time of
the engine's `step()` calls less the time blocked in the fetch of tokens
(`wait`, the one phase in which the host waits on the device), over the steps
dispatched.  How far the device's step can shrink before the host sets the
pace.

`from: "counters"`: `profiler.serving_summary()["tick"]` over the whole timed
window (the kind resets it at window open), the engine's own always-on clock.
`from: "spans"`: the same from the `engine.tick.<phase>` spans (`FLAGS_trace`;
from the same stamps) that START inside the traced seconds: every phase but
`engine.tick.wait`, over the `engine.tick.dispatch` spans there; under 10 of
those nothing is reported.  In a traced run the first holds 47 s outside the
profiler and 3 inside, the second the 3 inside alone: their difference is what
the profiler and the span recording add to the host's part of a tick.

A program without the counter or the spans gives nothing to read.

args: from ("counters" | "spans")."""

PREFIX = "engine.tick."
LEAST_STEPS = 10


def split(ms):
    return ", ".join(f"{p} {v:.3f}" for p, v in ms.items())


def read(ctx, args):
    if args["from"] == "counters":
        tick = ctx.counters.get("serving", {}).get("tick")
        if not tick or not tick.get("steps"):
            return None
        ctx.log(f"tick_host (counters): {tick['steps']} steps in {tick['wall_s']:.3f}s of ticks, "
                f"wait_share {tick['wait_share']:.4f}; ms a step: "
                f"{split({p: 1e3 * s / tick['steps'] for p, s in tick['phases_s'].items()})}; longest ticks (ms): "
                f"{[(round(t['ms'], 1), max(t['phases_ms'], key=t['phases_ms'].get)) for t in tick['longest']]}")
        return tick["host_ms_mean"]
    if not ctx.trace_window or not ctx.spans:
        return None
    lo, hi = ctx.trace_window
    by_phase = {}
    for name, a, b in ctx.spans:
        if name.startswith(PREFIX) and lo <= a < hi:
            n, total = by_phase.get(name[len(PREFIX):], (0, 0.0))
            by_phase[name[len(PREFIX):]] = (n + 1, total + (b - a))
    steps = by_phase.get("dispatch", (0, 0.0))[0]
    if steps < LEAST_STEPS:
        return None
    ctx.log(f"tick_host (spans): {steps} steps start in the traced {hi - lo:.3f}s; ms a step: "
            f"{split({p: 1e3 * s / steps for p, (_, s) in sorted(by_phase.items())})}")
    return 1e3 * sum(s for p, (_, s) in by_phase.items() if p != "wait") / steps
