"""From a profiler trace (`.xplane.pb`) to numbers: device busy time as the
union of the intervals in which an operation ran, the idle share, time per
operation name, and the longest idle gaps labelled by what the host was
doing.  Reads the file with `jax.profiler.ProfileData` and nothing else.

Device planes are named `/device:TPU:<n>`; their line `XLA Ops` holds one
event per executed operation (the other lines repeat the same time by
module, step or annotation, and would count it twice).  The traced window
is the host event `WINDOW_EVENT`, which the harness wraps around the traced
part of the run; it also ties the trace's clock to `time.perf_counter`.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_EVENT = "bench.traced_window"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_ops(profile):
    """{plane name: [(op name, start_ns, end_ns)]}, sorted by start."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            ev = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                  for e in line.events]
            out[plane.name] = sorted(ev, key=lambda t: t[1])
    return out


def host_events(profile, name):
    """[(start_ns, end_ns)] of every host event called `name`."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    out.append((float(e.start_ns), float(e.start_ns + e.duration_ns)))
    return sorted(out)


_HLO = re.compile(r"^%?(\S+) = (.*?)\s([\w-]+)\(")
_SHAPE = re.compile(r"\w+\[[\d,]*\]")


def short_name(name):
    """An event of `XLA Ops` is named by its whole HLO instruction; keep the
    instruction's name, its opcode and its first output shape."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    shape = _SHAPE.search(m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape.group(0) if shape else ''}".strip()


def union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy, lo, hi):
    """The intervals of [lo, hi] that `busy` (disjoint, sorted) leaves."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def label_gap(gap, spans):
    """The name of the host span that covers most of `gap`; spans are
    (name, start_ns, end_ns) on the trace's clock."""
    best, cover = "host (no span)", 0.0
    for name, a, b in spans:
        c = min(b, gap[1]) - max(a, gap[0])
        if c > cover:
            best, cover = name, c
    return best


def label_gaps(gaps, spans):
    """`label_gap(g, spans)` for every g of `gaps` (sorted, disjoint), in one
    sweep: near-linear where a call per gap walks every span for every gap.

    A span that covers a gap whole overlaps it by the gap's length, which no
    overlap exceeds, so of those only the first in the list can win: they sit
    in a heap by list index, entered once the sweep has passed their start,
    left from the top once they end before a gap does.  Every other span
    that overlaps a gap starts or ends inside it, and a point lies inside one
    gap at most.  `label_gap` then chooses among those, in the list's order."""
    by_start = sorted(range(len(spans)), key=lambda i: spans[i][1])
    by_end = sorted(range(len(spans)), key=lambda i: spans[i][2])
    starts = [spans[i][1] for i in by_start]
    ends = [spans[i][2] for i in by_end]
    out, covering, k = [], [], 0
    for g0, g1 in gaps:
        first_inside = bisect.bisect_right(starts, g0)
        for i in by_start[k:first_inside]:
            heapq.heappush(covering, i)
        k = first_inside
        while covering and spans[covering[0]][2] < g1:
            heapq.heappop(covering)
        inside = (by_start[k:bisect.bisect_left(starts, g1)]
                  + by_end[bisect.bisect_right(ends, g0):bisect.bisect_left(ends, g1)])
        out.append(label_gap((g0, g1), [spans[i] for i in sorted(covering[:1] + inside)]))
    return out


def reduce(profile, spans_perf=(), window_perf=None, top=10, log=None):
    """The whole reduction.

    spans_perf: (name, t0, t1) host spans in `time.perf_counter` seconds.
    window_perf: (t0, t1) of WINDOW_EVENT on the same clock, to tie clocks;
    without it the gaps are not labelled.
    log: takes one line, the counts of what was reduced.

    Returns busy_s and window_s (averaged over device planes), ops
    {name: seconds} and op_counts {name: events} summed over planes and
    divided by their number, device_ops and idle_gaps for the breakdown."""
    planes = device_ops(profile)
    if not planes:
        raise ValueError("the trace holds no device plane with an 'XLA Ops' line")
    marks = host_events(profile, WINDOW_EVENT)
    if marks:
        lo, hi = marks[0][0], marks[-1][1]
    else:
        lo = min(ev[0][1] for ev in planes.values() if ev)
        hi = max(max(e[2] for e in ev) for ev in planes.values() if ev)
    spans_ns = []
    if marks and window_perf is not None:
        shift = lo - window_perf[0] * 1e9
        spans_ns = [(n, a * 1e9 + shift, b * 1e9 + shift) for n, a, b in spans_perf]
        # a span outside the window overlaps no gap; the list's order stays
        spans_ns = [s for s in spans_ns if s[2] > lo and s[1] < hi]
    busy_total, ops, counts, gap_by_label, n_gaps = 0.0, {}, {}, {}, 0
    for ev in planes.values():
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in ev if b > lo and a < hi]
        busy = union([(a, b) for _, a, b in inside])
        busy_total += sum(b - a for a, b in busy)
        for n, a, b in inside:
            ops[n] = ops.get(n, 0.0) + (b - a)
            counts[n] = counts.get(n, 0) + 1
        idle = gaps(busy, lo, hi)
        n_gaps += len(idle)
        for g, lab in zip(idle, label_gaps(idle, spans_ns)):
            gap_by_label[lab] = gap_by_label.get(lab, 0.0) + (g[1] - g[0])
    if log:
        log(f"{sum(len(ev) for ev in planes.values())} device operations, {n_gaps} idle gaps "
            f"labelled by {len(spans_ns)} spans of {len(spans_perf)} handed over")
    k = len(planes)
    ops = {n: t / k / 1e9 for n, t in ops.items()}
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_total / k / 1e9,
        "window_s": (hi - lo) / 1e9,
        "ops": ops,
        "op_counts": {n: c / k for n, c in counts.items()},
        "device_ops": [[short_name(n), t] for n, t in rank(ops)],
        "idle_gaps": [[n, t / k / 1e9] for n, t in rank(gap_by_label)],
        "planes": k,
    }


def ops_matching(ops, needles):
    """Seconds (or events) of every operation whose name holds one of
    `needles`."""
    return sum(t for n, t in ops.items() if any(s in n for s in needles))
