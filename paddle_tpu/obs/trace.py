"""Distributed request tracing: mint, propagate, record, export.

A trace context is the pair ``(trace_id, parent_span_id)``.  The router (or
the first hop that sees a request) mints a ``trace_id``; every stage records
a *completed* span — name, two ``perf_counter`` stamps, status, small attrs —
into one bounded module-level buffer guarded by one lock.  Recording is pure
host-side Python (a dict append); it never touches a tensor, never syncs the
device, and is therefore safe inside the sanitizer's steady-state zones and
inside the engine scheduler's hot loop.

Span recording is a no-op unless ``FLAGS_trace`` is set, so the untraced
serving path pays one dict lookup per would-be span.  Context *minting* is
always on — error bodies carry a ``trace_id`` even when span recording is
off, so a 502 can be joined to its span tree the moment tracing is enabled.

What a record costs (``timeit`` on the sandbox's CPU, PR 37; the engine's hot
loop pays it whenever tracing is on, so the engine records at most six spans a
tick, not one a request): ``record()`` with the flag on 4.4 us (8.4 us while
an id was a ``uuid.uuid4()``, 3.0 us of it the id), with the flag off 0.44 us;
an id 0.38 us; ``enabled()`` 0.08 us.  Ids are a per-process random prefix and
a counter (``_seed_ids``).

Cross-process propagation rides two hop headers next to ``X-Deadline-Ms``:

    X-Trace-Id:    16-hex trace id, same for every hop of one request
    X-Parent-Span: span id of the caller's enclosing span (the router's
                   ``replica.forward`` attempt, or the client's own span)

The buffer is queryable as flat spans (``spans``), a per-request tree
(``tree``, served on ``GET /trace/<id>``), or Chrome-trace/Perfetto JSON
(``chrome_trace``, load in ``chrome://tracing`` or ui.perfetto.dev).
"""

import collections
import contextlib
import itertools
import os
import threading
import time

from ..framework import core as _core

HDR_TRACE = "X-Trace-Id"
HDR_PARENT = "X-Parent-Span"

_DEFAULT_CAPACITY = 4096

# one lock for every mutation of the span buffer and its counters; sections
# are tiny and allocation-light, and nothing is called while holding it
_mu = threading.Lock()
_spans = collections.deque(maxlen=_DEFAULT_CAPACITY)
_capacity = _DEFAULT_CAPACITY
_recorded = 0
_dropped = 0

# perf_counter -> wall-clock anchor, taken once at import: spans carry
# monotonic stamps at the call sites (cheap, never steps backwards) but
# export as epoch-based timestamps so traces from separate processes
# (router + replicas) line up on one timeline
_T0_WALL = time.time()
_T0_PERF = time.perf_counter()


def enabled():
    """Span recording on?  (``FLAGS_trace``; minting ids is always on.)"""
    try:
        return bool(_core.flag("FLAGS_trace"))
    except Exception:
        return False


def _seed_ids():
    """Ids are 16 hex: 8 the process drew from the OS once, 8 a counter that
    starts at a drawn value.  Two processes mint the same id only if they
    drew the same prefix (1 in 2**32 a pair) AND their counters overlap; a
    forked child draws anew.  `count.__next__` is one C call under the GIL:
    no lock."""
    global _id_prefix, _id_next
    _id_prefix = os.urandom(4).hex()
    _id_next = itertools.count(int.from_bytes(os.urandom(4), "big")).__next__


_seed_ids()
os.register_at_fork(after_in_child=_seed_ids)


def new_trace_id():
    return f"{_id_prefix}{_id_next() & 0xFFFFFFFF:08x}"


new_span_id = new_trace_id


def ctx_from_headers(headers):
    """Decode an incoming hop's trace context from its HTTP headers.

    Returns ``(trace_id, parent_span_id)`` or ``None`` when the caller sent
    no ``X-Trace-Id`` (then the receiver mints its own root context).
    """
    if headers is None:
        return None
    tid = headers.get(HDR_TRACE)
    if not tid:
        return None
    return (str(tid), str(headers.get(HDR_PARENT) or ""))


def _ensure_capacity_locked():
    global _spans, _capacity
    try:
        cap = int(_core.flag("FLAGS_obs_buffer_events"))
    except Exception:
        cap = _DEFAULT_CAPACITY
    cap = max(16, cap)
    if cap != _capacity:
        _spans = collections.deque(_spans, maxlen=cap)
        _capacity = cap


def record(name, trace_id, *, t0, t1, span_id=None, parent_id=None,
           status="ok", **attrs):
    """Record one completed span from two ``perf_counter`` stamps.

    Returns the span id (minted when not given) so callers can parent later
    children on it even before the span itself completes — pre-mint with
    ``new_span_id()``, hand it to children, record the parent at the end.
    No-op (returns ``span_id`` unchanged) unless ``FLAGS_trace`` is on.
    """
    if not trace_id or not enabled():
        return span_id or ""
    sid = span_id or new_span_id()
    span_rec = {
        "name": str(name),
        "trace_id": str(trace_id),
        "span_id": sid,
        "parent_id": str(parent_id or ""),
        "ts": _T0_WALL + (t0 - _T0_PERF),
        "dur_s": max(0.0, t1 - t0),
        "status": str(status),
        "pid": os.getpid(),
        "thread": threading.current_thread().name,
    }
    clean = {k: v for k, v in attrs.items() if v is not None}
    if clean:
        span_rec["attrs"] = clean
    global _recorded, _dropped
    with _mu:
        _ensure_capacity_locked()
        if len(_spans) == _spans.maxlen:
            _dropped += 1
        _spans.append(span_rec)
        _recorded += 1
    # mirror terminal completions into the flight-recorder ring AFTER
    # releasing _mu (single-lock-at-a-time: no ordering with flight._mu)
    try:
        from . import flight
        flight.note_span(span_rec)
    except Exception:
        pass
    return sid


class _OpenSpan:
    """Mutable handle yielded by ``span()``: set attrs/status before exit."""

    __slots__ = ("span_id", "status", "attrs")

    def __init__(self, span_id):
        self.span_id = span_id
        self.status = "ok"
        self.attrs = {}


@contextlib.contextmanager
def span(name, trace_id, parent_id=None, span_id=None, **attrs):
    """Context manager recording one span around a block.

    The span id is minted eagerly so the block can hand it to children
    (``s.span_id``); an exception marks the span ``error`` and re-raises.
    """
    s = _OpenSpan(span_id or new_span_id())
    s.attrs.update(attrs)
    t0 = time.perf_counter()
    try:
        yield s
    except BaseException:
        s.status = "error"
        raise
    finally:
        record(name, trace_id, t0=t0, t1=time.perf_counter(),
               span_id=s.span_id, parent_id=parent_id, status=s.status,
               **s.attrs)


def spans(trace_id=None):
    """Flat snapshot of buffered spans, optionally for one trace."""
    with _mu:
        out = list(_spans)
    if trace_id:
        out = [s for s in out if s["trace_id"] == trace_id]
    return out


def trace_ids():
    """Distinct trace ids currently buffered, most recent last."""
    seen = {}
    for s in spans():
        seen[s["trace_id"]] = True
    return list(seen)


def tree(trace_id):
    """Per-request span tree for ``GET /trace/<id>``.

    Returns a list of root nodes (spans whose parent is unknown or remote),
    each a span dict plus ``children`` sorted by start time.
    """
    flat = sorted(spans(trace_id), key=lambda s: (s["ts"], s["span_id"]))
    nodes = {s["span_id"]: dict(s, children=[]) for s in flat}
    roots = []
    for s in flat:
        node = nodes[s["span_id"]]
        parent = nodes.get(s["parent_id"])
        if parent is not None and parent is not node:
            parent["children"].append(node)
        else:
            roots.append(node)
    return roots


def chrome_trace(trace_id=None):
    """Chrome-trace/Perfetto JSON (``chrome://tracing`` / ui.perfetto.dev)."""
    events = []
    for s in spans(trace_id):
        args = dict(s.get("attrs", {}))
        args.update(trace_id=s["trace_id"], span_id=s["span_id"],
                    parent_id=s["parent_id"], status=s["status"])
        events.append({
            "name": s["name"],
            "cat": "paddle_tpu",
            "ph": "X",
            "ts": s["ts"] * 1e6,
            "dur": s["dur_s"] * 1e6,
            "pid": s["pid"],
            "tid": s["thread"],
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def stats():
    """Buffer counters for /metrics (recorded/dropped/buffered)."""
    with _mu:
        return {
            "spans_recorded": _recorded,
            "spans_dropped": _dropped,
            "spans_buffered": len(_spans),
        }


def reset():
    global _recorded, _dropped
    with _mu:
        _spans.clear()
        _recorded = 0
        _dropped = 0
