"""paddle.profiler (reference: python/paddle/profiler/profiler.py over the
native CUPTI tracer) — TPU-native: wraps jax.profiler (XPlane/libtpu) with
the reference's API shape (Profiler, RecordEvent, make_scheduler,
export_chrome_tracing)."""

from __future__ import annotations

import contextlib
import enum
import os
import threading
import time

import jax


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        period = closed + ready + record
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        prof._export_dir = dir_name

    return handler


def export_protobuf(dir_name, worker_name=None):
    return export_chrome_tracing(dir_name, worker_name)


# ---------------------------------------------------------------------------
# Async step-pipeline gauges (ISSUE 4): the hapi fit loop reports, per step,
# how long the host spent dispatching work vs blocked on the device
# (backpressure + log-boundary materialization) and how many steps were in
# flight.  wall - dispatch - host_blocked estimates pure device-bound time
# the host successfully hid.
# ---------------------------------------------------------------------------

_step_gauges = {
    "steps": 0,
    "dispatch_s": 0.0,
    "host_blocked_s": 0.0,
    "wall_s": 0.0,
    "inflight_sum": 0,
    "inflight_max": 0,
}

# One lock for every gauge dict in this module.  The counters are written
# from the engine scheduler thread, the HTTP front door, the engine
# supervisor, and the training loop concurrently; +=-on-dict-entry is NOT
# atomic under free-threading (and only incidentally so under the GIL), so
# every record/reset/summary takes this lock.  All sections are tiny and
# allocation-free — the lock never shows up in profiles.
_counters_lock = threading.Lock()


def record_step(dispatch_s=0.0, host_blocked_s=0.0, inflight=0, wall_s=0.0):
    """One training step's host-time split + in-flight ring depth."""
    with _counters_lock:
        g = _step_gauges
        g["steps"] += 1
        g["dispatch_s"] += dispatch_s
        g["host_blocked_s"] += host_blocked_s
        g["wall_s"] += wall_s
        g["inflight_sum"] += inflight
        if inflight > g["inflight_max"]:
            g["inflight_max"] = inflight


def _reset_step_locked():
    for k in _step_gauges:
        _step_gauges[k] = 0 if isinstance(_step_gauges[k], int) else 0.0


def reset_step_breakdown():
    with _counters_lock:
        _reset_step_locked()


def step_breakdown():
    """Aggregated step-time split: host-blocked vs dispatch vs device
    estimate, plus the in-flight-depth gauge (avg/max)."""
    with _counters_lock:
        g = dict(_step_gauges)
    n = g["steps"]
    out = {"steps": n}
    if not n:
        return out
    out["dispatch_ms_avg"] = g["dispatch_s"] / n * 1e3
    out["host_blocked_ms_avg"] = g["host_blocked_s"] / n * 1e3
    out["wall_ms_avg"] = g["wall_s"] / n * 1e3
    out["device_ms_avg_est"] = max(
        0.0, (g["wall_s"] - g["dispatch_s"] - g["host_blocked_s"]) / n * 1e3
    )
    out["inflight_depth_avg"] = g["inflight_sum"] / n
    out["inflight_depth_max"] = g["inflight_max"]
    return out


# ---------------------------------------------------------------------------
# Serving gauges (ISSUE 5): the continuous-batching engine reports one tick
# per decode step (slot occupancy at that instant + admission-queue depth)
# and one record per finished request (TTFT, generated tokens, wall time from
# submit to finish).  tokens/s here is aggregate throughput over the engine's
# busy window, the number the ≥1.5x-vs-lock-step acceptance gate checks.
# ---------------------------------------------------------------------------

_TTFT_KEEP = 10000  # bound the percentile buffer; serving runs are long

# where a scheduler tick's wall time goes (the engine's `step()`, stamped at
# the phase boundaries; `inference/engine.py` `_TickClock`): evicting, admitting
# (prefill dispatch included), preparing the decode step's operands,
# dispatching it, blocked in the fetch of tokens (`wait`: the one phase in
# which the host waits on the device), delivering them (callbacks,
# finishes), and the rest.  Disjoint; they add up to the tick
TICK_PHASES = (
    "evict", "admit", "prepare", "dispatch", "wait", "deliver", "other",
)
_TICK_LONGEST_KEEP = 8


def _new_tick_gauges():
    return {"steps": 0, "wall_s": 0.0, "phases_s": [0.0] * len(TICK_PHASES),
            "longest": []}


_serving_gauges = {
    "requests": 0,
    "tokens": 0,
    "ttfts_s": [],
    "busy_s": 0.0,
    "ticks": 0,
    "occupancy_sum": 0.0,
    "occupancy_peak": 0.0,
    "queue_depth_sum": 0,
    "queue_depth_max": 0,
    "faults": {},  # serving fault-domain counters, by kind
    # deadline-miss-rate EWMA SET by the engine at each terminal
    # resolution (a rate, not an accumulated counter; last writer wins —
    # one engine per serving process in production)
    "deadline_miss_rate": 0.0,
    # slots seated and slots left (a request makes two), and the times the
    # engine let NOTHING stay in flight on the device, by what forced it
    "membership_changes": 0,
    "drains": {},
    # every `step()` of the engine by phase (TICK_PHASES), and the longest:
    # `_tick_view` renders it
    "tick": _new_tick_gauges(),
}

# serving fault-domain counter kinds (PR 6): engine restarts, requests
# failed by a restart, deadline evictions/admission rejections,
# cancellations, and non-finite logit windows
_SERVING_FAULT_KINDS = (
    "restarts", "restarted_requests", "deadline_miss", "rejected_deadline",
    "cancelled", "nonfinite",
)


def record_serving_fault(kind, n=1):
    """Count one serving fault-domain event (see _SERVING_FAULT_KINDS;
    unknown kinds are counted too so call sites never have to guard)."""
    with _counters_lock:
        f = _serving_gauges["faults"]
        f[kind] = f.get(kind, 0) + int(n)


# why the engine fetched everything it had dispatched, leaving the device
# nothing to run while the host worked: a request that watches for EOS (its
# values decide membership), a length bound with work queued behind it and no
# slot left to run, an admission that must have the host's mirrors whole (a
# handoff import), a first token that a draft is made from (speculation), an
# eviction, an upload of the token vector from the host, a stop, a poisoned
# step.  Since PR 32 a finish, an admission and a prefill's first token are
# none of them: `length`, `admission` and `first_token` read 0 on plain
# streaming traffic.  All are rendered, at 0 too
_DRAIN_CAUSES = (
    "eos_watch", "length", "admission", "first_token", "evict", "rebuild",
    "stop", "poison",
)


def record_serving_drain(cause):
    """The engine fetched every step in flight, for `cause`."""
    with _counters_lock:
        d = _serving_gauges["drains"]
        d[cause] = d.get(cause, 0) + 1


def record_membership_change():
    """A slot was seated or left."""
    with _counters_lock:
        _serving_gauges["membership_changes"] += 1


def record_deadline_miss_rate(rate):
    """Publish the engine's deadline-miss-rate EWMA (ISSUE 16): the engine
    owns the blend (engine._MISS_EWMA_ALPHA over terminal resolutions);
    this just makes the current value scrapeable from /metrics next to the
    monotonic `deadline_miss` fault counter."""
    with _counters_lock:
        _serving_gauges["deadline_miss_rate"] = float(rate)


def record_serving_request(ttft_s, tokens, wall_s):
    """One finished generation request: time-to-first-token, tokens emitted,
    submit->finish wall time."""
    with _counters_lock:
        g = _serving_gauges
        g["requests"] += 1
        g["tokens"] += int(tokens)
        g["ttfts_s"].append(float(ttft_s))
        if len(g["ttfts_s"]) > _TTFT_KEEP:
            del g["ttfts_s"][: -_TTFT_KEEP]


def record_serving_tick(occupancy, queue_depth=0, busy_s=0.0, phases=None,
                        end_s=0.0):
    """One scheduler tick of the engine, in one critical section.  Where it
    dispatched a decode step: the fraction of slots active in it, the queued
    requests, and the step's wall time (summed into the busy window for
    tokens/s); `occupancy` None says it dispatched none.  `phases`: the
    tick's seconds by TICK_PHASES, counted for EVERY tick (one that only
    flushes is host time between two device steps all the same); the longest
    ticks are kept with `end_s`, the `perf_counter` at their end."""
    with _counters_lock:
        g = _serving_gauges
        if occupancy is not None:
            g["ticks"] += 1
            g["occupancy_sum"] += float(occupancy)
            if occupancy > g["occupancy_peak"]:
                g["occupancy_peak"] = float(occupancy)
            g["queue_depth_sum"] += int(queue_depth)
            g["busy_s"] += float(busy_s)
            if queue_depth > g["queue_depth_max"]:
                g["queue_depth_max"] = int(queue_depth)
        if phases is not None:
            t = g["tick"]
            t["steps"] += occupancy is not None
            wall, total = 0.0, t["phases_s"]
            for i, sec in enumerate(phases):
                total[i] += sec
                wall += sec
            t["wall_s"] += wall
            longest = t["longest"]
            if len(longest) < _TICK_LONGEST_KEEP or wall > longest[-1][0]:
                longest.append((wall, end_s, tuple(phases)))
                longest.sort(reverse=True)
                del longest[_TICK_LONGEST_KEEP:]


def _tick_view(t):
    """The `tick` block of `serving_summary()` and `metrics_snapshot()`, from
    the raw gauges (caller holds _counters_lock): `host_s` is the ticks' wall
    time less the time blocked on the device, `host_ms_mean` that per
    dispatched step (None before one), `longest` the longest ticks first."""
    named = lambda secs, k=1.0: {p: k * s for p, s in zip(TICK_PHASES, secs)}
    wall, wait = t["wall_s"], t["phases_s"][TICK_PHASES.index("wait")]
    return {
        "steps": t["steps"], "wall_s": wall, "phases_s": named(t["phases_s"]),
        "host_s": wall - wait,
        "host_ms_mean": 1e3 * (wall - wait) / t["steps"] if t["steps"] else None,
        "wait_share": wait / wall if wall else None,
        "longest": [
            {"ms": 1e3 * w, "at_s": at, "phases_ms": named(ph, 1e3)}
            for w, at, ph in t["longest"]
        ],
    }


def _reset_serving_locked():
    _serving_gauges.update(
        requests=0, tokens=0, ttfts_s=[], busy_s=0.0, ticks=0,
        occupancy_sum=0.0, occupancy_peak=0.0, queue_depth_sum=0,
        queue_depth_max=0, faults={}, deadline_miss_rate=0.0,
        membership_changes=0, drains={}, tick=_new_tick_gauges(),
    )


def reset_serving():
    with _counters_lock:
        _reset_serving_locked()


# ---------------------------------------------------------------------------
# Paged-KV gauges (ISSUE 7): the paged serving engine reports admission-time
# prefix-cache outcomes (hit/miss, prompt tokens whose prefill was skipped,
# copy-on-write page copies) and allocator events (cache evictions, cache
# commits), plus a per-tick page-occupancy gauge so peak arena pressure is
# visible next to slot occupancy.  Separately, flash-attention records every
# Pallas->XLA fallback by reason so "why is attention slow" is answerable
# from the summary instead of from scrolling warnings.
# ---------------------------------------------------------------------------

_paging_gauges = {
    "prefix_hits": 0,
    "prefix_misses": 0,
    "prefill_tokens_saved": 0,
    "cow_copies": 0,
    "cache_evictions": 0,
    "cache_commits": 0,
    "ticks": 0,
    "pages_used_sum": 0,
    "pages_used_peak": 0,
    "pages_total": 0,
}

_flash_fallbacks = {}  # reason -> count of Pallas-ineligible compilations
_flash_pallas = {}  # kernel -> count of Pallas kernel compilations dispatched


def record_flash_fallback(reason):
    """One flash-attention dispatch that fell back from the Pallas kernel to
    the XLA blockwise path; counted per compiled shape, keyed by reason."""
    with _counters_lock:
        _flash_fallbacks[reason] = _flash_fallbacks.get(reason, 0) + 1


def flash_fallback_summary():
    with _counters_lock:
        return dict(_flash_fallbacks)


def reset_flash_fallbacks():
    with _counters_lock:
        _flash_fallbacks.clear()


def record_flash_pallas_call(kernel):
    """One flash-attention dispatch that took a Pallas kernel (the positive
    counterpart to record_flash_fallback): counted per compiled shape, keyed
    by kernel name — benches prove the fast path ran by this moving."""
    with _counters_lock:
        _flash_pallas[kernel] = _flash_pallas.get(kernel, 0) + 1


def flash_pallas_summary():
    with _counters_lock:
        return dict(_flash_pallas)


def reset_flash_pallas():
    with _counters_lock:
        _flash_pallas.clear()


_PAGED_WALK_FIELDS = (
    "b", "sq", "heads_per_step", "grid_steps", "kv_bytes_per_step", "pages_per_step", "kv_operands")
_paged_walks = {}  # a tuple of `_PAGED_WALK_FIELDS` a distinct walk, in order of first trace


def record_paged_walk(**geometry):
    """The geometry the page-walk decode kernel took for one traced call
    (ops/flash_attention.py picks it from the static shape): recorded at
    trace time, like `record_flash_pallas_call`."""
    key = tuple(int(geometry[f]) for f in _PAGED_WALK_FIELDS)
    with _counters_lock:
        _paged_walks[key] = None


def paged_walk_summary():
    """One entry per distinct walk traced since the last reset (a model's
    layers trace the same one): slots `b`, q rows a slot `sq`, KV heads a
    grid step takes, grid steps a call (`b * kv_heads / heads_per_step` where
    the walk loops inside the step, times the table's columns where a page
    is a grid step), bytes one copy block holds (`pages_per_step` pages of
    `heads_per_step` heads from each of `kv_operands` arenas: 2, K and V,
    or 1 where the values are the keys' rows)."""
    with _counters_lock:
        return [dict(zip(_PAGED_WALK_FIELDS, key)) for key in _paged_walks]


_GROUPED_EXPERTS_FIELDS = ("tokens", "held", "expert_bytes", "experts_in_flight", "grid_steps")
_grouped_experts = {}  # a tuple of `_GROUPED_EXPERTS_FIELDS` a distinct expert layer, in order of first trace


def record_grouped_experts(**geometry):
    """The geometry of one traced `grouped_experts` call (ops/grouped_experts.py),
    recorded at trace time like `record_paged_walk`."""
    key = tuple(int(geometry[f]) for f in _GROUPED_EXPERTS_FIELDS)
    with _counters_lock:
        _grouped_experts[key] = None


def grouped_experts_summary():
    """One entry per distinct expert layer traced through the grouped-expert
    kernel since the last reset (a model's layers trace the same one): the
    step's `tokens`, the experts `held`, the bytes of one expert's three
    matrices, the experts whose copies are in flight or computing, and the
    grid steps a call (1: the walk over the hit experts loops inside it)."""
    with _counters_lock:
        return [dict(zip(_GROUPED_EXPERTS_FIELDS, key)) for key in _grouped_experts]


_KDA_DECODE_FIELDS = ("slots", "heads", "dk", "dv", "heads_per_step", "grid_steps", "state_bytes_per_step")
_kda_decodes = {}  # a tuple of `_KDA_DECODE_FIELDS` a distinct KDA decode step, in order of first trace


def record_kda_decode(**geometry):
    """The geometry of one traced `kda_state_step` call (ops/kda_decode.py),
    recorded at trace time like `record_grouped_experts`."""
    key = tuple(int(geometry[f]) for f in _KDA_DECODE_FIELDS)
    with _counters_lock:
        _kda_decodes[key] = None


def kda_decode_summary():
    """One entry per distinct KDA decode step traced through the state kernel
    since the last reset (a model's layers trace the same one): `slots`,
    `heads`, the state's `dk` x `dv` a head, the heads a grid step takes, the
    grid steps a call, and the state's bytes one grid step reads (and writes
    back)."""
    with _counters_lock:
        return [dict(zip(_KDA_DECODE_FIELDS, key)) for key in _kda_decodes]


_MLA_PREFILL_FIELDS = ("rows", "heads", "kb", "heads_per_step", "grid_steps", "mask", "vmem_bytes")
_mla_prefills = {}  # a tuple of `_MLA_PREFILL_FIELDS` a distinct MLA prefill attention, in order of first trace


def record_mla_prefill(**geometry):
    """The geometry of one traced `mla_prefill` call (ops/mla_prefill.py),
    recorded at trace time like `record_grouped_experts`; `mask` is `causal`
    or `selected`."""
    key = tuple(str(geometry[f]) if f == "mask" else int(geometry[f]) for f in _MLA_PREFILL_FIELDS)
    with _counters_lock:
        _mla_prefills[key] = None


def mla_prefill_summary():
    """One entry per distinct chunk attention traced through the MLA prefill
    kernel since the last reset (a model's layers trace the same one): the
    chunk's `rows`, `heads`, the key block `kb`, the heads a grid step takes,
    the grid steps a call (head groups x key blocks of the context's table;
    those past the context skip their work), the mask's form and the VMEM
    the call's blocks, scratch and temporaries take."""
    with _counters_lock:
        return [dict(zip(_MLA_PREFILL_FIELDS, key)) for key in _mla_prefills]


_TOPK_SELECT_FIELDS = ("rows", "cols", "row_tile", "kb", "vmem_bytes")
_topk_selects = {}  # a tuple of `_TOPK_SELECT_FIELDS` a distinct top-k selection, in order of first trace


def record_topk_select(**geometry):
    """The geometry of one traced `topk_select` call (ops/topk_select.py),
    recorded at trace time like `record_grouped_experts`."""
    key = tuple(int(geometry[f]) for f in _TOPK_SELECT_FIELDS)
    with _counters_lock:
        _topk_selects[key] = None


def topk_select_summary():
    """One entry per distinct chunk selection traced through the top-k
    kernel since the last reset (a model's layers trace the same one): the
    chunk's `rows`, the page table's `cols`, the rows a grid step holds, the
    key block `kb` (blocks past the context are not read) and the VMEM the
    call's blocks, scratch and temporaries take."""
    with _counters_lock:
        return [dict(zip(_TOPK_SELECT_FIELDS, key)) for key in _topk_selects]


def reset():
    """Zero EVERY counter family (step, serving, paging, router, flash
    fallbacks) in one critical section, so one run's router/serving gauges
    can't leak into the next run's printed summary; the per-family
    reset_*() helpers remain for callers that want to keep the others."""
    with _counters_lock:
        _reset_step_locked()
        _reset_serving_locked()
        _reset_paging_locked()
        _reset_speculation_locked()
        _reset_lora_locked()
        _reset_router_locked()
        _reset_autoscale_locked()
        _reset_disagg_locked()
        _reset_mesh_locked()
        _reset_kv_quant_locked()
        _reset_session_locked()
        _flash_fallbacks.clear()
        _flash_pallas.clear()
        _paged_walks.clear()
        _grouped_experts.clear()
        _kda_decodes.clear()
        _mla_prefills.clear()
        _topk_selects.clear()
        _reset_moe_locked()


def metrics_snapshot():
    """Raw one-lock snapshot of every gauge family for the /metrics
    renderer (paddle_tpu.obs.metrics).  Unlike the *_summary() helpers this
    never omits zero-valued counters, so exported metric names are stable
    whether or not traffic has flowed yet."""
    with _counters_lock:
        serving = dict(_serving_gauges)
        serving["ttfts_s"] = list(serving["ttfts_s"])
        serving["faults"] = dict(serving["faults"])
        serving["drains"] = dict(serving["drains"])
        serving["tick"] = _tick_view(serving["tick"])
        router = dict(_router_gauges)
        router["replica_states"] = dict(router["replica_states"])
        return {
            "step": dict(_step_gauges),
            "serving": serving,
            "paging": dict(_paging_gauges),
            "speculation": dict(_spec_gauges),
            "lora": dict(_lora_gauges),
            "router": router,
            "autoscale": dict(_autoscale_gauges),
            "disagg": dict(_disagg_gauges),
            "mesh": dict(_mesh_gauges),
            "kv_quant": dict(_kv_quant_gauges),
            "sessions": dict(_session_gauges),
            "flash_fallbacks": dict(_flash_fallbacks),
            "flash_pallas": dict(_flash_pallas),
        }


def record_prefix_lookup(hit, tokens_saved=0, cow_copies=0):
    """One admission-time prefix-cache lookup: whether any cached prefix was
    reused, how many prompt tokens skipped prefill, and how many shared
    pages were copy-on-written for the new reader."""
    with _counters_lock:
        g = _paging_gauges
        if hit:
            g["prefix_hits"] += 1
            g["prefill_tokens_saved"] += int(tokens_saved)
            g["cow_copies"] += int(cow_copies)
        else:
            g["prefix_misses"] += 1


def record_paging_event(kind, n=1):
    """Count an allocator event: 'cache_evictions' or 'cache_commits'."""
    with _counters_lock:
        g = _paging_gauges
        g[kind] = g.get(kind, 0) + int(n)


def record_paging_tick(pages_used, pages_total):
    """One engine step's page-pool occupancy snapshot."""
    with _counters_lock:
        g = _paging_gauges
        g["ticks"] += 1
        g["pages_used_sum"] += int(pages_used)
        g["pages_total"] = int(pages_total)
        if pages_used > g["pages_used_peak"]:
            g["pages_used_peak"] = int(pages_used)


def _reset_paging_locked():
    for k in _paging_gauges:
        _paging_gauges[k] = 0


def reset_paging():
    with _counters_lock:
        _reset_paging_locked()


def paging_summary():
    """Aggregated paged-KV metrics: prefix hit rate, prefill tokens saved,
    COW copies, cache churn, and mean/peak page occupancy."""
    with _counters_lock:
        g = dict(_paging_gauges)
    out = {}
    lookups = g["prefix_hits"] + g["prefix_misses"]
    if lookups:
        out["prefix_lookups"] = lookups
        out["prefix_hits"] = g["prefix_hits"]
        out["prefix_hit_rate"] = g["prefix_hits"] / lookups
        out["prefill_tokens_saved"] = g["prefill_tokens_saved"]
        out["cow_copies"] = g["cow_copies"]
    if g["cache_evictions"]:
        out["cache_evictions"] = g["cache_evictions"]
    if g["cache_commits"]:
        out["cache_commits"] = g["cache_commits"]
    if g["ticks"]:
        out["pages_used_mean"] = g["pages_used_sum"] / g["ticks"]
        out["pages_used_peak"] = g["pages_used_peak"]
        out["pages_total"] = g["pages_total"]
    return out


# ---------------------------------------------------------------------------
# Mesh-topology gauges (ISSUE 14): the engine records its device mesh at
# construction — total visible devices, tensor-parallel degree, and the
# static per-step allreduce count GSPMD inserts for the row-parallel outputs
# — so /metrics and the flight recorder can state which topology a replica
# is serving on.  Pure descriptors (set, not accumulated).
# ---------------------------------------------------------------------------

_mesh_gauges = {
    "devices": 0,            # jax devices visible to the process
    "tp": 1,                 # tensor-parallel degree ('mp' axis size)
    "cp": 1,                 # context-parallel degree ('cp' axis, ISSUE 20)
    "allreduce_per_step": 0, # static GSPMD allreduces per compiled step
}


def record_mesh_topology(devices, tp, allreduce_per_step, cp=1):
    """Record the serving mesh topology (engine construction time)."""
    with _counters_lock:
        g = _mesh_gauges
        g["devices"] = int(devices)
        g["tp"] = int(tp)
        g["cp"] = int(cp)
        g["allreduce_per_step"] = int(allreduce_per_step)


def _reset_mesh_locked():
    _mesh_gauges["devices"] = 0
    _mesh_gauges["tp"] = 1
    _mesh_gauges["cp"] = 1
    _mesh_gauges["allreduce_per_step"] = 0


# session KV gauges (ISSUE 20): the engine pushes its SessionStore's
# stats() here on every mutation (bind / evict / reuse) so /metrics can
# render paddle_session_* without reaching into a live engine object
_session_gauges = {
    "sessions_resident": 0,
    "session_tenants": 0,
    "session_pages_pinned": 0,
    "session_prefill_tokens_saved_total": 0,
    "session_evictions_total": 0,
    "session_binds_total": 0,
}


def record_session_stats(stats):
    """Fold one SessionStore.stats() dict into the session gauges."""
    with _counters_lock:
        for k in _session_gauges:
            if k in stats:
                _session_gauges[k] = int(stats[k])


def _reset_session_locked():
    for k in _session_gauges:
        _session_gauges[k] = 0


def reset_sessions():
    with _counters_lock:
        _reset_session_locked()


def session_summary():
    """Latest session-KV gauges ({} until a SessionStore has pushed one) —
    consumed by the flight-recorder dump header."""
    with _counters_lock:
        g = dict(_session_gauges)
    if not any(g.values()):
        return {}
    return g


def reset_mesh():
    with _counters_lock:
        _reset_mesh_locked()


def mesh_summary():
    """Current mesh descriptors ({} until an engine has recorded one) —
    consumed by the flight-recorder dump header."""
    with _counters_lock:
        g = dict(_mesh_gauges)
    if not g["devices"]:
        return {}
    return g


# ---------------------------------------------------------------------------
# KV-quantization gauges (ISSUE 18): the paged engine records its arena
# precision at construction — mode, value-arena HBM bytes, scale-arena HBM
# bytes (set, not accumulated, like the mesh descriptors) — and counts
# quantize/dequantize page operations as decode traffic flows, so "which
# precision is this replica serving at and is the quant path actually hot"
# is answerable from /metrics and the flight-recorder header.
# ---------------------------------------------------------------------------

_kv_quant_gauges = {
    "mode": "none",      # arena storage precision ('none' | 'int8')
    "arena_bytes": 0,    # K/V value-arena HBM bytes across all layers
    "scale_bytes": 0,    # scale-arena HBM bytes (0 unless quantized)
    "quantize": 0,       # KV row-pairs quantized on write (per slot-step)
    "dequantize": 0,     # mapped pages dequantized per decode dispatch
}


def record_kv_quant(mode, arena_bytes, scale_bytes):
    """Record the paged arena's storage precision (engine construction)."""
    with _counters_lock:
        g = _kv_quant_gauges
        g["mode"] = str(mode)
        g["arena_bytes"] = int(arena_bytes)
        g["scale_bytes"] = int(scale_bytes)


def record_kv_quant_event(kind, n=1):
    """Count quant-path work: 'quantize' (KV row-pairs written through the
    quantizing scatters) or 'dequantize' (mapped pages the decode kernel
    dequantized in VMEM)."""
    with _counters_lock:
        g = _kv_quant_gauges
        g[kind] = g.get(kind, 0) + int(n)


def _reset_kv_quant_locked():
    _kv_quant_gauges["mode"] = "none"
    _kv_quant_gauges["arena_bytes"] = 0
    _kv_quant_gauges["scale_bytes"] = 0
    _kv_quant_gauges["quantize"] = 0
    _kv_quant_gauges["dequantize"] = 0


def reset_kv_quant():
    with _counters_lock:
        _reset_kv_quant_locked()


def kv_quant_summary():
    """Current KV-quant descriptors ({} while no QUANTIZED arena has been
    recorded — full-precision processes omit the flight-header section, the
    same contract as mesh/lora; /metrics still renders the family via
    metrics_snapshot())."""
    with _counters_lock:
        g = dict(_kv_quant_gauges)
    if g["mode"] == "none":
        return {}
    return g


# ---------------------------------------------------------------------------
# Speculative-decoding gauges (ISSUE 11): the paged engine reports one record
# per verify step — drafts proposed, drafts accepted, tokens emitted, and the
# slot-steps the step covered — so acceptance rate and mean emitted tokens
# per slot-step (the speculation multiplier) are answerable from the summary,
# /metrics, and the flight-recorder header.
# ---------------------------------------------------------------------------

# -- expert layer, sparse attention, arena rows (ISSUE 29) ------------------------
# counted inside the compiled decode step by a model that has such layers
# (models/deepseek_v32.py) and fetched with the step's tokens: no extra sync

_moe_gauges = {"steps": 0, "tokens": 0, "picks_held": 0, "experts_hit": 0, "max_load": 0}
_sparse_attn_gauges = {"rows": 0, "rows_over_topk": 0, "selected": 0, "context": 0}
_arena_bytes = {}  # cache kind (a token's rows, a slot's state) -> bytes over the layers that hold it, set at engine construction
# linear-attention layers with a fixed state per slot (ISSUE 33), counted the same way
_linear_attn_gauges = {"steps": 0, "live_slots": 0, "state_bytes_read": 0, "state_bytes_written": 0,
                       "prefill_rows": 0, "chunks_resumed": 0}
# layers whose rows are windowed, in a page group of their own (ISSUE 35): the rows in reach are
# counted inside the step, the pages by the engine's page manager
_window_gauges = {"steps": 0, "live_slots": 0, "rows_in_reach_full": 0, "rows_in_reach_window": 0}
_page_groups = {}  # group name -> gauges, set by the engine that was built last
# latent rows in reach of the decode steps' MLA layers (ISSUE 39), counted inside the step
_latent_walk_gauges = {"steps": 0, "live_slots": 0, "rows_in_reach": 0}
# gated short-convolution layers with a tail per slot (ISSUE 43), and the packed K/V rows in reach
# of the attention layers beside them, counted inside the step
_short_conv_gauges = {"steps": 0, "live_slots": 0, "tail_bytes_read": 0, "tail_bytes_written": 0,
                      "prefill_rows": 0, "chunks_resumed": 0}
_kv_walk_gauges = {"steps": 0, "live_slots": 0, "rows_in_reach": 0}


def record_moe_step(tokens, picks_held, experts_hit, max_load):
    """One decode step's routing, summed over the expert layers: tokens
    routed, picks that landed on an expert held here, held experts that got
    any pick, the heaviest held expert's load (the largest of any step)."""
    with _counters_lock:
        g = _moe_gauges
        g["steps"] += 1
        g["tokens"] += int(tokens)
        g["picks_held"] += int(picks_held)
        g["experts_hit"] += int(experts_hit)
        g["max_load"] = max(g["max_load"], int(max_load))


def record_sparse_attn_step(rows, rows_over_topk, selected, context):
    """One decode step of learned sparse attention: rows decoded, rows whose
    context exceeds the top-k (selection bites), rows selected and rows in
    context, both per layer."""
    with _counters_lock:
        g = _sparse_attn_gauges
        g["rows"] += int(rows)
        g["rows_over_topk"] += int(rows_over_topk)
        g["selected"] += int(selected)
        g["context"] += int(context)


def record_linear_attn_step(live_slots, state_bytes):
    """One decode step of the layers that hold a state per slot: live slots,
    and the bytes of state they read and wrote again, all such layers
    together (an idle slot's state is not touched)."""
    with _counters_lock:
        g = _linear_attn_gauges
        g["steps"] += 1
        g["live_slots"] += int(live_slots)
        g["state_bytes_read"] += int(state_bytes)
        g["state_bytes_written"] += int(state_bytes)


def record_linear_attn_prefill(rows, resumed):
    """One prefill program of such a model: the prompt rows its chunked
    scans went over, and whether it resumed from the state an earlier chunk
    of the same prompt left (a fresh prefill starts from zero)."""
    with _counters_lock:
        _linear_attn_gauges["prefill_rows"] += int(rows)
        _linear_attn_gauges["chunks_resumed"] += int(bool(resumed))


def record_window_rows(rows_full, rows_window, live_slots):
    """One decode step of a model with windowed layers, counted inside the
    step: the K/V rows in reach of the live slots, summed over the full
    layers (`pos + 1` a slot a layer) and over the windowed ones (`min(pos +
    1, reach)`)."""
    with _counters_lock:
        g = _window_gauges
        g["steps"] += 1
        g["live_slots"] += int(live_slots)
        g["rows_in_reach_full"] += int(rows_full)
        g["rows_in_reach_window"] += int(rows_window)


def record_latent_walk_step(rows, live_slots):
    """One decode step of a model whose MLA layers walk a latent page cache,
    counted inside the step: the rows in reach of the live slots (`pos + 1` a
    slot), summed over those layers."""
    with _counters_lock:
        g = _latent_walk_gauges
        g["steps"] += 1
        g["live_slots"] += int(live_slots)
        g["rows_in_reach"] += int(rows)


def record_short_conv_step(live_slots, tail_bytes):
    """One decode step of the short-convolution layers: live slots, and the
    bytes of tail they read and wrote again, all such layers together (an
    idle slot's tail is not touched)."""
    with _counters_lock:
        g = _short_conv_gauges
        g["steps"] += 1
        g["live_slots"] += int(live_slots)
        g["tail_bytes_read"] += int(tail_bytes)
        g["tail_bytes_written"] += int(tail_bytes)


def record_short_conv_prefill(rows, resumed):
    """One prefill program of such a model: the prompt rows it convolved,
    and whether it resumed from the tails an earlier chunk of the same
    prompt left (a fresh prefill starts from zeros)."""
    with _counters_lock:
        _short_conv_gauges["prefill_rows"] += int(rows)
        _short_conv_gauges["chunks_resumed"] += int(bool(resumed))


def record_kv_walk_step(rows, live_slots):
    """One decode step of a model whose attention layers walk a K/V page
    cache, counted inside the step: the rows in reach of the live slots
    (`pos + 1` a slot), summed over those layers."""
    with _counters_lock:
        g = _kv_walk_gauges
        g["steps"] += 1
        g["live_slots"] += int(live_slots)
        g["rows_in_reach"] += int(rows)


def record_page_group(name, pool_pages, reach, pages_live, slot_pages=0, prefill_pages=0,
                      released_behind=0):
    """What one page group of the engine's cache manager holds, as of now
    (`pool_pages`, `reach`: tokens, None for a group that keeps every row;
    `pages_live`) and since the last reset (the most pages live at once; the
    most pages ONE decoding slot held, and one prefilling; `released_behind`
    more pages given back behind a window while their slot ran on)."""
    with _counters_lock:
        g = _page_groups.setdefault(str(name), {
            "pages_live_peak": 0, "slot_pages_peak": 0, "prefill_pages_peak": 0, "released_behind": 0})
        g.update(pool_pages=int(pool_pages), reach=None if reach is None else int(reach),
                 pages_live=int(pages_live))
        g["pages_live_peak"] = max(g["pages_live_peak"], int(pages_live))
        g["slot_pages_peak"] = max(g["slot_pages_peak"], int(slot_pages))
        g["prefill_pages_peak"] = max(g["prefill_pages_peak"], int(prefill_pages))
        g["released_behind"] += int(released_behind)


def record_arena_bytes(by_kind):
    """Set at engine construction; the page groups are that engine's too."""
    with _counters_lock:
        _page_groups.clear()
        _arena_bytes.clear()
        _arena_bytes.update({str(k): int(v) for k, v in by_kind.items()})


def _reset_moe_locked():
    for g in (_moe_gauges, _sparse_attn_gauges, _linear_attn_gauges, _window_gauges, _latent_walk_gauges,
              _short_conv_gauges, _kv_walk_gauges):
        for k in g:
            g[k] = 0
    for g in _page_groups.values():  # sizes stay, what was counted goes
        for k in ("pages_live_peak", "slot_pages_peak", "prefill_pages_peak", "released_behind"):
            g[k] = 0


def reset_moe():
    with _counters_lock:
        _reset_moe_locked()


def moe_summary():
    """{} before any counted step; else the totals and `experts_hit_per_step`
    (expert-layer hits a step, all expert layers together)."""
    with _counters_lock:
        g = dict(_moe_gauges)
    if not g["steps"]:
        return {}
    g["experts_hit_per_step"] = g["experts_hit"] / g["steps"]
    return g


def sparse_attn_summary():
    """{} before any counted step; else the totals and `selected_share`."""
    with _counters_lock:
        g = dict(_sparse_attn_gauges)
    if not g["rows"]:
        return {}
    g["selected_share"] = g["selected"] / max(g["context"], 1)
    return g


def linear_attn_summary():
    """{} before any counted step or prefill; else the totals."""
    with _counters_lock:
        g = dict(_linear_attn_gauges)
    return g if g["steps"] or g["prefill_rows"] else {}


def latent_walk_summary():
    """{} before any counted step; else the totals and `rows_per_step`."""
    with _counters_lock:
        g = dict(_latent_walk_gauges)
    if not g["steps"]:
        return {}
    g["rows_per_step"] = g["rows_in_reach"] / g["steps"]
    return g


def short_conv_summary():
    """{} before any counted step or prefill; else the totals."""
    with _counters_lock:
        g = dict(_short_conv_gauges)
    return g if g["steps"] or g["prefill_rows"] else {}


def kv_walk_summary():
    """{} before any counted step; else the totals and `rows_per_step`."""
    with _counters_lock:
        g = dict(_kv_walk_gauges)
    if not g["steps"]:
        return {}
    g["rows_per_step"] = g["rows_in_reach"] / g["steps"]
    return g


def window_cache_summary():
    """{} unless an engine with a windowed page group was built; else by page
    group (`full`, `window`) what `record_page_group` keeps, and under
    `decode` what `record_window_rows` counted inside the decode steps."""
    with _counters_lock:
        if "window" not in _page_groups:
            return {}
        out = {name: dict(g) for name, g in _page_groups.items()}
        out["decode"] = dict(_window_gauges)
    return out


def arena_summary():
    """Bytes of the engine's cache per kind: a paged arena's row kinds and a
    slot-state buffer's names alike; a windowed page group's kinds are
    `<kind>.window` ({} before an engine is built)."""
    with _counters_lock:
        return dict(_arena_bytes)


_spec_gauges = {
    "steps": 0,       # verify dispatches
    "proposed": 0,    # draft tokens offered to the verifier
    "accepted": 0,    # draft tokens that matched the model's greedy path
    "emitted": 0,     # tokens emitted (accepted drafts + 1 bonus per slot)
    "slot_steps": 0,  # sum over steps of active slots (the 1x baseline)
}


def record_speculation(proposed, accepted, emitted, slots):
    """One speculative verify step: drafts proposed/accepted across the
    batch, tokens emitted, and how many active slots took part."""
    with _counters_lock:
        g = _spec_gauges
        g["steps"] += 1
        g["proposed"] += int(proposed)
        g["accepted"] += int(accepted)
        g["emitted"] += int(emitted)
        g["slot_steps"] += int(slots)


def _reset_speculation_locked():
    for k in _spec_gauges:
        _spec_gauges[k] = 0


def reset_speculation():
    with _counters_lock:
        _reset_speculation_locked()


def speculation_summary():
    """Aggregated speculation metrics: acceptance rate over proposed drafts
    and mean emitted tokens per slot-step (1.0 = no speedup; the plain
    engine's ratio by construction).  Empty dict before any verify step."""
    with _counters_lock:
        g = dict(_spec_gauges)
    if not g["steps"]:
        return {}
    out = {
        "steps": g["steps"],
        "proposed": g["proposed"],
        "accepted": g["accepted"],
        "emitted": g["emitted"],
    }
    if g["proposed"]:
        out["acceptance_rate"] = g["accepted"] / g["proposed"]
    if g["slot_steps"]:
        out["tokens_per_step"] = g["emitted"] / g["slot_steps"]
    return out


# ---------------------------------------------------------------------------
# LoRA-serving gauges (ISSUE 12): the adapter arena counts residency
# lookups (hit = adapter already device-resident, miss = a load was
# needed), uploads, and LRU evictions, plus resident/capacity gauges — so
# "is the arena thrashing" is answerable from the summary, /metrics, and
# the flight-recorder header.
# ---------------------------------------------------------------------------

_lora_gauges = {
    "loads": 0,            # adapter uploads into an arena slot
    "evictions": 0,        # LRU evictions of an idle resident adapter
    "residency_hits": 0,   # acquire() found the adapter resident
    "residency_misses": 0, # acquire() had to load (or park)
    "resident": 0,         # adapters currently resident (gauge)
    "capacity": 0,         # arena slots (gauge; excludes the base slot)
}


def record_lora_event(kind, n=1):
    """Count one adapter-arena event: 'loads', 'evictions',
    'residency_hits', 'residency_misses' (unknown kinds are counted too so
    call sites never have to guard)."""
    with _counters_lock:
        g = _lora_gauges
        g[kind] = g.get(kind, 0) + int(n)


def record_lora_residency(resident, capacity):
    """Latest resident-adapter count and arena capacity."""
    with _counters_lock:
        _lora_gauges["resident"] = int(resident)
        _lora_gauges["capacity"] = int(capacity)


def _reset_lora_locked():
    for k in _lora_gauges:
        _lora_gauges[k] = 0


def reset_lora():
    with _counters_lock:
        _reset_lora_locked()


def lora_summary():
    """Aggregated multi-tenant LoRA metrics: residency hit rate, loads,
    evictions, resident/capacity.  Empty dict before any acquire."""
    with _counters_lock:
        g = dict(_lora_gauges)
    lookups = g["residency_hits"] + g["residency_misses"]
    if not lookups and not g["loads"]:
        return {}
    out = {
        "loads": g["loads"],
        "evictions": g["evictions"],
        "resident": g["resident"],
        "capacity": g["capacity"],
    }
    if lookups:
        out["residency_lookups"] = lookups
        out["residency_hit_rate"] = g["residency_hits"] / lookups
    return out


# ---------------------------------------------------------------------------
# Router gauges (ISSUE 9): the multi-replica serving router counts every
# routed request, retry/failover, breaker transition, hedge, and brownout
# shed, plus a per-replica state snapshot — so "which replica is sick and
# how much traffic moved" is answerable from profiler.summary().
# ---------------------------------------------------------------------------

_router_gauges = {
    "requests": 0,
    "retries": 0,
    "failovers": 0,
    "breaker_trips": 0,
    "breaker_half_open": 0,
    "breaker_closes": 0,
    "hedges": 0,
    "hedge_wins": 0,
    "brownout_sheds": 0,
    "deadline_sheds": 0,
    "no_replica": 0,
    "idem_hits": 0,
    "idem_joins": 0,
    "journal_appends": 0,
    "journal_compactions": 0,
    "journal_torn_records": 0,
    "takeovers": 0,
    "crashes": 0,
    "replica_states": {},  # replica id -> last observed state string
}


def record_router_event(kind, n=1):
    """Count one router event: 'requests', 'retries', 'failovers',
    'breaker_trips', 'breaker_half_open', 'breaker_closes', 'hedges',
    'hedge_wins', 'brownout_sheds', 'deadline_sheds', 'no_replica',
    'idem_hits', 'idem_joins', 'journal_appends', 'journal_compactions',
    'journal_torn_records', 'takeovers', 'crashes'
    (unknown kinds are counted too so call sites never have to guard)."""
    with _counters_lock:
        g = _router_gauges
        g[kind] = g.get(kind, 0) + int(n)


def record_router_replica_state(replica_id, state):
    """Latest observed state of one replica (ready/draining/dead/...)."""
    with _counters_lock:
        _router_gauges["replica_states"][str(replica_id)] = str(state)


def _reset_router_locked():
    for k in _router_gauges:
        _router_gauges[k] = {} if k == "replica_states" else 0


def reset_router():
    with _counters_lock:
        _reset_router_locked()


def router_summary():
    """Router counters + the per-replica state snapshot."""
    with _counters_lock:
        g = dict(_router_gauges)
        g["replica_states"] = dict(g["replica_states"])
    return g


# ---------------------------------------------------------------------------
# Autoscaler gauges (ISSUE 16): the closed-loop controller counts every
# control tick and decision by direction (plus spawn failures from the
# autoscale.spawn chaos point), and SETS the current/peak managed replica
# count — so "did the loop act, and why is the fleet this size" is
# answerable from profiler.summary() and /metrics without grepping flight
# dumps.
# ---------------------------------------------------------------------------

_autoscale_gauges = {
    "ticks": 0,
    "scale_ups": 0,
    "scale_downs": 0,
    "holds": 0,
    "spawn_failures": 0,
    "reaps": 0,  # dead managed workers deregistered (chaos kill -9, crash)
    "replicas": 0,  # last observed fleet size (set, not accumulated)
    "replicas_peak": 0,
}


def record_autoscale_event(kind, n=1):
    """Count one autoscaler event: 'ticks', 'scale_ups', 'scale_downs',
    'holds', 'spawn_failures' (unknown kinds are counted too so call sites
    never have to guard)."""
    with _counters_lock:
        g = _autoscale_gauges
        g[kind] = g.get(kind, 0) + int(n)


def record_autoscale_replicas(n):
    """Latest fleet size under the autoscaler's control (gauge + peak)."""
    with _counters_lock:
        _autoscale_gauges["replicas"] = int(n)
        if int(n) > _autoscale_gauges["replicas_peak"]:
            _autoscale_gauges["replicas_peak"] = int(n)


def _reset_autoscale_locked():
    for k in _autoscale_gauges:
        _autoscale_gauges[k] = 0


def reset_autoscale():
    with _counters_lock:
        _reset_autoscale_locked()


def autoscale_summary():
    """Autoscaler counters ({} until the control loop has ticked)."""
    with _counters_lock:
        g = dict(_autoscale_gauges)
    return g if g["ticks"] or g["scale_ups"] or g["scale_downs"] else {}


# ---------------------------------------------------------------------------
# Disaggregated serving gauges (ISSUE 19): every prefill->decode handoff
# counted on both sides — exports/imports, raw handoff bytes on the wire,
# router pair-picks, reservation failures, and the typed no-decode-capacity
# sheds — so "is the handoff path healthy and what does it cost" is
# answerable from profiler.summary() and /metrics.
# ---------------------------------------------------------------------------

_disagg_gauges = {
    "exports": 0,        # prefill-side page exports completed
    "imports": 0,        # decode-side handoff imports landed
    "import_pages": 0,   # arena pages written by imports
    "handoff_bytes": 0,  # raw (pre-base64) payload bytes exported
    "pair_picks": 0,     # router (prefill, decode) pair selections
    "handoff_retries": 0,  # zero-token failovers of the handoff pipeline
    "reserve_fails": 0,  # decode-side reservation attempts that shed
    "no_decode_capacity": 0,  # typed 503s when no decode worker had pages
}


def record_disagg_event(kind, n=1):
    """Count one disaggregated-serving event: 'exports', 'imports',
    'import_pages', 'handoff_bytes', 'pair_picks', 'handoff_retries',
    'reserve_fails', 'no_decode_capacity' (unknown kinds are counted too so
    call sites never have to guard)."""
    with _counters_lock:
        g = _disagg_gauges
        g[kind] = g.get(kind, 0) + int(n)


def _reset_disagg_locked():
    for k in _disagg_gauges:
        _disagg_gauges[k] = 0


def reset_disagg():
    with _counters_lock:
        _reset_disagg_locked()


def disagg_summary():
    """Disaggregated-serving counters ({} until any handoff traffic)."""
    with _counters_lock:
        g = dict(_disagg_gauges)
    return g if any(g.values()) else {}


def _pctl(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def serving_summary():
    """Aggregated serving metrics: requests, tokens, aggregate tokens/s over
    the busy window, TTFT p50/p95, mean slot occupancy, queue depth avg/max,
    the slots seated and left (`membership_changes`) and the engine's
    `drains` by cause (see `_DRAIN_CAUSES`), the scheduler's `tick` by phase
    (see `TICK_PHASES` and `_tick_view`) — plus a nested `speculation`
    block (acceptance rate, tokens/step) when any verify step ran."""
    with _counters_lock:
        g = dict(_serving_gauges)
        g["ttfts_s"] = list(g["ttfts_s"])
        g["faults"] = dict(g["faults"])
        drains = dict(g["drains"])
        tick = _tick_view(g["tick"])
    out = {"requests": g["requests"], "tokens": g["tokens"],
           "membership_changes": g["membership_changes"],
           "drains": {c: drains.get(c, 0) for c in _DRAIN_CAUSES},
           "tick": tick}
    if g["busy_s"] > 0:
        out["tokens_per_s"] = g["tokens"] / g["busy_s"]
    ttfts = sorted(g["ttfts_s"])
    if ttfts:
        out["ttft_p50_ms"] = _pctl(ttfts, 0.50) * 1e3
        out["ttft_p95_ms"] = _pctl(ttfts, 0.95) * 1e3
    if g["ticks"]:
        out["occupancy_mean"] = g["occupancy_sum"] / g["ticks"]
        out["occupancy_peak"] = g["occupancy_peak"]
        out["queue_depth_avg"] = g["queue_depth_sum"] / g["ticks"]
        out["queue_depth_max"] = g["queue_depth_max"]
    if g["faults"]:
        out["faults"] = dict(g["faults"])
    spec = speculation_summary()
    if spec:
        out["speculation"] = spec
    lora = lora_summary()
    if lora:
        out["lora"] = lora
    return out


class RecordEvent:
    """Host-span annotation; shows up in the XPlane host timeline
    (reference: platform::RecordEvent)."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._ctx = None

    def begin(self):
        self._ctx = jax.profiler.TraceAnnotation(self.name)
        self._ctx.__enter__()
        from . import native as _native

        lib = _native.get_lib()
        self._nid = lib.pt_trace_begin(self.name.encode()) if lib else -1

    def end(self):
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None
        from . import native as _native

        lib = _native.get_lib()
        if lib is not None and getattr(self, "_nid", -1) >= 0:
            lib.pt_trace_end(self._nid)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None, record_shapes=False, profile_memory=False, timer_only=False, with_flops=False):
        self._scheduler = scheduler if callable(scheduler) else None
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self._scheduler = lambda step: (
                ProfilerState.RECORD if lo <= step < hi else ProfilerState.CLOSED
            )
        self._on_trace_ready = on_trace_ready
        self._export_dir = os.path.join(os.getcwd(), "profiler_log")
        self._running = False
        self._step = 0
        self._timer_only = timer_only
        self._step_times = []
        self._last = None

    def start(self):
        self._step = 0
        if not self._timer_only:
            state = self._scheduler(self._step) if self._scheduler else ProfilerState.RECORD
            if state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
                self._begin_trace()
        self._last = time.perf_counter()

    def _begin_trace(self):
        if not self._running:
            if self._on_trace_ready is not None:
                self._on_trace_ready(self)
            os.makedirs(self._export_dir, exist_ok=True)
            try:
                jax.profiler.start_trace(self._export_dir)
                self._running = True
            except Exception:
                self._running = False

    def _end_trace(self):
        if self._running:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._running = False

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last is not None:
            self._step_times.append(now - self._last)
        self._last = now
        self._step += 1
        if self._timer_only or self._scheduler is None:
            return
        state = self._scheduler(self._step)
        if state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._begin_trace()
        else:
            self._end_trace()

    def stop(self):
        self._end_trace()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def export(self, path=None, format="json"):
        pass

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False, time_unit="ms"):
        if self._step_times:
            avg = sum(self._step_times) / len(self._step_times)
            print(f"steps: {len(self._step_times)}  avg step time: {avg*1000:.3f} ms")
        bd = step_breakdown()
        if bd["steps"]:
            print(
                "async pipeline: {steps} steps  dispatch {dispatch_ms_avg:.3f} ms"
                "  host-blocked {host_blocked_ms_avg:.3f} ms"
                "  device(est) {device_ms_avg_est:.3f} ms"
                "  inflight avg {inflight_depth_avg:.2f} max {inflight_depth_max}".format(**bd)
            )
        sv = serving_summary()
        if sv["requests"]:
            print(
                "serving: {requests} requests  {tokens} tokens"
                "  {tok_s:.0f} tok/s  ttft p50 {p50:.1f} ms p95 {p95:.1f} ms"
                "  occupancy {occ:.2f}  queue avg {qa:.1f} max {qm}".format(
                    requests=sv["requests"], tokens=sv["tokens"],
                    tok_s=sv.get("tokens_per_s", 0.0),
                    p50=sv.get("ttft_p50_ms", 0.0), p95=sv.get("ttft_p95_ms", 0.0),
                    occ=sv.get("occupancy_mean", 0.0),
                    qa=sv.get("queue_depth_avg", 0.0),
                    qm=sv.get("queue_depth_max", 0),
                )
            )
        if sv.get("faults"):
            print(
                "serving faults: "
                + "  ".join(f"{k} {v}" for k, v in sorted(sv["faults"].items()))
            )
        rt = router_summary()
        if rt["requests"] or rt["replica_states"]:
            print(
                "router: {req} requests  retries {rt}  failovers {fo}"
                "  breaker trips {bt}  hedges {hg}  brownout sheds {bs}".format(
                    req=rt["requests"], rt=rt["retries"], fo=rt["failovers"],
                    bt=rt["breaker_trips"], hg=rt["hedges"],
                    bs=rt["brownout_sheds"],
                )
            )
            if rt["replica_states"]:
                print(
                    "router replicas: "
                    + "  ".join(
                        f"{k}={v}" for k, v in sorted(rt["replica_states"].items())
                    )
                )
        asc = autoscale_summary()
        if asc:
            print(
                "autoscaler: {t} ticks  up {up}  down {dn}"
                "  spawn failures {sf}  replicas {n} (peak {pk})".format(
                    t=asc["ticks"], up=asc["scale_ups"], dn=asc["scale_downs"],
                    sf=asc["spawn_failures"], n=asc["replicas"],
                    pk=asc["replicas_peak"],
                )
            )
        dg = disagg_summary()
        if dg:
            print(
                "disagg: {ex} exports  {im} imports ({pgs} pages)"
                "  {by} handoff bytes  pair picks {pp}  retries {rt}"
                "  reserve fails {rf}  no-capacity sheds {nc}".format(
                    ex=dg["exports"], im=dg["imports"],
                    pgs=dg["import_pages"], by=dg["handoff_bytes"],
                    pp=dg["pair_picks"], rt=dg["handoff_retries"],
                    rf=dg["reserve_fails"], nc=dg["no_decode_capacity"],
                )
            )
        pg = paging_summary()
        if pg.get("prefix_lookups"):
            print(
                "paged kv: hit rate {hr:.2f} ({hits}/{lk})"
                "  tokens saved {saved}  cow copies {cow}"
                "  pages mean {pm:.1f} peak {pp}/{pt}".format(
                    hr=pg["prefix_hit_rate"], hits=pg["prefix_hits"],
                    lk=pg["prefix_lookups"],
                    saved=pg["prefill_tokens_saved"], cow=pg["cow_copies"],
                    pm=pg.get("pages_used_mean", 0.0),
                    pp=pg.get("pages_used_peak", 0),
                    pt=pg.get("pages_total", 0),
                )
            )
        fb = flash_fallback_summary()
        if fb:
            print(
                "flash fallbacks: "
                + "  ".join(f"{k} {v}" for k, v in sorted(fb.items()))
            )
        # the runtime sanitizer's verdict rides along: unexpected traces/
        # compiles/syncs in steady-state regions, each attributed to the
        # user-level line that caused it (FLAGS_debug_sanitize)
        try:
            from .analysis import sanitizer as _san

            rep = _san.report()
            if rep:
                print(rep)
        except Exception:
            pass
        # compile caches dominate cold-start cost: surface them next to the
        # step timing so "why was the first step slow" is answerable here
        try:
            from .jit import cache_report

            print(cache_report())
        except Exception:
            pass

    def step_info(self, unit=None):
        if self._step_times:
            return f"step time: {self._step_times[-1]*1000:.3f} ms"
        return ""


@contextlib.contextmanager
def profile(dir_name="profiler_log"):
    os.makedirs(dir_name, exist_ok=True)
    jax.profiler.start_trace(dir_name)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load_profiler_result(path):
    raise NotImplementedError("use TensorBoard / xprof to view XPlane traces")
