"""Prometheus text exposition over every counter family in the repo.

``render(labels={...})`` returns the classic ``text/plain; version=0.0.4``
format: ``# HELP``/``# TYPE`` per metric name, then one sample per label
set.  It reads ``profiler.metrics_snapshot()`` (one raw one-lock snapshot
that, unlike the ``*_summary()`` helpers, never omits zero-valued counters
— exported metric NAMES are stable whether or not traffic has flowed),
plus the runtime sanitizer's counters and the obs buffers' own gauges.

Both HTTP front doors mount this on ``GET /metrics``: ``serve()`` labels
every sample ``{replica="host:port"}``, the router ``{role="router"}``, so
a fleet scrape distinguishes replicas without per-process config.

Metric-name reference (the stable surface the scrape test pins):

    paddle_train_steps_total            paddle_serving_requests_total
    paddle_train_dispatch_seconds_total paddle_serving_tokens_total
    paddle_train_host_blocked_seconds_total
    paddle_train_wall_seconds_total     paddle_serving_ticks_total
    paddle_train_inflight_max           paddle_serving_busy_seconds_total
    paddle_serving_tick_phase_seconds_total{phase="evict"|"admit"|"prepare"|
        "dispatch"|"wait"|"deliver"|"other"}
    paddle_serving_ttft_seconds{quantile="0.5"|"0.95"}
    paddle_serving_occupancy_mean / _peak
    paddle_serving_queue_depth_max
    paddle_serving_faults_total{kind=...}
    paddle_serving_deadline_miss_rate
    paddle_paging_prefix_hits_total / _misses_total
    paddle_paging_prefill_tokens_saved_total
    paddle_paging_cow_copies_total
    paddle_paging_cache_evictions_total / _commits_total
    paddle_paging_pages_used_peak / paddle_paging_pages_total
    paddle_spec_steps_total / paddle_spec_proposed_tokens_total
    paddle_spec_accepted_tokens_total / paddle_spec_emitted_tokens_total
    paddle_spec_acceptance_rate / paddle_spec_tokens_per_step
    paddle_lora_loads_total / paddle_lora_evictions_total
    paddle_lora_residency_hits_total / _misses_total
    paddle_lora_resident / paddle_lora_capacity
    paddle_router_requests_total, _retries_total, _failovers_total,
    paddle_router_breaker_trips_total / _half_open_total / _closes_total
    paddle_router_hedges_total / _hedge_wins_total
    paddle_router_brownout_sheds_total / _deadline_sheds_total
    paddle_router_no_replica_total
    paddle_router_idem_hits_total / _idem_joins_total
    paddle_router_journal_appends_total / _compactions_total /
        _torn_records_total
    paddle_router_takeovers_total / _crashes_total
    paddle_router_replica_state{replica=...,state=...} 1
    paddle_autoscaler_ticks_total / _scale_ups_total / _scale_downs_total
    paddle_autoscaler_holds_total / _spawn_failures_total / _reaps_total
    paddle_autoscaler_replicas / _replicas_peak
    paddle_disagg_exports_total / _imports_total / _import_pages_total
    paddle_disagg_handoff_bytes_total / _pair_picks_total
    paddle_disagg_handoff_retries_total / _reserve_fails_total
    paddle_disagg_no_decode_capacity_total
    paddle_mesh_devices / paddle_mesh_tp_degree
    paddle_mesh_allreduce_per_step
    paddle_cp_degree / paddle_cp_decode_compiles_total
    paddle_session_resident / paddle_session_pages_pinned
    paddle_session_binds_total / paddle_session_evictions_total
    paddle_session_prefill_tokens_saved_total
    paddle_session_pin_hits_total / paddle_session_repins_total
    paddle_kv_quant_mode{mode=...} 1
    paddle_kv_quant_arena_bytes / paddle_kv_quant_scale_bytes
    paddle_kv_quant_page_ops_total{op="quantize"|"dequantize"}
    paddle_flash_fallbacks_total{reason=...}  (zero-filled label set)
    paddle_flash_pallas_calls_total{kernel=...}  (zero-filled label set)
    paddle_sanitizer_<counter>_total  (traces, eager_misses, host_syncs,
        unexpected_traces, unexpected_eager, unexpected_syncs,
        allowed_events)
    paddle_obs_spans_recorded_total / _dropped_total / _buffered
    paddle_flight_events_total / paddle_flight_dumps_total
"""

CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# serving fault kinds always exported (zero-filled) so the label set is
# stable for dashboards that join across replicas
_FAULT_KINDS = (
    "restarts", "restarted_requests", "deadline_miss", "rejected_deadline",
    "cancelled", "nonfinite",
)


def _escape(v):
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_value(v):
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    return format(float(v), ".10g")


class _Exposition:
    """Accumulates samples; emits HELP/TYPE once per metric name."""

    def __init__(self, base_labels=None):
        self.base = dict(base_labels or {})
        self.lines = []
        self._seen = set()

    def add(self, name, value, help_text, mtype="counter", labels=None):
        if name not in self._seen:
            self._seen.add(name)
            self.lines.append(f"# HELP {name} {help_text}")
            self.lines.append(f"# TYPE {name} {mtype}")
        merged = dict(self.base)
        merged.update(labels or {})
        if merged:
            inner = ",".join(
                f'{k}="{_escape(v)}"' for k, v in sorted(merged.items())
            )
            self.lines.append(f"{name}{{{inner}}} {_fmt_value(value)}")
        else:
            self.lines.append(f"{name} {_fmt_value(value)}")

    def text(self):
        return "\n".join(self.lines) + "\n"


def _pctl(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def render(labels=None):
    """Render every counter family as Prometheus text.

    ``labels`` (e.g. ``{"replica": "127.0.0.1:8866"}``) is applied to every
    sample.  Pure host-side reads; safe to scrape a live engine.
    """
    from .. import profiler as _prof

    exp = _Exposition(labels)
    snap = _prof.metrics_snapshot()

    g = snap["step"]
    exp.add("paddle_train_steps_total", g["steps"],
            "training steps recorded by record_step")
    exp.add("paddle_train_dispatch_seconds_total", g["dispatch_s"],
            "host seconds spent dispatching training steps")
    exp.add("paddle_train_host_blocked_seconds_total", g["host_blocked_s"],
            "host seconds blocked on the device (backpressure + sync)")
    exp.add("paddle_train_wall_seconds_total", g["wall_s"],
            "wall seconds across recorded training steps")
    exp.add("paddle_train_inflight_max", g["inflight_max"],
            "peak in-flight steps in the async ring", "gauge")

    g = snap["serving"]
    exp.add("paddle_serving_requests_total", g["requests"],
            "finished generation requests")
    exp.add("paddle_serving_tokens_total", g["tokens"],
            "generated tokens across finished requests")
    exp.add("paddle_serving_ticks_total", g["ticks"],
            "engine decode scheduler ticks")
    exp.add("paddle_serving_busy_seconds_total", g["busy_s"],
            "summed decode-step wall seconds (the tokens/s busy window)")
    # all seven phases, at 0 before any traffic: wall seconds of every
    # scheduler tick; host seconds are their sum less phase="wait"
    for phase, sec in g["tick"]["phases_s"].items():
        exp.add("paddle_serving_tick_phase_seconds_total", sec,
                "scheduler tick wall seconds by phase (disjoint; 'wait' is "
                "the host blocked on the device)", "counter",
                {"phase": phase})
    ttfts = sorted(g["ttfts_s"])
    for q in (0.5, 0.95):
        exp.add("paddle_serving_ttft_seconds", _pctl(ttfts, q),
                "time to first token quantiles over the retained window",
                "gauge", {"quantile": str(q)})
    ticks = g["ticks"] or 1
    exp.add("paddle_serving_occupancy_mean", g["occupancy_sum"] / ticks,
            "mean fraction of KV slots active per tick", "gauge")
    exp.add("paddle_serving_occupancy_peak", g["occupancy_peak"],
            "peak fraction of KV slots active", "gauge")
    exp.add("paddle_serving_queue_depth_max", g["queue_depth_max"],
            "peak admission-queue depth", "gauge")
    faults = dict(g["faults"])
    for kind in _FAULT_KINDS:
        faults.setdefault(kind, 0)
    for kind in sorted(faults):
        exp.add("paddle_serving_faults_total", faults[kind],
                "serving fault-domain events by kind", "counter",
                {"kind": kind})
    # always rendered (0.0 before traffic): the autoscaler's SLO input must
    # be a stable scrape target, not a series that appears under pressure
    exp.add("paddle_serving_deadline_miss_rate",
            g.get("deadline_miss_rate", 0.0),
            "deadline-miss-rate EWMA over terminal resolutions (a rate; "
            "the monotonic total is paddle_serving_faults_total"
            '{kind="deadline_miss"})', "gauge")

    g = snap["paging"]
    exp.add("paddle_paging_prefix_hits_total", g["prefix_hits"],
            "admission-time prefix-cache hits")
    exp.add("paddle_paging_prefix_misses_total", g["prefix_misses"],
            "admission-time prefix-cache misses")
    exp.add("paddle_paging_prefill_tokens_saved_total",
            g["prefill_tokens_saved"],
            "prompt tokens whose prefill was skipped via cached prefixes")
    exp.add("paddle_paging_cow_copies_total", g["cow_copies"],
            "copy-on-write page copies for new prefix readers")
    exp.add("paddle_paging_cache_evictions_total", g["cache_evictions"],
            "prefix-cache page evictions")
    exp.add("paddle_paging_cache_commits_total", g["cache_commits"],
            "prompt page sets committed to the prefix cache")
    exp.add("paddle_paging_pages_used_peak", g["pages_used_peak"],
            "peak pages in use in the paged-KV pool", "gauge")
    exp.add("paddle_paging_pages_total", g["pages_total"],
            "total pages in the paged-KV pool", "gauge")

    g = snap["speculation"]
    exp.add("paddle_spec_steps_total", g["steps"],
            "speculative verify steps dispatched")
    exp.add("paddle_spec_proposed_tokens_total", g["proposed"],
            "draft tokens proposed by the prompt-lookup drafter")
    exp.add("paddle_spec_accepted_tokens_total", g["accepted"],
            "draft tokens accepted by the batched verify step")
    exp.add("paddle_spec_emitted_tokens_total", g["emitted"],
            "tokens emitted by verify steps (accepted + 1 per slot-step)")
    exp.add("paddle_spec_acceptance_rate",
            (g["accepted"] / g["proposed"]) if g["proposed"] else 0.0,
            "accepted / proposed draft tokens", "gauge")
    exp.add("paddle_spec_tokens_per_step",
            (g["emitted"] / g["slot_steps"]) if g["slot_steps"] else 0.0,
            "mean emitted tokens per slot-step (1.0 = no speculation win)",
            "gauge")

    g = snap["lora"]
    exp.add("paddle_lora_loads_total", g["loads"],
            "LoRA adapter uploads into arena slots")
    exp.add("paddle_lora_evictions_total", g["evictions"],
            "LRU evictions of idle resident LoRA adapters")
    exp.add("paddle_lora_residency_hits_total", g["residency_hits"],
            "adapter acquires that found the adapter already resident")
    exp.add("paddle_lora_residency_misses_total", g["residency_misses"],
            "adapter acquires that had to upload (or park on a full arena)")
    exp.add("paddle_lora_resident", g["resident"],
            "LoRA adapters currently resident in the arena", "gauge")
    exp.add("paddle_lora_capacity", g["capacity"],
            "LoRA arena adapter slots (excludes the pinned base slot)",
            "gauge")

    g = snap["mesh"]
    exp.add("paddle_mesh_devices", g["devices"],
            "jax devices visible to the serving process", "gauge")
    exp.add("paddle_mesh_tp_degree", g["tp"],
            "tensor-parallel degree of the serving mesh ('mp' axis size)",
            "gauge")
    exp.add("paddle_mesh_allreduce_per_step", g["allreduce_per_step"],
            "static GSPMD allreduces per compiled step (row-parallel "
            "outputs + sampling reduction; 0 at tp=1)", "gauge")
    exp.add("paddle_cp_degree", g.get("cp", 1),
            "context-parallel degree of the serving mesh ('cp' axis size; "
            "pages shard round-robin across it)", "gauge")
    cp_compiles = sum(
        v for k, v in snap.get("flash_pallas", {}).items()
        if k.startswith("paged_decode_fused_cp")
    )
    exp.add("paddle_cp_decode_compiles_total", cp_compiles,
            "context-parallel fused paged-decode kernel compilations "
            "(shard-local partials + softmax allreduce combine)")

    g = snap.get("kv_quant", {})
    exp.add("paddle_kv_quant_mode", 1,
            "paged-KV arena storage precision (1 = current mode)", "gauge",
            {"mode": g.get("mode", "none")})
    exp.add("paddle_kv_quant_arena_bytes", g.get("arena_bytes", 0),
            "K/V value-arena HBM bytes across all layers", "gauge")
    exp.add("paddle_kv_quant_scale_bytes", g.get("scale_bytes", 0),
            "per-row dequant scale-arena HBM bytes (0 unless quantized)",
            "gauge")
    for op in ("quantize", "dequantize"):
        exp.add("paddle_kv_quant_page_ops_total", g.get(op, 0),
                "KV quant-path work: rows quantized on write / mapped pages "
                "dequantized in-kernel", "counter", {"op": op})

    g = snap.get("sessions", {})
    exp.add("paddle_session_resident", g.get("sessions_resident", 0),
            "resident KV sessions (pinned committed-page chains)", "gauge")
    exp.add("paddle_session_pages_pinned", g.get("session_pages_pinned", 0),
            "prefix-cache pages pinned by resident sessions", "gauge")
    exp.add("paddle_session_binds_total", g.get("session_binds_total", 0),
            "session (re)binds at turn finish")
    exp.add("paddle_session_evictions_total",
            g.get("session_evictions_total", 0),
            "whole-session LRU evictions under page pressure")
    exp.add("paddle_session_prefill_tokens_saved_total",
            g.get("session_prefill_tokens_saved_total", 0),
            "prompt tokens whose prefill was skipped via session KV reuse")

    g = snap["router"]
    for key, name in (
        ("requests", "paddle_router_requests_total"),
        ("retries", "paddle_router_retries_total"),
        ("failovers", "paddle_router_failovers_total"),
        ("breaker_trips", "paddle_router_breaker_trips_total"),
        ("breaker_half_open", "paddle_router_breaker_half_open_total"),
        ("breaker_closes", "paddle_router_breaker_closes_total"),
        ("hedges", "paddle_router_hedges_total"),
        ("hedge_wins", "paddle_router_hedge_wins_total"),
        ("brownout_sheds", "paddle_router_brownout_sheds_total"),
        ("deadline_sheds", "paddle_router_deadline_sheds_total"),
        ("no_replica", "paddle_router_no_replica_total"),
        ("idem_hits", "paddle_router_idem_hits_total"),
        ("idem_joins", "paddle_router_idem_joins_total"),
        ("journal_appends", "paddle_router_journal_appends_total"),
        ("journal_compactions", "paddle_router_journal_compactions_total"),
        ("journal_torn_records", "paddle_router_journal_torn_records_total"),
        ("takeovers", "paddle_router_takeovers_total"),
        ("crashes", "paddle_router_crashes_total"),
        ("session_pin_hits", "paddle_session_pin_hits_total"),
        ("session_repins", "paddle_session_repins_total"),
    ):
        exp.add(name, g.get(key, 0), f"router events: {key}")
    for rid, state in sorted(g["replica_states"].items()):
        exp.add("paddle_router_replica_state", 1,
                "last observed state per replica (1 = current state)",
                "gauge", {"replica": rid, "state": state})

    g = snap.get("autoscale", {})
    for key, name in (
        ("ticks", "paddle_autoscaler_ticks_total"),
        ("scale_ups", "paddle_autoscaler_scale_ups_total"),
        ("scale_downs", "paddle_autoscaler_scale_downs_total"),
        ("holds", "paddle_autoscaler_holds_total"),
        ("spawn_failures", "paddle_autoscaler_spawn_failures_total"),
        ("reaps", "paddle_autoscaler_reaps_total"),
    ):
        exp.add(name, g.get(key, 0), f"autoscaler control-loop events: {key}")
    exp.add("paddle_autoscaler_replicas", g.get("replicas", 0),
            "fleet size under the autoscaler's control", "gauge")
    exp.add("paddle_autoscaler_replicas_peak", g.get("replicas_peak", 0),
            "peak fleet size under the autoscaler's control", "gauge")

    g = snap.get("disagg", {})
    for key, name in (
        ("exports", "paddle_disagg_exports_total"),
        ("imports", "paddle_disagg_imports_total"),
        ("import_pages", "paddle_disagg_import_pages_total"),
        ("handoff_bytes", "paddle_disagg_handoff_bytes_total"),
        ("pair_picks", "paddle_disagg_pair_picks_total"),
        ("handoff_retries", "paddle_disagg_handoff_retries_total"),
        ("reserve_fails", "paddle_disagg_reserve_fails_total"),
        ("no_decode_capacity", "paddle_disagg_no_decode_capacity_total"),
    ):
        exp.add(name, g.get(key, 0),
                f"disaggregated prefill/decode serving events: {key}")

    # zero-filled label sets (like _FAULT_KINDS): a fallback regression must
    # show as a counter MOVING on a dashboard, not as a series appearing —
    # and the retired reasons' permanent zeros prove the gaps stay closed
    try:
        from ..ops import flash_attention as _fa
        known_kernels = _fa._PALLAS_KERNELS
        known_reasons = _fa._FALLBACK_REASONS
    except Exception:
        known_kernels = known_reasons = ()
    fallbacks = dict(snap["flash_fallbacks"])
    for reason in known_reasons:
        fallbacks.setdefault(reason, 0)
    for reason in sorted(fallbacks):
        exp.add("paddle_flash_fallbacks_total", fallbacks[reason],
                "flash-attention Pallas->XLA fallbacks by reason",
                "counter", {"reason": reason})
    pallas = dict(snap.get("flash_pallas", {}))
    for kern in known_kernels:
        pallas.setdefault(kern, 0)
    for kern in sorted(pallas):
        exp.add("paddle_flash_pallas_calls_total", pallas[kern],
                "flash-attention Pallas kernel dispatches by kernel",
                "counter", {"kernel": kern})

    try:
        from ..analysis import sanitizer as _san
        for key, n in sorted(_san.counters().items()):
            exp.add(f"paddle_sanitizer_{key}_total", n,
                    "runtime trace/sync sanitizer counters")
    except Exception:
        pass

    from . import flight, trace
    ts = trace.stats()
    exp.add("paddle_obs_spans_recorded_total", ts["spans_recorded"],
            "spans recorded into the trace buffer")
    exp.add("paddle_obs_spans_dropped_total", ts["spans_dropped"],
            "spans evicted from the bounded trace buffer")
    exp.add("paddle_obs_spans_buffered", ts["spans_buffered"],
            "spans currently buffered", "gauge")
    fs = flight.stats()
    exp.add("paddle_flight_events_total", fs["events_total"],
            "events recorded into the flight-recorder ring")
    exp.add("paddle_flight_dumps_total", fs["dumps_total"],
            "flight-recorder JSONL dumps written")

    return exp.text()
