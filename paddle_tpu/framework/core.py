"""Core framework state: dtypes, places, devices, global modes.

TPU-native re-design of the reference's platform layer
(paddle/phi/common/place.h, paddle/phi/core/flags.cc — see SURVEY.md §2.1
"Device/platform" / "Flags/config").  Instead of a DeviceContext pool over
CUDA streams, devices are JAX/PJRT devices; `set_device` selects the default
placement for newly created tensors.
"""

from __future__ import annotations

import os
import threading
import contextlib

import numpy as np
import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# dtypes
# ---------------------------------------------------------------------------

_STR2DTYPE = {
    "float16": jnp.float16,
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float64": jnp.float64,
    "int8": jnp.int8,
    "int16": jnp.int16,
    "int32": jnp.int32,
    "int64": jnp.int64,
    "uint8": jnp.uint8,
    "uint16": jnp.uint16,
    "uint32": jnp.uint32,
    "bool": jnp.bool_,
    "complex64": jnp.complex64,
    "complex128": jnp.complex128,
}

_ALIASES = {
    "fp16": "float16",
    "bf16": "bfloat16",
    "fp32": "float32",
    "fp64": "float64",
    "half": "float16",
    "float": "float32",
    "double": "float64",
    "int": "int32",
    "long": "int64",
}


def convert_dtype(dtype):
    """Normalize a dtype spec (string / numpy / jnp dtype) to a canonical string."""
    if dtype is None:
        return None
    if isinstance(dtype, str):
        name = _ALIASES.get(dtype, dtype)
        if name not in _STR2DTYPE:
            raise ValueError(f"Unsupported dtype string: {dtype!r}")
        return name
    try:
        name = np.dtype(dtype).name
    except TypeError:
        name = getattr(dtype, "name", None) or str(dtype)
    name = {"bool_": "bool"}.get(name, name)
    if name not in _STR2DTYPE:
        raise ValueError(f"Unsupported dtype: {dtype!r}")
    return name


_X64_DEMOTE = {"int64": jnp.int32, "uint64": jnp.uint32, "float64": jnp.float32}


def to_jax_dtype(dtype):
    if dtype is None:
        return None
    name = convert_dtype(dtype)
    # TPU-native: 32-bit integers/floats by default (x64 disabled) — wide
    # dtypes demote silently, mirroring jax's canonical dtype policy.
    if not jax.config.jax_enable_x64 and name in _X64_DEMOTE:
        return _X64_DEMOTE[name]
    return _STR2DTYPE[name]


def is_floating_dtype(dtype) -> bool:
    return jnp.issubdtype(jnp.dtype(to_jax_dtype(convert_dtype(dtype))), jnp.inexact)


_default_dtype = "float32"


def set_default_dtype(d):
    global _default_dtype
    d = convert_dtype(d)
    if d not in ("float16", "bfloat16", "float32", "float64"):
        raise TypeError(f"set_default_dtype only supports float dtypes, got {d}")
    _default_dtype = d


def get_default_dtype() -> str:
    return _default_dtype


# ---------------------------------------------------------------------------
# Places / devices
# ---------------------------------------------------------------------------


class Place:
    """Device placement, mirroring the reference's phi::Place classes.

    On this framework a place maps onto a JAX device: ``TPUPlace(i)`` is the
    i-th accelerator chip (PJRT device), ``CPUPlace()`` the host platform.
    """

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self.device_type, self._device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self._device_id})"

    # -- JAX bridge ------------------------------------------------------
    def jax_device(self):
        devs = _devices_for(self.device_type)
        if not 0 <= self._device_id < len(devs):
            raise RuntimeError(
                f"{self!r} names no device: {len(devs)} {self.device_type} "
                "device(s) present"
            )
        return devs[self._device_id]


class CPUPlace(Place):
    device_type = "cpu"

    def __repr__(self):
        return "CPUPlace"


class TPUPlace(Place):
    device_type = "tpu"

    def __repr__(self):
        return f"TPUPlace({self._device_id})"


class CUDAPlace(Place):  # accepted for API compat; maps to accelerator if any
    device_type = "gpu"


class CUDAPinnedPlace(CPUPlace):
    pass


def _devices_for(kind: str):
    if kind == "cpu":
        return jax.devices("cpu")
    # any non-cpu accelerator backend counts as "tpu"/"gpu"
    default = jax.devices()
    if default and default[0].platform != "cpu":
        return default
    return []


_current_place = None
_place_lock = threading.Lock()


def _default_place() -> Place:
    devs = jax.devices()
    if devs[0].platform == "cpu":
        return CPUPlace(0)
    return TPUPlace(0)


def get_device() -> str:
    p = _expected_place()
    if isinstance(p, CPUPlace):
        return "cpu"
    return f"{p.device_type}:{p.get_device_id()}"


def _expected_place() -> Place:
    global _current_place
    if _current_place is None:
        with _place_lock:
            if _current_place is None:
                _current_place = _default_place()
    return _current_place


def set_device(device) -> Place:
    """paddle.set_device: 'cpu', 'tpu', 'tpu:0', 'gpu:0' (alias of tpu here)."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return device
    dev = str(device).lower()
    if ":" in dev:
        kind, _, idx = dev.partition(":")
        idx = int(idx)
    else:
        kind, idx = dev, 0
    if kind == "cpu":
        place = CPUPlace(idx)
    elif kind in ("tpu", "xpu", "gpu", "cuda"):
        # reference scripts say gpu; route to the accelerator
        place = TPUPlace(idx)
    else:
        raise ValueError(f"Unknown device {device!r}")
    # steer jax's default placement (tensors stay uncommitted so they can
    # combine with mesh-sharded operands).  A device that is not there is
    # an error: carrying on would run the program on the CPU unasked.
    jax.config.update("jax_default_device", place.jax_device())
    _current_place = place
    return place


def device_count(kind: str = "tpu") -> int:
    return len(_devices_for(kind))


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return bool(_devices_for("tpu"))


# ---------------------------------------------------------------------------
# Global execution modes (grad, trace) — thread-local
# ---------------------------------------------------------------------------


class _ModeState(threading.local):
    def __init__(self):
        self.grad_enabled = True
        self.trace = None  # active jit trace (paddle_tpu.jit), or None
        self.amp = None  # active amp state (paddle_tpu.amp), or None


_mode = _ModeState()


def grad_enabled() -> bool:
    return _mode.grad_enabled


def set_grad_enabled(flag: bool) -> bool:
    old = _mode.grad_enabled
    _mode.grad_enabled = bool(flag)
    return old


@contextlib.contextmanager
def no_grad_ctx():
    old = set_grad_enabled(False)
    try:
        yield
    finally:
        set_grad_enabled(old)


@contextlib.contextmanager
def enable_grad_ctx():
    old = set_grad_enabled(True)
    try:
        yield
    finally:
        set_grad_enabled(old)


def active_trace():
    return _mode.trace


def set_active_trace(tr):
    old = _mode.trace
    _mode.trace = tr
    return old


# Birth registry: tensors created while a jit trace is active are "trace-born"
# and excluded from implicit state capture (see paddle_tpu/jit).  Side table
# because Tensor uses __slots__.
import weakref as _weakref

_birth = {}  # id(tensor) -> (weakref, trace token)


def mark_born_if_tracing(t):
    tr = _mode.trace
    if tr is not None:
        _birth[id(t)] = (_weakref.ref(t), tr.token)


def unmark_born(t):
    """Declare a tensor created mid-trace as PERSISTENT state: its payload is
    concrete (caller must build it under jax.ensure_compile_time_eval) and it
    participates in state capture like pre-existing tensors."""
    _birth.pop(id(t), None)


def get_born_token(t):
    rec = _birth.get(id(t))
    if rec is None:
        return None
    ref, token = rec
    if ref() is not t:
        _birth.pop(id(t), None)
        return None
    return token


_name_counters: dict = {}


def unique_name(prefix="tensor"):
    """Process-wide unique name generator (reference:
    python/paddle/utils/unique_name.py) — construction-order deterministic, so
    names are stable across processes that build the same model."""
    n = _name_counters.get(prefix, 0)
    _name_counters[prefix] = n + 1
    return f"{prefix}_{n}"


def active_amp():
    return _mode.amp


def set_active_amp(state):
    old = _mode.amp
    _mode.amp = state
    return old


# ---------------------------------------------------------------------------
# Flags registry (reference: PHI_DEFINE_EXPORTED_* gflags, paddle.set_flags)
# ---------------------------------------------------------------------------

_FLAG_DEFS = {}  # name -> (type, default, help)
_flags = {}


def define_flag(name: str, default, help: str = ""):
    _FLAG_DEFS[name] = (type(default), default, help)
    env = os.environ.get(name)
    if env is not None:
        _flags[name] = _parse_flag(type(default), env)
    else:
        _flags[name] = default


def _parse_flag(typ, text):
    if typ is bool:
        return text.lower() in ("1", "true", "yes", "on")
    return typ(text)


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    return {n: _flags[n] for n in names}


def set_flags(flags: dict):
    for k, v in flags.items():
        if k not in _FLAG_DEFS:
            raise KeyError(f"Unknown flag {k!r}")
        typ = _FLAG_DEFS[k][0]
        _flags[k] = _parse_flag(typ, v) if isinstance(v, str) and typ is not str else typ(v)


def flag(name):
    return _flags[name]


# core flags mirroring the reference's most used ones
define_flag("FLAGS_check_nan_inf", False, "scan op outputs for nan/inf")
define_flag("FLAGS_cudnn_deterministic", False, "deterministic ops (no-op on XLA)")
define_flag("FLAGS_use_stride_kernel", False, "compat only")
define_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.92, "compat only; XLA preallocation")
define_flag("FLAGS_eager_delete_tensor_gb", 0.0, "compat only; GC by refcount")
define_flag("FLAGS_log_level", 0, "VLOG level for python-side logging")
define_flag(
    "FLAGS_compile_cache_dir",
    os.environ.get("PADDLE_COMPILE_CACHE_DIR", ""),
    "root of the AOT executable snapshot tier (jit/cache.py keeps "
    "@to_static programs under <dir>/aot so a restart skips trace+lower); "
    "empty disables the tier.  It does not place jax's persistent XLA "
    "cache: that is always on, at JAX_COMPILATION_CACHE_DIR when the "
    "environment sets it and at <checkout>/.jax_cache otherwise",
)
define_flag(
    "FLAGS_eager_cache_max_entries", 4096,
    "LRU bound on the eager dispatch executable cache (ops/dispatch.py)",
)
define_flag(
    "FLAGS_max_inflight_steps", 2,
    "bound on device steps the async hapi train loop keeps in flight before "
    "the host blocks (backpressure without a value transfer); 1 = strict "
    "per-step sync fallback, identical numerics",
)
define_flag(
    "FLAGS_serve_slots", 4,
    "continuous-batching engine: number of slots (max concurrently "
    "decoding requests)",
)
define_flag(
    "FLAGS_serve_queue_depth", 32,
    "continuous-batching engine: admission queue bound; submissions beyond "
    "it fail fast (serve() maps this to HTTP 503)",
)
define_flag(
    "FLAGS_serve_prefill_buckets", "16,32,64,128",
    "continuous-batching engine: comma-separated prompt-length buckets; each "
    "bucket compiles one prefill executable (prompts pad up to the bucket)",
)
define_flag(
    "FLAGS_serve_step_timeout_sec", 0.0,
    "serving watchdog deadline (s) for the engine's armed regions (prefill "
    "dispatch, decode dispatch, token fetch); a region overrunning it trips "
    "the EngineSupervisor into a bounded warm engine restart.  0 disables.",
)
define_flag(
    "FLAGS_serve_max_restarts", 3,
    "EngineSupervisor restart budget: after this many engine restarts the "
    "supervisor declares the engine dead and fails all pending requests",
)
define_flag(
    "FLAGS_serve_restart_backoff", 0.5,
    "initial delay (s) before an engine restart, doubled per consecutive "
    "restart (the serving mirror of launch --restart_backoff)",
)
define_flag(
    "FLAGS_serve_drain_grace", 10.0,
    "SIGTERM drain budget (s) for serve(): stop admitting, finish in-flight "
    "up to this long, then exit cleanly.  Overridden by PADDLE_STOP_GRACE "
    "when launched under distributed.launch (--stop_grace).",
)
define_flag(
    "FLAGS_serve_debug_invariants", False,
    "after every scheduler step assert slot-pool invariants (no slot both "
    "free and active, one live request per slot, positions <= max_len) — "
    "turns silent slot leaks into loud failures in tests/CI.  With paged KV "
    "it additionally audits the page pool: refcounts match the slot tables "
    "plus prefix-cache holds, the free list is exact, no page leaks",
)
define_flag(
    "FLAGS_serve_kv_page_size", 128,
    "paged KV: tokens per page.  Clamped to the engine max_len; every "
    "sequence holds ceil(len/page_size) pages instead of a dense max_len "
    "row, which is where the concurrency win comes from",
)
define_flag(
    "FLAGS_serve_kv_pool_pages", 0,
    "paged KV: total pages in the pool (page 0 is a permanent scratch page "
    "for masked/inactive writes).  0 = auto: slots * pages_per_seq + 1, "
    "every slot can hold a max_len sequence",
)
define_flag(
    "FLAGS_serve_prefix_cache", True,
    "paged KV: keep committed prompt pages in a host-side prefix index so a "
    "request sharing a cached prefix maps those pages read-only (refcounted, "
    "copy-on-write into partially filled pages) and prefills only its "
    "unshared suffix",
)
define_flag(
    "FLAGS_serve_spec_k", 0,
    "paged engine: speculative decoding draft length — an n-gram/prompt-"
    "lookup drafter proposes up to k tokens per greedy slot from the slot's "
    "own prompt+generated history and the target model verifies all k+1 "
    "positions in ONE compiled forward (shaped [slots, k+1]; acceptance is "
    "data, so slot churn still causes zero recompiles).  0 disables "
    "speculation (the plain one-token decode step).  Per-request 'spec_k' "
    "clamps below this engine-wide cap",
)
define_flag(
    "FLAGS_serve_spec_ngram", 3,
    "speculative decoding: longest n-gram the prompt-lookup drafter matches "
    "against the slot's history (it backs off n..1 and proposes nothing on "
    "a miss — a prompt shorter than n just drafts from lower orders)",
)
define_flag(
    "FLAGS_serve_kv_quant", "none",
    "paged engine: KV-cache storage precision — 'none' stores pages in the "
    "model's cache dtype; 'int8' stores K/V pages as int8 with per-token-"
    "row, per-kv-head float32 scales in a parallel scale arena that rides "
    "the same page tables/refcounts/COW/prefix machinery, roughly doubling "
    "the page pool the same HBM budget buys (FLAGS_serve_kv_pool_pages "
    "auto-sizing accounts for the scale bytes).  The fused Pallas decode "
    "kernel dequantizes per page tile in VMEM; the gather oracle applies "
    "the same dequant math",
)
define_flag(
    "FLAGS_serve_tp", 1,
    "tensor-parallel serving: shard the model's column/row-parallel "
    "projections, the paged KV arena (kv_heads axis), and the fused "
    "paged-decode kernel across the first N devices of an 'mp' mesh built "
    "at engine construction.  All per-slot scheduling state stays host-side "
    "and replicated, so the compiled budget and zero-recompile contract are "
    "unchanged; heads/kv_heads must divide by N (typed ShardingError "
    "otherwise).  1 disables (single-device engine, no mesh installed)",
)
define_flag(
    "FLAGS_serve_cp", 1,
    "context-parallel serving (long-context tier): block-shard the paged KV "
    "arena's PAGE axis across N devices of a 'cp' mesh axis (composing with "
    "FLAGS_serve_tp as a cp x mp mesh over the first cp*tp devices).  One "
    "sequence's pages spread round-robin over the shards — sequence page k "
    "lives on shard k % cp — so a 64k-token prompt's KV never has to fit "
    "one device's arena; each shard runs the fused paged-decode kernel "
    "over its local page-table slice and the shards merge per-row online-"
    "softmax partials (m, l, acc) with one pmax + two psums per step.  "
    "Requires role=colocated; pool auto-sizing and "
    "admission headroom become per-shard quantities.  1 disables",
)
define_flag(
    "FLAGS_serve_session_max", 256,
    "session KV (multi-turn serving): maximum resident sessions per engine. "
    "A request carrying 'session_id' pins its committed prompt+generation "
    "pages in the prefix cache so turn N+1 chunk-prefills only the unshared "
    "suffix; sessions beyond this bound (or under page pressure once the "
    "unpinned prefix cache is exhausted) are evicted whole, LRU first.  "
    "Requires the prefix cache enabled",
)
define_flag(
    "FLAGS_serve_role", "colocated",
    "disaggregated serving: role this replica plays in the fleet — "
    "'colocated' (classic single-box engine: prefill and decode on the "
    "same worker), 'prefill' (runs chunked prefill into committed pages "
    "and exports the quantized page rows + prefix-chain metadata as a "
    "handoff payload; never decodes past the first token), or 'decode' "
    "(imports handoff payloads into its own page arena via a compiled "
    "page scatter and streams the remaining tokens)",
)
define_flag(
    "FLAGS_serve_reserve_ttl_s", 30.0,
    "disaggregated serving: seconds a decode-side page reservation "
    "(POST /reserve) stays valid before it is reclaimed.  The router "
    "reserves decode pages before prefill starts so the handoff can "
    "never strand a finished prefill with nowhere to land; a crashed "
    "router or dropped handoff simply lets the TTL expire, returning "
    "the reserved headroom to the admission path",
)
define_flag(
    "FLAGS_serve_lora_capacity", 8,
    "multi-tenant LoRA serving: resident-adapter slots in the paged adapter "
    "arena (slot 0 is the pinned base-model passthrough on top of this).  "
    "Residency is refcounted + LRU-evicted exactly like KV pages; a request "
    "naming a non-resident adapter loads it at admission (or parks under "
    "pressure).  Per-slot adapter ids are traced data, so any mix of "
    "resident adapters co-batches in the same compiled decode step",
)
define_flag(
    "FLAGS_serve_lora_rank_max", 8,
    "multi-tenant LoRA serving: the arena's stacked A/B factors are padded "
    "to this rank; registering an adapter with a higher rank than the "
    "engine's arena fails at submit.  Padding columns are zero — exact",
)
define_flag(
    "FLAGS_router_probe_interval", 0.25,
    "serving router: seconds between /healthz probes of each registered "
    "replica (drives live/ready/draining/dead tracking and load gauges)",
)
define_flag(
    "FLAGS_router_probe_timeout", 2.0,
    "serving router: per-probe HTTP timeout (s); a timed-out probe counts "
    "as a replica failure toward the circuit breaker",
)
define_flag(
    "FLAGS_router_max_retries", 3,
    "serving router: retry budget per request — connect failures, 503s, and "
    "retriable 504s fail over to another replica with jittered exponential "
    "backoff up to this many extra attempts (0 disables failover)",
)
define_flag(
    "FLAGS_router_retry_backoff", 0.05,
    "serving router: initial retry delay (s), doubled per attempt with "
    "+/-50% jitter; always clamped by the request's remaining deadline",
)
define_flag(
    "FLAGS_router_breaker_threshold", 3,
    "serving router: consecutive replica failures that trip its circuit "
    "breaker open (closed -> open -> half-open probe -> closed)",
)
define_flag(
    "FLAGS_router_breaker_cooldown", 1.0,
    "serving router: seconds an open circuit breaker waits before letting "
    "ONE half-open trial request through; success closes it, failure "
    "re-opens for another cooldown",
)
define_flag(
    "FLAGS_router_max_inflight", 64,
    "serving router: bounded admission — requests in flight through the "
    "router beyond this are shed with 503 + Retry-After from the healthiest "
    "replica's drain estimate (brownout)",
)
define_flag(
    "FLAGS_router_hedge_s", 0.0,
    "serving router: hedged dispatch delay (s) — a zero-token request still "
    "unanswered after this long is duplicated onto a second replica and the "
    "first completed response wins (pure generation makes the duplicate "
    "safe).  0 disables hedging.",
)
define_flag(
    "FLAGS_router_idem_ttl", 300.0,
    "crash-proof front door: seconds a completed response stays cached "
    "against its X-Idempotency-Key (router AND serve-side dedupe).  Within "
    "the TTL a resubmitted key replays the stored bytes instead of "
    "generating again; an in-flight resubmit joins the live request",
)
define_flag(
    "FLAGS_router_journal_segment_records", 1024,
    "control-plane journal: records per append-only segment file before "
    "rotating to a new one (checksummed lines, atomic-rename compaction; "
    "see serving/journal.py)",
)
define_flag(
    "FLAGS_router_takeover_timeout", 2.0,
    "router standby: seconds the primary's heartbeat seq may sit still "
    "(on the STANDBY's own clock — no cross-process clock comparison) "
    "before the standby declares it dead and takes over",
)
define_flag(
    "FLAGS_router_retry_after_jitter", 0.25,
    "serving router: +/- fractional jitter applied to Retry-After values "
    "emitted on sheds (brownout, no-replica, deadline-infeasible) so "
    "simultaneous 503s during takeover don't resynchronize clients into a "
    "thundering herd at the successor.  0 disables jitter",
)
define_flag(
    "FLAGS_autoscale_min_replicas", 1,
    "serving autoscaler: floor of the replica band — scale-down never "
    "drains below this many ready replicas",
)
define_flag(
    "FLAGS_autoscale_max_replicas", 4,
    "serving autoscaler: ceiling of the replica band — scale-up never "
    "spawns beyond this many managed replicas",
)
define_flag(
    "FLAGS_autoscale_interval", 0.5,
    "serving autoscaler: seconds between control-loop ticks (each tick "
    "reads every replica's probe snapshot and decides up/down/hold)",
)
define_flag(
    "FLAGS_autoscale_up_ticks", 2,
    "serving autoscaler hysteresis: consecutive pressured ticks required "
    "before a scale-up fires (one noisy probe must not spawn a replica)",
)
define_flag(
    "FLAGS_autoscale_down_ticks", 6,
    "serving autoscaler hysteresis: consecutive idle ticks required before "
    "a scale-down fires (asymmetric on purpose: scaling up is cheap to "
    "undo, draining a warm replica is not)",
)
define_flag(
    "FLAGS_autoscale_up_cooldown", 2.0,
    "serving autoscaler: seconds after ANY scaling action before another "
    "scale-UP may fire (lets the new replica's probes land before the "
    "loop judges the fleet under-provisioned again)",
)
define_flag(
    "FLAGS_autoscale_down_cooldown", 10.0,
    "serving autoscaler: seconds after ANY scaling action before a "
    "scale-DOWN may fire (longer than up: flapping capacity away during a "
    "burst lull re-queues real work)",
)
define_flag(
    "FLAGS_autoscale_up_drain_s", 0.5,
    "serving autoscaler pressure signal: the fleet's BEST (minimum) "
    "queue-drain estimate above this many seconds counts as a pressured "
    "tick — every replica already owes this much wall time",
)
define_flag(
    "FLAGS_autoscale_up_queue_depth", 4.0,
    "serving autoscaler pressure signal: mean queued requests per ready "
    "replica above this counts as a pressured tick",
)
define_flag(
    "FLAGS_autoscale_up_miss_rate", 0.05,
    "serving autoscaler pressure signal: any replica's deadline-miss-rate "
    "EWMA above this counts as a pressured tick (the SLO input)",
)
define_flag(
    "FLAGS_autoscale_min_page_free", 0.05,
    "serving autoscaler pressure signal: any replica's KV page-pool free "
    "fraction below this counts as a pressured tick (arena exhaustion "
    "rejects work the queue gauges cannot see)",
)
define_flag(
    "FLAGS_autoscale_down_drain_s", 0.05,
    "serving autoscaler idle signal: a tick is idle only when every ready "
    "replica's drain estimate is below this, no queue holds work, and the "
    "fleet is above the min band",
)
define_flag(
    "FLAGS_autoscale_tp_max", 1,
    "serving autoscaler: cap on the --tp degree chosen for a spawned "
    "replica (the controller picks the largest power of two that fits the "
    "unclaimed devices, clamped here; 1 = always single-device replicas)",
)
define_flag(
    "FLAGS_autoscale_down_idle_tokens_s", 0.0,
    "serving autoscaler cost signal: a scale-down additionally requires at "
    "least this much reclaimable idle decode capacity (tokens/s summed "
    "over idle ready replicas) — down-scaling optimizes $/token, not just "
    "emptiness.  0 keeps the pure-emptiness behavior",
)
define_flag(
    "FLAGS_debug_sanitize", False,
    "runtime trace/sync sanitizer (paddle_tpu.analysis.sanitizer): count "
    "every fresh trace, eager-cache miss, and device->host sync; inside a "
    "declared steady-state region (serving scheduler after warmup, the "
    "in-flight ring) any unexpected one is attributed to its user-level "
    "source line, surfaced in profiler.summary(), and raised as a hard "
    "error by the test suite's sanitize fixture",
)
define_flag(
    "FLAGS_trace",
    os.environ.get("PADDLE_TRACE", "") not in ("", "0", "false"),
    "host-side request tracing (paddle_tpu.obs): record per-stage spans "
    "(router.admit, replica.forward, serve.handle, engine.queue/prefill/"
    "decode/fetch, fit.step/window) into a bounded in-memory buffer, "
    "exported on GET /trace/<id> and as Chrome-trace JSON.  Pure host-side "
    "bookkeeping — no recompiles, no device syncs; off by default",
)
define_flag(
    "FLAGS_obs_buffer_events", 4096,
    "capacity of the obs span buffer and the flight-recorder event ring "
    "(paddle_tpu.obs); oldest entries are evicted first",
)


# ---------------------------------------------------------------------------
# Persistent compilation cache: every XLA compile — eager op executables,
# @to_static train steps, the serving engine's steps — goes through jax's
# disk cache, so a (program, topology, version) pays its compile bill once
# per machine, not once per process.  The directory is part of an entry's
# identity to whoever looks for it next, so it never moves: where the
# environment places it (JAX_COMPILATION_CACHE_DIR, which jax reads itself
# and this module then leaves alone), else one fixed path in the checkout.
# The AOT snapshot tier (jit/cache.py, FLAGS_compile_cache_dir) sits above
# this and additionally skips trace+lower.
# ---------------------------------------------------------------------------

DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_compile_cache_stats = {"disk_hits": 0, "requests": 0}


def _install_cc_listener():
    """Count jax's persistent-cache traffic: requests == compile calls that
    consulted the disk cache; disk_hits == loads that skipped XLA entirely.
    requests - disk_hits is therefore the fresh-XLA-compile count."""
    from jax._src import monitoring as _mon

    def _listener(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            _compile_cache_stats["disk_hits"] += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            _compile_cache_stats["requests"] += 1

    _mon.register_event_listener(_listener)


def compile_cache_stats():
    d = jax.config.jax_compilation_cache_dir
    out = dict(_compile_cache_stats)
    out["dir"] = d or ""
    out["misses"] = out["requests"] - out["disk_hits"]
    entries = 0
    size = 0
    if d:
        try:
            for name in os.listdir(d):
                if name.endswith("-cache"):
                    entries += 1
                    try:
                        size += os.path.getsize(os.path.join(d, name))
                    except OSError:
                        pass
        except OSError:
            pass
    out["entries"] = entries
    out["bytes"] = size
    return out


def _setup_compile_cache():
    """Import-time, once: give jax's persistent cache its directory unless
    the environment already did, and cache every executable — the default
    thresholds skip small/fast compiles, but cold-start latency is exactly
    the sum of those."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _install_cc_listener()


_setup_compile_cache()
