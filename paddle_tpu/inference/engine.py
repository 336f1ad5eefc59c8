"""Continuous-batching inference engine (reference capability: the inference
runtime's flash-decode serving path, SURVEY §2.1 L8 — scheduling layer).

The lock-step `GenerationPredictor` runs every request in a batch from first
token to last together: one long generation holds the whole batch hostage,
and a new request waits for the batch to drain.  This engine instead keeps
`slots` sequences in flight over ONE block-paged KV ARENA per layer
(`[num_pages, kv_heads, page_size, width]` per row kind the model declares
through `cache_rows()`; a model whose layers differ declares each layer's
rows and its fixed STATE PER SLOT, `[slots, ...]` buffers beside the arenas,
through `cache_layers()`: a layer without rows has no arena, and a model with
state has no prefix cache) and runs ONE compiled decode step whatever the
occupancy: per-slot `pos`, `active` masks and the page tables
(`[slots, max_pages_per_seq]` int32) are DATA, never shapes, so requests
joining, finishing, and slots being recycled cause zero recompiles after
warmup.  A request only occupies pages covering `prompt + max_new_tokens`,
so the arena serves more concurrent sequences than `slots * max_len` rows
would.

New requests are prefilled through length-bucketed compiled prefill
executables — the prompt pads up to its bucket, attends to itself causally,
and its K/V scatter into the pages of its table row (the row is data, so one
executable per bucket serves every slot); a prompt longer than the largest
bucket is admitted as consecutive chunks of it.  Prefills interleave with
in-flight decode at step granularity, dispatched behind the step in flight
and fetched with it a step late; a slot at max_new_tokens leaves the step by
the host's count and is recycled when its last token is delivered, a tick
later (at EOS, whose value decides, at once).

Why padding garbage is safe: padding rows of a prefill never touch a mapped
page (they fall on the scratch page, page 0 of each shard, which no sequence
maps, or are dropped), and inactive slots decode at pos 0 through an
all-zero table row, so their write is scratch too.  Rows of a mapped page
past a sequence's `pos` are masked to zero weight, and the decode step that
first brings such a row into the attended window overwrites it first.

Prefix cache: a host-side `PrefixCache` indexes committed prompt pages.  A
request sharing a cached prefix maps the shared full pages READ-ONLY
(refcounted), copy-on-writes only a partially filled shared page, and
prefills just the unshared suffix through a chunk-prefill executable (rope
offset and page table as data).

Compiled-executable budget: 2 * len(prefill_buckets) + 2 (fresh + chunk per
bucket, decode, page copy), asserted by tests via `compile_counts()` (keys
prefill/chunk_prefill/decode/copy; a decode-role engine adds `import`,
speculation adds `verify`).  Every function rides @to_static, so the
persistent compile cache and AOT snapshots apply per bucket: a restarted
server binds the previous process's executables without tracing.

Admission gates on pages: submit raises QueueFull when a request's worst
case page need exceeds the pool, and the scheduler defers admission (the
request stays at the head of the line) until free + cache-evictable pages
cover it.  Restart keeps the pool AND the prefix cache warm.

Serving fault domain (the serving mirror of the training fault domain):

- **Request lifecycle** — every submitted request resolves EXACTLY once:
  queued → prefilling → decoding → {eos, length, timeout, cancelled,
  restarted, error}.  `deadline_s` evicts an expired slot at step
  granularity (slot recycle, no recompile) and `submit` rejects requests
  whose deadline cannot beat the current queue-drain estimate; `cancel()`
  frees the slot the same way.
- **Watchdogged regions** — prefill dispatch, decode dispatch, and the
  host token fetch run under `fault.watchdog.arm` with deadline
  `FLAGS_serve_step_timeout_sec`; an overrun records a trip (it does NOT
  kill the process — serving restarts the ENGINE) that the
  `fault.EngineSupervisor` turns into a bounded warm restart.
- **Warm restart** — `restart()` abandons a wedged scheduler thread via a
  generation counter (the stale thread aborts at its next state touch),
  re-queues in-flight requests that emitted no tokens yet, fails the rest
  with the typed `EngineRestarted` error, and rebinds the SAME compiled
  executables and KV arena: 0 fresh compiles, asserted by the chaos drills.
  Reusing the arena un-scrubbed is safe by the padding-garbage invariant
  above.
- **Injectable faults** — `serve.prefill.hang` (blocks the prefill
  dispatch), `serve.decode.nan` (poisons ONE slot's logits with NaN as
  traced data for one step; only that request errors, co-batched requests
  are bit-identical to an unpoisoned run), `serve.loop.crash` (kills the
  scheduler thread) — armed via the usual `FLAGS_fault_inject` registry.

Speculative decoding (ISSUE 11, FLAGS_serve_spec_k > 0):
decode is HBM-bandwidth-bound — one token per step leaves the FLOPs idle —
so the engine drafts k candidate tokens per greedy slot with a host-side
prompt-lookup `NgramDrafter` (no second model; spec.py) and the target
model verifies all k+1 positions in ONE compiled forward over the same
paged arena (`_verify_paged_body`, shaped [slots, k+1]).  Acceptance
length, proposed tokens, and per-slot draft validity are DATA, so the
compiled budget grows by exactly one executable (`compile_counts()` gains
`verify`) and join/finish/recycle still cause zero recompiles.  Greedy
equivalence is structural: draft i is accepted only while it equals the
model's own greedy continuation, so output is token-identical to the
plain engine whatever the drafter proposes — rejected-position KV writes
land on scratch (page-table redirect) or past the advanced `pos`, where
the next window overwrites them before anything attends them.  Sampled
(temp > 0) slots ride the same step at draft length 0, column 0 sampling
on the plain decode's key schedule.  The drain/admission EWMA consumes
observed tokens-per-step so Retry-After and DeadlineUnattainable stay
honest when steps emit >1 token.
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import math
import queue
import threading
import time

import numpy as np

from ..analysis import sanitizer as _san
from ..fault import injection as _inj
from ..fault import watchdog as _wd
from ..framework import core as _fcore
from ..obs import flight as _flight
from ..obs import trace as _obs
from ..models.llama import (
    PagedDecodeView,
    PagedKVCache,
    PagedPrefillView,
)
from ..tensor import Tensor
from .paging import (
    PagePool,
    PrefixCache,
    QuantConfigError,
    SessionStore,
    WindowPages,
    check_scale_arenas,
    check_table_bounds,
    kv_page_bytes,
    shard_kv_for_tp,
    spec_write_pages,
    validate_kv_quant,
)
from .spec import NgramDrafter

logger = logging.getLogger("paddle_tpu")

# deadline-miss-rate EWMA weight per terminal resolution: ~20-request
# memory, so one miss reads 0.05 and a sustained miss storm saturates
# toward 1.0 within a few dozen requests — fast enough for an autoscaler
# tick, long enough that one straggler does not flap the fleet
_MISS_EWMA_ALPHA = 0.05


class EngineUnavailable(RuntimeError):
    """The engine cannot take this request right now (queue full, draining,
    dead, or an unattainable deadline) — serve() maps this family to HTTP
    503 with a Retry-After derived from the queue-drain estimate."""

    def __init__(self, msg, retry_after_s=None):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class QueueFull(EngineUnavailable):
    """Admission queue at capacity — submit() fails fast (serve() maps this
    to HTTP 503)."""


class DeadlineUnattainable(EngineUnavailable):
    """Deadline-aware admission: the request's deadline cannot beat the
    current queue-drain estimate, so admitting it would only burn a slot on
    work guaranteed to be evicted."""


class ContextOverflow(ValueError):
    """Typed 400 (ISSUE 20): the prompt (or prompt + requested generation)
    cannot fit this engine's context — raised at ADMISSION, before any page
    is reserved or allocated, so an over-length request costs nothing.
    Carries the capacity geometry (per-shard under cp) for the HTTP body."""

    def __init__(self, prompt_len, max_len, cp=1, pages_per_shard=0,
                 page_size=0):
        self.prompt_len = int(prompt_len)
        self.max_len = int(max_len)
        self.cp = int(cp)
        self.pages_per_shard = int(pages_per_shard)
        self.page_size = int(page_size)
        detail = f"prompt length {self.prompt_len} exceeds engine capacity: "
        detail += f"max_len={self.max_len}"
        if self.cp > 1:
            detail += (
                f" (cp={self.cp} shards x {self.pages_per_shard} pages x "
                f"{self.page_size} tokens/page per shard)"
            )
        super().__init__(detail)

    def body(self):
        """JSON-safe capacity record for the serving layer's 400 body."""
        out = {
            "prompt_len": self.prompt_len,
            "max_len": self.max_len,
            "cp": self.cp,
        }
        if self.pages_per_shard:
            out["pages_per_shard"] = self.pages_per_shard
            out["page_size"] = self.page_size
            out["tokens_per_shard"] = self.pages_per_shard * self.page_size
        return out


class UnsupportedByModel(ValueError):
    """The engine was asked for something the served model declares it cannot
    do (`model.engine_unsupported`): refused at construction, never inside a
    compiled step."""

    def __init__(self, model, feature, asked):
        self.feature = feature
        super().__init__(
            f"{type(model).__name__} does not support {feature} ({asked}): "
            f"it declares engine_unsupported={sorted(model.engine_unsupported)}"
        )


class DeadlineExceeded(TimeoutError):
    """The request's deadline expired mid-flight; its slot was evicted at
    step granularity (recycled, no recompile)."""

    def __init__(self, request_id, tokens_done, max_new_tokens, deadline_s):
        self.request_id = request_id
        self.tokens_done = tokens_done
        super().__init__(
            f"request {request_id} missed its {deadline_s}s deadline "
            f"({tokens_done}/{max_new_tokens} tokens generated)"
        )


class RequestCancelled(RuntimeError):
    """The request was cancelled via EngineRequest.cancel()."""

    def __init__(self, request_id, tokens_done):
        self.request_id = request_id
        self.tokens_done = tokens_done
        super().__init__(
            f"request {request_id} cancelled ({tokens_done} tokens generated)"
        )


class EngineRestarted(RuntimeError):
    """503-style typed error: the engine restarted (or died) while this
    request was in flight and its decode state was lost.  The request was
    NOT silently dropped — retry it."""

    def __init__(self, request_id, reason=""):
        self.request_id = request_id
        self.reason = reason
        msg = f"engine restarted while request {request_id} was in flight"
        if reason:
            msg += f" ({reason})"
        super().__init__(msg + "; retry the request")


class NonFiniteLogits(FloatingPointError):
    """This request's decode produced a non-finite logit window; it errors
    alone — co-batched slots are row-independent and finish unaffected."""


class _StaleEngine(Exception):
    """Internal: the scheduler generation this thread was started for was
    superseded by a restart; abort without touching engine state."""


class _Unfetched:
    """One dispatched program whose tokens are still on the device: a decode
    step (`nxt` [S, 1], `finite` [S]) or a prefill's first token (`nxt` [1],
    `first` the attributes of its `engine.prefill` span).  `pairs` are the (slot, request) it ran
    for, as seated at dispatch: by the fetch the slot may hold another.
    `eager` asks for delivery a step behind the device and no later: it holds
    a streamed token, a first token, or a slot's last."""
    __slots__ = ("nxt", "finite", "pairs", "t0", "stats", "first", "eager")

    def __init__(self, nxt, finite, pairs, t0, stats=(), first=None, eager=False):
        self.nxt, self.finite, self.pairs, self.t0 = nxt, finite, pairs, t0
        self.stats, self.first, self.eager = stats, first, eager


# the phases of a scheduler tick: `profiler.TICK_PHASES`, by index.  The first
# six are spans under FLAGS_trace (`engine.tick.<phase>`), `other` is counted
# only
_EVICT, _ADMIT, _PREPARE, _DISPATCH, _WAIT, _DELIVER, _OTHER = range(7)


class _TickClock:
    """Where one `step()`'s wall time went, by phase.  One `perf_counter`
    stamp at every phase boundary: the seconds since the stamp before go to
    the phase that was current, so the phases are disjoint and add up to the
    tick.  A flush notes the phase it found, enters `wait` and `deliver`, and
    enters what it found again when it is done: its seconds come out of
    whatever enclosed it.  Outside a tick (`stop()`'s flush) no phase is
    current and nothing is counted; `enter` still returns its stamp.  `first`
    is when the tick first entered a phase (its span's start, where the phase
    gets one); `step` is the step the tick dispatched, if it did: (slots in
    it, requests queued, `busy_s`), for `record_serving_tick`; `n` is the
    tick's ordinal in the engine's life.  Floats on the host, touched by the
    scheduler alone: no lock, and legal inside the sanitizer's steady-state
    zone."""

    __slots__ = ("n", "phase", "mark", "secs", "first", "step")

    def __init__(self):
        self.n, self.phase = 0, None

    def open(self):
        self.n += 1
        self.secs, self.first, self.step = [0.0] * 7, [None] * 7, None
        self.phase, self.mark = _OTHER, time.perf_counter()

    def enter(self, phase):
        now = time.perf_counter()
        if self.phase is not None:
            self.secs[self.phase] += now - self.mark
            self.phase, self.mark = phase, now
            if phase is not None and self.first[phase] is None:
                self.first[phase] = now
        return now


class EngineRequest:
    """Handle for one submitted generation: streaming callback target,
    completion event, deadline/cancellation, and timing for the serving
    gauges.  Lifecycle: queued → prefilling → decoding → one of
    {eos, length, timeout, cancelled, restarted, error} — exactly once."""

    def __init__(self, rid, prompt, max_new_tokens, temperature, eos_token_id,
                 on_token, deadline_s=None, trace=None, spec_k=None,
                 adapter=None):
        self.id = int(rid)
        # (trace_id, parent_span_id) from the submitting hop, or None;
        # every engine-stage span for this request parents under it
        self.trace = trace
        self.prompt = prompt  # np.int32 [L]
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_token_id = eos_token_id
        # per-request speculation cap: None = engine default, 0 = opt out,
        # >0 clamps below the engine-wide FLAGS_serve_spec_k
        self.spec_k = None if spec_k is None else int(spec_k)
        # resolved LoRAAdapter (None = base model); adapter_slot is the
        # arena row this request's binding ref pins, set at admission
        self.adapter = adapter
        self.adapter_slot = None
        self.on_token = on_token
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self.tokens = []  # generated ids (includes eos when hit)
        self.finished = threading.Event()
        self.finish_reason = None  # eos|length|timeout|cancelled|restarted|error
        self.state = "queued"  # live phase; finish_reason once terminal
        self.cancelled = False
        self.error = None
        # disaggregated serving (ISSUE 19): export_kv asks the engine to
        # read the committed prompt pages into kv_export at finish; handoff
        # is the (deserialized layers, first_token) pair a decode-role
        # engine imports instead of prefilling; reservation names the
        # decode-side page hold this admission consumes
        self.export_kv = False
        self.kv_export = None
        self.handoff = None
        self.reservation = None
        # session KV (ISSUE 20): session_id names the multi-turn KV hold
        # this request rides; session_reused_tokens counts prompt tokens
        # whose KV came from the session's pinned pages (skipped prefill)
        self.session_id = None
        self.session_reused_tokens = 0
        self.ttft_s = None
        self._submit_t = None
        self._deadline_t = None  # absolute perf_counter deadline
        self._finish_t = None

    def cancel(self):
        """Ask the scheduler to evict this request at its next step: a
        queued request resolves without ever taking a slot, a slotted one
        has its slot recycled (no recompile).  Idempotent; resolution is
        still exactly-once (`finish_reason == "cancelled"`)."""
        self.cancelled = True
        return self

    def expired(self, now=None):
        if self._deadline_t is None:
            return False
        return (time.perf_counter() if now is None else now) >= self._deadline_t

    def wait(self, timeout=None):
        """Block until the request finishes; returns prompt + generated ids.
        Raises a TimeoutError naming the request and its live state when
        `timeout` elapses first (never a None-ish partial result), and
        re-raises the request's typed error (DeadlineExceeded,
        RequestCancelled, EngineRestarted, NonFiniteLogits, ...) when the
        request resolved unsuccessfully."""
        if not self.finished.wait(timeout):
            raise TimeoutError(
                f"request {self.id} not finished after {timeout}s "
                f"(state={self.state}, "
                f"{len(self.tokens)}/{self.max_new_tokens} tokens)"
            )
        if self.error is not None:
            raise self.error
        return np.concatenate([self.prompt, np.asarray(self.tokens, np.int32)])


class ContinuousBatchingEngine:
    """Slot-pooled continuous-batching engine over a causal-LM with the
    compiled static-KV decode contract (`model.llama(toks, caches=, pos=)` +
    `model.lm_head`, i.e. LlamaForCausalLM and shape-compatible models).

    submit() enqueues (bounded admission queue -> QueueFull, deadline-aware
    admission -> DeadlineUnattainable); the scheduler — either the
    background thread started by start()/serve(), or synchronous
    step()/run_until_idle() calls — admits queued requests into free slots
    via bucketed prefill and advances all active slots one token per decode
    step.  Tokens stream through per-request `on_token` callbacks as they
    are produced.  Pair with `fault.EngineSupervisor` for watchdogged
    restart-with-backoff of a wedged/dead scheduler.
    """

    def __init__(self, model, slots=None, max_len=None, prefill_buckets=None,
                 queue_depth=None, seed=0, page_size=None,
                 pool_pages=None, prefix_cache=None, spec_k=None, lora=None,
                 decode_kernel=None, tp=None, kv_quant=None, role=None,
                 cp=None, session_max=None):
        import jax

        from .. import jit, to_tensor

        cfg = model.config
        self.model = model
        # what the served model cannot do, by the constructor argument's name;
        # each is checked where the argument is resolved, flags included
        unsupported = frozenset(getattr(model, "engine_unsupported", ()))

        def refuse(feature, on, asked):
            if on and feature in unsupported:
                raise UnsupportedByModel(model, feature, asked)

        self.slots = int(slots if slots is not None else _fcore.flag("FLAGS_serve_slots"))
        max_len = max_len if max_len is not None else cfg.max_position_embeddings
        # rope tables (and therefore positions) top out at max_position_embeddings
        self.max_len = int(min(max_len, cfg.max_position_embeddings))
        if prefill_buckets is None:
            raw = str(_fcore.flag("FLAGS_serve_prefill_buckets"))
            prefill_buckets = [int(x) for x in raw.split(",") if x.strip()]
        self.prefill_buckets = sorted(
            {int(b) for b in prefill_buckets if 0 < int(b) < self.max_len}
        )
        if not self.prefill_buckets:
            raise ValueError("prefill_buckets must contain a value < max_len")
        self.queue_depth = int(
            queue_depth if queue_depth is not None else _fcore.flag("FLAGS_serve_queue_depth")
        )

        # generation is inference: dropout must not bake into the cached
        # executables (they outlive any later train() switch)
        if getattr(model, "training", False):
            model.eval()

        # tensor-parallel serving (ISSUE 14): validate + install the 'mp'
        # mesh and re-place the weights BEFORE any cache/arena below is
        # allocated, so every serving buffer is born with its mesh layout.
        # All per-slot scheduling state stays host-side and replicated —
        # the compiled budget and zero-recompile contract are unchanged.
        from .. import profiler as _prof
        from ..distributed import mesh as _mesh_mod
        from ..distributed.sharding import ShardingError, validate_tp

        self.tp = int(_fcore.flag("FLAGS_serve_tp") if tp is None else tp)
        refuse("tp", self.tp > 1, f"tp={self.tp}")
        validate_tp(cfg, self.tp)
        # context-parallel serving (ISSUE 20): 'cp' composes with 'mp' —
        # the paged arena's PAGE axis block-shards over cp shards while kv
        # heads shard over mp.  Validated here, typed errors at
        # construction; all host-side page bookkeeping becomes per-shard
        # (PagePool shards, round-robin sequence-page placement).
        self.cp = int(_fcore.flag("FLAGS_serve_cp") if cp is None else cp)
        if self.cp < 1:
            raise ShardingError(f"cp must be >= 1, got {self.cp}")
        refuse("cp", self.cp > 1, f"cp={self.cp}")
        self._mesh = None
        if self.tp > 1:
            if int(getattr(cfg, "tensor_parallel_degree", 1)) != self.tp:
                raise ShardingError(
                    f"engine tp={self.tp} but the model was built with "
                    f"tensor_parallel_degree={cfg.tensor_parallel_degree}: "
                    "construct the model with LlamaConfig(tensor_parallel_"
                    f"degree={self.tp}) so its projections are the column/"
                    "row-parallel layers the mesh shards"
                )
        if self.tp > 1 or self.cp > 1:
            self._mesh = _mesh_mod.serving_mesh(self.tp, cp=self.cp)
            if self.tp > 1:
                from ..models.llama import shard_llama_for_tp

                shard_llama_for_tp(model)
        # per compiled step at TP>1, GSPMD inserts one allreduce per
        # row-parallel output (o_proj + down_proj per layer) plus one for
        # the vocab-sharded logits' sampling reduction
        _prof.record_mesh_topology(
            devices=len(jax.devices()),
            tp=self.tp,
            cp=self.cp,
            # ISSUE 20: cp adds one online-softmax partials combine (pmax +
            # 2x psum, fused) per layer per decode step on top of the TP
            # row-parallel allreduces
            allreduce_per_step=(
                (2 * cfg.num_hidden_layers + 1 if self.tp > 1 else 0)
                + (cfg.num_hidden_layers if self.cp > 1 else 0)
            ),
        )

        # a token's rows in each layer's cache, as the model declares them:
        # (name, heads, width, dtype)
        rows = list(model.cache_rows())
        # per layer (rows, state): a model whose layers differ declares them
        # with `cache_layers()`; state is a slot's, [(name, shape, dtype)]
        # a third entry is the REACH of the layer's rows in tokens (None: a
        # query reads every row before it): such a layer's pages live in a
        # second page group, released behind the window (`WindowPages`)
        declared = (
            list(model.cache_layers()) if hasattr(model, "cache_layers")
            else [(rows, [])] * cfg.num_hidden_layers
        )
        layers = [(list(e[0]), list(e[1])) for e in declared]
        reaches = [e[2] if len(e) > 2 else None for e in declared]
        windowed = {int(r) for r in reaches if r is not None}
        if len(windowed) > 1:
            raise ValueError(
                f"windowed layers of one reach make one page group; got {sorted(windowed)}"
            )
        # the handoff, the int8 arena and the fused kernel's limits exist for
        # the engine's own page group alone, and speak of the geometry of ITS
        # first kind of rows (K's), not of whatever the model declares first
        _, kv_heads, head_dim, cache_dtype = next(
            (r[0] for (r, _), reach in zip(layers, reaches) if r and reach is None),
            rows[0],
        )
        if windowed:
            # what rests on every layer holding the whole prefix, or on one
            # page group: refused by name like what the model refuses itself
            unsupported |= {"prefix_cache", "spec_k", "role", "cp", "kv_quant"}
            refuse("cp", self.cp > 1, f"cp={self.cp}")
        # quantized KV serving (ISSUE 18): validated HERE — typed
        # QuantConfigError at construction, never a dtype mismatch inside a
        # compiled step — and folded into every cache-key surface: the
        # arenas' int8/scale avals, the paged_flash_decode closure, and the
        # FLAGS_serve_kv_quant entries in ops.dispatch._dispatch_salt and
        # the AOT snapshot fingerprint
        self.kv_quant = validate_kv_quant(
            _fcore.flag("FLAGS_serve_kv_quant") if kv_quant is None
            else kv_quant
        )
        refuse("kv_quant", self.kv_quant != "none", f"kv_quant={self.kv_quant!r}")
        # disaggregated serving (ISSUE 19): the role decides which side of
        # the paged-KV handoff this engine plays.  'prefill' exports its
        # committed prompt pages at finish; 'decode' grows ONE extra
        # compiled executable (the page-import scatter) and accepts
        # handoff submissions; 'colocated' is the classic single-box
        # engine with an unchanged compiled budget.
        self.role = str(
            _fcore.flag("FLAGS_serve_role") if role is None else role
        ).strip().lower()
        refuse("role", self.role in ("prefill", "decode"), f"role={self.role!r}")
        if self.role not in ("colocated", "prefill", "decode"):
            raise ValueError(
                f"role must be colocated|prefill|decode, got {self.role!r}"
            )
        if self.cp > 1 and self.role != "colocated":
            raise ShardingError(
                f"cp={self.cp} with role={self.role!r}: the disaggregated "
                "handoff assumes single-shard page ownership; run cp on "
                "colocated replicas"
            )
        ps = int(
            page_size if page_size is not None
            else _fcore.flag("FLAGS_serve_kv_page_size")
        )
        # a page never needs to exceed a sequence; clamping keeps the
        # default flag sane for tiny test engines
        self.page_size = max(1, min(ps, self.max_len))
        self.pages_per_seq = -(-self.max_len // self.page_size)
        if self.cp > 1:
            # per-shard geometry: sequence page k lives on shard k % cp,
            # so the table width pads to a cp multiple (shard s's local
            # table is exactly columns {s, s+cp, ...}) and every shard
            # holds pages_per_seq/cp entries of a full-length sequence
            self.pages_per_seq = -(-self.pages_per_seq // self.cp) * self.cp
        # paged-attention kernel selection (ISSUE 13): validated HERE so
        # a forced-fused engine fails at construction, not mid-traffic
        # inside a compiled step
        dk = "auto" if decode_kernel is None else str(decode_kernel)
        if dk not in ("auto", "fused", "gather"):
            raise ValueError(
                f"decode_kernel must be auto|fused|gather, got {dk!r}"
            )
        if dk == "fused":
            head_ok = head_dim <= 256
            page_ok = self.page_size % 8 == 0
            if not (head_ok and page_ok):
                raise ValueError(
                    "decode_kernel='fused' needs head_dim <= 256 and a "
                    f"sublane-aligned page_size (8|ps); got head_dim="
                    f"{head_dim}, page_size={self.page_size}"
                )
        self.decode_kernel = dk
        pp = int(
            pool_pages if pool_pages is not None
            else _fcore.flag("FLAGS_serve_kv_pool_pages")
        )
        cache_dtype_bytes = int(
            np.dtype(_fcore.to_jax_dtype(cache_dtype)).itemsize
        )
        if pp <= 0:  # auto: every slot can hold a max_len sequence
            pp = self.slots * self.pages_per_seq + 1
            if self.cp > 1:
                # PER-SHARD auto-sizing (ISSUE 20): each shard stores
                # pages_per_seq/cp pages of every slot's sequence plus
                # its own scratch page — the pool total is cp * that,
                # the same per-device HBM budget as the cp=1 pool
                pp = self.cp * (
                    self.slots * (self.pages_per_seq // self.cp) + 1
                )
            if self.kv_quant == "int8":
                # same HBM budget, more pages: the auto pool holds the
                # BYTES of the full-precision pool, so the int8 arena's
                # page count scales by full_page_bytes / (int8 page +
                # its scale rows) — ~1.94x at bf16 head_dim=128.  Scale
                # bytes are charged here, not hidden: the ratio uses
                # kv_page_bytes which counts the 4-byte f32 scale per
                # (row, kv head)
                full = kv_page_bytes(
                    self.page_size, kv_heads, head_dim,
                    cache_dtype_bytes, "none",
                )
                q8 = kv_page_bytes(
                    self.page_size, kv_heads, head_dim,
                    cache_dtype_bytes, "int8",
                )
                pp = (self.slots * self.pages_per_seq * full) // q8 + 1
        if self.cp > 1:
            # the pool block-shards over cp: equal per-shard ranges,
            # each with its own scratch page at the range head
            pp = max(pp, 2 * self.cp)
            pp = -(-pp // self.cp) * self.cp
        self.pool_pages = int(pp)
        # one cache object a layer: an arena for each kind of rows it
        # declares (none: no arena), a [slots, ...] buffer for each state
        self._window = (
            WindowPages(self.slots, self.pages_per_seq, self.page_size,
                        windowed.pop(), self.prefill_buckets[-1])
            if windowed else None
        )
        # which page group a layer's views read: 0 the engine's own, 1 the
        # window's
        self._layer_group = [0 if r is None else 1 for r in reaches]
        self._arenas = [
            PagedKVCache(self._window.pool_pages if g else self.pool_pages,
                         self.page_size, rows=r, quant=self.kv_quant,
                         state=st, slots=self.slots)
            for (r, st), g in zip(layers, self._layer_group)
        ]
        self._has_state = any(st for _, st in layers)
        if self.tp > 1 or self.cp > 1:
            for a in self._arenas:
                shard_kv_for_tp(a)
        # observability (ISSUE 18): arena + scale HBM bytes as set (not
        # accumulated) gauges, all layers included — /metrics renders
        # them as paddle_kv_quant_*
        scale_b = (
            2 * self.page_size * kv_heads * 4
            if self.kv_quant == "int8" else 0
        )
        # bytes per kind (a token's rows, a slot's state), over the layers
        # that hold it, as the buffers hold them
        by_kind, row_bytes = {}, 0
        for a, g in zip(self._arenas, self._layer_group):
            for n in a.row_names + a.state_names:
                t = getattr(a, n)
                nb = int(np.prod(t.shape)) * int(np.dtype(t._data.dtype).itemsize)
                kind = n + ".window" if g and n in a.row_names else n
                by_kind[kind] = by_kind.get(kind, 0) + nb
                row_bytes += nb if n in a.row_names else 0
        _prof.record_kv_quant(
            mode=self.kv_quant,
            arena_bytes=row_bytes,
            scale_bytes=cfg.num_hidden_layers * self.pool_pages * scale_b,
        )
        _prof.record_arena_bytes(by_kind)
        self._pool = PagePool(self.pool_pages, shards=self.cp)
        self._report_page_groups()
        # a hit resumes from pages alone, which a model with state per slot
        # cannot: asked for, it is refused; left to the flag, it is off
        refuse("prefix_cache", bool(prefix_cache), f"prefix_cache={prefix_cache!r}")
        use_prefix = bool(
            _fcore.flag("FLAGS_serve_prefix_cache")
            if prefix_cache is None else prefix_cache
        ) and "prefix_cache" not in unsupported
        self._prefix = PrefixCache(self.page_size) if use_prefix else None
        # session KV (ISSUE 20): named multi-turn holds on prefix-cache
        # chains.  Rides the prefix cache — without it, session_id still
        # parses but every turn re-prefills statelessly.
        self._sessions = (
            SessionStore(capacity=int(
                _fcore.flag("FLAGS_serve_session_max")
                if session_max is None else session_max
            ))
            if self._prefix is not None else None
        )
        # ignore sub-threshold matches: an accidental few-token overlap
        # between unrelated prompts must not flip a request onto the
        # chunk-prefill path (and its different first-token rounding)
        self.min_prefix_match = 8
        self._page_table = np.zeros(
            (self.slots, self.pages_per_seq), np.int32
        )
        self._slot_pages = [[] for _ in range(self.slots)]
        self._tables_t = None  # device mirror, rebuilt with _dev
        self._decode_fn = jit.to_static(self._decode_paged_body)
        self._prefill_fn = jit.to_static(self._prefill_paged_body)
        self._chunk_fn = jit.to_static(self._chunk_prefill_body)
        self._copy_fn = jit.to_static(self._copy_page_body)
        # handoff geometry, captured once: submit() validates incoming
        # payloads against it and the exporter stamps it on the wire
        self._kv_heads = int(kv_heads)
        self._head_dim = int(head_dim)
        self._kv_dtype_np = np.dtype(_fcore.to_jax_dtype(cache_dtype))
        # the import scatter is built ONLY for decode-role engines, so
        # colocated/prefill compile_counts() keep their exact dict shape
        self._import_fn = (
            jit.to_static(
                self._import_page_q8_body if self.kv_quant == "int8"
                else self._import_page_body
            )
            if self.role == "decode" else None
        )
        # multi-tenant LoRA (ISSUE 12): an AdapterArena whose per-slot ids
        # ride the executables as DATA — co-batched slots on different
        # adapters share one compiled step, id 0 is the base passthrough
        refuse("lora", lora is not None, "lora=")
        self._lora = lora
        if lora is not None and self.tp > 1:
            lora.shard_for_tp()
        # arena slot bound per ENGINE slot (0 = base model); mirrors
        # _page_table's lifecycle: set at slot landing, cleared at recycle
        self._slot_adapter = np.zeros(self.slots, np.int32)
        self._adapters_t = None  # device mirror, rebuilt with _dev
        # speculative decoding: it rides the page scatter's scratch redirect
        # for rejected-row safety
        sk = int(_fcore.flag("FLAGS_serve_spec_k") if spec_k is None else spec_k)
        if sk < 0:
            raise ValueError("spec_k must be >= 0")
        refuse("spec_k", sk > 0, f"spec_k={sk}")
        self.spec_k = sk
        self._spec_on = self.spec_k > 0
        self._spec_ngram = int(_fcore.flag("FLAGS_serve_spec_ngram"))
        self._verify_fn = (
            jit.to_static(self._verify_paged_body) if self._spec_on else None
        )
        self._drafters = [None] * self.slots  # per-slot NgramDrafter or None
        # EWMA of emitted tokens per slot-step (1.0 without speculation) —
        # the drain estimate divides by it so admission stays honest when
        # verify steps emit accepted runs
        self._tok_rate_ewma = 1.0
        self._key = to_tensor(np.asarray(jax.random.PRNGKey(int(seed))))

        # runtime-sanitizer bookkeeping: after warmup() the scheduler tick
        # runs inside a steady_state region (every fresh trace/compile/sync
        # in it is a finding)
        self._warmed = False

        # host-side slot table — mutated only under _mu, by the scheduler
        # generation that owns the engine (restart supersedes via _gen)
        self._slot_req = [None] * self.slots
        self._pos = np.zeros(self.slots, np.int32)
        self._last_tok = np.zeros(self.slots, np.int32)
        self._temps = np.zeros(self.slots, np.float32)
        # decode steps still to dispatch for each slot: set when it is seated
        # (max_new_tokens less the prefill's token), counted down at dispatch.
        # At 0 the slot's last step is in flight and it runs in no further one
        self._left = np.zeros(self.slots, np.int32)
        # what the host knows of the decode loop's device state (pos, active,
        # temps; with it the page tables and the adapters): None when slot
        # membership changed, uploaded anew from the host mirrors before the
        # next step.  No fetch: _pos is advanced at dispatch
        self._dev = None
        # the loop's token vector [S, 1], which the host does NOT know while
        # steps are in flight: the newest step's output, with each prefill's
        # first token written in on the device.  None only while _last_tok is
        # whole (nothing unfetched): then the next step uploads that
        self._toks_t = None
        # open decode-epoch summary for tracing: {"t0", "ticks", "members"},
        # one engine.decode span per traced member when membership changes
        self._ep = None
        # the traced flushes since the last epoch close, folded: [first
        # flush's start, last flush's end, flushes, entries fetched, seconds
        # blocked in the fetch]; one engine.fetch span per traced member
        # beside its engine.decode.  None with tracing off
        self._fold = None
        # the scheduler's clock of the tick in progress, and the trace the
        # engine's own spans (engine.tick.<phase>, one a phase a tick under
        # FLAGS_trace) go under: the engine's, no request's
        self._clock = _TickClock()
        self.trace_id = _obs.new_trace_id()
        # programs dispatched whose tokens the host has not fetched, in
        # dispatch order: [_Unfetched]
        self._pending_fetch = []
        # all-False poison vector reused every un-poisoned step (no per-step
        # H2D); serve.decode.nan swaps in a one-hot row for one step
        self._poison_zero = to_tensor(np.zeros(self.slots, bool))

        self._queue = queue.Queue(maxsize=self.queue_depth)
        self._requeue = []  # restart-recovered requests, ahead of the queue
        self._queued_new_tokens = 0  # tokens owed to queued+requeued work
        self._admitting = None  # request between queue-pop and slot landing
        # disaggregated page reservations (ISSUE 19): rid -> (pages, expiry).
        # A reservation is a PLAIN COUNTER against fresh-allocation headroom,
        # never a fake pool refcount — the page-invariant audit demands refs
        # equal observable holds exactly.  Expired entries are purged every
        # scheduler tick (TTL covers a router that died mid-handoff).
        self._reserved = {}
        self._reserved_pages = 0
        self._cv = threading.Condition()
        self._mu = threading.RLock()  # slot table / device state / requeue
        self._thread = None
        self._stop = False

        # fault domain: generation counter fences restarted-away schedulers;
        # the per-engine watchdog records trips instead of exiting
        self._gen = 0
        self._dead = False
        self._draining = False
        self.restart_count = 0
        self._watchdog = _wd.Watchdog(action=self._on_watchdog)
        self._watchdog_trip = None  # (region, deadline_s) set by the monitor
        self._last_progress = time.monotonic()
        self._step_ewma_s = None  # EWMA wall seconds per decode round
        self._last_flush_t = 0.0  # when the last fetch of decode steps ended
        # deadline-miss RATE over terminal resolutions (EWMA, not the
        # monotonic faults counter): 1.0 for a timeout eviction, 0.0 for a
        # normal finish, blended at _MISS_EWMA_ALPHA — the autoscaler and
        # brownout logic need "how often are we missing NOW", which a
        # running total cannot answer without a scrape-side derivative
        self._miss_ewma = 0.0

    # -- compiled bodies ----------------------------------------------------

    def _decode_paged_body(self, toks, pos, active, temps, poison, key, tables,
                           adapters):
        """One token for every slot: toks [S,1], pos [S], active [S] bool,
        temps [S] f32 (0 = greedy, >0 = sampled — per-slot, as data), poison
        [S] bool (chaos-only NaN injection — identity when all-False), key
        uint32[2].  Inactive slots run at pos 0 (scratch, see module doc).
        Each slot's K/V rows are reached through its page-table row (`tables`
        [slots, max_pages_per_seq] int32 — DATA, so remaps never retrace);
        rows beyond `pos` are masked to zero weight.  `adapters` [slots]
        int32 (data too) names each slot's LoRA arena row; with an arena
        attached every projection adds the gathered low-rank delta, and row
        0 (all-zero factors) keeps base-model slots bit-identical.
        Returns (next tokens [S,1], advanced pos [S], finite [S], key[,
        stats]): the loop state is device-resident and threads straight back
        in — between membership changes a decode step costs one executable
        dispatch plus the [S] token fetch, zero host->device transfers; at
        one the tokens stay (a prefill writes its first into them) and only
        pos, active, temps, tables and adapters are sent again.
        `finite` is the per-slot non-finite-logit-window watch: a
        poisoned/diverged slot errors alone, its co-batched rows are
        independent."""
        import jax
        import jax.numpy as jnp

        from ..ops.dispatch import apply

        pos_eff = apply(
            lambda p, a: jnp.where(a, p, 0), [pos, active], name="serve_pos_mask"
        )
        views = [
            PagedDecodeView(a, t, self.max_len, kernel=self.decode_kernel,
                            live=active)
            for a, t in zip(self._arenas, self._tables_by_layer(tables))
        ]
        lora = self._lora.view(adapters) if self._lora is not None else None
        hidden, _ = self.model.backbone(toks, caches=views, pos=pos_eff, lora=lora)
        logits = self.model.lm_head(hidden)[:, -1]  # [S, V]

        def f(lg, ky, tp, p, a, po):
            lgf = lg.astype(jnp.float32)
            lgf = jnp.where(po[:, None], jnp.nan, lgf)
            finite = jnp.all(jnp.isfinite(lgf), axis=-1) | ~a
            greedy = jnp.argmax(lgf, axis=-1).astype(jnp.int32)
            ky, sub = jax.random.split(ky)
            samp = jax.random.categorical(
                sub, lgf / jnp.maximum(tp, 1e-6)[:, None], axis=-1
            ).astype(jnp.int32)
            nxt = jnp.where(tp > 0.0, samp, greedy)
            return nxt[:, None], jnp.where(a, p + 1, p), finite, ky

        nxt, new_pos, finite, key = apply(
            f, [logits, key, temps, pos, active, poison], multi=True,
            name="serve_sample",
        )
        # a model that counts inside its step (routed picks, selected rows)
        # hands the counters over as one more output, fetched with the tokens
        stats = self.model.step_stats() if hasattr(self.model, "step_stats") else None
        if stats is not None:
            return nxt, new_pos, finite, key, stats
        return nxt, new_pos, finite, key

    def _tables_by_layer(self, tables):
        """The page table (a decode step's `[slots, P]`, a prefill's row
        `[P]`) each layer's view reads.  An engine with a window group is
        handed both groups' tables stacked on a leading axis, its own first:
        one operand, data like the one table of every other engine, whose
        programs this leaves as they were."""
        from ..ops.dispatch import apply

        if self._window is None:
            return [tables] * len(self._arenas)
        by_group = [
            apply(lambda t, g=g: t[g], [tables], name="serve_group_table")
            for g in (0, 1)
        ]
        return [by_group[g] for g in self._layer_group]

    def _verify_paged_body(self, toks, pos, active, valid_len, temps, poison,
                           key, tables, adapters):
        """Speculative verify: ONE compiled forward scores k+1 positions per
        slot.  toks [S, k+1] — column 0 the committed last token (not yet in
        KV; this window writes it), columns 1..k the host-side prompt-lookup
        drafts; valid_len [S] counts the committed token plus real drafts
        (1 == plain decode for that row).  Window row i writes KV at pos+i
        through the page table and attends j <= pos+i, so greedy[i] is the
        model's next token after prefix + window[:i+1].  Draft i is accepted
        iff it equals greedy[i-1] and every earlier draft was (cumulative
        product), and the emitted run is greedy[0..n_acc] — exactly the
        tokens one-at-a-time decode would have produced (greedy
        equivalence; draft quality only moves the acceptance rate).
        Rejected rows need no rollback: their KV sits past the advanced pos
        (or on scratch via the table redirect) and the next window rewrites
        [new_pos, new_pos+k] before anything attends it.  Sampled slots
        (temp > 0) ride at valid_len 1; column 0 samples on the SAME
        one-split-per-step key schedule as `_decode_paged_body`.  The verify
        window gathers the same per-slot `adapters` ids as plain decode, so
        speculation composes with multi-tenant LoRA: greedy equivalence is
        per-adapter (draft i accepted only while it matches THAT adapter's
        greedy continuation).  Returns (out [S,k+1], n_emit [S],
        new_pos [S], finite [S], key)."""
        import jax
        import jax.numpy as jnp

        from ..ops.dispatch import apply

        pos_eff = apply(
            lambda p, a: jnp.where(a, p, 0), [pos, active], name="serve_pos_mask"
        )
        views = [
            PagedDecodeView(a, tables, self.max_len, kernel=self.decode_kernel,
                            live=active)
            for a in self._arenas
        ]
        lora = self._lora.view(adapters) if self._lora is not None else None
        hidden, _ = self.model.backbone(toks, caches=views, pos=pos_eff, lora=lora)
        logits = self.model.lm_head(hidden)  # [S, k+1, V]

        def f(lg, tk, ky, tp, p, a, vl, po):
            lgf = lg.astype(jnp.float32)
            lgf = jnp.where(po[:, None, None], jnp.nan, lgf)
            greedy = jnp.argmax(lgf, axis=-1).astype(jnp.int32)  # [S, k+1]
            k1 = greedy.shape[1]
            drafts_ok = tk[:, 1:] == greedy[:, :-1]
            considered = (
                jnp.arange(k1 - 1, dtype=jnp.int32)[None, :] < (vl - 1)[:, None]
            )
            acc = jnp.cumprod((drafts_ok & considered).astype(jnp.int32), axis=1)
            n_acc = acc.sum(axis=1).astype(jnp.int32)
            n_emit = jnp.where(a, n_acc + 1, 0).astype(jnp.int32)
            ky, sub = jax.random.split(ky)
            samp0 = jax.random.categorical(
                sub, lgf[:, 0] / jnp.maximum(tp, 1e-6)[:, None], axis=-1
            ).astype(jnp.int32)
            out = greedy.at[:, 0].set(jnp.where(tp > 0.0, samp0, greedy[:, 0]))
            # the non-finite watch covers only EMITTED rows: a rejected
            # draft's logits are discarded, they must not error the slot
            row_finite = jnp.all(jnp.isfinite(lgf), axis=-1)  # [S, k+1]
            emit_mask = (
                jnp.arange(k1, dtype=jnp.int32)[None, :] < n_emit[:, None]
            )
            finite = jnp.all(row_finite | ~emit_mask, axis=1) | ~a
            return out, n_emit, p + n_emit, finite, ky

        out, n_emit, new_pos, finite, key = apply(
            f, [logits, toks, key, temps, pos, active, valid_len, poison],
            multi=True, name="serve_verify",
        )
        return out, n_emit, new_pos, finite, key

    def _prefill_paged_body(self, toks, row_table, true_len, temp, key,
                            adapters, last_toks, slot):
        """Bucketed fresh prefill: toks [1, bucket] (right-padded), true_len
        a scalar (data).  The prompt attends to itself causally while its
        K/V scatter into the pages of `row_table` ([max_pages_per_seq]
        int32, data); returns the first generated token from the logits at
        true_len - 1.  `adapters` ([1] int32, data) is the request's LoRA
        arena row (0 = base).  Padding rows land on scratch.  `last_toks`
        ([S, 1], the decode loop's token vector) comes back with that token
        at row `slot` (int32 scalar, data): see `_first_token`."""
        from jax import lax

        from ..ops.dispatch import apply

        views = [
            PagedPrefillView(a, t, true_len, self.max_len,
                             kernel=self.decode_kernel, slot=slot)
            for a, t in zip(self._arenas, self._tables_by_layer(row_table))
        ]
        lora = self._lora.view(adapters) if self._lora is not None else None
        hidden, _ = self.model.backbone(toks, caches=views, lora=lora)
        h_last = apply(
            lambda h, n: lax.dynamic_slice_in_dim(h, n - 1, 1, 1),
            [hidden, true_len], name="serve_prefill_last",
        )
        logits = self.model.lm_head(h_last)[:, -1]  # [1, V]
        return self._first_token(logits, key, temp, last_toks, slot)

    def _chunk_prefill_body(self, toks, row_table, true_len, start, temp, key,
                            adapters, last_toks, slot):
        """Prefix-cache-hit prefill: only the UNSHARED suffix runs through
        the model.  toks [1, bucket] holds the suffix (right-padded),
        true_len its real length, start (int32[1], data) the absolute
        position of suffix row 0 — suffix row i writes page
        table[(start+i)//ps] and attends positions j <= start+i through the
        table gather, shared prefix pages included.  `adapters` ([1] int32,
        data) is the request's LoRA arena row — safe to combine with prefix
        sharing because cache entries are keyed by (adapter, token chain):
        a hit guarantees the shared pages were prefilled under the SAME
        adapter.  `last_toks` and `slot` as in `_prefill_paged_body`."""
        from jax import lax

        from ..ops.dispatch import apply

        views = [
            PagedPrefillView(a, t, true_len, self.max_len, start=start,
                             kernel=self.decode_kernel, slot=slot)
            for a, t in zip(self._arenas, self._tables_by_layer(row_table))
        ]
        lora = self._lora.view(adapters) if self._lora is not None else None
        hidden, _ = self.model.backbone(toks, caches=views, lora=lora)
        h_last = apply(
            lambda h, n: lax.dynamic_slice_in_dim(h, n - 1, 1, 1),
            [hidden, true_len], name="serve_prefill_last",
        )
        logits = self.model.lm_head(h_last)[:, -1]  # [1, V]
        return self._first_token(logits, key, temp, last_toks, slot)

    def _first_token(self, logits, key, temp, last_toks, slot):
        """A prefill's last step: the request's first token from `logits`
        [1, V], and the decode loop's token vector with it written at row
        `slot` ON THE DEVICE, so that the next decode step reads it without
        the host having seen it.  Returns (token [1], key, vector [S, 1])."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from ..ops.dispatch import apply

        def f(lg, ky, tp, vec, sl):
            lgf = lg.astype(jnp.float32)
            greedy = jnp.argmax(lgf, axis=-1).astype(jnp.int32)
            ky, sub = jax.random.split(ky)
            samp = jax.random.categorical(
                sub, lgf / jnp.maximum(tp, 1e-6), axis=-1
            ).astype(jnp.int32)
            tok = jnp.where(tp > 0.0, samp, greedy)
            return tok, ky, lax.dynamic_update_slice(vec, tok[:, None], (sl, 0))

        return apply(f, [logits, key, temp, last_toks, slot], multi=True,
                     name="serve_sample1")

    def _copy_page_body(self, src, dst):
        """Copy-on-write: duplicate arena page `src` into `dst` (scalar int32
        Tensors — data) across every layer's K and V, inside ONE compiled
        dispatch.  Used exactly once per admission that extends a partially
        filled shared page; decode never copies (frontier pages are always
        exclusively owned).  Under an int8 arena the COW tail carries its
        SCALE rows too — the copy dequantizes identically to its source,
        and the writer's appends requantize only its own new rows."""
        from ..ops.dispatch import apply

        def f(c, s_, d_):
            return c.at[d_].set(c[s_])

        for a in self._arenas:
            for t in a.buffers():  # every row kind, and an int8 arena's scales
                t._data = apply(f, [t, src, dst], name="kv_page_copy")._data
        return dst

    def _import_page_body(self, k_tiles, v_tiles, dst):
        """Disaggregated handoff import (ISSUE 19): land ONE page's worth of
        prompt K/V rows — shipped by a prefill worker — into arena page
        `dst` across every layer, in one compiled dispatch.  `k_tiles` /
        `v_tiles` are `[n_layers, kv_heads, page_size, head_dim]` stacks
        (partial last pages arrive zero-padded; padded rows sit past the
        slot's pos, masked like any other garbage row) and `dst` a scalar
        int32 — ALL data, so the decode worker imports any number of
        handoffs through this single executable with zero recompiles."""
        from ..ops.dispatch import apply

        for i, a in enumerate(self._arenas):
            a.k._data = apply(
                lambda c, t, d_, _i=i: c.at[d_].set(t[_i]),
                [a.k, k_tiles, dst], name="kv_page_import",
            )._data
            a.v._data = apply(
                lambda c, t, d_, _i=i: c.at[d_].set(t[_i]),
                [a.v, v_tiles, dst], name="kv_page_import",
            )._data
        return dst

    def _import_page_q8_body(self, k_tiles, v_tiles, k_scale_tiles,
                             v_scale_tiles, dst):
        """`_import_page_body` for an int8 arena: the handoff ships the
        quantized rows AS STORED plus their float32 scale rows
        (`[n_layers, kv_heads, 1, page_size]` stacks), so the import writes
        bit-identical arena state — no requantization, no drift, and the
        wire pays int8 prices (~2x cheaper than the cache dtype)."""
        from ..ops.dispatch import apply

        for i, a in enumerate(self._arenas):
            a.k._data = apply(
                lambda c, t, d_, _i=i: c.at[d_].set(t[_i]),
                [a.k, k_tiles, dst], name="kv_page_import",
            )._data
            a.v._data = apply(
                lambda c, t, d_, _i=i: c.at[d_].set(t[_i]),
                [a.v, v_tiles, dst], name="kv_page_import",
            )._data
            a.k_scale._data = apply(
                lambda c, t, d_, _i=i: c.at[d_].set(t[_i]),
                [a.k_scale, k_scale_tiles, dst], name="kv_page_import",
            )._data
            a.v_scale._data = apply(
                lambda c, t, d_, _i=i: c.at[d_].set(t[_i]),
                [a.v_scale, v_scale_tiles, dst], name="kv_page_import",
            )._data
        return dst

    # -- public API ---------------------------------------------------------

    def submit(self, input_ids, max_new_tokens=32, temperature=0.0,
               eos_token_id=None, on_token=None, deadline_s=None,
               trace=None, spec_k=None, adapter=None, export_kv=False,
               handoff=None, reservation=None, session_id=None):
        """Enqueue one request (1-D token ids).  Returns an EngineRequest
        handle immediately; raises QueueFull when the admission queue is at
        capacity, DeadlineUnattainable when `deadline_s` cannot beat the
        current queue-drain estimate (deadline-aware admission), and
        EngineUnavailable while draining or after the restart budget is
        spent.  `spec_k` caps this request's speculative draft length below
        the engine-wide FLAGS_serve_spec_k (0 opts out, None = default).
        `adapter` names a registered LoRA adapter (name or stable id; None
        or 0 = base model) — AdapterUnknown propagates for unregistered
        names, so clients see the typed 404 before the request ever
        queues.  Disaggregated serving (ISSUE 19): `export_kv=True` makes
        the engine read the request's committed prompt pages into a
        handoff payload (`req.kv_export`) when it finishes; `handoff`
        carries such a payload INTO a decode-role engine — the prompt's KV
        is imported through the compiled page scatter instead of
        prefilled, and the payload's first token becomes the request's
        first emitted token.  `reservation` names a reserve_pages() hold
        this admission consumes.  `session_id` (ISSUE 20) names a KV
        session: the request chunk-prefills only the suffix past the
        session's pinned pages, and at finish the full committed sequence
        (prompt + generated) is re-bound so turn N+1 resumes from it."""
        from .. import profiler as _prof
        from .paging import HandoffFormatError, deserialize_kv_handoff

        ids = np.asarray(input_ids, np.int32).reshape(-1)
        if ids.size == 0:
            raise ValueError("empty prompt")
        if ids.size >= self.max_len:
            # typed 400 BEFORE any page is reserved (ISSUE 20): carries the
            # capacity geometry (per-shard under cp) so the client's error
            # body says exactly how much context this tier holds
            raise ContextOverflow(
                ids.size, self.max_len, cp=self.cp,
                pages_per_shard=self.pages_per_seq // self.cp,
                page_size=self.page_size,
            )
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError("deadline_s must be > 0")
        if spec_k is not None and int(spec_k) < 0:
            raise ValueError("spec_k must be >= 0")
        adapter_obj = None
        if adapter is not None and adapter != 0:
            if self._lora is None:
                raise ValueError(
                    "engine has no LoRA arena (construct with lora=) but "
                    f"request named adapter {adapter!r}"
                )
            # resolve NOW: an unknown name is terminal (AdapterUnknown ->
            # HTTP 404), a known one is validated against the arena rank cap
            adapter_obj = self._lora.registry.resolve(adapter)
            if adapter_obj.rank > self._lora.rank_max:
                raise ValueError(
                    f"adapter {adapter_obj.name!r} rank {adapter_obj.rank} "
                    f"exceeds the arena rank_max {self._lora.rank_max}"
                )
        handoff_state = None
        if handoff is not None:
            # typed validation BEFORE the request queues: wrong role,
            # foreign arena geometry, or corrupt rows must surface as a
            # client error, never inside a compiled step
            if self.role != "decode":
                raise ValueError(
                    "handoff import requires an engine in the 'decode' "
                    f"role (this engine: role={self.role!r})"
                )
            if adapter_obj is not None:
                raise ValueError(
                    "handoff requests cannot name a LoRA adapter: the "
                    "prefill worker's exported KV embeds no adapter deltas"
                )
            layers, hL = deserialize_kv_handoff(
                handoff, self.kv_quant, self._kv_heads, self._head_dim,
                len(self._arenas), self._kv_dtype_np.name,
            )
            if hL != int(ids.size):
                raise HandoffFormatError(
                    f"handoff prompt_len {hL} != submitted prompt length "
                    f"{int(ids.size)}"
                )
            first_tok = handoff.get("first_token")
            if first_tok is None:
                raise HandoffFormatError(
                    "handoff payload missing first_token (the prefill "
                    "worker's sampled token)"
                )
            handoff_state = (layers, int(first_tok))
        if self._dead:
            raise EngineUnavailable(
                "engine is dead (restart budget exhausted); restart the server"
            )
        if self._draining:
            raise EngineUnavailable(
                "engine is draining (shutdown in progress)",
                retry_after_s=self.estimate_drain_s(),
            )
        if deadline_s is not None:
            est = self.estimate_drain_s()
            if est > float(deadline_s):
                _prof.record_serving_fault("rejected_deadline")
                _flight.record(
                    "admission", "rejected_deadline",
                    deadline_s=float(deadline_s), drain_est_s=round(est, 3),
                )
                raise DeadlineUnattainable(
                    f"deadline {deadline_s}s cannot beat the current "
                    f"queue-drain estimate {est:.2f}s",
                    retry_after_s=est,
                )
        # page-aware admission: a request whose WORST-CASE page need
        # (no prefix sharing assumed) exceeds the pool can never be
        # scheduled — fail fast with the same 503 family the queue
        # bound uses instead of parking it forever
        need = self._pages_for(ids.size, max_new_tokens)
        # under cp the binding bound is PER SHARD: sequence page k only
        # ever comes from shard k % cp, so the worst shard must hold
        # ceil(need / cp) pages out of its per_shard - 1 usable
        if -(-need // self.cp) > self._pool.per_shard - 1:
            raise QueueFull(
                f"request needs {need} KV pages (prompt {ids.size} + "
                f"max_new {max_new_tokens} at page size {self.page_size})"
                f" but the pool holds {self._pool.usable_pages}"
                + (f" across cp={self.cp} shards" if self.cp > 1 else ""),
                retry_after_s=self._shed_retry_after(deadline_s),
            )
        if self._window is not None and (
            self._window.pages_for(ids.size) > self._window.pool.usable_pages
        ):
            raise QueueFull(
                f"request needs {self._window.pages_for(ids.size)} pages of "
                f"the window group, which holds {self._window.pool.usable_pages}",
                retry_after_s=self._shed_retry_after(deadline_s),
            )
        req = EngineRequest(
            next(self._req_ids), ids, max_new_tokens, temperature,
            eos_token_id, on_token, deadline_s=deadline_s, trace=trace,
            spec_k=spec_k, adapter=adapter_obj,
        )
        req.export_kv = bool(export_kv)
        req.handoff = handoff_state
        req.reservation = None if reservation is None else str(reservation)
        if session_id is not None:
            if self._sessions is None:
                raise ValueError(
                    "session_id requires an engine with a prefix cache "
                    "(construct with prefix_cache=True)"
                )
            if handoff_state is not None:
                raise ValueError(
                    "session_id cannot combine with a KV handoff import: "
                    "sessions live on the prefill-owning replica's pages"
                )
            req.session_id = str(session_id)
        req._submit_t = time.perf_counter()
        if deadline_s is not None:
            req._deadline_t = req._submit_t + float(deadline_s)
        try:
            self._queue.put_nowait(req)
        except queue.Full:
            _flight.record("admission", "queue_full",
                           queue_depth=self.queue_depth)
            raise QueueFull(
                f"admission queue full ({self.queue_depth} pending)",
                retry_after_s=self._shed_retry_after(deadline_s),
            ) from None
        with self._mu:
            self._queued_new_tokens += req.max_new_tokens
        with self._cv:
            self._cv.notify()
        return req

    _req_ids = itertools.count(1)  # request ids unique across engines

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 eos_token_id=None, timeout=None, adapter=None):
        """Submit + wait.  Drives the scheduler inline when no background
        thread is running; returns prompt + generated ids (np.int32)."""
        req = self.submit(input_ids, max_new_tokens=max_new_tokens,
                          temperature=temperature, eos_token_id=eos_token_id,
                          adapter=adapter)
        if self._thread is None:
            self.run_until_idle()
        return req.wait(timeout)

    def warmup(self):
        """Trace/compile (or AOT-load via FLAGS_compile_cache_dir) every
        prefill bucket and the decode step before traffic arrives.  Dummy
        data through the real executables, every write aimed at the scratch
        page.  Call before start()."""
        from .. import to_tensor

        # all-zero tables aim every warmup write at scratch page 0;
        # all-zero adapter ids ride the base (zero-delta) arena row
        lead = () if self._window is None else (2,)  # both groups' tables, stacked
        zero_row = to_tensor(np.zeros(lead + (self.pages_per_seq,), np.int32))
        zero_ad1 = to_tensor(np.zeros(1, np.int32))
        zero_ads = to_tensor(np.zeros(self.slots, np.int32))
        zero_toks = to_tensor(np.zeros((self.slots, 1), np.int32))
        slot0 = to_tensor(np.int32(0))
        for b in self.prefill_buckets:
            # analysis: allow GRAFT010 — warmup runs before the scheduler thread exists; steady-state _key writes hold _mu
            _, self._key, _ = self._prefill_fn(
                to_tensor(np.zeros((1, b), np.int32)), zero_row,
                to_tensor(np.int32(b)), to_tensor(np.float32(0.0)),
                self._key, zero_ad1, zero_toks, slot0,
            )
            _, self._key, _ = self._chunk_fn(
                to_tensor(np.zeros((1, b), np.int32)), zero_row,
                to_tensor(np.int32(b)),
                to_tensor(np.zeros(1, np.int32)),
                to_tensor(np.float32(0.0)), self._key, zero_ad1, zero_toks,
                slot0,
            )
        self._copy_fn(  # scratch onto itself: a no-op through the real fn
            to_tensor(np.int32(0)), to_tensor(np.int32(0))
        )
        if self._import_fn is not None:
            # decode role: warm the handoff import scatter with zero
            # tiles aimed at scratch page 0 (already zeros — a no-op
            # through the real executable, like the copy warm above)
            nl = len(self._arenas)
            elem = (
                np.dtype(np.int8) if self.kv_quant == "int8"
                else self._kv_dtype_np
            )
            tile = (nl, self._kv_heads, self.page_size, self._head_dim)
            args = [
                to_tensor(np.zeros(tile, elem)),
                to_tensor(np.zeros(tile, elem)),
            ]
            if self.kv_quant == "int8":
                srow = (nl, self._kv_heads, 1, self.page_size)
                args += [
                    to_tensor(np.ones(srow, np.float32)),
                    to_tensor(np.ones(srow, np.float32)),
                ]
            self._import_fn(*args, to_tensor(np.int32(0)))
        _, _, _, self._key, *_ = self._decode_fn(
            zero_toks,
            to_tensor(np.zeros(self.slots, np.int32)),
            to_tensor(np.zeros(self.slots, bool)),
            to_tensor(np.zeros(self.slots, np.float32)),
            self._poison_zero,
            self._key,
            to_tensor(np.zeros(lead + (self.slots, self.pages_per_seq), np.int32)),
            zero_ads,
        )
        if self._spec_on:
            # the one extra executable speculation buys: all-inactive
            # rows aim every window write at scratch page 0
            _, _, _, _, self._key = self._verify_fn(
                to_tensor(np.zeros((self.slots, self.spec_k + 1), np.int32)),
                to_tensor(np.zeros(self.slots, np.int32)),
                to_tensor(np.zeros(self.slots, bool)),
                to_tensor(np.ones(self.slots, np.int32)),
                to_tensor(np.zeros(self.slots, np.float32)),
                self._poison_zero,
                self._key,
                to_tensor(
                    np.zeros((self.slots, self.pages_per_seq), np.int32)
                ),
                zero_ads,
            )
        self._warmed = True
        return self

    def compile_counts(self):
        """Trace counts per executable + AOT snapshot hits — the test
        contract is prefill == chunk_prefill == len(buckets warmed), decode
        == 1 and copy == 1, forever (engine restarts included: restart
        rebinds the same executables; prefix-cache hits and COW copies ride
        them with zero fresh traces).  Speculation adds verify (== 1):
        acceptance churn is data, the [slots, k+1] shape never changes."""
        fns = (self._prefill_fn, self._decode_fn, self._chunk_fn, self._copy_fn)
        out = {
            "prefill": self._prefill_fn.trace_count,
            "decode": self._decode_fn.trace_count,
            "aot_hits": sum(f.aot_hits for f in fns),
            "chunk_prefill": self._chunk_fn.trace_count,
            "copy": self._copy_fn.trace_count,
        }
        if self._import_fn is not None:
            # decode role only (ISSUE 19): the handoff import scatter is one
            # executable forever — payload churn is data
            out["import"] = self._import_fn.trace_count
            out["aot_hits"] += self._import_fn.aot_hits
        if self._spec_on:
            out["verify"] = self._verify_fn.trace_count
            out["aot_hits"] += self._verify_fn.aot_hits
        return out

    @property
    def active_slots(self):
        return sum(1 for r in self._slot_req if r is not None)

    @property
    def pending(self):
        return self._queue.qsize() + len(self._requeue)

    def has_work(self):
        """True when anything is queued, being admitted, or decoding."""
        return bool(
            self._queue.qsize() or self._requeue or self._admitting is not None
            or self.active_slots
        )

    def estimate_drain_s(self):
        """Rough wall seconds until the current backlog drains: tokens still
        owed to active slots plus tokens requested by queued work, decoded
        `slots` at a time at the EWMA decode-round wall time, scaled by the
        EWMA tokens-per-step (speculative steps emit accepted runs — pricing
        them at 1 token/step would over-reject deadlines and mis-rank this
        replica in least-loaded routing).  0 before any traffic (no
        evidence, admit everything) — feeds deadline-aware admission and
        the Retry-After header on 503s."""
        ew = self._step_ewma_s
        if not ew:
            return 0.0
        with self._mu:
            active = sum(
                max(0, r.max_new_tokens - len(r.tokens))
                for r in self._slot_req if r is not None
            )
            queued = max(0, self._queued_new_tokens)
        if not (active or queued):
            return 0.0
        rate = max(1e-6, self._tok_rate_ewma)
        return math.ceil((active + queued) / (max(1, self.slots) * rate)) * ew

    def _shed_retry_after(self, deadline_s):
        """Retry-After for a QueueFull shed: the drain estimate, clamped by
        the request's own deadline — a client must never be told to retry
        after its deadline has already passed.  (DeadlineUnattainable keeps
        the raw estimate on purpose: there the whole point is telling the
        client WHEN the backlog clears, which is past its deadline.)"""
        est = self.estimate_drain_s()
        if deadline_s is not None:
            return min(est, float(deadline_s))
        return est

    def healthz(self):
        """Liveness/readiness snapshot for serve()'s /healthz: live (engine
        exists, scheduler not running), ready (scheduler thread alive),
        draining, or dead (restart budget exhausted) — plus occupancy,
        queue depth, restart count, and the queue-drain estimate.  Also
        carries the load signals a fleet router needs to pick a replica:
        page-pool free fraction, prefix-cache size, and the EWMA
        decode-round wall time."""
        t = self._thread
        if self._dead:
            status = "dead"
        elif self._draining:
            status = "draining"
        elif t is not None and t.is_alive():
            status = "ready"
        else:
            status = "live"
        usable = max(1, self._pool.usable_pages)
        # live reservations are spoken-for headroom: the router's
        # decode-side scoring must see pages a pending handoff will
        # consume as already gone, or it over-admits into the gap
        page_free = max(
            0, self._pool.free_count() - self._reserved_pages
        ) / usable
        ew = self._step_ewma_s
        out = {
            "status": status,
            "slots": self.slots,
            "active_slots": self.active_slots,
            "occupancy": self.active_slots / self.slots,
            "queue_depth": self.pending,
            "restarts": self.restart_count,
            "drain_estimate_s": round(self.estimate_drain_s(), 3),
            "page_free_frac": round(page_free, 4),
            "prefix_cache_size": len(self._prefix) if self._prefix is not None else 0,
            "decode_ewma_ms": round(ew * 1e3, 3) if ew else 0.0,
            # observed mean emitted tokens per slot-step (1.0 unless
            # speculation is accepting drafts) — the factor decode_ewma_ms
            # must be divided by when comparing replica throughput
            "tokens_per_step": round(self._tok_rate_ewma, 3),
            # deadline-miss-rate EWMA over terminal resolutions (ISSUE 16):
            # always present (0.0 before any traffic) so the scrape surface
            # and the autoscaler's pressure signal are shape-stable
            "deadline_miss_rate": round(self._miss_ewma, 4),
            # KV storage precision (ISSUE 18): 'int8' replicas pack ~2x the
            # pages into the same HBM — page_free_frac stays a FRACTION of
            # this replica's own usable pages, so router scoring needs no
            # mode awareness
            "kv_quant": self.kv_quant,
            # disaggregated serving (ISSUE 19): the fleet role this replica
            # plays, plus the pages currently spoken for by un-consumed
            # handoff reservations — the router's pair-pick reads both
            "role": self.role,
            "reserved_pages": int(self._reserved_pages),
            # mesh topology (ISSUE 14/20): degrees + axis shape so a fleet
            # operator can see TP- and CP-sharded replicas from /healthz
            "tp": self.tp,
            "cp": self.cp,
            "mesh_shape": (
                {a: int(s) for a, s in self._mesh.shape.items() if int(s) > 1}
                if self._mesh is not None else {}
            ),
        }
        if self._window is not None:
            # the second page group (windowed layers): its own pool
            out["window_page_free_frac"] = round(
                self._window.pool.free_count() / self._window.pool.usable_pages, 4
            )
        if self.cp > 1:
            # per-shard free pages: the router's long-context scoring needs
            # the WORST shard (a sequence page can only land on its own
            # shard), not the flattering pool-wide sum
            out["page_free_by_shard"] = [
                int(self._pool.free_count(sh)) for sh in range(self.cp)
            ]
        if self._sessions is not None:
            # session KV residency (ISSUE 20): the router's session
            # pinning and the paddle_session_* metrics families read these
            out["sessions"] = self._sessions.stats()
        if self._lora is not None:
            # adapter residency for the router: a replica already holding a
            # request's adapter skips the load stall — least-loaded scoring
            # prefers it
            lora = dict(self._lora.stats())
            lora["adapters"] = self._lora.resident()
            out["lora"] = lora
        return out

    # -- disaggregated handoff: page reservations (ISSUE 19) -----------------

    def reserve_pages(self, prompt_len, max_new_tokens, ttl_s=None):
        """Reserve decode-side page headroom for a handoff BEFORE prefill
        starts, so a finished prefill can never strand with nowhere to
        land.  Returns {"reservation", "pages", "ttl_s"}; raises QueueFull
        (503 family) when the worst-case page need exceeds the current
        fresh headroom.  The hold is a counter against admission headroom —
        it pins no specific pages and takes no pool refs — and it expires
        after `ttl_s` (FLAGS_serve_reserve_ttl_s default): a router that
        dies mid-handoff just lets the TTL return the headroom."""
        if self._dead:
            raise EngineUnavailable(
                "engine is dead (restart budget exhausted); restart the server"
            )
        if self._draining:
            raise EngineUnavailable(
                "engine is draining (shutdown in progress)",
                retry_after_s=self.estimate_drain_s(),
            )
        need = self._pages_for(int(prompt_len), int(max_new_tokens))
        ttl = float(
            _fcore.flag("FLAGS_serve_reserve_ttl_s") if ttl_s is None
            else ttl_s
        )
        with self._mu:
            self._purge_reservations_locked()
            if need > self._page_fresh_headroom_locked(()):
                raise QueueFull(
                    f"cannot reserve {need} KV pages (prompt {prompt_len} + "
                    f"max_new {max_new_tokens}): only "
                    f"{max(0, self._page_fresh_headroom_locked(()))} "
                    "unreserved pages of headroom",
                    retry_after_s=self.estimate_drain_s(),
                )
            rid = f"rsv-{next(self._rsv_ids)}"
            self._reserved[rid] = (need, time.perf_counter() + ttl)
            self._reserved_pages += need
        _flight.record("disagg", "reserve", rid=rid, pages=need)
        return {"reservation": rid, "pages": int(need), "ttl_s": ttl}

    _rsv_ids = itertools.count(1)  # reservation ids unique across engines

    def _purge_reservations_locked(self, now=None):
        """Drop expired reservations, returning their headroom.  Caller
        holds _mu."""
        if not self._reserved:
            return
        now = time.perf_counter() if now is None else now
        for rid in [r for r, (_, exp) in self._reserved.items() if exp <= now]:
            n, _exp = self._reserved.pop(rid)
            self._reserved_pages -= n
            _flight.record("disagg", "reserve_expired", rid=rid, pages=n)

    def _consume_reservation_locked(self, rid):
        """Release one reservation (the admission it covered is here, or
        the router abandoned it).  Idempotent — an unknown/expired rid is
        a no-op, the request simply competes for headroom unreserved.
        Caller holds _mu."""
        ent = self._reserved.pop(str(rid), None)
        if ent is None:
            return False
        self._reserved_pages -= ent[0]
        return True

    # -- scheduler ----------------------------------------------------------

    def step(self, gen=None):
        """One scheduling tick: evict expired/cancelled slots, admit queued
        requests into free slots (bucketed prefill), then advance every
        active slot one token.  Returns the number of tokens emitted
        (prefill first-tokens included).  Synchronous alternative to
        start() — never mix the two."""
        gen = self._gen if gen is None else gen
        # after warmup() the whole tick is a steady-state region: every
        # compiled body is traced, so any fresh trace/eager compile — and
        # any host sync outside the declared flush boundaries — is a
        # sanitizer finding attributed to the line that caused it
        ctx = (
            _san.steady_state("serving.engine.step")
            if self._warmed and _san.enabled()
            else contextlib.nullcontext()
        )
        clk = self._clock
        clk.open()
        with ctx:
            clk.enter(_EVICT)
            evicted = self._evict_expired(gen)
            clk.enter(_ADMIT)
            emitted = self._admit(gen)
            clk.enter(_PREPARE)
            n = emitted + self._decode_once(gen)
            clk.enter(_OTHER)
        if _fcore.flag("FLAGS_serve_debug_invariants"):
            self._check_invariants()
        # analysis: allow GRAFT010 — liveness stamp: a raced write only delays the watchdog one tick
        self._last_progress = time.monotonic()
        self._tick_close(evicted, emitted)
        return n

    def _tick_close(self, evicted, admitted):
        """The tick's phases go to the serving gauges, with the step it
        dispatched if it dispatched one (the one `record_serving_tick` a
        tick), and under FLAGS_trace to one span a phase, from the clock's
        own stamps: `engine.tick.evict` only if something was evicted,
        `.admit` only if a request was seated, the others if the tick entered
        them.  A span starts where the tick first entered its phase and lasts
        the phase's seconds of the tick (a flush of several entries enters
        `wait` and `deliver` in turn).  Siblings under the engine's trace id,
        no span around them."""
        from .. import profiler as _prof

        clk = self._clock
        now = clk.enter(None)
        live, queued, busy_s = clk.step or (None, 0, 0.0)
        _prof.record_serving_tick(
            None if live is None else live / self.slots, queued, busy_s,
            phases=clk.secs, end_s=now,
        )
        if not _obs.enabled():
            return
        for ph in range(_OTHER):
            t0 = clk.first[ph]
            if t0 is None or (ph == _EVICT and not evicted) or (
                ph == _ADMIT and not admitted
            ):
                continue
            _obs.record(
                "engine.tick." + _prof.TICK_PHASES[ph], self.trace_id,
                t0=t0, t1=t0 + clk.secs[ph], tick=clk.n, live=live,
            )

    def run_until_idle(self):
        """Drive step() until queue and slots are empty (synchronous mode)."""
        total = 0
        while not self._dead and self.has_work():
            total += self.step()
        return total

    def start(self):
        """Run the scheduler on a daemon thread (serve() calls this)."""
        if self._thread is not None:
            return self
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, name="cb-engine", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, timeout=30.0):
        """Stop the scheduler (bounded join) and flush pending host token
        fetches, so a stop racing an in-flight decode cannot leave
        dispatched tokens unemitted or `on_token` callbacks unfired."""
        t = self._thread
        if t is not None:
            self._stop = True
            with self._cv:
                self._cv.notify_all()
            t.join(timeout)
            if t.is_alive():
                # wedged mid-dispatch: abandon it behind the generation fence
                logger.error(
                    "engine scheduler did not stop within %.1fs; abandoning "
                    "the thread", timeout,
                )
                with self._mu:
                    self._gen += 1
            self._thread = None
        with self._mu:
            try:
                self._flush_pending_locked(cause="stop")
            except _StaleEngine:
                pass
            except Exception:
                logger.exception("engine stop: pending-token flush failed")

    def drain(self):
        """Stop admitting (submit raises EngineUnavailable / serve() sheds
        with 503 + Retry-After); in-flight work keeps decoding."""
        self._draining = True
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def __del__(self):
        try:
            t = self._thread
            if t is not None:
                self._stop = True
                with self._cv:
                    self._cv.notify_all()
                t.join(timeout=1.0)
        except Exception:
            pass

    # -- fault domain: restart / fail-all ------------------------------------

    def _on_watchdog(self, region, elapsed):
        # recorded, not fatal: serving restarts the ENGINE, not the process;
        # the EngineSupervisor polls this trip into a bounded warm restart
        self._watchdog_trip = (region, elapsed)

    def _wd_timeout(self):
        return float(_fcore.flag("FLAGS_serve_step_timeout_sec"))

    def _check_gen(self, gen):
        if gen != self._gen:
            raise _StaleEngine(
                f"scheduler generation {gen} superseded by {self._gen}"
            )

    def restart(self, reason=""):
        """Bounded warm restart (EngineSupervisor calls this): abandon the
        possibly-wedged scheduler thread behind the generation fence,
        resolve every in-flight request exactly once — re-queued for
        re-prefill when it emitted no tokens yet, failed with the typed
        EngineRestarted error when its stream already started — and start a
        fresh scheduler bound to the SAME compiled executables and KV pool
        (0 fresh compiles; the pool needs no scrub, garbage rows are never
        attended)."""
        from .. import profiler as _prof

        # a thread wedged inside the armed fetch region may hold _mu; after
        # a bounded wait we proceed anyway — the generation fence makes the
        # stale thread drop its results instead of corrupting the new life
        locked = self._mu.acquire(timeout=1.0)
        try:
            self._gen += 1
            old, self._thread = self._thread, None
            was_threaded = old is not None
            requeue, fail = [], []
            adm, self._admitting = self._admitting, None
            if adm is not None and not adm.finished.is_set():
                (requeue if not adm.tokens else fail).append(adm)
            for s in range(self.slots):
                req = self._slot_req[s]
                self._slot_req[s] = None
                if req is None or req.finished.is_set():
                    continue
                (requeue if not req.tokens else fail).append(req)
            # warm restart keeps the POOL and the PREFIX CACHE: only the
            # per-slot mappings drop (an admission interrupted mid-
            # dispatch also parked pages here — release those too, its
            # stale thread bails at the generation fence).  Re-queued
            # requests re-prefill and re-hit the cache.
            for s in range(self.slots):
                self._release_slot_pages_locked(s)
            self._tables_t = None
            if self._lora is not None:
                # warm restart keeps the ARENA too: binding refs drop
                # (re-queued requests re-acquire at re-admission) but
                # residency holds survive — resident adapters stay
                # uploaded, zero re-loads after the restart
                for req in requeue:
                    self._release_adapter_locked(req)
                for req in fail:
                    self._release_adapter_locked(req)
                self._slot_adapter[:] = 0
                self._adapters_t = None
            self._pos[:] = 0
            self._last_tok[:] = 0
            self._temps[:] = 0.0
            self._left[:] = 0
            # drafters rebuild cleanly at re-admission (reset from prompt +
            # first token) — stale host n-gram state must not outlive the
            # slot assignment it indexed
            self._drafters = [None] * self.slots
            self._ep = self._fold = None  # epoch members were restarted; drop, don't record
            self._dev = None
            self._toks_t = None  # nothing unfetched is kept: _last_tok is whole
            self._pending_fetch = []
            self._watchdog_trip = None
            self._last_progress = time.monotonic()
            for req in requeue:
                req.state = "queued"
                self._queued_new_tokens += req.max_new_tokens
            self._requeue = requeue + self._requeue
            self.restart_count += 1
        finally:
            if locked:
                self._mu.release()
        for req in fail:
            req.error = EngineRestarted(req.id, reason)
            self._resolve(req, "restarted")
        _inj.record_event("engine", f"restart #{self.restart_count}: {reason}")
        _prof.record_serving_fault("restarts")
        logger.warning(
            "engine restart #%d (%s): %d request(s) re-queued, %d failed "
            "with EngineRestarted", self.restart_count, reason or "?",
            len(requeue), len(fail),
        )
        self._stop = False
        if was_threaded:
            self.start()
        return self

    def fail_all(self, reason=""):
        """Terminal: mark the engine dead (submit raises EngineUnavailable)
        and resolve EVERY pending request — queued, admitting, slotted —
        with the typed EngineRestarted error, exactly once.  Called by the
        EngineSupervisor when the restart budget is spent: clients get
        errors, never hangs."""
        self._dead = True
        pending = []
        locked = self._mu.acquire(timeout=1.0)
        try:
            self._gen += 1  # fence out any wedged scheduler
            self._thread = None
            adm, self._admitting = self._admitting, None
            if adm is not None:
                pending.append(adm)
            for s in range(self.slots):
                if self._slot_req[s] is not None:
                    pending.append(self._slot_req[s])
                    self._slot_req[s] = None
            pending.extend(self._requeue)
            self._requeue = []
            while True:
                try:
                    pending.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            self._queued_new_tokens = 0
            for s in range(self.slots):
                self._release_slot_pages_locked(s)
            self._tables_t = None
            if self._lora is not None:
                for req in pending:
                    self._release_adapter_locked(req)
                self._slot_adapter[:] = 0
                self._adapters_t = None
            self._pos[:] = 0
            self._last_tok[:] = 0
            self._temps[:] = 0.0
            self._left[:] = 0
            self._drafters = [None] * self.slots
            self._ep = None
            self._dev = None
            self._toks_t = None  # nothing unfetched is kept: _last_tok is whole
            self._pending_fetch = []
        finally:
            if locked:
                self._mu.release()
        for req in pending:
            if not req.finished.is_set():
                req.error = EngineRestarted(req.id, reason or "engine dead")
                self._resolve(req, "restarted")
        _inj.record_event("engine", f"fail_all: {reason} ({len(pending)} requests)")
        logger.error(
            "engine dead (%s): %d pending request(s) failed with "
            "EngineRestarted", reason or "?", len(pending),
        )
        return len(pending)

    def _loop(self):
        gen = self._gen
        while not self._stop and gen == self._gen:
            self._last_progress = time.monotonic()
            if not self.has_work():
                with self._cv:
                    if (
                        not self._stop
                        and not self._queue.qsize()
                        and not self._requeue
                    ):
                        self._cv.wait(timeout=0.05)
                continue
            try:
                _inj.inject("serve.loop.crash", context="scheduler loop")
                self.step(gen=gen)
            except _StaleEngine:
                return  # a restart superseded this thread
            except _inj.InjectedFault as e:
                # chaos drill: the scheduler thread dies (loudly, but not as
                # an unhandled thread exception); the supervisor sees a dead
                # thread and restarts the engine
                logger.error("engine scheduler crashed: %s", e)
                return
            except Exception as e:  # poison every in-flight request, keep serving
                with self._mu:
                    if gen != self._gen:
                        return
                    self._pending_fetch.clear()
                    self._toks_t = None
                    for s, req in enumerate(self._slot_req):
                        if req is not None:
                            req.error = e
                            self._finish(s, req, "error")

    # -- internals ----------------------------------------------------------

    def _bucket_for(self, n):
        """The smallest prefill bucket that holds n rows (a chunk is never
        longer than the largest bucket)."""
        return next(b for b in self.prefill_buckets if n <= b)

    # -- paged-KV allocator ---------------------------------------------------

    def _pages_for(self, prompt_len, max_new):
        """Worst-case pages a request occupies over its whole lifetime (no
        prefix sharing assumed): its positions span [0, L + max_new')."""
        span = int(prompt_len) + min(int(max_new), self.max_len - int(prompt_len))
        return -(-span // self.page_size)

    def _page_headroom_locked(self):
        """Pages obtainable without touching a live slot's mapping: the free
        list plus every page only the prefix cache still holds (ref == 1 for
        a cache-held page means no slot maps it; repeated leaf eviction can
        always reach it).  Caller holds _mu."""
        return self._page_fresh_headroom_locked(())

    def _page_fresh_headroom_locked(self, exclude):
        """Headroom available for FRESH allocations when the pages in
        `exclude` (a request's matched prefix pages, about to be mapped by
        incref) must stay resident: they cannot be counted as evictable or
        the admission check double-counts them.  Session-pinned cache pages
        (ISSUE 20) still count — the allocator may evict the LRU session to
        reach them, which is exactly the pressure behavior sessions promise.
        Caller holds _mu."""
        free = self._pool.free_count()
        if self._prefix is not None:
            free += sum(
                1 for e in self._prefix.entries()
                if self._pool.refs[e.page] == 1 and e.page not in exclude
            )
        # un-consumed handoff reservations (ISSUE 19) are spoken for: fresh
        # allocations for anyone else must leave them covered.  A handoff
        # admission consumes its own reservation BEFORE this check, so the
        # hold converts into exactly the headroom it promised.
        return free - self._reserved_pages

    def _page_fresh_headroom_by_shard_locked(self, exclude):
        """Per-cp-shard fresh headroom (ISSUE 20): under context parallelism
        sequence page k must come from pool shard k % cp, so admission has
        to cover each shard's demand separately — a pool that is half free
        on shard 0 cannot serve shard 1's pages.  Same evictability rules as
        the scalar check.  Caller holds _mu."""
        free = [self._pool.free_count(sh) for sh in range(self.cp)]
        if self._prefix is not None:
            for e in self._prefix.entries():
                if self._pool.refs[e.page] == 1 and e.page not in exclude:
                    free[self._pool.shard_of(e.page)] += 1
        if self._reserved_pages:
            # reservations are not shard-annotated (disagg roles exclude
            # cp); cover them conservatively against every shard
            r = -(-self._reserved_pages // self.cp)
            free = [f - r for f in free]
        return free

    def _fresh_need_by_shard(self, start, stop):
        """How many fresh pages sequence-page indices [start, stop) demand
        from each cp shard under the round-robin layout (index k -> shard
        k % cp)."""
        out = [0] * self.cp
        for j in range(int(start), int(stop)):
            out[j % self.cp] += 1
        return out

    def _alloc_page_locked(self, shard=0):
        """One fresh page from cp shard `shard`, evicting LRU prefix-cache
        entries — and, when every evictable entry on the shard is
        session-pinned, whole LRU sessions (ISSUE 20) — under pressure.
        Only called after the admission headroom check covered the request,
        so the eviction loop terminates with a page.  Caller holds _mu."""
        from .. import profiler as _prof

        while self._pool.free_count(shard) == 0:
            if self._prefix is not None and self._prefix.evict_one(
                self._pool, shard=shard if self.cp > 1 else None
            ) is not None:
                _prof.record_paging_event("cache_evictions")
                continue
            if (
                self._sessions is not None
                and self._sessions.evict_lru() is not None
            ):
                # the evicted session's pins dropped: its chain entries are
                # now ordinary LRU-evictable cache entries — loop back into
                # evict_one to actually free a page on this shard
                _prof.record_paging_event("session_evictions")
                _prof.record_session_stats(self._sessions.stats())
                _flight.record("session", "evicted_for_pages", shard=shard)
                continue
            raise RuntimeError(
                "KV page pool exhausted mid-admission — the headroom "
                "check should have deferred this request (accounting bug)"
            )
        return self._pool.alloc(shard)

    def _report_page_groups(self):
        """The page groups' sizes and live pages to the profiler
        (`window_cache_summary()`).  Caller holds _mu, or is the
        constructor."""
        from .. import profiler as _prof

        _prof.record_page_group(
            "full", self.pool_pages, None, self._pool.used_count()
        )
        if self._window is not None:
            win = self._window
            _prof.record_page_group(
                "window", win.pool_pages, win.reach, win.pool.used_count()
            )

    def _release_slot_pages_locked(self, s):
        """Drop slot `s`'s page mappings (finish/evict/restart): every mapped
        page holds one ref for the mapping — shared prefix pages stay alive
        through the cache's own hold.  Caller holds _mu."""
        for p in self._slot_pages[s]:
            self._pool.decref(p)
        self._slot_pages[s] = []
        self._page_table[s, :] = 0
        if self._window is not None:
            self._window.release(s)
            self._report_page_groups()

    # -- LoRA adapter bindings ------------------------------------------------

    @staticmethod
    def _req_adapter_id(req):
        """STABLE registry id for prefix-cache keying (0 = base).  Never the
        arena slot — slots are recycled across adapters, ids are not."""
        return 0 if req.adapter is None else req.adapter.adapter_id

    def _release_adapter_locked(self, req):
        """Drop the request's arena binding ref (residency survives — the
        adapter stays warm for the next request).  Idempotent; caller holds
        _mu."""
        slot = req.adapter_slot
        req.adapter_slot = None
        if slot:
            self._lora.release(slot)

    def _evict_expired(self, gen):
        """Evict cancelled/deadline-expired slots at step granularity: flush
        the tokens already dispatched, then recycle the slot (no recompile)
        and resolve the request with its typed error.  Returns how many it
        found to evict."""
        with self._mu:
            self._check_gen(gen)
            now = time.perf_counter()
            self._purge_reservations_locked(now)
            victims = []
            for s, req in enumerate(self._slot_req):
                if req is None:
                    continue
                if req.cancelled:
                    victims.append((s, req, "cancelled"))
                elif req.expired(now):
                    victims.append((s, req, "timeout"))
            if not victims:
                return 0
            # emit what was already dispatched: a victim keeps every token
            # it was given a step for
            self._flush_pending_locked(cause="evict")
            for s, req, reason in victims:
                if self._slot_req[s] is not req:
                    continue  # resolved during the flush (eos/length/nan)
                if reason == "cancelled":
                    req.error = RequestCancelled(req.id, len(req.tokens))
                else:
                    req.error = DeadlineExceeded(
                        req.id, len(req.tokens), req.max_new_tokens,
                        req.deadline_s,
                    )
                self._finish(s, req, reason)
            return len(victims)

    def _pop_request(self):
        """Next admissible request (restart-requeued work first), resolving
        dead-on-arrival entries (cancelled / already past deadline) without
        burning a prefill.  Caller holds _mu."""
        while True:
            if self._requeue:
                req = self._requeue.pop(0)
            else:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    return None
            self._queued_new_tokens -= req.max_new_tokens
            if req.finished.is_set():
                continue
            if req.cancelled:
                req.error = RequestCancelled(req.id, 0)
                self._resolve(req, "cancelled")
                continue
            if req.expired():
                req.error = DeadlineExceeded(
                    req.id, 0, req.max_new_tokens, req.deadline_s
                )
                self._resolve(req, "timeout")
                continue
            return req

    def _admit(self, gen):
        emitted = 0
        for s in range(self.slots):
            with self._mu:
                self._check_gen(gen)
                if self._slot_req[s] is not None:
                    continue
                req = self._pop_request()
                if req is None:
                    break
                # a handoff admission consumes its reservation FIRST:
                # inside this same critical section the returned
                # headroom flows straight into the check below, so the
                # hold converts into the pages it promised (ISSUE 19)
                if req.reservation is not None:
                    self._consume_reservation_locked(req.reservation)
                    req.reservation = None
                # prefix-aware admission: pages a cache hit will map by
                # incref cost no fresh allocation, so only the unshared
                # remainder counts against headroom — this is what lets
                # shared-prefix traffic pack more than `slots * max_len` rows of
                # sequences into the same page budget.  Safe to check
                # here and act in _prefill_into_paged: this scheduler
                # thread is the only inserter/evictor, so the match
                # cannot shrink in between.  Matched pages are excluded
                # from the evictable count — they are about to be pinned.
                # Handoff imports always land ALL pages fresh (they
                # commit to the cache after, so future prompts share).
                coverage = self._pages_for(
                    req.prompt.size, req.max_new_tokens
                )
                need = coverage
                exclude = ()
                if self._prefix is not None and req.handoff is None:
                    m, fulls, tail, _rows = self._prefix.lookup(
                        req.prompt, adapter=self._req_adapter_id(req)
                    )
                    if m >= self.min_prefix_match:
                        need -= len(fulls)
                        exclude = set(fulls)
                        if tail is not None:
                            exclude.add(tail)
                if self.cp > 1:
                    # per-shard admission (ISSUE 20): fresh pages land at
                    # sequence indices [coverage - need, coverage), shard
                    # k % cp each — every shard must cover its slice
                    head = self._page_fresh_headroom_by_shard_locked(
                        exclude
                    )
                    by_shard = self._fresh_need_by_shard(
                        coverage - need, coverage
                    )
                    short = any(
                        n > h for n, h in zip(by_shard, head)
                    )
                else:
                    short = need > self._page_fresh_headroom_locked(
                        exclude
                    )
                if self._window is not None:
                    # the window group's gate: the pages the prompt's chunks
                    # hold at once (in decode the slot holds fewer)
                    short = short or (
                        self._window.pages_for(req.prompt.size)
                        > self._window.pool.free_count()
                    )
                if short:
                    # page pressure: park the request at the head of the
                    # line (FIFO preserved) until draining slots release
                    # enough pages — submit guaranteed need <= pool, so
                    # progress is certain
                    self._requeue.insert(0, req)
                    self._queued_new_tokens += req.max_new_tokens
                    break
                if req.adapter is not None:
                    # arena admission AFTER the page check, so a parked
                    # request never sits in the queue holding a binding
                    from ..lora.arena import AdapterArenaFull

                    try:
                        req.adapter_slot = self._lora.acquire(req.adapter)
                    except AdapterArenaFull:
                        # every arena slot is pinned by in-flight work:
                        # park exactly like page pressure — a finishing
                        # request's release unblocks us
                        self._requeue.insert(0, req)
                        self._queued_new_tokens += req.max_new_tokens
                        break
                self._admitting = req
                req.state = "prefilling"
            try:
                self._prefill_into(s, req, gen)
                emitted += 1
            except _StaleEngine:
                raise  # the restart now owns this request — hands off
            except Exception as e:  # fail THIS request, keep the engine alive
                req.error = e
                with self._mu:
                    if self._slot_req[s] is req:
                        self._finish(s, req, "error")
                    else:
                        if gen == self._gen:
                            # the prefill died after mapping pages but before
                            # the slot landed — unmap them (a restart raced
                            # ahead releases them itself) and drop the
                            # adapter binding the admission took
                            self._release_slot_pages_locked(s)
                            self._release_adapter_locked(req)
                        self._resolve(req, "error")
            finally:
                with self._mu:
                    if self._admitting is req:
                        self._admitting = None
        return emitted

    def _prefill_into(self, s, req, gen):
        if req.handoff is not None:
            return self._import_into_paged(s, req, gen)
        return self._prefill_into_paged(s, req, gen)

    def _prefill_into_paged(self, s, req, gen):
        """Paged admission: prefix-cache lookup, page mapping (shared fulls
        read-only, COW for a matched partial page, fresh pages for the
        rest), then either a fresh bucketed prefill or a chunk prefill of
        just the unshared suffix — dispatched outside the mutex, BEHIND
        whatever decode step is in flight and without waiting for it: the
        device runs its programs in the order of their dispatch, so pages a
        finished slot gave back are safe to map, and the pages committed to
        the prefix cache here are written before any later program reads
        them.  The first token is not fetched either: the prefill writes it
        into the decode loop's token vector on the device, and the host gets
        it (and the request its `ttft_s`) with the next flush."""
        from .. import profiler as _prof
        from .. import to_tensor

        L = int(req.prompt.size)
        pinned = None  # COW source, kept alive across our own allocations
        with self._mu:
            self._check_gen(gen)
            key = self._key
            last_toks = self._token_vector_locked()
            req.max_new_tokens = min(req.max_new_tokens, self.max_len - L)
            coverage = self._pages_for(L, req.max_new_tokens)
            match_len, shared_full, tail_page, tail_rows = 0, [], None, 0
            if self._prefix is not None:
                m, fp, tp, tr = self._prefix.lookup(
                    req.prompt, adapter=self._req_adapter_id(req)
                )
                if m >= self.min_prefix_match:
                    match_len, shared_full, tail_page, tail_rows = m, fp, tp, tr
                else:
                    tp = None
                if tp is not None and tr > 0:
                    # pin the COW source: allocating fresh pages below may
                    # evict cache entries, and the source must survive until
                    # the copy lands
                    self._pool.incref(tp)
                    pinned = tp
            pages = []
            try:
                for p in shared_full:
                    self._pool.incref(p)
                    pages.append(p)
                # fresh pages go to their sequence index's cp shard (index
                # k -> shard k % cp, shards=1 under no cp) — the round-robin
                # layout the context-parallel decode kernel assumes
                for i in range(len(shared_full), coverage):
                    pages.append(self._alloc_page_locked(i % self.cp))
            except RuntimeError:
                if match_len == 0:
                    raise
                # rare corner (tiny pools): the COW pin itself kept the last
                # evictable page alive.  Fall back to a fresh prefill — the
                # admission headroom check guarantees full coverage without
                # any sharing.
                for p in pages:
                    self._pool.decref(p)
                if pinned is not None:
                    self._pool.decref(pinned)
                    pinned = None
                match_len, shared_full, tail_page, tail_rows = 0, [], None, 0
                pages = [
                    self._alloc_page_locked(i % self.cp)
                    for i in range(coverage)
                ]
            copy_args = None
            if match_len and tail_rows > 0:
                copy_args = (tail_page, pages[len(shared_full)])
            self._page_table[s, :] = 0
            self._page_table[s, : len(pages)] = pages
            self._slot_pages[s] = list(pages)
            _prof.record_prefix_lookup(
                match_len > 0, tokens_saved=match_len,
                cow_copies=1 if copy_args else 0,
            )
            if req.session_id is not None and self._sessions is not None:
                # session accounting (ISSUE 20): every matched prompt token
                # is prefill work the session's pinned chain (or the shared
                # prefix cache) absorbed; bump the session's LRU clock so
                # an active conversation never evicts under its own turns
                req.session_reused_tokens = match_len
                self._sessions.tokens_saved_total += match_len
                self._sessions.touch(req.session_id)
                _prof.record_session_stats(self._sessions.stats())
            row_table = self._page_table[s].copy()
        suffix = L - match_len
        win = self._window
        # a remainder longer than the largest bucket goes in as consecutive
        # chunks of that bucket, each at its offset through the page table
        # (the first of a fresh prompt through the fresh-prefill program); the
        # last chunk takes the smallest bucket that holds it.  No bucket is
        # grown, so nothing compiles mid-traffic
        step = self.prefill_buckets[-1]
        chunks = [(o, min(step, L - o)) for o in range(match_len, L, step)]
        bucket = self._bucket_for(chunks[-1][1])
        t_pf = time.perf_counter()
        if req.trace:
            _obs.record("engine.queue", req.trace[0], t0=req._submit_t,
                        t1=t_pf, parent_id=req.trace[1], req=req.id)
        try:
            # dispatch OUTSIDE the mutex: the armed region (and the injected
            # hang standing in for a wedged device) must not block submitters
            # or a restart
            with self._watchdog.arm(
                "serve.prefill", timeout=self._wd_timeout(),
                context=f"req {req.id}",
            ):
                _inj.inject_hang("serve.prefill.hang", context=f"req {req.id}")
                # a restart during the hang owns this request (and released
                # the pages we just mapped) — bail before writing the arena
                self._check_gen(gen)
                if copy_args is not None:
                    self._copy_fn(
                        to_tensor(np.int32(copy_args[0])),
                        to_tensor(np.int32(copy_args[1])),
                    )
                ad_t = to_tensor(
                    np.full(1, req.adapter_slot or 0, np.int32)
                )
                table_t = to_tensor(row_table) if win is None else None
                temp_t = to_tensor(np.float32(req.temperature))
                slot_t = to_tensor(np.int32(s))
                for offset, n in chunks:
                    b = self._bucket_for(n)
                    toks = np.zeros((1, b), np.int32)
                    toks[0, :n] = req.prompt[offset:offset + n]
                    t_ch = time.perf_counter()
                    self._check_gen(gen)  # between chunks too: a restart owns the pages
                    if win is not None:
                        # a table a chunk: the window group maps the rows this
                        # chunk writes and the reach before them, and gives
                        # back what lies behind.  The chunk before was
                        # dispatched with the table it needed
                        with self._mu:
                            self._check_gen(gen)
                            win.map_range(s, win.first_visible(offset), offset + n - 1)
                            _prof.record_page_group(
                                "window", win.pool_pages, win.reach,
                                win.pool.used_count(), prefill_pages=win.held(s),
                            )
                            table_t = to_tensor(np.stack([row_table, win.table[s]]))
                    if self._has_state:
                        _prof.record_linear_attn_prefill(n, resumed=offset != 0)
                    # only the last chunk's token is the request's first: of
                    # an earlier chunk's vector nothing is kept
                    if offset == 0:
                        nxt, key, seated = self._prefill_fn(
                            to_tensor(toks), table_t,
                            to_tensor(np.int32(n)), temp_t, key, ad_t,
                            last_toks, slot_t,
                        )
                    else:
                        nxt, key, seated = self._chunk_fn(
                            to_tensor(toks), table_t,
                            to_tensor(np.int32(n)),
                            to_tensor(np.full(1, offset, np.int32)),
                            temp_t, key, ad_t, last_toks, slot_t,
                        )
                    if req.trace and len(chunks) > 1:
                        _obs.record(
                            "engine.prefill_chunk", req.trace[0], t0=t_ch,
                            t1=time.perf_counter(), parent_id=req.trace[1],
                            req=req.id, offset=offset, rows=n, bucket=b,
                        )
        finally:
            if pinned is not None:
                with self._mu:
                    self._pool.decref(pinned)
        with self._mu:
            self._check_gen(gen)  # a restart while we dispatched owns req now
            self._key = key
            self._toks_t = seated
            if self._prefix is not None:
                inserted = self._prefix.commit(
                    req.prompt, pages, self._pool,
                    adapter=self._req_adapter_id(req),
                )
                if inserted:
                    _prof.record_paging_event("cache_commits", inserted)
            if win is not None:
                # seated, the slot keeps what its first decode step can see
                win.map_range(s, win.first_visible(L), L - 1)
            self._seat_locked(s, req, L)
            self._pending_fetch.append(_Unfetched(
                nxt, None, [(s, req)], t_pf, eager=True,
                first={
                    "bucket": bucket, "prefix_match": match_len or None,
                    "chunks": len(chunks) if len(chunks) > 1 else None,
                },
            ))
            if self._spec_on:
                # a draft is made on the host from the tokens so far, the
                # first among them
                self._flush_pending_locked(cause="first_token")

    def _token_vector_locked(self):
        """The decode loop's token vector on the device.  Where there is none
        (a fresh or restarted engine, a handoff import) it is uploaded from
        _last_tok, which is whole only once nothing is unfetched.  Caller
        holds _mu."""
        from .. import to_tensor

        if self._toks_t is None:
            self._flush_pending_locked(cause="rebuild")
            self._toks_t = to_tensor(self._last_tok.reshape(self.slots, 1))
        return self._toks_t

    def _seat_locked(self, s, req, L):
        """Slot `s` is `req`'s from the next decode step on, at position L
        with one token made (by its prefill, or by the worker that handed it
        over).  Caller holds _mu."""
        from .. import profiler as _prof

        self._slot_req[s] = req
        self._pos[s] = L
        self._left[s] = req.max_new_tokens - 1
        self._temps[s] = req.temperature
        self._slot_adapter[s] = req.adapter_slot or 0
        self._drafters[s] = None
        req.state = "decoding"
        self._obs_epoch_close()
        self._dev = None  # membership changed: upload the host's part anew
        _prof.record_membership_change()

    def _first_token_locked(self, s, req, tok, now):
        """The host has `req`'s first token (fetched, or handed over): it is
        the slot's last token, the drafter's seed, and the request's first
        emission.  Caller holds _mu."""
        req.ttft_s = now - req._submit_t
        self._last_tok[s] = tok
        if self._spec_on and req.temperature == 0.0 and (
            req.spec_k is None or req.spec_k > 0
        ):
            # greedy slots draft from their own history (prompt + first
            # token); sampled slots ride the verify step undrafted —
            # greedy equivalence is the only acceptance rule we prove
            self._drafters[s] = NgramDrafter(self._spec_ngram).reset(
                [int(t) for t in req.prompt] + [tok]
            )
        self._emit(s, req, tok)

    def _import_into_paged(self, s, req, gen):
        """Disaggregated admission (ISSUE 19): the prompt's KV arrives in
        `req.handoff` instead of being prefilled.  Maps fresh pages, lands
        the shipped rows page-by-page through the compiled import scatter
        (one executable, payload is data), commits the prompt pages to the
        prefix cache so FUTURE identical prompts share them, then seats the
        slot exactly like a prefill landing: pos = L, last_tok = the
        prefill worker's sampled first token.  Greedy continuation is
        bit-identical to a colocated engine at the same seed — same weights
        and identical arena rows leave the decode step nothing to differ
        on."""
        from .. import profiler as _prof
        from .. import to_tensor

        ps = self.page_size
        L = int(req.prompt.size)
        layers, first_tok = req.handoff
        n_prompt_pages = -(-L // ps)
        with self._mu:
            self._check_gen(gen)
            self._flush_pending_locked(cause="admission")
            req.max_new_tokens = min(req.max_new_tokens, self.max_len - L)
            coverage = self._pages_for(L, req.max_new_tokens)
            pages = [
                self._alloc_page_locked(i % self.cp) for i in range(coverage)
            ]
            self._page_table[s, :] = 0
            self._page_table[s, : len(pages)] = pages
            self._slot_pages[s] = list(pages)
        t_pf = time.perf_counter()
        if req.trace:
            _obs.record("engine.queue", req.trace[0], t0=req._submit_t,
                        t1=t_pf, parent_id=req.trace[1], req=req.id)
        nl = len(self._arenas)
        q8 = self.kv_quant == "int8"
        elem = np.dtype(np.int8) if q8 else self._kv_dtype_np
        kvh, hd = self._kv_heads, self._head_dim
        # dispatch OUTSIDE the mutex (same contract as the prefill paths):
        # the armed region must not block submitters or a restart
        with self._watchdog.arm(
            "serve.import", timeout=self._wd_timeout(),
            context=f"req {req.id} ({n_prompt_pages} pages)",
        ):
            # a restart during a wedged import owns this request (and
            # released the pages we just mapped) — bail before writing
            self._check_gen(gen)
            for i in range(n_prompt_pages):
                lo, hi = i * ps, min(L, (i + 1) * ps)
                rows = hi - lo
                # wire rows are [L, kv_heads, head_dim]; arena pages are
                # [kv_heads, page_size, head_dim] — transpose per page
                kt = np.zeros((nl, kvh, ps, hd), elem)
                vt = np.zeros((nl, kvh, ps, hd), elem)
                for li, ly in enumerate(layers):
                    kt[li, :, :rows] = ly["k"][lo:hi].swapaxes(0, 1)
                    vt[li, :, :rows] = ly["v"][lo:hi].swapaxes(0, 1)
                args = [to_tensor(kt), to_tensor(vt)]
                if q8:
                    # padding rows carry scale 1.0, never 0: they sit past
                    # the slot's pos and are position-masked, but their
                    # dequantized values still flow through the masked
                    # attention sum and must stay finite
                    kst = np.ones((nl, kvh, 1, ps), np.float32)
                    vst = np.ones((nl, kvh, 1, ps), np.float32)
                    for li, ly in enumerate(layers):
                        kst[li, :, 0, :rows] = ly["k_scale"][lo:hi, :, 0].T
                        vst[li, :, 0, :rows] = ly["v_scale"][lo:hi, :, 0].T
                    args += [to_tensor(kst), to_tensor(vst)]
                self._import_fn(*args, to_tensor(np.int32(pages[i])))
        with self._mu:
            self._check_gen(gen)  # a restart while we imported owns req now
            if self._prefix is not None:
                inserted = self._prefix.commit(
                    req.prompt, pages, self._pool,
                    adapter=self._req_adapter_id(req),
                )
                if inserted:
                    _prof.record_paging_event("cache_commits", inserted)
            req.handoff = None  # the arena owns the rows now; free the copy
            _prof.record_disagg_event("imports")
            _prof.record_disagg_event("import_pages", n_prompt_pages)
            self._seat_locked(s, req, L)  # handoffs never carry an adapter
            # the first token came over the wire, so the host has it and the
            # device does not: the next step uploads _last_tok, which the
            # flush above made whole
            self._toks_t = None
            self._first_token_locked(s, req, first_tok, time.perf_counter())
        if req.trace:
            _obs.record(
                "engine.import", req.trace[0], t0=t_pf,
                t1=time.perf_counter(), parent_id=req.trace[1], req=req.id,
                slot=s, pages=n_prompt_pages,
            )

    def _decode_once(self, gen):
        if self._spec_on:
            return self._decode_once_spec(gen)
        from .. import profiler as _prof
        from .. import to_tensor

        with self._mu:
            self._check_gen(gen)
            toks_t = self._token_vector_locked()
            run = [
                s for s in range(self.slots)
                if self._slot_req[s] is not None and self._left[s] > 0
            ]
            if not run:
                # every seated slot has its last step in flight (or none is
                # seated): nothing to dispatch beside the fetch.  With work
                # queued behind those slots that is a drain the length bound
                # forced; with none the device is out of work anyway
                self._flush_pending_locked(
                    cause="length" if self.pending else None
                )
                return 0
            t0 = time.perf_counter()
            if self._dev is None:
                # membership changed: what the host knows goes up anew, and
                # the host knows it without a fetch (_pos is advanced at
                # dispatch).  What it does not know, the tokens, stays where
                # it is: the newest step's output, first tokens written in
                self._obs_epoch_close()
                active = np.zeros(self.slots, bool)
                active[run] = True
                self._dev = (
                    to_tensor(self._pos.copy()), to_tensor(active),
                    to_tensor(self._temps.copy()),
                )
                # page tables (and adapter bindings) change exactly when
                # membership does — the same events that invalidate _dev
                # — so one H2D mirror per membership change covers every
                # following step
                self._adapters_t = to_tensor(self._slot_adapter.copy())
                self._obs_epoch_open(run)
                self._tables_t = None
            if self._window is not None:
                # the window group's table moves with `pos`, not with
                # membership alone: the page this step writes is mapped, the
                # pages behind its reach go back to their pool, and a table
                # that changed goes up again (64 KB; data, no retrace)
                win = self._window
                moved = [
                    win.map_range(s, win.first_visible(self._pos[s]), self._pos[s])
                    for s in run
                ]
                if any(moved):
                    self._tables_t = None
                _prof.record_page_group(
                    "window", win.pool_pages, win.reach, win.pool.used_count(),
                    slot_pages=max(win.held(s) for s in run),
                    released_behind=win.released_behind,
                )
                win.released_behind = 0
            if self._tables_t is None:
                # a slot that sits out (its last step in flight, its finish a
                # tick away) still maps its pages: its row goes up as zeros,
                # so that the inactive slot's write at pos 0 lands on scratch
                # and not on its prompt's first row
                groups = [self._page_table] + (
                    [self._window.table] if self._window is not None else []
                )
                tables = [np.zeros_like(t) for t in groups]
                for t, g in zip(tables, groups):
                    t[run] = g[run]
                self._tables_t = to_tensor(
                    tables[0] if self._window is None else np.stack(tables)
                )
            pos_t, active_t, temps_t = self._dev
            key = self._key
            poison_t, poisoned = self._poison_zero, None
            if _inj.should_fire("serve.decode.nan", context=f"slot {run[0]}"):
                poisoned = run[0]
                pz = np.zeros(self.slots, bool)
                pz[poisoned] = True
                poison_t = to_tensor(pz)
        clk = self._clock
        clk.enter(_DISPATCH)
        with self._watchdog.arm(
            "serve.decode", timeout=self._wd_timeout(),
            context=f"{len(run)} active slots",
        ):
            nxt, new_pos, finite, key, *stats = self._decode_fn(
                toks_t, pos_t, active_t, temps_t, poison_t, key,
                self._tables_t, self._adapters_t,
            )
        clk.enter(_OTHER)
        with self._mu:
            self._check_gen(gen)
            self._key = key
            self._toks_t = nxt
            self._dev = (new_pos, active_t, temps_t)
            pairs = [(s, self._slot_req[s]) for s in run]
            for s in run:
                self._pos[s] += 1
                self._left[s] -= 1
            last = any(self._left[s] == 0 for s in run)
            if last:
                # the host counted: this was some slot's last step.  It is
                # inactive from the next one on (a new mask, no fetch), and
                # its request finishes when its last token is delivered
                self._dev = None
            # The host fetches a step only when something needs the values.
            # What decides membership by VALUE is fetched in its own tick: an
            # EOS watch, a poisoned step.  Everything else stays a step
            # behind the device: the step just dispatched stays in flight
            # while those before it are fetched and emitted, so the host's
            # work between two steps (fetch, callbacks, a finish, an
            # admission, the next dispatch) runs beside the device's and not
            # in its way.  A length bound waits on nothing: the slot leaves
            # the mask by count, and `_finish` runs a tick later with the
            # delivery of its last token.  Nor does an admission: see
            # `_prefill_into_paged`.  An entry nobody is waiting for (no
            # stream, no first token, no last) stays unfetched until one
            # behind it is, so a batch that streams nothing is fetched once
            # per finish, as deep as the shortest remaining length.
            self._pending_fetch.append(_Unfetched(
                nxt, finite, pairs, t0, stats,
                eager=last or any(r.on_token is not None for _, r in pairs),
            ))
            if poisoned is not None:
                self._flush_pending_locked(cause="poison")
            elif any(r.eos_token_id is not None for _, r in pairs):
                self._flush_pending_locked(cause="eos_watch")
            elif any(e.eager for e in self._pending_fetch[:-1]):
                self._flush_pending_locked(keep=1)
            if self._ep is not None:
                self._ep["ticks"] += 1
            clk.step = (
                len(run), self._queue.qsize(), time.perf_counter() - t0
            )
            _prof.record_paging_tick(
                self._pool.used_count(), self._pool.usable_pages
            )
            if self._window is not None:
                _prof.record_page_group(
                    "full", self.pool_pages, None, self._pool.used_count(),
                    slot_pages=max(len(self._slot_pages[s]) for s in run),
                )
            if self.kv_quant == "int8":
                # per-layer work divided out: one KV row-pair quantized
                # per active slot, every mapped page dequantized in the
                # kernel's page walk
                _prof.record_kv_quant_event("quantize", len(run))
                _prof.record_kv_quant_event(
                    "dequantize",
                    sum(len(self._slot_pages[s]) for s in run),
                )
        return len(run)

    def _decode_once_spec(self, gen):
        """One speculative round for every active slot: draft on the host
        (prompt-lookup, free), verify k+1 positions in ONE compiled dispatch,
        emit the accepted run.  Shapes are fixed at [slots, spec_k+1] —
        draft content, validity, and acceptance are data, so acceptance
        churn and slot churn alike cause zero recompiles.  Unlike the plain
        path this fetches every step (the next draft needs this step's
        accepted tokens on the host); the batching the plain path buys with
        deferred fetches is what speculation replaces — >1 token per sync."""
        from .. import profiler as _prof
        from .. import to_tensor

        K1 = self.spec_k + 1
        with self._mu:
            self._check_gen(gen)
            active_idx = [s for s in range(self.slots) if self._slot_req[s] is not None]
            if not active_idx:
                return 0
            t0 = time.perf_counter()
            if self._dev is None:
                self._obs_epoch_close()
                active = np.zeros(self.slots, bool)
                active[active_idx] = True
                # spec loop state is (pos, active, temps): tokens rebuild
                # host-side every step from _last_tok + fresh drafts
                self._dev = (
                    to_tensor(self._pos.copy()), to_tensor(active),
                    to_tensor(self._temps.copy()),
                )
                self._tables_t = to_tensor(self._page_table.copy())
                self._adapters_t = to_tensor(self._slot_adapter.copy())
                self._obs_epoch_open(active_idx)
            pos_t, active_t, temps_t = self._dev
            key = self._key
            toks = np.zeros((self.slots, K1), np.int32)
            vl = np.ones(self.slots, np.int32)
            proposed = 0
            for s in active_idx:
                req = self._slot_req[s]
                toks[s, 0] = self._last_tok[s]
                dr = self._drafters[s]
                if dr is None:
                    continue  # sampled or spec_k=0 request: plain-decode row
                # the clamp that keeps every COMMITTED row mapped: at most
                # remaining-1 drafts, so n_emit never overshoots the length
                # bound and the last committed row stays < max_len
                budget = min(
                    self.spec_k,
                    self.spec_k if req.spec_k is None else req.spec_k,
                    req.max_new_tokens - len(req.tokens) - 1,
                )
                draft = dr.propose(budget) if budget > 0 else []
                if draft:
                    toks[s, 1:1 + len(draft)] = draft
                    vl[s] = 1 + len(draft)
                    proposed += len(draft)
            if self._ep is not None:
                self._ep["proposed"] += proposed
            poison_t, poisoned = self._poison_zero, None
            if _inj.should_fire("serve.decode.nan", context=f"slot {active_idx[0]}"):
                poisoned = active_idx[0]
                pz = np.zeros(self.slots, bool)
                pz[poisoned] = True
                poison_t = to_tensor(pz)
            toks_t = to_tensor(toks)
            vl_t = to_tensor(vl)
        clk = self._clock
        clk.enter(_DISPATCH)
        with self._watchdog.arm(
            "serve.decode", timeout=self._wd_timeout(),
            context=f"{len(active_idx)} active slots (spec k={self.spec_k})",
        ):
            out, n_emit, new_pos, finite, key = self._verify_fn(
                toks_t, pos_t, active_t, vl_t, temps_t, poison_t, key,
                self._tables_t, self._adapters_t,
            )
        clk.enter(_OTHER)
        with self._mu:
            self._check_gen(gen)
            self._key = key
            self._dev = (new_pos, active_t, temps_t)
            t_w0 = clk.enter(_WAIT)
            with self._watchdog.arm(
                "serve.fetch", timeout=self._wd_timeout(),
                context=f"verify fetch ({len(active_idx)} slots)",
            ), _san.allowed_sync("speculative verify fetch"):
                out_np = np.asarray(out.numpy())
                n_np = np.asarray(n_emit.numpy()).reshape(-1)
                fin_np = np.asarray(finite.numpy()).reshape(-1)
            now = clk.enter(_DELIVER)
            if _obs.enabled():
                self._fold_fetch(t_w0, now)
            # a restart that could not take the mutex may have superseded
            # us mid-fetch — bail before touching the new life's slot table
            self._check_gen(gen)
            per = now - t0
            self._step_ewma_s = (
                per if self._step_ewma_s is None
                else 0.8 * self._step_ewma_s + 0.2 * per
            )
            accepted = 0
            emitted_total = 0
            for s in active_idx:
                req = self._slot_req[s]
                if req is None:
                    continue
                if not fin_np[s]:
                    _prof.record_serving_fault("nonfinite")
                    req.error = NonFiniteLogits(
                        f"request {req.id}: non-finite logit window at "
                        f"position {int(self._pos[s])} (slot {s}); the slot "
                        "was evicted — co-batched requests are unaffected"
                    )
                    self._finish(s, req, "error")
                    continue
                n = int(n_np[s])
                self._pos[s] += n
                accepted += max(0, n - 1)
                emitted_total += n
                dr = self._drafters[s]
                for j in range(n):
                    if self._slot_req[s] is not req:
                        break  # EOS inside the accepted window right-trims
                    tok = int(out_np[s, j])
                    self._last_tok[s] = tok
                    if dr is not None:
                        dr.extend(tok)
                    self._emit(s, req, tok)
            if emitted_total:
                self._tok_rate_ewma = (
                    0.8 * self._tok_rate_ewma
                    + 0.2 * (emitted_total / len(active_idx))
                )
            if self._ep is not None:
                self._ep["ticks"] += 1
                self._ep["accepted"] += accepted
            clk.step = (
                len(active_idx), self._queue.qsize(), clk.enter(_OTHER) - t0
            )
            _prof.record_paging_tick(
                self._pool.used_count(), self._pool.usable_pages
            )
            _prof.record_speculation(
                proposed, accepted, emitted_total, len(active_idx)
            )
            if self.kv_quant == "int8":
                # the verify window quantizes k+1 row-pairs per active slot
                _prof.record_kv_quant_event(
                    "quantize", len(active_idx) * K1
                )
                _prof.record_kv_quant_event(
                    "dequantize",
                    sum(len(self._slot_pages[s]) for s in active_idx),
                )
        return len(active_idx)

    def _obs_epoch_open(self, active_idx):
        """Start a decode-epoch summary (caller holds _mu): the stretch of
        constant slot membership that begins at this device-state rebuild.
        Host-side bookkeeping only — a dict, no tensor touches — so it is
        legal inside the sanitizer's steady-state zone."""
        if not _obs.enabled():
            self._ep = self._fold = None
            return
        members = [(s, self._slot_req[s]) for s in active_idx]
        if not any(r.trace for _, r in members):
            self._ep = None
            return
        self._ep = {
            "t0": time.perf_counter(), "ticks": 0, "members": members,
            # speculation accounting over the epoch (zeros in plain mode)
            "proposed": 0, "accepted": 0,
        }

    def _obs_epoch_close(self):
        """Close the open decode epoch (caller holds _mu): one summarizing
        engine.decode span per traced member request — plus, when
        speculation is on, an engine.verify span carrying the epoch's
        proposed/accepted draft counts (the trace-visible acceptance
        evidence ISSUE 11 requires), and an engine.fetch span that folds the
        epoch's flushes.  Returns the members it recorded for."""
        ep, self._ep = self._ep, None
        if not ep or not ep["ticks"]:
            return ()
        t1 = time.perf_counter()
        fold, self._fold = self._fold, None
        for s, req in ep["members"]:
            if req.trace:
                _obs.record(
                    "engine.decode", req.trace[0], t0=ep["t0"], t1=t1,
                    parent_id=req.trace[1], req=req.id, slot=s,
                    ticks=ep["ticks"],
                    adapter=req.adapter.name if req.adapter is not None else None,
                )
                self._record_fetch(req, fold)
                if self._spec_on:
                    _obs.record(
                        "engine.verify", req.trace[0], t0=ep["t0"], t1=t1,
                        parent_id=req.trace[1], req=req.id, slot=s,
                        ticks=ep["ticks"], proposed=ep["proposed"],
                        accepted=ep["accepted"],
                    )
        return ep["members"]

    def _fold_fetch(self, t0, t1, new_flush=True):
        """A fetch blocked from t0 to t1 under FLAGS_trace (the first of its
        flush, or a later entry's): into the fold of the flushes since the
        last epoch close.  Caller holds _mu."""
        f = self._fold
        if f is None:
            f = self._fold = [t0, t1, 0, 0, 0.0]
        f[1] = t1
        f[2] += new_flush
        f[3] += 1
        f[4] += t1 - t0

    def _record_fetch(self, req, fold):
        """One engine.fetch span in a traced request's tree for a whole fold
        (an epoch's flushes, as a rule): `fetches` flushes took `steps`
        entries off the device and blocked `wait_s` seconds doing it.  What a
        single tick's fetch took is `engine.tick.wait`."""
        if fold is not None:
            _obs.record(
                "engine.fetch", req.trace[0], t0=fold[0], t1=fold[1],
                parent_id=req.trace[1], req=req.id, fetches=fold[2],
                steps=fold[3], wait_s=fold[4],
            )

    def _flush_pending_locked(self, keep=0, cause=None):
        """Fetch every dispatched-but-unfetched entry (but the newest
        `keep`, which stay in flight) and deliver its tokens, in dispatch
        order: a request's first token before its decode tokens.  Each goes
        to the request that held the slot when the program was dispatched,
        if it still holds it: one that left since (an error, an eviction) is
        owed nothing, and the slot's next tenant nothing of its.  A slot
        whose logit window went non-finite errors alone.  `cause` names why
        NOTHING may stay in flight (a drain, counted by cause in
        `profiler.serving_summary()`).  Caller holds _mu; the blocking fetch
        runs under the serve.fetch watchdog region and re-checks the
        generation after it (a restart that could not take the mutex may
        have superseded us mid-fetch)."""
        from .. import profiler as _prof

        n = len(self._pending_fetch) - keep
        if n <= 0:
            return
        if cause is not None:
            _prof.record_serving_drain(cause)
        gen0 = self._gen
        batches, self._pending_fetch = self._pending_fetch[:n], self._pending_fetch[n:]
        # the tick's clock: blocked in the fetch is `wait`, the rest of the
        # flush `deliver`, and both come out of the phase the flush found
        clk, traced = self._clock, _obs.enabled()
        outer = clk.phase
        t_f0 = clk.enter(_DELIVER)
        steps, t_first, t_step = 0, None, t_f0
        # entry by entry, each delivered as soon as it is fetched: a step's
        # tokens do not wait for the prefill dispatched behind it
        for i, e in enumerate(batches):
            t_w0 = clk.enter(_WAIT)
            with self._watchdog.arm(
                "serve.fetch", timeout=self._wd_timeout(),
                context=f"{len(batches)} buffered steps",
            ), _san.allowed_sync("batched decode-token flush"):
                nxt_np = np.asarray(e.nxt.numpy()).reshape(-1)
                if e.first is None:
                    fin_np = np.asarray(e.finite.numpy()).reshape(-1)
                if e.stats:  # the model's own counters of the step, same fetch
                    self.model.record_step_stats(np.asarray(e.stats[0].numpy()))
            now = clk.enter(_DELIVER)
            if traced:
                self._fold_fetch(t_w0, now, new_flush=i == 0)
            self._check_gen(gen0)
            if e.first is None:
                steps, t_step = steps + 1, now
                t_first = e.t0 if t_first is None else t_first
            for s, req in e.pairs:
                if self._slot_req[s] is not req:
                    continue  # left the slot since this was dispatched
                if e.first is not None:
                    if req.trace:
                        _obs.record(
                            "engine.chunk_prefill" if e.first["prefix_match"]
                            else "engine.prefill",
                            req.trace[0], t0=e.t0, t1=now,
                            parent_id=req.trace[1], req=req.id, slot=s,
                            adapter=req.adapter.name if req.adapter is not None else None,
                            **e.first,
                        )
                    self._first_token_locked(s, req, int(nxt_np[0]), now)
                elif not fin_np[s]:
                    _prof.record_serving_fault("nonfinite")
                    req.error = NonFiniteLogits(
                        f"request {req.id}: non-finite logit window at "
                        f"position {int(self._pos[s])} (slot {s}); the slot "
                        "was evicted — co-batched requests are unaffected"
                    )
                    self._finish(s, req, "error")
                else:
                    tok = int(nxt_np[s])
                    self._last_tok[s] = tok
                    self._emit(s, req, tok)
        now = clk.enter(outer)
        # EWMA decode-round wall time: dispatch-to-fetch of this burst's
        # decode steps (from the fetch before it, where its first step was
        # dispatched behind a step still in flight) over their count — feeds
        # estimate_drain_s / Retry-After
        if steps:
            per = (t_step - max(t_first, self._last_flush_t)) / steps
            self._step_ewma_s = (
                per if self._step_ewma_s is None
                else 0.8 * self._step_ewma_s + 0.2 * per
            )
        self._last_flush_t = now

    def _emit(self, s, req, tok):
        req.tokens.append(tok)
        if req.on_token is not None:
            try:
                req.on_token(tok)
            except Exception:
                pass  # a broken consumer must not take the engine down
        if req.eos_token_id is not None and tok == req.eos_token_id:
            self._finish(s, req, "eos")
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(s, req, "length")

    def _finish(self, s, req, reason):
        from .. import profiler as _prof

        if (
            req.export_kv and req.kv_export is None
            and reason in ("eos", "length")
        ):
            # disaggregated prefill (ISSUE 19): read the committed prompt
            # pages into the handoff payload NOW, while the slot still maps
            # them — one line down they return to the pool
            try:
                self._export_slot_locked(s, req)
            except Exception:
                # the handoff consumer sees kv_export None and fails the
                # hop; the pages must still be released below
                logger.exception(
                    "disagg: page export failed for request %d", req.id
                )
        if (
            self._sessions is not None
            and req.session_id is not None and reason in ("eos", "length")
        ):
            # session KV (ISSUE 20): commit + pin the FULL committed
            # sequence (prompt AND generated tokens, truncated to the rows
            # whose KV actually landed) while the slot still maps its pages
            # — turn N+1 chunk-prefills only past this point
            try:
                self._bind_session_locked(s, req)
            except Exception:
                # a failed bind degrades to stateless turn N+1 (re-prefill);
                # never let it take the finish path down with it
                logger.exception(
                    "session: bind failed for request %d (session %r)",
                    req.id, req.session_id,
                )
        # recycle immediately: no cache scrub needed — the slot's next
        # prefill overwrites rows [0, bucket) and decode masks the rest
        self._slot_req[s] = None
        self._pos[s] = 0
        self._left[s] = 0
        self._last_tok[s] = 0
        self._temps[s] = 0.0
        self._drafters[s] = None
        # mappings drop; committed prefix pages live on through the
        # cache's own hold, everything else returns to the free list
        self._release_slot_pages_locked(s)
        if self._lora is not None:
            # the binding ref drops; residency survives, so the adapter
            # stays warm until arena LRU pressure needs its slot
            self._slot_adapter[s] = 0
            self._release_adapter_locked(req)
        closed = self._obs_epoch_close()
        if req.trace and not any(r is req for _, r in closed):
            # it leaves outside an epoch it ran in (a first token was its
            # last, an eviction before its first step): its tree still gets
            # the fetches so far, the one that delivered to it among them
            self._record_fetch(req, self._fold)
        self._dev = None  # membership changed: upload the host's part anew
        _prof.record_membership_change()
        self._resolve(req, reason)

    def _bind_session_locked(self, s, req):
        """Commit slot `s`'s committed rows to the prefix cache and (re)bind
        the request's session to the covering chain (ISSUE 20).  The
        committed sequence is concat(prompt, generated)[:pos] — the engine's
        decode invariant is that KV rows [0, pos) hold exactly those tokens;
        the LAST emitted token's KV is never written (it would land at row
        pos on the next step), so it is excluded and turn N+1's chunk
        prefill recomputes it at its true rope offset.  Caller holds _mu,
        slot still maps its pages."""
        from .. import profiler as _prof

        pos = int(self._pos[s])
        seq = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)]
        )[:pos]
        if seq.size == 0:
            return
        ad = self._req_adapter_id(req)
        inserted = self._prefix.commit(
            seq, self._slot_pages[s], self._pool, adapter=ad
        )
        if inserted:
            _prof.record_paging_event("cache_commits", inserted)
        entries, covered = self._prefix.chain(seq, adapter=ad)
        evicted = self._sessions.bind(
            req.session_id, seq, entries, adapter=ad
        )
        if evicted:
            _prof.record_paging_event("session_evictions", len(evicted))
        _prof.record_session_stats(self._sessions.stats())
        _flight.record(
            "session", "bind", req=req.id, sid=req.session_id,
            tokens=int(seq.size), pages=len(entries), covered=int(covered),
            turns=self._sessions.get(req.session_id)["turns"],
        )

    def _export_slot_locked(self, s, req):
        """Read slot `s`'s committed prompt rows — [0, L) of every layer's
        K/V through its page mapping — into a serialized handoff payload on
        `req.kv_export` (ISSUE 19).  Exactly the rows a colocated engine
        would hold after this prompt's prefill: the first generated token's
        KV is NOT yet written (it lands when the decode side feeds it back
        at position L), so export-at-finish of a max_new_tokens=1 prefill
        is the complete, sufficient handoff.  Rows ship as stored (int8 +
        scale rows under kv_quant='int8').  Caller holds _mu."""
        from .. import profiler as _prof
        from .paging import serialize_kv_handoff

        ps = self.page_size
        L = int(req.prompt.size)
        n_pages = -(-L // ps)
        idx = np.asarray(self._slot_pages[s][:n_pages], np.int64)

        def rows(t):
            # arena pages [n, kv_heads, page_size, head_dim] -> wire rows
            # [L, kv_heads, head_dim] (the page-size-agnostic handoff format)
            pages = np.asarray(t.numpy())[idx]
            return pages.swapaxes(1, 2).reshape(
                n_pages * ps, self._kv_heads, self._head_dim
            )[:L]

        def scale_rows(t):
            # scale pages [n, kv_heads, 1, page_size] -> [L, kv_heads, 1]
            pages = np.asarray(t.numpy())[idx]
            return pages.transpose(0, 3, 1, 2).reshape(
                n_pages * ps, self._kv_heads, 1
            )[:L]

        layers = []
        with _san.allowed_sync("disagg page export"):
            for a in self._arenas:
                ly = {
                    "k": rows(a.k),
                    "v": rows(a.v),
                }
                if a.k_scale is not None:
                    ly["k_scale"] = scale_rows(a.k_scale)
                    ly["v_scale"] = scale_rows(a.v_scale)
                layers.append(ly)
        payload = serialize_kv_handoff(
            layers, L, self.kv_quant, self._kv_dtype_np.name
        )
        payload["first_token"] = int(req.tokens[0]) if req.tokens else None
        req.kv_export = payload
        _prof.record_disagg_event("exports")
        _prof.record_disagg_event("handoff_bytes", payload["payload_bytes"])
        _flight.record(
            "disagg", "export", req=req.id, pages=n_pages,
            bytes=payload["payload_bytes"],
        )

    def _resolve(self, req, reason):
        """Terminal transition, exactly once: a request that already
        resolved (restart raced an eviction, stop raced a finish) is left
        untouched — never double-completed, never silently lost."""
        from .. import profiler as _prof

        if req.finished.is_set():
            return
        req.finish_reason = reason
        req.state = reason
        req._finish_t = time.perf_counter()
        if reason in ("eos", "length"):
            _prof.record_serving_request(
                req.ttft_s or 0.0, len(req.tokens),
                req._finish_t - req._submit_t,
            )
        elif reason == "timeout":
            _prof.record_serving_fault("deadline_miss")
        if reason in ("eos", "length", "timeout"):
            # miss-rate EWMA over ORGANIC terminal outcomes only — restarts
            # and cancellations are not deadline signal; _mu (reentrant)
            # covers resolution from both the scheduler thread and the
            # stop/fail_all paths; mirrored into the profiler gauge so
            # /metrics scrapes the same number /healthz reports
            with self._mu:
                self._miss_ewma = (
                    (1.0 - _MISS_EWMA_ALPHA) * self._miss_ewma
                    + _MISS_EWMA_ALPHA * (1.0 if reason == "timeout" else 0.0)
                )
                rate = self._miss_ewma
            _prof.record_deadline_miss_rate(rate)
        elif reason == "cancelled":
            _prof.record_serving_fault("cancelled")
        elif reason == "restarted":
            _prof.record_serving_fault("restarted_requests")
        req.finished.set()

    # -- debug invariants ----------------------------------------------------

    def _check_invariants(self):
        """FLAGS_serve_debug_invariants: loud failure instead of a silent
        slot leak.  After a step: a free slot is fully recycled (pos,
        last_tok, temps zeroed), an occupied slot holds exactly one LIVE
        request at a position within the cache, and no request occupies two
        slots."""
        with self._mu:
            seen = {}
            for s, req in enumerate(self._slot_req):
                if req is None:
                    if self._pos[s] != 0 or self._temps[s] != 0.0:
                        raise AssertionError(
                            f"slot invariant: slot {s} is free but not "
                            f"recycled (pos={int(self._pos[s])}, "
                            f"temp={float(self._temps[s])})"
                        )
                    continue
                if req.finished.is_set():
                    raise AssertionError(
                        f"slot invariant: slot {s} holds already-resolved "
                        f"request {req.id} ({req.finish_reason})"
                    )
                if id(req) in seen:
                    raise AssertionError(
                        f"slot invariant: request {req.id} occupies slots "
                        f"{seen[id(req)]} and {s}"
                    )
                seen[id(req)] = s
                if not 0 < int(self._pos[s]) <= self.max_len:
                    raise AssertionError(
                        f"slot invariant: slot {s} (request {req.id}) at "
                        f"position {int(self._pos[s])} outside (0, "
                        f"{self.max_len}]"
                    )
            if self._queued_new_tokens < 0:
                raise AssertionError(
                    "slot invariant: queued-token accounting went negative "
                    f"({self._queued_new_tokens})"
                )
            self._check_page_invariants_locked()
            if self._lora is not None:
                bindings = {}
                for s in range(self.slots):
                    a = int(self._slot_adapter[s])
                    if self._slot_req[s] is None:
                        if a:
                            raise AssertionError(
                                f"lora invariant: free slot {s} still bound "
                                f"to arena slot {a}"
                            )
                        continue
                    if a:
                        bindings[a] = bindings.get(a, 0) + 1
                self._lora.check_invariants(bindings)

    def _check_page_invariants_locked(self):
        """FLAGS_serve_debug_invariants, paged extension: every page's
        refcount equals its observable holds (slot mappings + prefix-cache
        entries), the free list is exactly the ref-0 pages, free slots map
        nothing, and an occupied slot's table covers every position it has
        written.  Caller holds _mu."""
        pool, ps = self._pool, self.page_size
        check_table_bounds(self._page_table, pool.num_pages)
        if self._window is not None:
            self._window.check([
                None if self._slot_req[s] is None else int(self._pos[s])
                for s in range(self.slots)
            ])
        # ISSUE 18: the scale arenas are audited alongside the K/V pages —
        # congruence (same page count, [ps, kv_heads, 1] f32 rows) is the
        # whole refcount story, because page p's scale rows share page p's
        # refcount by construction
        check_scale_arenas(
            [a for a, g in zip(self._arenas, self._layer_group) if not g],
            pool.num_pages, ps,
        )
        expected = np.zeros(pool.num_pages, np.int64)
        for p in pool.scratch_pages:
            expected[p] = 1  # scratch pin (one per cp shard, ISSUE 20)
        for s in range(self.slots):
            row = self._page_table[s]
            mapped = self._slot_pages[s]
            if self._slot_req[s] is None:
                if mapped or row.any():
                    raise AssertionError(
                        f"page invariant: free slot {s} still maps pages "
                        f"{mapped} (table row {row.tolist()})"
                    )
                continue
            if len(set(mapped)) != len(mapped) or 0 in mapped:
                raise AssertionError(
                    f"page invariant: slot {s} mapping {mapped} has "
                    "duplicates or scratch"
                )
            nz = [int(p) for p in row if p]
            if nz != list(mapped):
                raise AssertionError(
                    f"page invariant: slot {s} table row {row.tolist()} "
                    f"disagrees with its mapping {mapped}"
                )
            frontier = (int(self._pos[s]) - 1) // ps
            if frontier >= len(mapped):
                raise AssertionError(
                    f"page invariant: slot {s} at pos {int(self._pos[s])} "
                    f"writes page entry {frontier} but maps only "
                    f"{len(mapped)} pages"
                )
            if self._spec_on:
                # the next verify window may legally overrun the mapping
                # (rejected-draft territory), but every overrun entry must
                # scatter to scratch — a nonzero table value there would
                # aim garbage at a live page
                _win_in, win_over = spec_write_pages(
                    int(self._pos[s]), self.spec_k + 1, ps, len(mapped)
                )
                for e in win_over:
                    if e < row.shape[0] and row[e] != 0:
                        raise AssertionError(
                            f"page invariant: slot {s} verify window entry "
                            f"{e} is past its {len(mapped)}-page mapping but "
                            f"table row holds page {int(row[e])} (expected "
                            "0 = scratch redirect)"
                        )
            for p in mapped:
                expected[p] += 1
        if self._prefix is not None:
            for e in self._prefix.entries():
                if not 0 < e.rows <= ps:
                    raise AssertionError(
                        f"page invariant: cache entry on page {e.page} has "
                        f"row count {e.rows} outside (0, {ps}]"
                    )
                expected[e.page] += 1
        if not np.array_equal(expected, pool.refs):
            bad = [
                (p, int(pool.refs[p]), int(expected[p]))
                for p in range(pool.num_pages)
                if pool.refs[p] != expected[p]
            ]
            raise AssertionError(
                "page invariant: refcount drift (page, actual, expected): "
                f"{bad}"
            )
        free = sorted(pool._free)
        ref0 = [
            p for p in range(pool.num_pages)
            if pool.refs[p] == 0 and not pool.is_scratch(p)
        ]
        if free != ref0 or len(set(free)) != len(free):
            raise AssertionError(
                f"page invariant: free list {free} != ref-0 pages {ref0}"
            )
        if self.cp > 1:
            # cp layout invariant: every mapped sequence page sits on the
            # shard its table column demands (column j -> shard j % cp) —
            # a misplaced page would silently read as unmapped on device
            for s in range(self.slots):
                if self._slot_req[s] is None:
                    continue
                row = self._page_table[s]
                for j in range(row.shape[0]):
                    p = int(row[j])
                    if p and pool.shard_of(p) != j % self.cp:
                        raise AssertionError(
                            f"page invariant: slot {s} table column {j} "
                            f"maps page {p} on shard {pool.shard_of(p)}, "
                            f"expected shard {j % self.cp} (cp={self.cp})"
                        )
        if self._sessions is not None:
            # ISSUE 20 audit clause: session pins reconcile exactly with
            # live cache entries and their page refcounts
            self._sessions.check(self._prefix, pool)
