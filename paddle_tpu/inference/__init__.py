"""Inference export/serving (reference capability:
paddle/fluid/inference AnalysisPredictor + paddle.jit.save inference models —
SURVEY.md §2.1 "Inference runtime").

TPU-native path: the exported artifact is a serialized StableHLO program
(jax.export) + weights — portable across machines with compatible jaxlib,
re-compiled by XLA on load (the reference ships ProgramDesc + params and
re-optimizes with IR passes; same shape).
"""

from __future__ import annotations

import os
import pickle

import numpy as np
import jax

from .. import no_grad
from ..framework.io import load as _load
from ..framework.io import save as _save
from ..tensor import Tensor


def export(layer, path, example_inputs, with_weights=True, params_from=None):
    """Serialize `layer.forward` (or a plain callable) traced at
    example_inputs to StableHLO.

    example_inputs: list of Tensors/arrays defining shapes+dtypes.
    params_from: Layer whose state_dict to save when `layer` is a bare
    callable (e.g. a @to_static-decorated bound method).
    Produces: <path>.stablehlo (serialized program), <path>.pdiparams.
    """
    from jax import export as jexport

    weights_owner = params_from if params_from is not None else layer
    was_training = getattr(layer, "training", False)
    if hasattr(layer, "eval"):
        layer.eval()
    arrays = [
        (x._raw if isinstance(x, Tensor) else np.asarray(x)) for x in example_inputs
    ]

    def pure_fn(*xs):
        ts = []
        for a in xs:
            t = Tensor.__new__(Tensor)
            t._init_from_array(a, stop_gradient=True)
            ts.append(t)
        with no_grad():
            out = layer(*ts)
        if isinstance(out, Tensor):
            return out._raw
        return tuple(o._raw if isinstance(o, Tensor) else o for o in out)

    exported = jexport.export(jax.jit(pure_fn))(
        *[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]
    )
    blob = exported.serialize()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".stablehlo", "wb") as f:
        f.write(blob)
    if with_weights and hasattr(weights_owner, "state_dict"):
        _save(weights_owner.state_dict(), path + ".pdiparams")
    if was_training:
        layer.train()  # export must not flip the live model to eval
    return path


class Config:
    """API-compat config object (reference: paddle_infer::Config)."""

    def __init__(self, model_path=None, params_path=None):
        self.model_path = model_path
        self.params_path = params_path

    def enable_use_gpu(self, *a, **k):
        pass

    def set_cpu_math_library_num_threads(self, n):
        pass

    def switch_ir_optim(self, flag=True):
        pass


class Predictor:
    """Loads a serialized StableHLO program and runs it (reference:
    AnalysisPredictor::Run)."""

    def __init__(self, path_or_config):
        path = (
            path_or_config.model_path
            if isinstance(path_or_config, Config)
            else path_or_config
        )
        from jax import export as jexport

        with open(path + ".stablehlo", "rb") as f:
            self._exported = jexport.deserialize(f.read())
        # jit the exported call so its compile goes through jax's compilation
        # cache — with FLAGS_compile_cache_dir set, a restarted server loads
        # the XLA binary from disk instead of recompiling the program
        self._call = jax.jit(self._exported.call)

    def run(self, inputs):
        arrays = [
            x._raw if isinstance(x, Tensor) else np.asarray(x) for x in inputs
        ]
        out = self._call(*arrays)
        if isinstance(out, (list, tuple)):
            return [np.asarray(o) for o in out]
        return [np.asarray(out)]

    def get_input_names(self):
        return [f"x{i}" for i in range(len(self._exported.in_avals))]

    def get_output_names(self):
        return [f"y{i}" for i in range(len(self._exported.out_avals))]


def create_predictor(config):
    return Predictor(config)


class GenerationPredictor:
    """Serves autoregressive decoding over a model's compiled static-KV
    decode step (models/llama.py StaticKVCache): the first request compiles
    prefill+decode once; every later token — and every later request with
    the same batch/cache bucket — reuses the same two executables.
    (Reference capability: the inference runtime's flash-decode serving
    path, SURVEY §2.1 L8.)"""

    def __init__(self, model, max_new_tokens=32):
        self.model = model
        self.max_new_tokens = max_new_tokens

    def generate(self, input_ids, max_new_tokens=None, temperature=0.0,
                 eos_token_id=None):
        ids = np.asarray(input_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        n = self.max_new_tokens if max_new_tokens is None else int(max_new_tokens)
        out = self.model.generate(
            Tensor(ids), max_new_tokens=n, temperature=float(temperature),
            eos_token_id=eos_token_id,
        )
        return np.asarray(out.numpy())

    def warmup(self, batch_size=1, prompt_len=8, max_new_tokens=None, temperature=0.0):
        """Compile (or AOT-load, with FLAGS_compile_cache_dir set) the
        prefill + decode executables for one serving bucket before traffic
        arrives, so the first request pays no cold-start compile.  Runs a
        dummy generate on zero ids — model weights are read-only in decode,
        nothing is mutated."""
        ids = np.zeros((int(batch_size), int(prompt_len)), np.int32)
        self.generate(ids, max_new_tokens=max_new_tokens, temperature=temperature)
        return self


def serve(path_or_predictor, port=8866, host="127.0.0.1", block=True,
          supervise=True, handle_signals=None):
    """Serving loop (reference capability: the AnalysisPredictor behind
    paddle_serving — SURVEY.md §2.1 "Inference runtime").  Stdlib-only
    ThreadingHTTPServer with a bounded admission gate: requests beyond the
    queue bound (FLAGS_serve_queue_depth) get 503 + JSON instead of piling
    up behind the executable.

    - GET  /health            -> 200
    - GET  /healthz           -> lifecycle snapshot: status live/ready/
      draining/dead + occupancy, queue depth, restart count, drain estimate
    - GET  /metrics           -> Prometheus text exposition (profiler,
      sanitizer, trace and flight-recorder counters; replica label)
    - GET  /trace/<id>        -> per-request span tree (populated when
      FLAGS_trace is on; POST responses carry X-Trace-Id)
    - POST /predict           -> {"outputs": [...]}   (Predictor)
    - POST /generate          -> {"tokens": [...]}    (GenerationPredictor or
      ContinuousBatchingEngine; body: {"input_ids": [...] or [[...], ...],
      "max_new_tokens": n, "temperature": t, "eos_token_id": id,
      "deadline_s": s, "spec_k": k, "adapter": name,
      "session_id": sid}).  "spec_k" caps the
      request's speculative draft length below the engine-wide
      FLAGS_serve_spec_k (0 opts out of speculation; omitted = engine
      default).  "adapter" names a registered LoRA adapter served from the
      engine's adapter arena (omitted = base model); an unregistered name
      is a typed 404 (`AdapterUnknown`, retriable: false).  An
      `X-Idempotency-Key` header dedupes server-side: a completed key
      replays its cached response byte-identical (marked
      `X-Idempotency-Replay`) within `FLAGS_router_idem_ttl`, an in-flight
      key joins the live generation — at most one generation per key even
      through connection resets and router failover.  "session_id" (ISSUE
      20) names a multi-turn KV session on this replica: the engine pins
      the conversation's committed pages and later turns chunk-prefill
      only the new suffix.  A prompt past the engine's context is a typed
      400 (`ContextOverflow`, retriable: false) whose body carries the
      capacity geometry (`max_len`, and under cp the per-shard page
      budget) — raised at admission, before any page is touched
    - POST /prefill           -> disaggregated prefill hop (engine-backed,
      ISSUE 19): runs chunked prefill + ONE sampled token, exports the
      committed prompt pages, and answers {"first_token", "prompt_len",
      "handoff"} — the handoff payload a decode-role replica imports via
      /generate's "handoff" field (paired with a "reservation" from
      /reserve).  Quantized arenas ship int8 rows + scales as stored.
      With "export": false (the router's single-token fast path) the page
      export is skipped and "handoff" is null — the sampled token is the
      entire response.
    - POST /reserve           -> {"prompt_len": L, "max_new_tokens": n}
      reserves decode-side pages BEFORE prefill starts elsewhere; answers
      {"reservation", "pages", "ttl_s"} or typed 503 when the headroom
      isn't there.  Unconsumed reservations expire after ttl_s.

    A ContinuousBatchingEngine serves /generate with true continuous
    batching: concurrent requests decode interleaved in its slots, each
    finishing on its own EOS/length (the lock-based predictors serialize).

    Serving fault domain (PR 6): an engine-backed server runs under a
    ``fault.EngineSupervisor`` (``supervise=False`` opts out) — a wedged or
    dead scheduler gets a bounded warm restart, and past the budget clients
    get typed 503s instead of hangs.  Every 503 carries a ``Retry-After``
    header derived from the engine's queue-drain estimate.  SIGTERM (when
    serve() runs on the main thread, or ``handle_signals=True``) triggers
    DRAIN: stop admitting, finish in-flight work up to ``PADDLE_STOP_GRACE``
    seconds (exported by ``distributed.launch --stop_grace``; else
    ``FLAGS_serve_drain_grace``), then stop cleanly.  ``server.drain(grace)``
    does the same programmatically.
    """
    import json
    import signal as _signal
    import threading
    import time as _time
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from . import engine as engine_mod
    from .engine import ContinuousBatchingEngine, EngineUnavailable
    from ..lora.registry import AdapterUnknown
    from ..fault import EngineSupervisor
    from ..framework import core as _fcore
    from ..obs import flight as _flight
    from ..obs import metrics as _obs_metrics
    from ..obs import trace as _obs

    predictor = (
        path_or_predictor
        if isinstance(
            path_or_predictor,
            (Predictor, GenerationPredictor, ContinuousBatchingEngine),
        )
        else Predictor(path_or_predictor)
    )
    engine = predictor if isinstance(predictor, ContinuousBatchingEngine) else None
    supervisor = None
    if engine is not None:
        engine.start()
        if supervise:
            supervisor = EngineSupervisor(engine).start()
    lock = threading.Lock()
    # admission bound for the lock-based predictor paths: at most
    # queue_depth requests running-or-waiting; the rest shed with 503
    # (the engine has its own bounded queue — submit raises QueueFull)
    gate = threading.BoundedSemaphore(int(_fcore.flag("FLAGS_serve_queue_depth")))
    state = {"draining": False}
    # crash-proof front door (ISSUE 17): replica-side request dedupe.  A
    # /generate carrying X-Idempotency-Key completes into this cache BEFORE
    # its response bytes go out, so a connection reset (or a dead router)
    # after the generation finished leaves the response replayable — the
    # retry through the successor router gets the SAME bytes, not a second
    # generation.  journal-module import is stdlib-light by design.
    idem = None
    if engine is not None:
        from ..serving.journal import IdempotencyCache

        idem = IdempotencyCache()

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def _reply(self, code, payload, headers=None):
            key = getattr(self, "_idem_key", None)
            if key is not None and idem is not None:
                # complete BEFORE any response byte leaves: a reset between
                # completion and delivery must leave the response cached for
                # the client's (or successor router's) keyed retry
                self._idem_key = None
                idem.complete(key, code, payload, headers)
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            tid = getattr(self, "_trace_id", None)
            if tid:
                self.send_header(_obs.HDR_TRACE, tid)
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _reply_error(self, code, err_type, msg, retriable, retry_after=None):
            # uniformly typed error JSON: the router's retry decision is
            # driven by `retriable` + Retry-After, never by string matching;
            # trace_id joins the failure to its span tree across hops
            self._err = err_type
            headers = {}
            # `is not None`, not truthiness (the router-side fix's twin):
            # a 0.0 drain estimate still means "retry after 1s", not
            # "no header"
            if retry_after is not None:
                headers["Retry-After"] = str(max(1, int(retry_after + 0.5)))
            self._reply(
                code,
                {
                    "error": msg,
                    "type": err_type,
                    "retriable": bool(retriable),
                    "retry_after_s": retry_after or 0,
                    "trace_id": getattr(self, "_trace_id", None),
                },
                headers,
            )

        def _busy(self, msg="admission queue full, retry later",
                  retry_after=None, err_type="EngineUnavailable"):
            # Retry-After from the queue-drain estimate: a shed client
            # retries when a slot is plausibly free, not immediately
            if retry_after is None and engine is not None:
                retry_after = engine.estimate_drain_s()
            self._reply_error(503, err_type, msg, True, retry_after)

        def _healthz(self):
            if engine is not None:
                h = engine.healthz()
                if state["draining"] and h["status"] not in ("dead",):
                    h["status"] = "draining"
            else:
                h = {"status": "draining" if state["draining"] else "ready"}
            code = 200 if h["status"] in ("ready", "live") else 503
            self._reply(code, h)

        def do_GET(self):
            if self.path == "/health":
                self._reply(200, {"status": "ok"})
            elif self.path == "/healthz":
                self._healthz()
            elif self.path == "/metrics":
                # bound address, not the port argument (0 = ephemeral)
                bh, bp = self.server.server_address[:2]
                body = _obs_metrics.render(
                    labels={"replica": f"{bh}:{bp}"}
                ).encode()
                self.send_response(200)
                self.send_header("Content-Type", _obs_metrics.CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif self.path.startswith("/trace/"):
                tid = self.path[len("/trace/"):]
                roots = _obs.tree(tid)
                if roots:
                    self._reply(200, {"trace_id": tid, "spans": roots})
                else:
                    self._reply(404, {"error": f"no spans buffered for trace {tid!r}"})
            else:
                self._reply(404, {"error": "use POST /predict"})

        def _deadline_s(self, req):
            # per-request deadline: body field, else the router's
            # X-Deadline-Ms hop header (remaining budget at send time)
            d = req.get("deadline_s")
            if d is not None:
                return float(d)
            hdr = self.headers.get("X-Deadline-Ms")
            if hdr is not None:
                return float(hdr) / 1e3
            return None

        def _generate_engine(self):
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n))
                ids = req["input_ids"]
                deadline_s = self._deadline_s(req)
                if deadline_s is not None and deadline_s <= 0:
                    # the hop budget was spent in flight; don't even admit
                    self._reply_error(
                        504, "DeadlineExceeded",
                        "deadline exhausted before admission", False,
                    )
                    return
                rows = ids if ids and isinstance(ids[0], list) else [ids]
                handles = []
                try:
                    for row in rows:
                        handles.append(
                            engine.submit(
                                row,
                                max_new_tokens=int(req.get("max_new_tokens") or 32),
                                temperature=float(req.get("temperature", 0.0)),
                                eos_token_id=req.get("eos_token_id"),
                                deadline_s=deadline_s,
                                trace=(self._trace_id, self._handle_sid),
                                spec_k=(
                                    None if req.get("spec_k") is None
                                    else int(req["spec_k"])
                                ),
                                adapter=req.get("adapter"),
                                handoff=req.get("handoff"),
                                reservation=req.get("reservation"),
                                session_id=req.get("session_id"),
                            )
                        )
                except engine_mod.ContextOverflow as e:
                    # typed 400, terminal: no replica of this tier holds
                    # more context — the body carries the capacity geometry
                    # so the client can right-size or re-route by itself
                    self._err = type(e).__name__
                    self._reply(400, {
                        "error": str(e),
                        "type": type(e).__name__,
                        "retriable": False,
                        "retry_after_s": 0,
                        "capacity": e.body(),
                        "trace_id": getattr(self, "_trace_id", None),
                    })
                    return
                except AdapterUnknown as e:
                    # terminal 404: retrying cannot help until someone
                    # registers the adapter — the router must NOT fail over
                    self._reply_error(404, type(e).__name__, str(e), False)
                    return
                except engine_mod.DeadlineUnattainable as e:
                    # 504 but retriable: a LESS LOADED replica may still
                    # meet the deadline — the router fails over on this
                    self._reply_error(
                        504, type(e).__name__, str(e), True, e.retry_after_s
                    )
                    return
                except EngineUnavailable as e:
                    # queue full / draining / dead: rows already admitted
                    # still complete server-side; the client sheds and
                    # retries the whole batch
                    self._busy(str(e), retry_after=e.retry_after_s,
                               err_type=type(e).__name__)
                    return
                outs = [h.wait(timeout=600).tolist() for h in handles]
                self._reply(
                    200,
                    {"tokens": outs if isinstance(ids[0], list) else outs[0]},
                )
            except engine_mod.EngineRestarted as e:
                # in-flight state was lost to a warm restart: typed 503,
                # the request is safe to retry (no tokens were delivered)
                self._busy(str(e), err_type=type(e).__name__)
            except engine_mod.DeadlineExceeded as e:
                # the deadline passed while queued/decoding: retrying the
                # same budget elsewhere cannot succeed
                self._reply_error(504, type(e).__name__, str(e), False)
            except engine_mod.NonFiniteLogits as e:
                self._reply_error(500, type(e).__name__, str(e), False)
            except Exception as e:
                self._reply_error(
                    400, type(e).__name__, f"{type(e).__name__}: {e}", False
                )

        def _reserve_engine(self):
            # decode-side page hold, taken BEFORE prefill starts elsewhere:
            # the router reserves here, prefills on the prefill worker, then
            # spends the reservation in /generate's admission — so a prefill
            # never completes into a decode worker that can't seat it
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n))
                out = engine.reserve_pages(
                    int(req["prompt_len"]),
                    int(req.get("max_new_tokens") or 32),
                    ttl_s=(
                        None if req.get("ttl_s") is None
                        else float(req["ttl_s"])
                    ),
                )
                self._reply(200, out)
            except EngineUnavailable as e:
                self._busy(str(e), retry_after=e.retry_after_s,
                           err_type=type(e).__name__)
            except Exception as e:
                self._reply_error(
                    400, type(e).__name__, f"{type(e).__name__}: {e}", False
                )

        def _prefill_engine(self):
            from ..fault import injection as _inj

            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n))
                ids = req["input_ids"]
                if ids and isinstance(ids[0], list):
                    self._reply_error(
                        400, "ValueError",
                        "/prefill takes one prompt per request", False,
                    )
                    return
                deadline_s = self._deadline_s(req)
                if deadline_s is not None and deadline_s <= 0:
                    self._reply_error(
                        504, "DeadlineExceeded",
                        "deadline exhausted before admission", False,
                    )
                    return
                # export=false is the router's single-token fast path: the
                # sampled token is the entire response, so no page export
                # (and no handoff) is ever built
                want_export = bool(req.get("export", True))
                try:
                    # one sampled token: the decode side seats pos=L with
                    # this token as its first emission, so the handoff is
                    # exactly a colocated engine's post-prefill state.
                    # spec_k=0 — a 1-token request has nothing to draft.
                    h = engine.submit(
                        ids,
                        max_new_tokens=1,
                        temperature=float(req.get("temperature", 0.0)),
                        eos_token_id=req.get("eos_token_id"),
                        deadline_s=deadline_s,
                        trace=(self._trace_id, self._handle_sid),
                        spec_k=0,
                        export_kv=want_export,
                    )
                except engine_mod.DeadlineUnattainable as e:
                    self._reply_error(
                        504, type(e).__name__, str(e), True, e.retry_after_s
                    )
                    return
                except EngineUnavailable as e:
                    self._busy(str(e), retry_after=e.retry_after_s,
                               err_type=type(e).__name__)
                    return
                out = h.wait(timeout=600)
                if _inj.should_fire("disagg.prefill.crash", "serve./prefill"):
                    # kill -9 mid-handoff: the payload exists server-side
                    # but not one response byte leaves, so the router sees
                    # a transport error with response_started=False — a
                    # zero-token retriable failover, never a duplicate
                    self.close_connection = True
                    return
                if not want_export:
                    self._reply(200, {
                        "first_token": int(out[len(ids)]),
                        "prompt_len": len(ids),
                        "handoff": None,
                    })
                    return
                if h.kv_export is None:
                    self._reply_error(
                        503, "HandoffExportFailed",
                        "prefill finished but the page export failed; retry",
                        True,
                    )
                    return
                payload = h.kv_export
                self._reply(200, {
                    "first_token": payload.get("first_token"),
                    "prompt_len": payload["prompt_len"],
                    "handoff": payload,
                })
            except engine_mod.EngineRestarted as e:
                self._busy(str(e), err_type=type(e).__name__)
            except engine_mod.DeadlineExceeded as e:
                self._reply_error(504, type(e).__name__, str(e), False)
            except engine_mod.NonFiniteLogits as e:
                self._reply_error(500, type(e).__name__, str(e), False)
            except Exception as e:
                self._reply_error(
                    400, type(e).__name__, f"{type(e).__name__}: {e}", False
                )

        def do_POST(self):
            # trace context: join the caller's (router hop headers) or mint
            # a root — minting is always on so error bodies carry trace_id;
            # the serve.handle span id is pre-minted so engine stage spans
            # can parent on it before the handle span itself completes
            ctx = _obs.ctx_from_headers(self.headers)
            self._trace_id = ctx[0] if ctx else _obs.new_trace_id()
            self._handle_sid = _obs.new_span_id()
            self._err = None
            self._idem_key = None
            t0 = _time.perf_counter()
            try:
                self._do_post()
            finally:
                key = getattr(self, "_idem_key", None)
                if key is not None and idem is not None:
                    # the handler died without replying: wake joiners with
                    # no response so their keyed retries re-execute
                    self._idem_key = None
                    idem.abandon(key)
                _obs.record(
                    "serve.handle", self._trace_id,
                    t0=t0, t1=_time.perf_counter(),
                    span_id=self._handle_sid,
                    parent_id=(ctx[1] if ctx else None),
                    status="error" if self._err else "ok",
                    path=self.path, error=self._err,
                )

        def _do_post(self):
            if state["draining"]:
                self._busy("server draining, retry elsewhere",
                           err_type="Draining")
                return
            if self.path == "/generate" and engine is not None:
                key = self.headers.get("X-Idempotency-Key")
                if key and idem is not None:
                    verdict, val = idem.begin(key)
                    if verdict == "done":
                        status, body, hdrs = val
                        self._reply(status, body, headers={
                            **(hdrs or {}), "X-Idempotency-Replay": "hit",
                        })
                        return
                    if verdict == "join":
                        resp = idem.wait(val)
                        if resp is not None:
                            status, body, hdrs = resp
                            self._reply(status, body, headers={
                                **(hdrs or {}), "X-Idempotency-Replay": "join",
                            })
                            return
                        self._busy("idempotent join aborted; retry with the "
                                   "same key")
                        return
                    self._idem_key = key  # first sight: generate, then cache
                self._generate_engine()
                return
            if self.path == "/prefill" and engine is not None:
                self._prefill_engine()
                return
            if self.path == "/reserve" and engine is not None:
                self._reserve_engine()
                return
            if self.path == "/generate" and isinstance(predictor, GenerationPredictor):
                if not gate.acquire(blocking=False):
                    self._busy()
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n))
                    with lock:
                        toks = predictor.generate(
                            req["input_ids"],
                            max_new_tokens=req.get("max_new_tokens"),
                            temperature=req.get("temperature", 0.0),
                            eos_token_id=req.get("eos_token_id"),
                        )
                    self._reply(200, {"tokens": toks.tolist()})
                except Exception as e:
                    self._reply(400, {"error": f"{type(e).__name__}: {e}"})
                finally:
                    gate.release()
                return
            if self.path != "/predict" or not isinstance(predictor, Predictor):
                self._reply(404, {"error": "use POST /predict or /generate"})
                return
            if not gate.acquire(blocking=False):
                self._busy()
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                req = json.loads(self.rfile.read(n))
                avals = predictor._exported.in_avals
                # cast to each traced input's dtype (ids models take ints)
                arrays = [
                    np.asarray(x, avals[i].dtype if i < len(avals) else np.float32)
                    for i, x in enumerate(req["inputs"])
                ]
                with lock:  # one executable; serialize callers
                    outs = predictor.run(arrays)
                self._reply(200, {"outputs": [o.tolist() for o in outs]})
            except Exception as e:
                self._reply(400, {"error": f"{type(e).__name__}: {e}"})
            finally:
                gate.release()

    server = ThreadingHTTPServer((host, port), Handler)

    # -- graceful drain (SIGTERM / programmatic) ----------------------------
    prev_handler = {}

    def _restore_handler():
        if _signal.SIGTERM in prev_handler:
            try:
                _signal.signal(_signal.SIGTERM, prev_handler.pop(_signal.SIGTERM))
            except (ValueError, KeyError):
                pass

    def drain(grace=None):
        """Stop admitting (503 + Retry-After), let in-flight work finish up
        to `grace` seconds (PADDLE_STOP_GRACE env — exported by
        distributed.launch --stop_grace — else FLAGS_serve_drain_grace),
        then stop supervisor, engine, and HTTP loop.  Idempotent; returns
        the worker thread so callers can join it."""
        if state["draining"]:
            return state.get("drain_thread")
        state["draining"] = True
        # signal.signal only works on the main thread, which is where a
        # SIGTERM (and a block=False caller) runs drain(): restore here, not
        # in the worker below, or the handler — and through its closure the
        # server, the engine and every device buffer they own — outlives
        # the drain for as long as the process does
        _restore_handler()
        if grace is None:
            grace = float(
                os.environ.get(
                    "PADDLE_STOP_GRACE", _fcore.flag("FLAGS_serve_drain_grace")
                )
            )

        def _worker():
            # a drain is the process's last orderly moment — persist the
            # flight ring before in-flight work winds down and we exit
            try:
                _flight.dump("serve-drain")
            except Exception:
                pass
            if engine is not None:
                engine.drain()
                deadline = _time.monotonic() + float(grace)
                while engine.has_work() and _time.monotonic() < deadline:
                    _time.sleep(0.02)
            if supervisor is not None:
                supervisor.stop()
            if engine is not None:
                engine.stop()
            server.shutdown()

        t = threading.Thread(target=_worker, name="serve-drain", daemon=True)
        state["drain_thread"] = t
        t.start()
        return t

    server.drain = drain
    server.supervisor = supervisor
    server.engine = engine

    # SIGTERM → drain: installable only from the main thread; default to
    # trying when the caller did not say (tests spawn serve() off-thread and
    # silently skip, launched serving ranks run on main and get it)
    if handle_signals or handle_signals is None:
        try:
            prev_handler[_signal.SIGTERM] = _signal.signal(
                _signal.SIGTERM, lambda signum, frame: drain()
            )
        except ValueError:
            if handle_signals:
                raise

    if block:
        try:
            server.serve_forever()
        finally:
            _restore_handler()
        return server
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    return server


def __getattr__(name):
    # engine symbols load lazily: paddle_tpu/__init__ imports this module
    # during package init, before the model stack the engine depends on
    if name in (
        "ContinuousBatchingEngine", "EngineRequest", "QueueFull",
        "EngineUnavailable", "DeadlineUnattainable", "DeadlineExceeded",
        "RequestCancelled", "EngineRestarted", "NonFiniteLogits",
        "ContextOverflow",
    ):
        from . import engine as _engine

        return getattr(_engine, name)
    if name == "SessionStore":
        from .paging import SessionStore

        return SessionStore
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
