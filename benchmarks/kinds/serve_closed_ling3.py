"""Kind `serve_closed_ling3`: `serve_closed`'s closed loop of streaming clients
on one engine, for a Ling-3.0-flash configuration (`configs/ling-3.0-flash-*`):
a reasoning model's traffic, a prompt of a page or two and eight to
twenty-four thousand streamed tokens, so a request lasts five windows.  It
takes `Clients`, `Record` and `read_spans` from `serve_closed` and `describe`
from `serve_closed_dsv32`, and differs in this:

- the stream: the pool in one fixed order for every seed (as `longctx16`), the
  seed gives the token ids.  RESUMED START (`resume: "even"`): the stream's
  i-th request, i < clients, is its pool pair with the first `floor(phi_i *
  answer_len)` answer tokens already in the prompt as seeded ids, `phi_i = (i +
  0.5) / clients`, and asks for the rest; every later request is whole.  A
  ramp from empty would measure young streams and an empty arena;
- the wait: a client waits for its request however long it lives (`Clients`
  gives up after 300 s and submits the next);
- the window opens when every client has streamed a second token.  At its
  close the kind reads its counters, then CANCELS what is in flight (a drain
  would take ten minutes); a request the kind cancelled is not a failed one,
  and what it streamed stands;
- the traced seconds start when the first request submitted later than 2 s
  into the window is submitted (at 10 s if none came), so that one
  admission's prefill lies inside them;
- the model: `Ling3ForCausalLM` created in bfloat16 at the file's share, the
  seeded weights made and placed a layer at a time; warm-up as
  `serve_closed_dsv32` (`engine.warmup()`, then one prompt in chunks);
- the reference: `reference_ling3.py`; `logit_gap_mean` is the MEAN gap by
  which a served token's logit lies below the reference's best, over 3
  requests that streamed in the window, the longest among them, resumed
  prompt plus served tokens.

`itl_p95_ms`: p95 of every gap that ENDED in the window, of all requests.
`serve_tok_s`: tokens whose `on_token` fell in the window over its seconds.

params: clients, pool, prompt_len, answer_len, max_total, resume,
check_requests, trace_seconds, limits{logit_gap_mean}.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from .. import traffic
from .. import weights_ling3 as W
from . import serve_closed
from .common import log_memory, memory_peak_bytes, percentile, traced_window
from .serve_closed import read_spans
from .serve_closed_dsv32 import decoding_started, describe

ORDER = 11  # the seed of the one order in which every run takes the pool


def request_stream(params, seed, vocab_size):
    """Endless (prompt ids, answer_len): `traffic.request_pool` in ONE fixed
    order, again and again, the token ids uniform from the seed; the first
    `clients` requests resumed as the module says."""
    pool = traffic.request_pool(params)
    order = np.random.default_rng(ORDER).permutation(len(pool))
    rng = np.random.default_rng([int(seed), 1])
    clients = int(params["clients"])
    if params.get("resume", "even") != "even":
        raise ValueError(f"resume {params['resume']!r}: only 'even' is written")
    count = 0
    while True:
        for i in order:
            n, m = pool[i]
            done = int((count + 0.5) / clients * m) if count < clients else 0
            count += 1
            yield rng.integers(1, vocab_size, size=n + done, dtype=np.int64).astype(np.int32), m - done


class Record(serve_closed.Record):
    """`cut`: the kind cancelled it at window close; what it streamed stands."""
    __slots__ = ("cut",)

    def __init__(self, prompt, n):
        super().__init__(prompt, n)
        self.cut = False

    def failed(self):
        if self.cut and self.req is not None and self.error is None:
            return self.req.finish_reason not in ("cancelled", "length")
        return super().failed()


class Clients(serve_closed.Clients):
    """Clients that wait for a request as long as it lives, and whose
    requests in flight the kind cancels."""

    def _next(self):
        with self.lock:
            rec = Record(*next(self.stream))
            self.records.append(rec)
            return rec

    def _loop(self):
        from paddle_tpu.obs import trace as obs

        while not self.stop.is_set():
            rec = self._next()
            ctx = (obs.new_trace_id(), "") if self.traced else None
            rec.submit_t = time.perf_counter()
            try:
                rec.req = self.engine.submit(
                    rec.prompt, max_new_tokens=rec.n,
                    on_token=lambda _tok, t=rec.times: t.append(time.perf_counter()),
                    trace=ctx)
                while not rec.req.finished.wait(timeout=0.5):
                    pass
            except Exception as e:  # counted in `failed`, never carried past
                # without its traceback: the frames hold this object, and so the engine and
                # its arenas, which have to be gone before the reference runs
                rec.error = e.with_traceback(None)
                self.stop.wait(0.2)

    def cancel_in_flight(self, timeout=120.0):
        """Stops the clients and cancels every request not finished; returns
        whether all of them resolved."""
        self.stop.set()
        with self.lock:
            live = [r for r in self.records if r.req is not None and not r.req.finished.is_set()]
        for r in live:
            r.cut = True
            r.req.cancel()
        end = time.perf_counter() + timeout
        for r in live:
            r.req.finished.wait(max(0.0, end - time.perf_counter()))
        for t in self.threads:
            t.join(max(0.0, end - time.perf_counter()))
        return not any(t.is_alive() for t in self.threads)


def build_model(ctx):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import Ling3Config, Ling3ForCausalLM

    mc = W.model_cfg(ctx.cfg)
    keys = {f.name for f in dataclasses.fields(Ling3Config)} - {"dtype"}
    paddle.seed(0)
    model = Ling3ForCausalLM(Ling3Config(
        **{k: mc[k] for k in keys if k in mc}, dtype=mc["numerics"]["weights"]))
    log_memory(ctx, "the program's own model is built")
    named = dict(model.named_parameters())
    groups = [W.outer_leaves(mc)] + [W.layer_leaves(mc, l) for l in range(mc["num_hidden_layers"])]
    for leaves in groups:
        for name, a in W.make(ctx.seed, mc, leaves, jnp.dtype(mc["numerics"]["weights"])).items():
            p = named.pop(name)
            if tuple(p.shape) != tuple(a.shape) or p._data.dtype != a.dtype:
                raise ValueError(f"{name}: {p.shape} {p._data.dtype} != {a.shape} {a.dtype}")
            p._data = a
    if named:
        raise KeyError(f"leaves the seed did not make: {sorted(named)[:6]}")
    log_memory(ctx, "the seeded weights are loaded")
    return model


def build_engine(ctx, model):
    from paddle_tpu.inference.engine import ContinuousBatchingEngine

    e = ctx.cfg["engine"]
    return ContinuousBatchingEngine(model, slots=e["slots"], max_len=e["max_len"],
                                    prefill_buckets=e["prefill_buckets"], queue_depth=e["queue_depth"])


def warm_up(engine, cfg):
    engine.warmup()
    engine.start()
    n = engine.prefill_buckets[-1] + engine.prefill_buckets[0]
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], size=n).astype(np.int32)
    req = engine.submit(ids, max_new_tokens=2)
    req.wait(timeout=1500)
    if req.finish_reason != "length":
        raise RuntimeError(f"warm-up of a {n}-token prompt ended as {req.finish_reason}")


def served(record):
    """The tokens a request streamed: all it made, or what reached its
    callback before the kind cancelled it."""
    return np.asarray(record.req.tokens[: len(record.times)], np.int32)


def pick_sample(records, k, seed):
    """k sound requests drawn from the seed, the longest (prompt and streamed
    tokens) among them."""
    done = [r for r in records if not r.failed() and len(r.times) > 1]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.times))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 2])
    picks = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in picks]


def reference_logits(cfg, seed, sample, log, linear=None):
    from .. import reference_ling3 as R

    seqs = [np.concatenate([r.prompt, served(r)]) for r in sample]
    kw = {} if linear is None else {"linear": linear}
    return R.served_logit_gaps(cfg, seed, seqs, [len(r.prompt) for r in sample],
                               pad_to=cfg["engine"]["max_len"], log=log, **kw)


def served_gap(cfg, seed, sample, log):
    out = reference_logits(cfg, seed, sample, log)
    return describe(log, "served tokens", [best - got for best, got, _, _ in out])


def control_gap(cfg, seed, sample, log):
    """The control's reading: at each position of the same prompts and served
    tokens, the gap of the token the reference in float8 puts first.  The
    program's own reading is logged beside it."""
    from .. import reference

    low = [a for _, _, a, _ in reference_logits(cfg, seed, sample, None, reference.fp8_linear)]
    sound = reference_logits(cfg, seed, sample, log)
    describe(log, "served tokens (not compared in a control run)", [b - g for b, g, _, _ in sound])
    return describe(log, "the float8 control's tokens", [
        best - np.asarray(lg)[np.arange(len(first)), first]
        for (best, _, _, lg), first in zip(sound, low)])


def traced_part(ctx, clients, t0):
    """Sleeps to 2 s into the window, then until a request is submitted (to
    10 s into the window at most), and traces `trace_seconds` from there."""
    p = ctx.params
    lo = t0 + min(2.0, ctx.seconds / 4)
    latest = t0 + min(10.0, ctx.seconds / 2)
    time.sleep(max(0.0, lo - time.perf_counter()))
    while time.perf_counter() < latest:
        with clients.lock:
            if any(r.submit_t is not None and r.submit_t >= lo for r in clients.records[-len(clients.threads):]):
                break
        time.sleep(0.005)
    ctx.log(f"the traced seconds start {time.perf_counter() - t0:.2f}s into the window")
    with traced_window(ctx):
        time.sleep(min(p["trace_seconds"], ctx.seconds / 2))


def run(ctx):
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.models import Ling3ForCausalLM  # noqa: F401  a program without it stops here, at once

    p, cfg = ctx.params, ctx.cfg
    profiler.reset_flash_pallas()
    profiler.reset_flash_fallbacks()
    model = build_model(ctx)
    engine = build_engine(ctx, model)
    log_memory(ctx, f"the engine is built, cache bytes {profiler.arena_summary()}")
    t = time.perf_counter()
    warm_up(engine, cfg)
    warm = engine.compile_counts()
    ctx.log(f"engine warmed {warm} in {time.perf_counter() - t:.1f}s, pool_pages={engine.pool_pages}")
    if ctx.tracing:
        paddle.set_flags({"FLAGS_trace": True, "FLAGS_obs_buffer_events": 400000})
    profiler.reset_moe()
    clients = Clients(engine, request_stream(p, ctx.seed, cfg["vocab_size"]), p["clients"], ctx.tracing)
    t = time.perf_counter()
    clients.start()
    ramp_end = time.perf_counter() + 900.0
    while decoding_started(clients, p["clients"]) < p["clients"] and time.perf_counter() < ramp_end:
        time.sleep(0.05)
    resumed = profiler.linear_attn_summary()
    ctx.log(f"ramped in {time.perf_counter() - t:.1f}s: {decoding_started(clients, p['clients'])} clients have "
            f"streamed past their first token, {clients.decoding()} decoding; prefilled {resumed}")

    profiler.reset_serving()
    profiler.reset_moe()
    log_memory(ctx, "window opens")
    setup_s = time.perf_counter() - ctx.t_start
    t0 = time.perf_counter()
    if ctx.tracing:
        traced_part(ctx, clients, t0)
    time.sleep(max(0.0, t0 + ctx.seconds - time.perf_counter()))
    t1 = time.perf_counter()
    serving = profiler.serving_summary()
    ticks = profiler.metrics_snapshot()["serving"]
    moe, linear = profiler.moe_summary(), profiler.linear_attn_summary()
    log_memory(ctx, "window closed")
    if ctx.tracing:
        read_spans(ctx)
    t_cut = time.perf_counter()
    resolved = clients.cancel_in_flight()
    counts = engine.compile_counts()
    ctx.log(f"window {t1 - t0:.3f}s closed, in-flight requests cancelled (all resolved: {resolved}) in "
            f"{time.perf_counter() - t_cut:.1f}s, {len(clients.records)} requests")
    engine.stop()

    records = [r for r in clients.records if r.submit_t is not None]
    inside = [r for r in records if t0 <= r.submit_t < t1]
    gaps = [b - a for r in records if not r.failed()
            for a, b in zip(r.times, r.times[1:]) if t0 <= b < t1]
    streamed = [r for r in records if any(t0 <= t < t1 for t in r.times)]
    tokens_in = sum(1 for r in records for t in r.times if t0 <= t < t1)
    ctx.window = {"t0": t0, "t1": t1, "seconds": t1 - t0, "requests": len(inside),
                  "records": records, "tokens": tokens_in}
    ctx.counters = {
        "serving": serving, "slots": engine.slots, "moe": moe, "linear_attn": linear,
        "decode_busy_s": ticks["busy_s"], "decode_steps": ticks["ticks"],
        "arena_bytes": profiler.arena_summary(),
        "flash_pallas": profiler.flash_pallas_summary(),
        "flash_fallbacks": profiler.flash_fallback_summary(),
        "compile_counts": counts,
    }
    failed = sum(1 for r in records if r.failed())
    ended = sum(1 for r in records if not r.cut and r.times and t0 <= r.times[-1] < t1)
    ctx.log(f"{len(inside)} requests submitted in the window, {ended} ended in it, {len(streamed)} streamed "
            f"in it, {sum(r.cut for r in records)} cancelled at its close, {len(gaps)} gaps, {tokens_in} tokens, "
            f"{failed} failed of {len(records)}; {ticks['ticks']} decode steps in {ticks['busy_s']:.2f}s; "
            f"context at close {sorted(len(r.prompt) + len(r.times) for r in records if r.cut)}; "
            f"moe {moe}; linear_attn {linear}")
    peak = memory_peak_bytes()
    sample = pick_sample(streamed, p["check_requests"], ctx.seed)
    compiles = sum(abs(counts[k] - warm[k]) for k in warm)
    del engine, model, clients
    gc.collect()

    t_ref = time.perf_counter()
    worst = float("inf")  # no sound request to compare is not correct
    if sample:
        worst = (control_gap if ctx.control else served_gap)(cfg, ctx.seed, sample, ctx.log)
        ctx.log(f"reference over {len(sample)} requests of {[len(r.prompt) + len(r.times) for r in sample]} "
                f"tokens, {sum(len(r.times) for r in sample)} served, in {time.perf_counter() - t_ref:.1f}s")
    stalls = sorted(((b - a, b - t0) for r in records for a, b in zip(r.times, r.times[1:])
                     if t0 <= b < t1 and b - a > 0.1), reverse=True)
    seen = []  # one line a stall: every stream's gap over it ends at the same instant
    for dur, at in stalls:
        if all(abs(at - x) > 0.05 for _, x in seen):
            seen.append((dur, at))
    ctx.log(f"stalls over 100 ms (seconds, ending at seconds into the window): "
            f"{[(round(d, 3), round(x, 2)) for d, x in sorted(seen, key=lambda p: p[1])]}; requests submitted at "
            f"{[(round(r.submit_t - t0, 2), len(r.prompt)) for r in inside]}")
    e2e = {"setup_s": {"value": setup_s, "unit": "s"},
           "serve_tok_s": {"value": tokens_in / (t1 - t0), "unit": "tokens/s"}}
    if gaps:
        ctx.log(f"gap p50 {percentile(gaps, 50) * 1e3:.1f} p95 {percentile(gaps, 95) * 1e3:.1f} "
                f"p99 {percentile(gaps, 99) * 1e3:.1f} max {max(gaps) * 1e3:.1f} ms, "
                f"{sum(1 for g in gaps if g > 0.2)} over 200 ms")
        e2e["itl_p95_ms"] = {"value": percentile(gaps, 95) * 1e3, "unit": "ms"}
    return {
        "end_to_end": e2e,
        "attempted": len(records),
        "failed": failed,
        "checks": {
            "logit_gap_mean": {"value": worst, "limit": p["limits"]["logit_gap_mean"]},
            "failed_requests": {"value": failed, "limit": 0},
            "compiles_in_window": {"value": compiles, "limit": 0},
            "flash_fallbacks": {"value": sum(ctx.counters["flash_fallbacks"].values()), "limit": 0},
        },
        "memory_peak_bytes": peak,
    }
