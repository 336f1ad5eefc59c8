"""Kind `serve_closed`: a fixed pool of streaming clients on one engine.

Each client, in a loop: take the next request of the seed's stream,
`engine.submit(ids, max_new_tokens=n, on_token=cb)`, wait for it, go again.
The callback notes the clock and nothing else; every time is the host's.
Clients ramp up untimed until all are decoding, then the window opens.  At
its end they stop submitting and the requests in flight are drained,
untimed, so every request submitted in the window has its numbers.

When the window has closed and the engine is freed, the plain reference runs
once over a seeded sample of the finished requests (the longest among them),
prompt and served tokens together, and `logit_gap` is the widest gap by
which a served token's logit lies below the reference's best.  Every
request is greedy (the engine's default), which that comparison needs.

It reports `ttft_p95_ms`, `itl_p95_ms` and `serve_tok_s`; `BENCHMARK.json`
says which of them a cell is held to.

params: clients, pool, prompt_len, answer_len, max_total, check_requests,
trace_seconds, limits{logit_gap}.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from .. import traffic
from .common import build_model, log_memory, memory_peak_bytes, percentile, traced_window


class Record:
    """One request as its client saw it."""
    __slots__ = ("prompt", "n", "submit_t", "times", "req", "error")

    def __init__(self, prompt, n):
        self.prompt, self.n = prompt, n
        self.submit_t, self.times, self.req, self.error = None, [], None, None

    def failed(self):
        return (self.error is not None or self.req is None or self.req.error is not None
                or self.req.finish_reason != "length" or len(self.times) != self.n)


class Clients:
    """`n` closed-loop clients over one shared, seeded stream of requests."""

    def __init__(self, engine, stream, n, traced):
        self.engine, self.stream, self.traced = engine, stream, traced
        self.lock = threading.Lock()
        self.records = []
        self.stop = threading.Event()
        self.threads = [threading.Thread(target=self._loop, name=f"client-{i}", daemon=True)
                        for i in range(n)]

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
                rec.req.finished.wait(timeout=300)
            except Exception as e:  # counted in `failed`, never carried past
                rec.error = e

    def start(self):
        for t in self.threads:
            t.start()

    def decoding(self):
        """Clients whose current request has its first token."""
        with self.lock:
            recs = list(self.records)
        return sum(1 for r in recs if r.times and (r.req is None or not r.req.finished.is_set()))

    def drain(self, timeout=120.0):
        self.stop.set()
        end = time.perf_counter() + timeout
        for t in self.threads:
            t.join(max(0.0, end - time.perf_counter()))
        return not any(t.is_alive() for t in self.threads)


def build_engine(ctx, model):
    from paddle_tpu.inference.engine import ContinuousBatchingEngine

    e = ctx.cfg["engine"]
    return ContinuousBatchingEngine(model, slots=e["slots"], max_len=e["max_len"],
                                    prefill_buckets=e["prefill_buckets"])


def warm_up(engine, cfg):
    """One request of exactly each prefill bucket's length through `submit`,
    two tokens each: compiles (or loads) every prefill program and the decode
    step, the shapes this traffic uses and no others.  `engine.warmup()`
    would also build the chunk-prefill programs, which unshared prompts never
    run and which do not compile at these widths (PERF.md, Open questions)."""
    rng = np.random.default_rng(0)
    for b in engine.prefill_buckets:
        ids = rng.integers(1, cfg["vocab_size"], size=b).astype(np.int32)
        req = engine.submit(ids, max_new_tokens=2)
        req.wait(timeout=1500)
        if req.finish_reason != "length":
            raise RuntimeError(f"warm-up of bucket {b} ended as {req.finish_reason}")


def pick_sample(records, k, seed):
    """k finished requests drawn from the seed, the longest among them."""
    done = [r for r in records if not r.failed()]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + r.n)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 2])
    picks = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in picks]


def reference_logits(cfg, seed, sample, linear=None):
    """The reference over each sampled request, prompt and served tokens
    together: per request (best logit, served token's logit, argmax, logits)
    at every position that produced a served token."""
    from .. import reference

    seqs = [np.concatenate([r.prompt, np.asarray(r.req.tokens, np.int32)]) for r in sample]
    starts = [len(r.prompt) for r in sample]
    kw = {} if linear is None else {"linear": linear}
    return reference.served_logit_gaps(cfg, seed, seqs, starts,
                                       pad_to=cfg["engine"]["max_len"], **kw)


def served_gap(cfg, seed, sample):
    """The widest gap by which a served token's logit lies below the
    reference's best."""
    out = reference_logits(cfg, seed, sample)
    return max(float(np.max(best - got)) for best, got, _, _ in out)


def control_gap(cfg, seed, sample):
    """The control's reading of the same number: at each position of the same
    prompts and served tokens, the gap of the token that the reference in
    float8 puts first.  It need not decode."""
    from .. import reference

    low = [a for _, _, a, _ in reference_logits(cfg, seed, sample, reference.fp8_linear)]
    worst = 0.0
    for (best, _, _, lg), first in zip(reference_logits(cfg, seed, sample), low):
        got = np.asarray(lg)[np.arange(len(first)), first]
        worst = max(worst, float(np.max(best - got)))
    return worst


def read_spans(ctx):
    """The program's spans that intersect the traced window, in the order
    they were recorded: a span outside it overlaps no idle gap.  Read when
    the timed window closes and not when the traced one does: `engine.decode`
    spans are recorded when a decode epoch closes, and the ring behind them
    drops its oldest first, which the drain's spans would push out."""
    from paddle_tpu.obs import trace as obs

    stats = obs.stats()
    # spans carry wall-clock starts; the harness's clock is perf_counter
    wall_to_perf = time.perf_counter() - time.time()
    lo, hi = ctx.trace_window
    for s in obs.spans():
        a = s["ts"] + wall_to_perf
        if a < hi and a + s["dur_s"] > lo:
            ctx.spans.append((s["name"], a, a + s["dur_s"]))
    ctx.log(f"{len(ctx.spans)} spans meet the traced window, of {stats['spans_recorded']} "
            f"recorded and {stats['spans_dropped']} dropped")
    if stats["spans_dropped"]:
        ctx.log("SPANS WERE DROPPED: the ring lost its oldest, the traced window's first; "
                "the idle gaps' labels are INCOMPLETE")


def run(ctx):
    import paddle_tpu as paddle
    from paddle_tpu import profiler

    p, cfg = ctx.params, ctx.cfg
    profiler.reset_flash_pallas()
    profiler.reset_flash_fallbacks()
    model = build_model(ctx)
    engine = build_engine(ctx, model)
    engine.start()
    warm_up(engine, cfg)
    warm = engine.compile_counts()
    ctx.log(f"engine warmed {warm}, pool_pages={engine.pool_pages}")
    if ctx.tracing:
        paddle.set_flags({"FLAGS_trace": True, "FLAGS_obs_buffer_events": 400000})
    clients = Clients(engine, traffic.request_stream(p, ctx.seed, cfg["vocab_size"]),
                      p["clients"], ctx.tracing)
    clients.start()
    ramp_end = time.perf_counter() + 120.0
    while clients.decoding() < p["clients"] and time.perf_counter() < ramp_end:
        time.sleep(0.02)
    ctx.log(f"ramped: {clients.decoding()} clients decoding")

    profiler.reset_serving()
    log_memory(ctx, "window opens")
    setup_s = time.perf_counter() - ctx.t_start
    t0 = time.perf_counter()
    if ctx.tracing:
        time.sleep(min(2.0, ctx.seconds / 4))
        with traced_window(ctx):
            time.sleep(min(p["trace_seconds"], ctx.seconds / 2))
    time.sleep(max(0.0, t0 + ctx.seconds - time.perf_counter()))
    t1 = time.perf_counter()
    serving = profiler.serving_summary()
    log_memory(ctx, "window closed")
    if ctx.tracing:
        read_spans(ctx)
    t_drain = time.perf_counter()
    drained = clients.drain()
    counts = engine.compile_counts()
    ctx.log(f"window {t1 - t0:.3f}s closed, drained={drained} in "
            f"{time.perf_counter() - t_drain:.1f}s, {len(clients.records)} requests")
    engine.stop()

    records = [r for r in clients.records if r.submit_t is not None]
    inside = [r for r in records if t0 <= r.submit_t < t1]
    ok = [r for r in inside if not r.failed()]
    ttft = [r.times[0] - r.submit_t for r in ok]
    gaps = [b - a for r in ok for a, b in zip(r.times, r.times[1:])]
    tokens_in = sum(1 for r in records for t in r.times if t0 <= t < t1)
    ctx.window = {"t0": t0, "t1": t1, "seconds": t1 - t0, "requests": len(inside),
                  "records": records, "tokens": tokens_in}
    ctx.counters = {
        "serving": serving, "slots": engine.slots,
        "flash_pallas": profiler.flash_pallas_summary(),
        "flash_fallbacks": profiler.flash_fallback_summary(),
        "compile_counts": counts,
    }
    failed = sum(1 for r in records if r.failed())
    ctx.log(f"{len(inside)} requests submitted in the window, {len(ttft)} with numbers, "
            f"{len(gaps)} gaps, {tokens_in} tokens, {failed} failed of {len(records)}")
    peak = memory_peak_bytes()
    sample = pick_sample(inside, p["check_requests"], ctx.seed)
    compiles = sum(abs(counts[k] - warm[k]) for k in warm)
    del engine, model, clients
    gc.collect()

    t_ref = time.perf_counter()
    worst = float("inf")  # no finished request to compare is not correct
    if sample:
        worst = (control_gap if ctx.control else served_gap)(cfg, ctx.seed, sample)
        ctx.log(f"reference over {len(sample)} requests, {sum(r.n for r in sample)} served "
                f"tokens, in {time.perf_counter() - t_ref:.1f}s")
    e2e = {"setup_s": {"value": setup_s, "unit": "s"},
           "serve_tok_s": {"value": tokens_in / (t1 - t0), "unit": "tokens/s"}}
    if ttft and gaps:
        ctx.log(f"ttft p50 {percentile(ttft, 50) * 1e3:.1f} p95 {percentile(ttft, 95) * 1e3:.1f} ms, "
                f"gap p50 {percentile(gaps, 50) * 1e3:.1f} p95 {percentile(gaps, 95) * 1e3:.1f} ms")
        e2e["ttft_p95_ms"] = {"value": percentile(ttft, 95) * 1e3, "unit": "ms"}
        e2e["itl_p95_ms"] = {"value": percentile(gaps, 95) * 1e3, "unit": "ms"}
    return {
        "end_to_end": e2e,
        "attempted": len(records),
        "failed": failed,
        "checks": {
            "logit_gap": {"value": worst, "limit": p["limits"]["logit_gap"]},
            "failed_requests": {"value": failed, "limit": 0},
            "compiles_in_window": {"value": compiles, "limit": 0},
            "flash_fallbacks": {"value": sum(ctx.counters["flash_fallbacks"].values()), "limit": 0},
        },
        "memory_peak_bytes": peak,
    }
