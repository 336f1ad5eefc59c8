"""Kind `serve_closed_kimi`: `serve_closed_ling3`'s closed loop of streaming
clients on one engine (its stream with the resumed start, its clients that
wait and are cancelled at window close, its warm-up, its traced seconds that
start at an admission, its sample), for a Kimi-Linear configuration
(`configs/kimi-linear-*`): long-horizon reasoning, a task of a few pages in
and 8k-56k tokens out, so a stream reaches 30k-64k tokens.  It differs in
this:

- the model: `KimiLinearForCausalLM` created in bfloat16 at the file's share,
  the seeded weights (`weights_kimi_linear.py`) made and placed a layer at a
  time;
- the reference: `reference_kimi_linear.py`; `logit_gap_mean` as the other
  long-context cells hold it, over 3 requests that streamed in the window,
  the longest among them, resumed prompt plus served tokens;
- the counters: `profiler.latent_walk_summary()` (the latent rows in reach,
  counted inside the decode steps) beside `moe_summary()` and
  `linear_attn_summary()`, over the window and, for the walk's roofline, over
  the traced seconds alone.

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

from .. import weights_kimi_linear as W
from . import serve_closed_ling3 as base
from .common import log_memory, memory_peak_bytes, percentile, traced_window
from .serve_closed import read_spans
from .serve_closed_dsv32 import decoding_started, describe


def build_model(ctx):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import KimiLinearConfig, KimiLinearForCausalLM

    mc = W.model_cfg(ctx.cfg)
    keys = {f.name for f in dataclasses.fields(KimiLinearConfig)} - {"dtype"}
    paddle.seed(0)
    model = KimiLinearForCausalLM(KimiLinearConfig(
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


def reference_logits(cfg, seed, sample, log, linear=None, pick=None):
    from .. import reference_kimi_linear as R

    seqs = [np.concatenate([r.prompt, base.served(r)]) for r in sample]
    kw = {} if linear is None else {"linear": linear}
    return R.served_logit_gaps(cfg, seed, seqs, [len(r.prompt) for r in sample],
                               pad_to=cfg["engine"]["max_len"], log=log, pick=pick, **kw)


def served_gap(cfg, seed, sample, log):
    out = reference_logits(cfg, seed, sample, log)
    return describe(log, "served tokens", [best - got for best, got, _, _ in out])


def control_gap(cfg, seed, sample, log):
    """The control's reading: at each position of the same prompts and served
    tokens, the gap of the token the reference in float8 puts first.  The
    program's own reading is logged beside it."""
    from .. import reference

    low = [first for _, _, first, _ in reference_logits(cfg, seed, sample, None, reference.fp8_linear)]
    sound = reference_logits(cfg, seed, sample, log, pick=low)
    describe(log, "served tokens (not compared in a control run)", [b - g for b, g, _, _ in sound])
    return describe(log, "the float8 control's tokens", [best - at for best, _, _, at in sound])


def rows_counted(profiler):
    """The decode steps counted so far and the latent rows in reach summed
    over them (`latent_walk_summary()`; zeros from a program without it)."""
    d = getattr(profiler, "latent_walk_summary", dict)()
    return {k: d.get(k, 0) for k in ("steps", "rows_in_reach")}


def traced_part(ctx, clients, t0, profiler):
    """As `serve_closed_ling3.traced_part` (sleeps to 2 s into the window, then
    until a request is submitted, to 10 s at most, and traces `trace_seconds`
    from there); returns the decode steps and the latent rows in reach the
    program counted inside the traced seconds, for the walk's roofline."""
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
        before = rows_counted(profiler)
        time.sleep(min(p["trace_seconds"], ctx.seconds / 2))
        after = rows_counted(profiler)
    return {k: after[k] - before[k] for k in after}


def run(ctx):
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.models import KimiLinearForCausalLM  # noqa: F401  a program without it stops here, at once

    p, cfg = ctx.params, ctx.cfg
    profiler.reset_flash_pallas()
    profiler.reset_flash_fallbacks()
    model = build_model(ctx)
    engine = base.build_engine(ctx, model)
    log_memory(ctx, f"the engine is built, cache bytes {profiler.arena_summary()}")
    t = time.perf_counter()
    base.warm_up(engine, cfg)
    warm = engine.compile_counts()
    ctx.log(f"engine warmed {warm} in {time.perf_counter() - t:.1f}s, pool_pages={engine.pool_pages}")
    if ctx.tracing:
        paddle.set_flags({"FLAGS_trace": True, "FLAGS_obs_buffer_events": 400000})
    profiler.reset_moe()
    clients = base.Clients(engine, base.request_stream(p, ctx.seed, cfg["vocab_size"]), p["clients"],
                           ctx.tracing)
    t = time.perf_counter()
    clients.start()
    ramp_end = time.perf_counter() + 900.0
    while decoding_started(clients, p["clients"]) < p["clients"] and time.perf_counter() < ramp_end:
        time.sleep(0.05)
    ctx.log(f"ramped in {time.perf_counter() - t:.1f}s: {decoding_started(clients, p['clients'])} clients have "
            f"streamed past their first token, {clients.decoding()} decoding; "
            f"prefilled {profiler.linear_attn_summary()}")

    profiler.reset_serving()
    profiler.reset_moe()
    log_memory(ctx, "window opens")
    setup_s = time.perf_counter() - ctx.t_start
    t0 = time.perf_counter()
    traced = traced_part(ctx, clients, t0, profiler) if ctx.tracing else None
    time.sleep(max(0.0, t0 + ctx.seconds - time.perf_counter()))
    t1 = time.perf_counter()
    serving = profiler.serving_summary()
    ticks = profiler.metrics_snapshot()["serving"]
    moe, linear, walk = profiler.moe_summary(), profiler.linear_attn_summary(), profiler.latent_walk_summary()
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
        "serving": serving, "slots": engine.slots, "moe": moe, "linear_attn": linear, "latent_walk": walk,
        "decode_busy_s": ticks["busy_s"], "decode_steps": ticks["ticks"],
        "arena_bytes": profiler.arena_summary(),
        "flash_pallas": profiler.flash_pallas_summary(),
        "flash_fallbacks": profiler.flash_fallback_summary(),
        "compile_counts": counts,
    }
    if traced is not None:
        ctx.counters["traced_decode"] = traced
    failed = sum(1 for r in records if r.failed())
    ended = sum(1 for r in records if not r.cut and r.times and t0 <= r.times[-1] < t1)
    ctx.log(f"{len(inside)} requests submitted in the window, {ended} ended in it, {len(streamed)} streamed "
            f"in it, {sum(r.cut for r in records)} cancelled at its close, {len(gaps)} gaps, {tokens_in} tokens, "
            f"{failed} failed of {len(records)}; {ticks['ticks']} decode steps in {ticks['busy_s']:.2f}s; "
            f"context at close {sorted(len(r.prompt) + len(r.times) for r in records if r.cut)}; "
            f"moe {moe}; linear_attn {linear}; latent_walk {walk}; traced {traced}")
    peak = memory_peak_bytes()
    sample = base.pick_sample(streamed, p["check_requests"], ctx.seed)
    compiles = sum(abs(counts[k] - warm[k]) for k in warm)
    del engine, model, clients
    gc.collect()

    t_ref = time.perf_counter()
    worst = float("inf")  # no sound request to compare is not correct
    if sample:
        worst = (control_gap if ctx.control else served_gap)(cfg, ctx.seed, sample, ctx.log)
        ctx.log(f"reference over {len(sample)} requests of {[len(r.prompt) + len(r.times) for r in sample]} "
                f"tokens, {sum(len(r.times) for r in sample)} served, in {time.perf_counter() - t_ref:.1f}s")
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
