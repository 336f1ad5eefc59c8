"""Kind `serve_closed_dsv32`: `serve_closed`'s closed loop of streaming clients
on one engine, for a DeepSeek-V3.2 configuration (`configs/deepseek-v3.2-*`).
It takes `Clients`, `pick_sample`, `read_spans` and `traffic.request_pool`
from there and differs in four things:

- the stream: the pool in one fixed order for every seed (`request_stream`);

- the model: `DeepseekV32ForCausalLM` created in bfloat16 at the file's share
  (`weights_deepseek_v32.model_cfg`), the seeded weights made and placed a
  layer at a time (the whole model twice does not fit the chip);
- warm-up: `engine.warmup()` (every bucket's fresh and chunk prefill, the page
  copy, the decode step), then one prompt longer than the largest bucket
  through `submit`, so the chunked path has run before the window;
- the reference: `reference_deepseek_v32.py`, which makes its own top-k
  selection.  `logit_gap_mean` is the MEAN gap by which a served token's
  logit lies below the reference's best (`serve_closed` holds the widest).

Clients ramp up untimed until each has streamed past its first token: with
prefills of seconds, all of them need never decode at the same instant.  A request
outlasts the window (a thousand tokens and more at some 30 ms), so only a few
are submitted inside it: `itl_p95_ms` is the p95 of every gap that ENDED in
the window, of all requests, and the compared sample is drawn from the
requests that streamed inside it (the longest among them).

params: clients, pool, prompt_len, answer_len, max_total, check_requests,
trace_seconds, limits{logit_gap_mean}.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from .. import traffic
from .. import weights_deepseek_v32 as W
from .common import log_memory, memory_peak_bytes, percentile, traced_window
from .serve_closed import Clients, pick_sample, read_spans


ORDER = 11  # the seed of the one order in which every run takes the pool


def request_stream(params, seed, vocab_size):
    """Endless (prompt ids, answer_len): `traffic.request_pool` in ONE fixed
    order, again and again, the token ids uniform from the seed.  A request
    here lasts about as long as the window and a prompt's prefill up to a
    sixth of it, so `traffic.request_stream`'s seeded order puts another
    6 to 9 prompts of another 60k to 110k tokens into each seed's window
    (`serve_tok_s` 481 and 541 on two seeds, PERF.md): with one order the
    seeds differ in token ids, not in work."""
    pool = traffic.request_pool(params)
    order = np.random.default_rng(ORDER).permutation(len(pool))
    rng = np.random.default_rng([int(seed), 1])
    while True:
        for i in order:
            n, m = pool[i]
            yield rng.integers(1, vocab_size, size=n, dtype=np.int64).astype(np.int32), m


def build_model(ctx):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import DeepseekV32Config, DeepseekV32ForCausalLM

    mc = W.model_cfg(ctx.cfg)
    keys = {f.name for f in dataclasses.fields(DeepseekV32Config)} - {"dtype"}
    paddle.seed(0)
    model = DeepseekV32ForCausalLM(DeepseekV32Config(
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
                                    prefill_buckets=e["prefill_buckets"])


def warm_up(engine, cfg):
    engine.warmup()
    engine.start()
    n = engine.prefill_buckets[-1] + engine.prefill_buckets[0]
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], size=n).astype(np.int32)
    req = engine.submit(ids, max_new_tokens=2)
    req.wait(timeout=1500)
    if req.finish_reason != "length":
        raise RuntimeError(f"warm-up of a {n}-token prompt ended as {req.finish_reason}")


def reference_logits(cfg, seed, sample, linear=None):
    from .. import reference_deepseek_v32 as R

    seqs = [np.concatenate([r.prompt, np.asarray(r.req.tokens, np.int32)]) for r in sample]
    kw = {} if linear is None else {"linear": linear}
    return R.served_logit_gaps(cfg, seed, seqs, [len(r.prompt) for r in sample],
                               pad_to=cfg["engine"]["max_len"], **kw)


def describe(log, who, gaps):
    """The MEAN gap over every compared position, which the limit holds, with
    the rest of the distribution logged beside it.  The widest gap, which
    `serve_closed` holds, does not tell a sound run from the control here
    (PERF.md section 2): where a routed expert or a selected key falls the
    other way on a rounding, that position's state jumps, in both."""
    g = np.concatenate(gaps)
    starts = np.cumsum([0] + [len(x) for x in gaps])
    which = np.searchsorted(starts, np.argsort(-g)[:5], "right") - 1
    where = [(int(r), int(i - starts[r])) for r, i in zip(which, np.argsort(-g)[:5])]
    g = np.sort(g)
    log(f"{who}: {len(g)} positions, the reference's own first at {np.mean(g == 0):.4f} of them, "
        f"gap mean {g.mean():.4f} p50 {g[len(g) // 2]:.4f} p99 {g[int(0.99 * (len(g) - 1))]:.4f} "
        f"max {g[-1]:.4f}, {int((g > 0.5).sum())} over 0.5; widest at (request, served token) {where}")
    return float(g.mean())


def served_gap(cfg, seed, sample, log):
    out = reference_logits(cfg, seed, sample)
    return describe(log, "served tokens", [best - got for best, got, _, _ in out])


def control_gap(cfg, seed, sample, log):
    """The control's reading: at each position of the same prompts and served
    tokens, the gap of the token the reference in float8 puts first.  The
    program's own reading is logged beside it."""
    from .. import reference

    low = [a for _, _, a, _ in reference_logits(cfg, seed, sample, reference.fp8_linear)]
    sound = reference_logits(cfg, seed, sample)
    describe(log, "served tokens (not compared in a control run)", [b - g for b, g, _, _ in sound])
    return describe(log, "the float8 control's tokens", [
        best - np.asarray(lg)[np.arange(len(first)), first]
        for (best, _, _, lg), first in zip(sound, low)])


def selection_agreement(cfg, seed, record, log, rows=64):
    """Logs the share of selected positions on which program and reference
    agree, where both select from the same state: the first layer, for the
    last `rows` served tokens of one request.  The program's side is its own
    `indexer_selection` on the seed's weights in the served dtype."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import DeepseekV32Config
    from paddle_tpu.models import deepseek_v32 as program

    from .. import reference_deepseek_v32 as R

    mc = W.model_cfg(cfg)
    seq = np.concatenate([record.prompt, np.asarray(record.req.tokens, np.int32)])[:-1]
    at = np.arange(len(seq) - min(rows, record.n), len(seq), dtype=np.int32)
    ids = np.zeros(cfg["engine"]["max_len"], np.int32)  # one shape for every run: nothing compiles twice
    ids[: len(seq)] = seq
    want = np.asarray(R.first_layer_selection(cfg, seed, ids, at))
    dtype = jnp.dtype(mc["numerics"]["weights"])
    keys = {f.name for f in dataclasses.fields(DeepseekV32Config)} - {"dtype"}
    config = DeepseekV32Config(**{k: mc[k] for k in keys if k in mc}, dtype=dtype.name)
    pre = "model.layers.0."
    made = W.make(seed, mc, W.layer_leaves(mc, 0) + W.outer_leaves(mc)[:1], dtype)
    w = {n[len(pre + "self_attn."):]: a for n, a in made.items() if n.startswith(pre + "self_attn.")}
    w = {(n if "k_norm" in n else n.removesuffix(".weight")): a for n, a in w.items()}
    cos, sin = (t._data[: len(ids)] for t in program._rope_tables(config))

    @jax.jit
    def select(embed, norm, w, ids, at):
        x = program._rms(embed[ids], norm, config.rms_norm_eps)
        return program.indexer_selection(config, w, x, cos, sin, at)

    got = np.asarray(select(made["model.embed_tokens.weight"], made[pre + "input_layernorm.weight"], w,
                            jnp.asarray(ids), jnp.asarray(at)))
    share = np.mean([len(np.intersect1d(g[g >= 0], r[r >= 0])) / max(1, (r >= 0).sum())
                     for g, r in zip(got, want)])
    log(f"selection: program and reference agree on {share:.4f} of the selected positions "
        f"(first layer, {len(at)} queries at context {len(seq) - len(at)}-{len(seq)}, "
        f"top {config.index_topk})")
    return float(share)


def decoding_started(clients, n):
    """How many of the first n requests (one a client) have streamed a second
    token: the engine admits every queued prompt before it decodes again, so
    a first token alone may still wait for fifteen prefills."""
    with clients.lock:
        return sum(1 for r in clients.records[:n] if len(r.times) > 1)


def run(ctx):
    import paddle_tpu as paddle
    from paddle_tpu import profiler

    p, cfg = ctx.params, ctx.cfg
    profiler.reset_flash_pallas()
    profiler.reset_flash_fallbacks()
    model = build_model(ctx)
    engine = build_engine(ctx, model)
    log_memory(ctx, f"the engine is built, arena bytes {profiler.arena_summary()}")
    warm_up(engine, cfg)
    warm = engine.compile_counts()
    ctx.log(f"engine warmed {warm}, pool_pages={engine.pool_pages}")
    if ctx.tracing:
        paddle.set_flags({"FLAGS_trace": True, "FLAGS_obs_buffer_events": 400000})
    clients = Clients(engine, request_stream(p, ctx.seed, cfg["vocab_size"]),
                      p["clients"], ctx.tracing)
    clients.start()
    ramp_end = time.perf_counter() + 300.0
    while decoding_started(clients, p["clients"]) < p["clients"] and time.perf_counter() < ramp_end:
        time.sleep(0.05)
    ctx.log(f"ramped: {decoding_started(clients, p['clients'])} clients have streamed past "
            f"their first token, {clients.decoding()} decoding")

    profiler.reset_serving()
    profiler.reset_moe()
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
    ticks = profiler.metrics_snapshot()["serving"]
    moe, sparse = profiler.moe_summary(), profiler.sparse_attn_summary()
    log_memory(ctx, "window closed")
    if ctx.tracing:
        read_spans(ctx)
    t_drain = time.perf_counter()
    drained = clients.drain(timeout=240.0)
    counts = engine.compile_counts()
    ctx.log(f"window {t1 - t0:.3f}s closed, drained={drained} in "
            f"{time.perf_counter() - t_drain:.1f}s, {len(clients.records)} requests")
    engine.stop()

    records = [r for r in clients.records if r.submit_t is not None]
    inside = [r for r in records if t0 <= r.submit_t < t1]
    ttft = [r.times[0] - r.submit_t for r in inside if not r.failed()]
    # a request here outlasts the window, so few are SUBMITTED inside it: the
    # gaps are those that ended inside the window, of every request, and the
    # sample is drawn from the requests that streamed inside it
    gaps = [b - a for r in records if not r.failed()
            for a, b in zip(r.times, r.times[1:]) if t0 <= b < t1]
    streamed = [r for r in records if any(t0 <= t < t1 for t in r.times)]
    tokens_in = sum(1 for r in records for t in r.times if t0 <= t < t1)
    ctx.window = {"t0": t0, "t1": t1, "seconds": t1 - t0, "requests": len(inside),
                  "records": records, "tokens": tokens_in}
    ctx.counters = {
        "serving": serving, "slots": engine.slots, "moe": moe, "sparse_attn": sparse,
        "decode_busy_s": ticks["busy_s"], "decode_steps": ticks["ticks"],
        "arena_bytes": profiler.arena_summary(),
        "flash_fallbacks": profiler.flash_fallback_summary(),
        "compile_counts": counts,
    }
    failed = sum(1 for r in records if r.failed())
    ctx.log(f"{len(inside)} requests submitted in the window, {len(streamed)} streamed in it, "
            f"{len(gaps)} gaps, {tokens_in} tokens, {failed} failed of {len(records)}; "
            f"{ticks['ticks']} decode steps in {ticks['busy_s']:.2f}s; moe {moe}; sparse {sparse}")
    peak = memory_peak_bytes()
    sample = pick_sample(streamed, p["check_requests"], ctx.seed)
    compiles = sum(abs(counts[k] - warm[k]) for k in warm)
    del engine, model, clients
    gc.collect()

    t_ref = time.perf_counter()
    worst = float("inf")  # no finished request to compare is not correct
    if sample:
        worst = (control_gap if ctx.control else served_gap)(cfg, ctx.seed, sample, ctx.log)
        selection_agreement(cfg, ctx.seed, sample[-1], ctx.log)
        ctx.log(f"reference over {len(sample)} requests of {[len(r.prompt) + r.n for r in sample]} "
                f"tokens, {sum(r.n for r in sample)} served, in {time.perf_counter() - t_ref:.1f}s")
    e2e = {"setup_s": {"value": setup_s, "unit": "s"},
           "serve_tok_s": {"value": tokens_in / (t1 - t0), "unit": "tokens/s"}}
    if gaps:
        ctx.log(f"ttft {[round(t, 2) for t in sorted(ttft)]} s; gap p50 "
                f"{percentile(gaps, 50) * 1e3:.1f} p95 {percentile(gaps, 95) * 1e3:.1f} "
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
