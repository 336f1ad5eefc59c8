"""Benchmark matrix over BASELINE.md's config table, headline = Llama.

Prints ONE JSON line.  Top-level fields are the driver contract
({"metric", "value", "unit", "vs_baseline"}, measuring config 4's Llama
proxy); the "configs" field carries the rest of the matrix (ResNet50 AMP-O2
= config 2, BERT-base = config 3, a deeper remat Llama, and loss-parity
gates vs the CPU oracle for configs 1/4).  `python bench.py --all` prints
one JSON line per config instead, for humans.

Baseline semantics (BASELINE.md): the reference publishes no absolute
numbers; the contract is ">= per-chip A100 throughput".  A well-tuned A100
runs Llama-2-7B at ~3000 tokens/s/GPU (bf16) == 3000 * 6 * 7e9 FLOP/tok
~= 1.26e14 FLOP/s ~= 40% MFU of A100's 312 TFLOPs bf16.  Transformer
benches therefore report vs_baseline = achieved_MFU / 0.40 against this
chip's bf16 peak ("same silicon efficiency as the A100 parity bar");
ResNet50 reports images/s against the commonly cited ~2500 img/s A100 AMP
figure.  The Llama entry is a PROXY: 640M params (6 wide layers, h=2560)
sized to one v5e chip's HBM, not a 7B TP=8 run — labeled in the JSON.
"""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import time

import numpy as np

A100_BF16_PEAK = 312e12
A100_MFU_BAR = 0.40
A100_RESNET50_IMG_S = 2500.0


# bf16 peak FLOP/s of one chip, keyed by jax's device_kind (Google Cloud
# TPU documentation: v5e 197, v4 275, v5p 459 TFLOP/s)
TPU_BF16_PEAK = {
    "TPU v5 lite": 197e12,
    "TPU v4": 275e12,
    "TPU v5": 459e12,
}


def _chip_peak_flops():
    """bf16 peak of the chip this process runs on; None on a CPU, where no
    utilization is reported.  A TPU kind without an entry is an error."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        return None
    if d.device_kind not in TPU_BF16_PEAK:
        raise KeyError(
            f"no bf16 peak on record for device kind {d.device_kind!r}; add "
            "it to TPU_BF16_PEAK with its source"
        )
    return TPU_BF16_PEAK[d.device_kind]


def _mfu_fields(n_params, tok_s):
    """{"mfu", "vs_baseline"} from 6*N FLOP per token over the chip's
    peak; both None on a CPU — a CPU run yields no device metric."""
    peak = _chip_peak_flops()
    if peak is None:
        return {"mfu": None, "vs_baseline": None}
    mfu = 6.0 * n_params * tok_s / peak
    return {"mfu": round(mfu, 4), "vs_baseline": round(mfu / A100_MFU_BAR, 3)}


def _on_tpu():
    import jax

    return jax.default_backend() == "tpu"


def throughput_gate(value, minimum, enforced, key="min_steps_per_sec",
                    unexpected_recompiles=None):
    """Per-config regression gate: {key: bar, enforced, ok}.  `ok` is True
    when the bar is cleared OR the gate is unenforced (CPU CI throughput is
    noise; gates bind on the TPU chip).  main() exits nonzero when any
    enforced gate fails — after printing the full matrix, so the numbers
    behind the failure are always in the output.  Kept as a plain function
    so the gate logic itself is unit-testable without a TPU.

    `unexpected_recompiles` (the runtime sanitizer's steady-state trace/
    compile counter) is a CORRECTNESS gate, not a throughput gate: any
    nonzero count fails the leg even where the throughput bar is
    unenforced — a recompile in steady state is deterministic, CPU noise
    cannot excuse it."""
    gate = {key: float(minimum), "enforced": bool(enforced)}
    gate["ok"] = bool(value >= gate[key]) or not gate["enforced"]
    if unexpected_recompiles is not None:
        gate["unexpected_recompiles"] = int(unexpected_recompiles)
        gate["enforced"] = bool(gate["enforced"] or unexpected_recompiles > 0)
        gate["ok"] = gate["ok"] and int(unexpected_recompiles) == 0
    return gate


def _time_steps(step_fn, ids, steps):
    """Returns (measurement window seconds, time_to_first_step seconds).
    The first-step time includes trace+compile — the cold-start cost the
    compile cache (PADDLE_COMPILE_CACHE_DIR) is meant to kill."""
    t_start = time.perf_counter()
    loss = step_fn(*ids)
    loss.numpy()
    t_first = time.perf_counter() - t_start
    step_fn(*ids).numpy()  # second call: cached-executable path
    t0 = time.perf_counter()
    last = None
    for _ in range(steps):
        last = step_fn(*ids)
    last.numpy()
    return time.perf_counter() - t0, t_first


def _cache_probe():
    """Compile-cache counters snapshot; subtract two probes for a per-config
    delta (disk hits vs fresh XLA compiles, AOT snapshot hits/misses)."""
    from paddle_tpu import jit

    info = jit.cache_info()
    p, a = info["persistent"], info["aot"]
    return {
        "disk_hits": p["disk_hits"],
        "fresh_compiles": p["misses"],
        "aot_hits": a["hits"],
        "aot_misses": a["misses"],
    }


def _cache_delta(before):
    after = _cache_probe()
    return {k: after[k] - before[k] for k in before}


@contextlib.contextmanager
def _sanitized_serving():
    """Run a serving leg under FLAGS_debug_sanitize: the engine's steady-
    state step zone counts every fresh trace / eager compile / host sync,
    and the leg's gate fails on a nonzero unexpected count (the runtime
    twin of the compile_cache delta printed next to it)."""
    from paddle_tpu.analysis import sanitizer
    from paddle_tpu.framework import core as fcore

    fcore.set_flags({"FLAGS_debug_sanitize": True})
    sanitizer.reset()
    try:
        yield sanitizer
    finally:
        fcore.set_flags({"FLAGS_debug_sanitize": False})


def _sanitizer_summary(sanitizer):
    c = sanitizer.counters()
    return {
        "unexpected_recompiles": c["unexpected_traces"] + c["unexpected_eager"],
        "unexpected_syncs": c["unexpected_syncs"],
        "steady_traces": c["traces"],
        "allowed_events": c["allowed_events"],
    }


# ---------------------------------------------------------------------------
# config 4 proxy: Llama train step (the headline)
# ---------------------------------------------------------------------------


def bench_llama(deep=False):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = _on_tpu()
    if on_tpu and deep:
        # deeper model under real memory pressure: ~750M params, 12 layers,
        # activation recompute on — closer to a 7B's residency profile
        # (16 layers crashes the remote compile helper with the Pallas
        # backward kernels inside remat; 12 compiles)
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=12,
            num_attention_heads=16,
            num_key_value_heads=16,
            max_position_embeddings=2048,
            use_recompute=True,
        )
        batch, seqlen, steps = 8, 2048, 10
    elif on_tpu:
        # measured round-2 sweet spot: wide-but-shallow tiles the MXU like a
        # 7B's matmuls while fitting single-chip HBM with Adam state
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2560,
            intermediate_size=6912,
            num_hidden_layers=6,
            num_attention_heads=20,
            num_key_value_heads=20,
            max_position_embeddings=2048,
        )
        batch, seqlen, steps = 8, 2048, 20
    else:
        cfg = LlamaConfig.tiny()
        batch, seqlen, steps = 4, 128, 5

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    if on_tpu:
        model, opt = paddle.amp.decorate(model, opt, level="O2", dtype="bfloat16")
    n_params = sum(p.size for p in model.parameters())

    @paddle.jit.to_static
    def train_step(ids):
        loss, _ = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seqlen)).astype(np.int32))
    cc0 = _cache_probe()
    dt, t_first = _time_steps(train_step, (ids,), steps)

    tok_s = batch * seqlen * steps / dt
    return {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        **_mfu_fields(n_params, tok_s),
        "time_to_first_step_s": round(t_first, 3),
        "compile_cache": _cache_delta(cc0),
        "params": n_params,
        "proxy": "640M wide-6-layer single-chip proxy for config 4 (Llama-7B TP=8)"
        if not deep
        else "750M 12-layer remat single-chip proxy",
    }


# ---------------------------------------------------------------------------
# config 2: ResNet50 AMP O2
# ---------------------------------------------------------------------------


def bench_resnet50():
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.vision.models import resnet50

    on_tpu = _on_tpu()
    batch, steps = (128, 120) if on_tpu else (4, 2)
    size = 224 if on_tpu else 32
    # NHWC is the TPU-native layout (channels on the minor/lane axis) —
    # paddle's data_format="NHWC" option, same numerics as NCHW (tested in
    # tests/test_models.py).  Batch 128 is the measured v5e sweet spot:
    # 2635 img/s vs 2523 at 256 and 2390 at 512 (repro within ±0.2%) —
    # smaller working set keeps conv pipelining ahead of HBM.
    fmt = "NHWC" if on_tpu else "NCHW"

    paddle.seed(0)
    model = resnet50(num_classes=1000, data_format=fmt)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9, parameters=model.parameters())
    if on_tpu:
        model, opt = paddle.amp.decorate(model, opt, level="O2", dtype="bfloat16")
    ce = nn.CrossEntropyLoss()

    @paddle.jit.to_static
    def train_step(x, y):
        loss = ce(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    shape = (batch, 3, size, size) if fmt == "NCHW" else (batch, size, size, 3)
    x = paddle.to_tensor(rng.rand(*shape).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype(np.int64))
    # median of 3 measurement windows: the shared chip shows occasional
    # multi-second stalls that would otherwise sink one whole window
    cc0 = _cache_probe()
    rates = []
    t_first = None
    for _ in range(3 if on_tpu else 1):
        dt, tf = _time_steps(train_step, (x, y), steps)
        if t_first is None:
            t_first = tf
        rates.append(batch * steps / dt)
    img_s = sorted(rates)[len(rates) // 2]
    # the raw img/s ratio conflates chip peak (v5e 197 vs A100 312 TFLOPs);
    # the peak-normalized ratio compares silicon efficiency
    peak = _chip_peak_flops()
    return {
        "metric": "resnet50_amp_o2_images_per_sec",
        "value": round(img_s, 1),
        "unit": "images/s",
        "vs_baseline": round(img_s / A100_RESNET50_IMG_S, 3),
        "vs_a100_peak_normalized": None if peak is None else round(
            img_s / (A100_RESNET50_IMG_S * peak / A100_BF16_PEAK), 3
        ),
        "time_to_first_step_s": round(t_first, 3),
        "compile_cache": _cache_delta(cc0),
        "note": "A100 AMP bar ~2500 img/s (BASELINE.md config 2)",
    }


# ---------------------------------------------------------------------------
# decode: compiled static-KV-cache generation (inference runtime, SURVEY L8)
# ---------------------------------------------------------------------------


def bench_lenet_eager():
    """Config 1 (LeNet MNIST dygraph) in TRUE eager mode — no @to_static.
    Exercises the cached per-op fwd+VJP executables (ops/dispatch.py eager
    fast path; SURVEY §7 'per-op dispatch overhead').  Measured 5.9x over
    the uncached retrace path on the TPU chip (3.4 -> 19.9 steps/s)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-3, parameters=model.parameters())
    ce = nn.CrossEntropyLoss()
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.rand(16, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (16,)).astype(np.int64))

    def step():
        loss = ce(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    cc0 = _cache_probe()
    t0 = time.perf_counter()
    step().numpy()
    t_first = time.perf_counter() - t0
    cc_delta = _cache_delta(cc0)
    for _ in range(2):
        step()
    n = 30 if _on_tpu() else 10
    t0 = time.perf_counter()
    for _ in range(n):
        last = step()
    last.numpy()
    dt = time.perf_counter() - t0
    value = round(n / dt, 1)
    # regression gate (ROADMAP watch item: 65.3 -> 42.0 steps/s r04 -> r05
    # on TPU; traced to serving-leg process state bleeding into this config
    # plus per-call module lookups in the eager dispatch salt — see
    # ops/dispatch.py and the config ordering/gc in main()).
    gate = throughput_gate(value, 55.0, _on_tpu())
    return {
        "metric": "lenet_eager_steps_per_sec",
        "value": value,
        "unit": "steps/s",
        "time_to_first_step_s": round(t_first, 3),
        "compile_cache": cc_delta,
        "gate": gate,
        "note": "dygraph (no to_static); cached per-op executables, 5.9x vs retrace",
    }


def bench_hapi_async():
    """Async step pipeline (fit()'s bounded in-flight ring + device-resident
    losses/metrics) vs the strict per-step sync fallback
    (FLAGS_max_inflight_steps=1).  Same models, same data, same numerics —
    only the host/device overlap differs, so steps/s isolates the cost of
    per-step host materialization."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, profiler

    on_tpu = _on_tpu()

    def _run(build, data, batch, inflight):
        paddle.set_flags({"FLAGS_max_inflight_steps": inflight})
        paddle.seed(0)
        model = build()
        model.fit(data, batch_size=batch, epochs=1, verbose=0, shuffle=False)  # warmup: compile
        profiler.reset_step_breakdown()
        rates = []
        for _ in range(3):  # median-of-3 windows, like the other legs
            t0 = time.perf_counter()
            model.fit(data, batch_size=batch, epochs=1, verbose=0, shuffle=False)
            rates.append((len(data) // batch) / (time.perf_counter() - t0))
        return sorted(rates)[1], profiler.step_breakdown()

    def _case(build, data, batch):
        try:
            sync_sps, _ = _run(build, data, batch, 1)
            async_sps, bd = _run(build, data, batch, 2)
        finally:
            paddle.set_flags({"FLAGS_max_inflight_steps": 2})
        return {
            "sync_steps_per_sec": round(sync_sps, 1),
            "async_steps_per_sec": round(async_sps, 1),
            "speedup": round(async_sps / sync_sps, 3),
            "host_blocked_ms_avg": round(bd.get("host_blocked_ms_avg", 0.0), 3),
            "dispatch_ms_avg": round(bd.get("dispatch_ms_avg", 0.0), 3),
            "inflight_depth_max": bd.get("inflight_depth_max", 0),
        }

    rng = np.random.RandomState(0)

    def build_lenet():
        from paddle_tpu.vision.models import LeNet

        net = LeNet()
        model = paddle.Model(net)
        model.prepare(
            paddle.optimizer.Adam(learning_rate=1e-3, parameters=net.parameters()),
            nn.CrossEntropyLoss(),
            paddle.metric.Accuracy(),
        )
        return model

    n, batch = (512, 32) if on_tpu else (64, 16)
    lenet_data = [
        (rng.rand(1, 28, 28).astype(np.float32), np.int64(rng.randint(0, 10)))
        for _ in range(n)
    ]
    lenet = _case(build_lenet, lenet_data, batch)

    from paddle_tpu.models.bert import BertConfig, BertForSequenceClassification

    if on_tpu:
        bcfg = BertConfig.bert_base(
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0
        )
        bn, bbatch, bseq = 128, 16, 128
    else:
        bcfg = BertConfig.tiny()
        bn, bbatch, bseq = 32, 4, 64

    def build_bert():
        net = BertForSequenceClassification(bcfg, num_classes=2)
        model = paddle.Model(net)
        model.prepare(
            paddle.optimizer.AdamW(learning_rate=3e-5, parameters=net.parameters()),
            nn.CrossEntropyLoss(),
        )
        return model

    bert_data = [
        (
            rng.randint(0, bcfg.vocab_size, (bseq,)).astype(np.int32),
            np.int64(rng.randint(0, 2)),
        )
        for _ in range(bn)
    ]
    bert = _case(build_bert, bert_data, bbatch)

    return {
        "metric": "hapi_async_vs_sync_speedup",
        "value": bert["speedup"],
        "unit": "x",
        "lenet": lenet,
        "bert": bert,
        "note": "Model.fit steps/s, FLAGS_max_inflight_steps 2 vs 1; "
        "identical numerics (tests/test_async_pipeline.py parity test)",
    }


def bench_llama_decode():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=12,
            num_attention_heads=16,
            num_key_value_heads=16,
            max_position_embeddings=2048,
        )
        batch, prompt, new_toks = 8, 128, 128
    else:
        cfg = LlamaConfig.tiny()
        batch, prompt, new_toks = 2, 8, 8

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, prompt)).astype(np.int32))
    iters = 3 if on_tpu else 1

    # cold-start serving latency: prompt-to-first-full-response including
    # trace+compile (or AOT snapshot load, with the cache dir set)
    cc0 = _cache_probe()
    t0 = time.perf_counter()
    model.generate(ids, max_new_tokens=new_toks).numpy()
    t_first = time.perf_counter() - t0
    cc_delta = _cache_delta(cc0)

    def run(**kw):
        model.generate(ids, max_new_tokens=new_toks, **kw).numpy()  # compile
        rates = []
        for _ in range(3 if on_tpu else 1):  # median-of-3 windows
            t0 = time.perf_counter()
            for _ in range(iters):
                model.generate(ids, max_new_tokens=new_toks, **kw).numpy()
            rates.append(batch * new_toks * iters / (time.perf_counter() - t0))
        return sorted(rates)[len(rates) // 2]

    tok_s = run()
    # sampling draws INSIDE the compiled step (round-5): top-k/top-p +
    # categorical are part of the per-token executable, so sampled decode
    # must track greedy within ~20%
    tok_s_sampled = run(temperature=0.8, top_k=50, top_p=0.95, seed=0)
    return {
        "metric": "llama_decode_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "sampled_tokens_per_sec": round(tok_s_sampled, 1),
        "sampled_vs_greedy": round(tok_s_sampled / tok_s, 3),
        "compiles": model._gen_fns["greedy"].trace_count,
        "aot_hits": model._gen_fns["greedy"].aot_hits,
        "time_to_first_step_s": round(t_first, 3),
        "compile_cache": cc_delta,
        "note": "1.3B-class model, batch 8, static-KV compiled decode step; "
        "sampling (top-k/top-p + categorical) runs inside the compiled step",
    }


def bench_llama_serving():
    """Continuous batching vs lock-step GenerationPredictor (ISSUE 5): the
    same mixed-length workload (log-uniform max_new_tokens, Poisson
    arrivals) through the slot-pooled engine and through lock-step batches
    of `slots`, where every row pays the longest request in its batch.
    tokens/s counts REQUESTED tokens only — the padding rows the lock-step
    path decodes past each row's requested length are exactly the waste
    continuous batching removes.  Acceptance gate: >= 1.5x aggregate."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=12,
            num_attention_heads=16,
            num_key_value_heads=16,
            max_position_embeddings=2048,
        )
        slots, n_req, prompt, lo, hi = 8, 32, 64, 16, 256
        mean_gap = 0.005
    else:
        # big enough that a decode step is compute- not dispatch-bound —
        # the regime the scheduler is built for (tiny() steps are ~0.3 ms,
        # which scheduler bookkeeping would distort)
        cfg = LlamaConfig.tiny(
            hidden_size=256, intermediate_size=512, num_hidden_layers=4,
            num_attention_heads=8, num_key_value_heads=8,
        )
        slots, n_req, prompt, lo, hi = 4, 16, 8, 4, 64
        mean_gap = 0.0005

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, cfg.vocab_size, (n_req, prompt)).astype(np.int32)
    # log-uniform mixed lengths: mostly short requests, a few long ones —
    # the regime where a long generation holds a lock-step batch hostage
    new_toks = np.exp(
        rng.uniform(np.log(lo), np.log(hi + 1), size=n_req)
    ).astype(np.int64).clip(lo, hi)
    total_tokens = int(new_toks.sum())

    eng = ContinuousBatchingEngine(
        model, slots=slots, max_len=prompt + hi, prefill_buckets=[prompt],
        queue_depth=n_req, seed=0,
    )
    eng.warmup()
    profiler.reset_serving()
    gaps = rng.exponential(mean_gap, size=n_req)
    with _sanitized_serving() as _san:
        eng.start()
        handles = []
        t0 = time.perf_counter()
        for i in range(n_req):
            time.sleep(gaps[i])
            handles.append(eng.submit(prompts[i], max_new_tokens=int(new_toks[i])))
        for h in handles:
            h.wait(timeout=600)
        eng_wall = time.perf_counter() - t0
        eng.stop()
    san = _sanitizer_summary(_san)
    eng_tok_s = total_tokens / eng_wall
    s = profiler.serving_summary()

    # lock-step baseline: batches of `slots` in arrival order, each batch
    # sized to its longest request.  Warm every cache length the loop will
    # use so the timed region is pure steady-state decode on both sides
    # (the engine's warmup() does the same for its two executables).
    group_maxes = sorted(
        {int(new_toks[i : i + slots].max()) for i in range(0, n_req, slots)}
    )
    for m in group_maxes:
        model.generate(paddle.to_tensor(prompts[:slots]), max_new_tokens=m).numpy()
    t0 = time.perf_counter()
    for i in range(0, n_req, slots):
        grp = slice(i, i + slots)
        model.generate(
            paddle.to_tensor(prompts[grp]),
            max_new_tokens=int(new_toks[grp].max()),
        ).numpy()
    base_wall = time.perf_counter() - t0
    base_tok_s = total_tokens / base_wall

    return {
        "metric": "llama_serving_speedup_vs_lockstep",
        "value": round(eng_tok_s / base_tok_s, 3),
        "unit": "x",
        "engine_tokens_per_sec": round(eng_tok_s, 1),
        "lockstep_tokens_per_sec": round(base_tok_s, 1),
        "ttft_p50_ms": round(s.get("ttft_p50_ms", 0.0), 2),
        "ttft_p95_ms": round(s.get("ttft_p95_ms", 0.0), 2),
        "occupancy_mean": round(s.get("occupancy_mean", 0.0), 3),
        "requests": n_req,
        "slots": slots,
        "mixed_new_tokens": [int(lo), int(hi)],
        "compiles": eng.compile_counts(),
        "sanitizer": san,
        "gate": throughput_gate(
            eng_tok_s / base_tok_s, 1.5, on_tpu, key="min_serving_speedup",
            unexpected_recompiles=san["unexpected_recompiles"],
        ),
        "note": "Poisson arrivals, log-uniform request lengths; slot-pooled "
        "continuous batching vs lock-step batches of `slots` (each row pays "
        "its batch's max length); tokens/s counts requested tokens only",
    }


def bench_paged_serving():
    """Paged KV + copy-on-write prefix sharing vs dense slots (ISSUE 7),
    under the SAME simulated KV budget: the dense engine gets `dense_slots`
    full-length KV buffers; the paged engine gets a page pool holding
    exactly that many rows but twice the slots, and must cover the extra
    concurrency out of paging (requests only occupy their lifetime span)
    plus prefix sharing (70% of requests open with one of 4 system prompts,
    whose pages are mapped copy-free on a cache hit).  Gates: >= 2x peak
    concurrent sequences vs dense, and shared-prefix TTFT p50 reduced
    >= 30% (prefill only the unshared suffix)."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=12,
            num_attention_heads=16,
            num_key_value_heads=16,
            max_position_embeddings=2048,
        )
        dense_slots, n_req, sys_len, sfx, lo, hi = 4, 48, 96, 32, 16, 128
        page_size, mean_gap = 32, 0.002
    else:
        cfg = LlamaConfig.tiny(
            hidden_size=256, intermediate_size=512, num_hidden_layers=4,
            num_attention_heads=8, num_key_value_heads=8,
        )
        # hi >> lo is the dense-waste regime: dense commits max_len rows per
        # slot for requests that mostly stop near `lo`, paged only spends
        # pages on each request's actual lifetime span
        dense_slots, n_req, sys_len, sfx, lo, hi = 2, 24, 24, 8, 4, 64
        page_size, mean_gap = 8, 0.0003

    prompt_len = sys_len + sfx
    max_len = prompt_len + hi
    budget_rows = dense_slots * max_len  # what the dense engine commits
    pool_pages = budget_rows // page_size + 1  # +1: permanent scratch page

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")

    # 70% of requests share one of 4 system prompts (>= min_prefix_match
    # tokens) followed by a unique suffix; 30% are fully unique.  Greedy
    # decoding so the two engines' outputs are comparable token-for-token.
    rng = np.random.RandomState(0)
    sys_prompts = rng.randint(0, cfg.vocab_size, (4, sys_len))
    shared = rng.rand(n_req) < 0.7
    sys_ids = rng.randint(0, 4, size=n_req)
    prompts = []
    for i in range(n_req):
        tail = rng.randint(0, cfg.vocab_size, (sfx,))
        if shared[i]:
            prompts.append(np.concatenate([sys_prompts[sys_ids[i]], tail]))
        else:
            prompts.append(rng.randint(0, cfg.vocab_size, (prompt_len,)))
    new_toks = np.exp(
        rng.uniform(np.log(lo), np.log(hi + 1), size=n_req)
    ).astype(np.int64).clip(lo, hi)
    gaps = rng.exponential(mean_gap, size=n_req)

    def _run(eng):
        eng.warmup()
        profiler.reset_serving()
        profiler.reset_paging()
        eng.start()  # called inside _sanitized_serving() by the driver below
        handles = []
        t0 = time.perf_counter()
        for i in range(n_req):
            time.sleep(gaps[i])
            handles.append(
                eng.submit(
                    prompts[i].astype(np.int32),
                    max_new_tokens=int(new_toks[i]),
                    temperature=0.0,
                )
            )
        for h in handles:
            h.wait(timeout=600)
        wall = time.perf_counter() - t0
        sv, pg = profiler.serving_summary(), profiler.paging_summary()
        # unloaded sequential TTFT probes on the still-running engine:
        # queue-free, so TTFT is pure admission + prefill latency — the
        # channel prefix caching actually cuts (it prefills only the
        # unshared suffix once the system prompt's pages are cached)
        probes = []
        for i in range(n_req):
            if not shared[i]:
                continue
            h = eng.submit(
                prompts[i].astype(np.int32), max_new_tokens=2, temperature=0.0
            )
            h.wait(timeout=600)
            probes.append(h.ttft_s)
        eng.stop()
        return wall, sv, pg, handles, sorted(probes)

    dense_eng = ContinuousBatchingEngine(
        model, slots=dense_slots, max_len=max_len,
        prefill_buckets=[prompt_len], queue_depth=n_req, seed=0, paged=False,
    )
    with _sanitized_serving() as _san:
        d_wall, d_sv, _, d_handles, d_probes = _run(dense_eng)

        paged_eng = ContinuousBatchingEngine(
            model, slots=2 * dense_slots, max_len=max_len,
            prefill_buckets=[sfx, prompt_len], queue_depth=n_req, seed=0,
            paged=True, page_size=page_size, pool_pages=pool_pages,
            prefix_cache=True,
        )
        p_wall, p_sv, p_pg, p_handles, p_probes = _run(paged_eng)
    san = _sanitizer_summary(_san)

    d_tok = sum(len(h.tokens) for h in d_handles)
    p_tok = sum(len(h.tokens) for h in p_handles)
    d_concurrent = d_sv.get("occupancy_peak", 0.0) * dense_slots
    p_concurrent = p_sv.get("occupancy_peak", 0.0) * 2 * dense_slots
    ratio = p_concurrent / max(d_concurrent, 1.0)
    d_shared_p50 = d_probes[len(d_probes) // 2] if d_probes else 0.0
    p_shared_p50 = p_probes[len(p_probes) // 2] if p_probes else 0.0
    reduction = 1.0 - p_shared_p50 / d_shared_p50 if d_shared_p50 > 0 else 0.0
    # both acceptance bars ride one gate dict (main() checks one per config);
    # the sanitizer's recompile count is a correctness bar that binds even
    # where the throughput bars are CPU-unenforced
    g_conc = throughput_gate(ratio, 2.0, on_tpu, key="min_concurrency_ratio")
    g_ttft = throughput_gate(
        reduction, 0.30, on_tpu, key="min_shared_ttft_reduction"
    )
    recompiles = san["unexpected_recompiles"]
    gate = {**g_conc, **g_ttft,
            "unexpected_recompiles": recompiles,
            "enforced": bool(on_tpu or recompiles > 0),
            "ok": g_conc["ok"] and g_ttft["ok"] and recompiles == 0}

    return {
        "metric": "paged_vs_dense_concurrency_ratio",
        "value": round(ratio, 3),
        "unit": "x",
        "kv_budget_rows": budget_rows,
        "dense": {
            "slots": dense_slots,
            "tokens_per_sec": round(d_tok / d_wall, 1),
            "ttft_p50_ms": round(d_sv.get("ttft_p50_ms", 0.0), 2),
            "ttft_p95_ms": round(d_sv.get("ttft_p95_ms", 0.0), 2),
            "peak_concurrent": round(d_concurrent, 2),
        },
        "paged": {
            "slots": 2 * dense_slots,
            "page_size": page_size,
            "pool_pages": pool_pages,
            "tokens_per_sec": round(p_tok / p_wall, 1),
            "ttft_p50_ms": round(p_sv.get("ttft_p50_ms", 0.0), 2),
            "ttft_p95_ms": round(p_sv.get("ttft_p95_ms", 0.0), 2),
            "peak_concurrent": round(p_concurrent, 2),
            "prefix_hit_rate": round(p_pg.get("prefix_hit_rate", 0.0), 3),
            "prefill_tokens_saved": p_pg.get("prefill_tokens_saved", 0),
            "cow_copies": p_pg.get("cow_copies", 0),
            "pages_used_peak": p_pg.get("pages_used_peak", 0),
            "pages_total": p_pg.get("pages_total", 0),
            "compiles": paged_eng.compile_counts(),
        },
        "shared_ttft_probe_p50_ms": {  # unloaded sequential probes, cache warm
            "dense": round(d_shared_p50 * 1e3, 2),
            "paged": round(p_shared_p50 * 1e3, 2),
            "reduction": round(reduction, 3),
        },
        "greedy_outputs_match": bool(
            all(dh.tokens == ph.tokens for dh, ph in zip(d_handles, p_handles))
        ),
        "flash_fallbacks": profiler.flash_fallback_summary(),
        "sanitizer": san,
        "gate": gate,
        "note": "same KV rows both sides; dense commits slots*max_len up "
        "front, paged spends pages on lifetime spans and maps 70%-shared "
        "system prompts copy-free, so it runs 2x the slots in the budget",
    }


def bench_llama_spec_decode():
    """Speculative decoding on the paged engine (ISSUE 11): prompt-lookup
    n-gram drafts verified in ONE batched forward over the paged KV arena —
    no second model, and exactly one executable added to the compiled
    budget (verify, shaped [slots, k+1]).  Two legs against the identical
    engine with spec_k=0: (a) single-stream greedy decode on a
    drafter-friendly (self-repeating) stream — the >= 2x decode-tokens/s
    bar binds on TPU; (b) Poisson co-batched traffic.  Token identity is a
    correctness bar on BOTH tiers (greedy acceptance only changes WHEN
    tokens land, never WHICH), as is the sanitizer's recompile count:
    acceptance churn is data, a recompile under it is a bug."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=12,
            num_attention_heads=16,
            num_key_value_heads=16,
            max_position_embeddings=2048,
        )
        prompt_len, single_new = 64, 256
        n_req, lo, hi, slots, page_size, mean_gap = 24, 16, 96, 4, 32, 0.002
    else:
        cfg = LlamaConfig.tiny(
            hidden_size=256, intermediate_size=512, num_hidden_layers=4,
            num_attention_heads=8, num_key_value_heads=8,
        )
        prompt_len, single_new = 24, 192
        n_req, lo, hi, slots, page_size, mean_gap = 10, 8, 24, 3, 8, 0.0003
    spec_k = 5
    max_len = prompt_len + single_new + spec_k + 8

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")

    # drafter-friendly streams: short repeated patterns (structured output /
    # code-completion proxy).  Greedy decode on a repetitive prefix settles
    # into a cycle the n-gram drafter predicts; acceptance is REPORTED, not
    # assumed — a workload where drafts miss degrades toward 1.0x, never
    # below-1-correctness.
    rng = np.random.RandomState(0)

    def _cyclic(period):
        pat = rng.randint(1, cfg.vocab_size, (period,))
        reps = -(-prompt_len // period)
        return np.tile(pat, reps)[:prompt_len].astype(np.int32)

    single_prompt = _cyclic(6)
    prompts = [_cyclic(4 + i % 5) for i in range(n_req)]
    new_toks = np.exp(
        rng.uniform(np.log(lo), np.log(hi + 1), size=n_req)
    ).astype(np.int64).clip(lo, hi)
    gaps = rng.exponential(mean_gap, size=n_req)

    def _engine(k):
        return ContinuousBatchingEngine(
            model, slots=slots, max_len=max_len,
            prefill_buckets=[prompt_len], queue_depth=n_req, seed=0,
            paged=True, page_size=page_size, spec_k=k,
        )

    def _single(eng):
        t0 = time.perf_counter()
        h = eng.submit(single_prompt, max_new_tokens=single_new)
        h.wait(timeout=600)
        wall = time.perf_counter() - t0
        decode_s = max(wall - (h.ttft_s or 0.0), 1e-9)
        toks = list(h.tokens)
        return (len(toks) - 1) / decode_s, toks

    def _poisson(eng):
        handles = []
        t0 = time.perf_counter()
        for i in range(n_req):
            time.sleep(gaps[i])
            handles.append(
                eng.submit(prompts[i], max_new_tokens=int(new_toks[i]))
            )
        for h in handles:
            h.wait(timeout=600)
        wall = time.perf_counter() - t0
        return sum(len(h.tokens) for h in handles) / wall, \
            [list(h.tokens) for h in handles]

    def _run(k):
        eng = _engine(k)
        eng.warmup()
        profiler.reset_serving()
        profiler.reset_speculation()
        eng.start()
        single_rate, single_toks = _single(eng)
        spec_single = profiler.speculation_summary()
        profiler.reset_speculation()
        poisson_rate, poisson_toks = _poisson(eng)
        spec_poisson = profiler.speculation_summary()
        counts = eng.compile_counts()
        eng.stop()
        return {
            "single_rate": single_rate, "single_toks": single_toks,
            "poisson_rate": poisson_rate, "poisson_toks": poisson_toks,
            "spec_single": spec_single, "spec_poisson": spec_poisson,
            "compiles": counts,
        }

    with _sanitized_serving() as _san:
        plain = _run(0)
        spec = _run(spec_k)
    san = _sanitizer_summary(_san)

    speedup = spec["single_rate"] / max(plain["single_rate"], 1e-9)
    identical = bool(
        spec["single_toks"] == plain["single_toks"]
        and spec["poisson_toks"] == plain["poisson_toks"]
    )
    recompiles = san["unexpected_recompiles"]
    gate = throughput_gate(
        speedup, 2.0, on_tpu, key="min_single_stream_speedup",
        unexpected_recompiles=recompiles,
    )
    # token identity is the correctness half of the bargain: enforced on
    # both tiers, like the recompile count
    gate["tokens_identical"] = identical
    gate["enforced"] = bool(gate["enforced"] or not identical)
    gate["ok"] = gate["ok"] and identical

    def _spec_view(s):
        return {
            "acceptance_rate": round(s.get("acceptance_rate", 0.0), 3),
            "tokens_per_step": round(s.get("tokens_per_step", 0.0), 3),
            "proposed": s.get("proposed", 0),
            "accepted": s.get("accepted", 0),
        }

    return {
        "metric": "spec_decode_single_stream_speedup",
        "value": round(speedup, 3),
        "unit": "x",
        "spec_k": spec_k,
        "single_stream": {
            "plain_tokens_per_sec": round(plain["single_rate"], 1),
            "spec_tokens_per_sec": round(spec["single_rate"], 1),
            "speculation": _spec_view(spec["spec_single"]),
        },
        "poisson": {
            "requests": n_req,
            "plain_tokens_per_sec": round(plain["poisson_rate"], 1),
            "spec_tokens_per_sec": round(spec["poisson_rate"], 1),
            "speedup": round(
                spec["poisson_rate"] / max(plain["poisson_rate"], 1e-9), 3
            ),
            "speculation": _spec_view(spec["spec_poisson"]),
        },
        "tokens_identical": identical,
        "compiles": spec["compiles"],
        "flash_fallbacks": profiler.flash_fallback_summary(),
        "sanitizer": san,
        "gate": gate,
        "note": "same model/engine both sides, spec_k=0 vs 3; n-gram drafts "
        "verified in one [slots, k+1] forward, acceptance is traced data; "
        "repetitive streams are the drafter's best case — acceptance rate "
        "is reported so the win is attributable",
    }


def bench_lora_serving():
    """Multi-tenant LoRA serving (ISSUE 12): 16 adapters behind ONE paged
    engine, Poisson arrivals with Zipf adapter popularity, vs the SAME
    engine serving the single most-popular adapter only.  The arena holds
    fewer slots than tenants, so the mixed leg pays real residency churn
    (upload + LRU eviction) — reported as the residency hit rate next to
    both throughputs.  Correctness bars on both tiers: zero unexpected
    recompiles/host-syncs under the sanitizer (adapter ids are traced DATA;
    churn rewrites arena rows in place) and compile counts frozen at the
    warmup budget.  The throughput bar (mixed >= 0.7x single-adapter)
    binds on TPU only."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.lora import AdapterArena, AdapterRegistry, make_random
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=12,
            num_attention_heads=16,
            num_key_value_heads=16,
            max_position_embeddings=2048,
        )
        prompt_len = 64
        n_req, lo, hi, slots, page_size, mean_gap = 48, 16, 96, 4, 32, 0.002
        rank, capacity = 8, 8
    else:
        cfg = LlamaConfig.tiny(
            hidden_size=256, intermediate_size=512, num_hidden_layers=4,
            num_attention_heads=8, num_key_value_heads=8,
        )
        prompt_len = 16
        n_req, lo, hi, slots, page_size, mean_gap = 48, 4, 16, 3, 8, 0.0003
        rank, capacity = 2, 8
    n_adapters = 16
    max_len = prompt_len + hi + 8

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")

    registry = AdapterRegistry(cfg)
    for i in range(n_adapters):
        make_random(registry, f"tenant{i:02d}", rank=rank, seed=i + 1)

    rng = np.random.RandomState(0)
    prompts = [
        rng.randint(1, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        for _ in range(n_req)
    ]
    new_toks = np.exp(
        rng.uniform(np.log(lo), np.log(hi + 1), size=n_req)
    ).astype(np.int64).clip(lo, hi)
    gaps = rng.exponential(mean_gap, size=n_req)
    # Zipf(s=1.1) popularity: a few hot tenants, a long cold tail — the
    # distribution under which an LRU arena smaller than the tenant count
    # still earns a high residency hit rate
    zipf_p = 1.0 / np.arange(1, n_adapters + 1) ** 1.1
    zipf_p /= zipf_p.sum()
    mixed_assign = [
        f"tenant{i:02d}" for i in rng.choice(n_adapters, size=n_req, p=zipf_p)
    ]

    def _run(assign):
        eng = ContinuousBatchingEngine(
            model, slots=slots, max_len=max_len,
            prefill_buckets=[prompt_len], queue_depth=n_req, seed=0,
            paged=True, page_size=page_size,
            lora=AdapterArena(registry, capacity=capacity, rank_max=rank),
        )
        eng.warmup()
        warm = eng.compile_counts()
        profiler.reset_serving()
        profiler.reset_lora()
        eng.start()
        handles = []
        t0 = time.perf_counter()
        for i in range(n_req):
            time.sleep(gaps[i])
            handles.append(
                eng.submit(prompts[i], max_new_tokens=int(new_toks[i]),
                           adapter=assign[i])
            )
        for h in handles:
            h.wait(timeout=600)
        wall = time.perf_counter() - t0
        lora = profiler.lora_summary()
        frozen = eng.compile_counts() == warm
        counts = eng.compile_counts()
        eng.stop()
        return {
            "rate": sum(len(h.tokens) for h in handles) / wall,
            "lora": lora,
            "compiles_frozen": frozen,
            "compiles": counts,
        }

    with _sanitized_serving() as _san:
        single = _run(["tenant00"] * n_req)
        mixed = _run(mixed_assign)
    san = _sanitizer_summary(_san)

    ratio = mixed["rate"] / max(single["rate"], 1e-9)
    recompiles = san["unexpected_recompiles"]
    gate = throughput_gate(
        ratio, 0.7, on_tpu, key="min_mixed_vs_single_ratio",
        unexpected_recompiles=recompiles,
    )
    frozen = bool(single["compiles_frozen"] and mixed["compiles_frozen"])
    gate["compiles_frozen"] = frozen
    gate["enforced"] = bool(gate["enforced"] or not frozen)
    gate["ok"] = gate["ok"] and frozen

    ml = mixed["lora"]
    return {
        "metric": "lora_mixed_vs_single_tokens_ratio",
        "value": round(ratio, 3),
        "unit": "x",
        "adapters": n_adapters,
        "arena_capacity": capacity,
        "rank": rank,
        "requests": n_req,
        "single_adapter_tokens_per_sec": round(single["rate"], 1),
        "mixed_tokens_per_sec": round(mixed["rate"], 1),
        "residency_hit_rate": round(ml.get("residency_hit_rate", 0.0), 3),
        "adapter_loads": ml.get("loads", 0),
        "adapter_evictions": ml.get("evictions", 0),
        "compiles": mixed["compiles"],
        "compiles_frozen": frozen,
        "sanitizer": san,
        "gate": gate,
        "note": "16 tenants, Zipf(1.1) popularity, Poisson arrivals on one "
        "paged engine; arena capacity 8 < 16 tenants so the mixed leg pays "
        "real LRU churn; baseline is the SAME engine serving only the "
        "hottest tenant; adapter ids ride executables as traced data",
    }


def bench_paged_decode_kernel():
    """Fused paged-decode attention (ISSUE 13): the SAME paged engine and
    greedy request stream under decode_kernel="fused" (the Pallas kernel
    reads the arena through the page tables in-kernel) vs "gather" (the
    materialize-then-dense oracle it replaces).  Correctness bars on both
    tiers: token-identical outputs, compile counts frozen at warmup, zero
    unexpected recompiles/host-syncs under the sanitizer, zero fallbacks on
    the fused leg, and the RETIRED fallback reasons ("seq not a
    128-multiple", "attn_mask given") at zero.  The throughput bar — fused
    >= 1.5x gather decode tokens/s, the HBM gather tax converted to speed —
    binds on TPU only: on CPU the fused leg runs the kernel in Pallas
    interpret mode, which proves parity, not performance."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    import paddle_tpu.ops.flash_attention as fa

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=12,
            num_attention_heads=16,
            num_key_value_heads=16,
            max_position_embeddings=2048,
        )
        prompt_len, n_req, lo, hi, slots, page_size = 64, 32, 32, 128, 4, 32
    else:
        cfg = LlamaConfig.tiny()
        prompt_len, n_req, lo, hi, slots, page_size = 8, 10, 3, 8, 3, 8
    max_len = prompt_len + hi + 8

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")

    rng = np.random.RandomState(0)
    prompts = [
        rng.randint(1, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        for _ in range(n_req)
    ]
    new_toks = rng.randint(lo, hi + 1, size=n_req)

    def _run(kernel):
        # off-TPU the fused kernel only exists in interpret mode; scope the
        # override to this run so the gather leg measures the plain XLA path
        saved = fa._FORCE_INTERPRET
        if kernel == "fused" and not on_tpu:
            fa._FORCE_INTERPRET = True
        try:
            # kernel dispatch is counted at TRACE time (executables embed
            # their kernel choice), so reset BEFORE construction/warmup —
            # the counters prove what the warmed executables were built with
            profiler.reset_flash_pallas()
            profiler.reset_flash_fallbacks()
            eng = ContinuousBatchingEngine(
                model, slots=slots, max_len=max_len,
                prefill_buckets=[prompt_len], queue_depth=n_req, seed=0,
                paged=True, page_size=page_size, decode_kernel=kernel,
            )
            eng.warmup()
            warm = eng.compile_counts()
            profiler.reset_serving()
            handles = []
            t0 = time.perf_counter()
            for i in range(n_req):
                handles.append(
                    eng.submit(prompts[i], max_new_tokens=int(new_toks[i]))
                )
            eng.run_until_idle()
            for h in handles:
                h.wait(timeout=600)
            wall = time.perf_counter() - t0
            frozen = eng.compile_counts() == warm
            return {
                "rate": sum(len(h.tokens) for h in handles) / wall,
                "tokens": [list(h.tokens) for h in handles],
                "compiles_frozen": frozen,
                "pallas_calls": profiler.flash_pallas_summary(),
                "fallbacks": profiler.flash_fallback_summary(),
            }
        finally:
            fa._FORCE_INTERPRET = saved

    with _sanitized_serving() as _san:
        gather = _run("gather")
        fused = _run("fused")
    san = _sanitizer_summary(_san)

    identical = fused["tokens"] == gather["tokens"]
    frozen = bool(fused["compiles_frozen"] and gather["compiles_frozen"])
    retired = sum(
        fused["fallbacks"].get(r, 0) + gather["fallbacks"].get(r, 0)
        for r in ("seq not a 128-multiple", "attn_mask given")
    )
    fused_clean = not fused["fallbacks"]
    dispatched = fused["pallas_calls"].get("paged_decode_fused", 0) > 0
    ratio = fused["rate"] / max(gather["rate"], 1e-9)
    gate = throughput_gate(
        ratio, 1.5, on_tpu, key="min_fused_speedup",
        unexpected_recompiles=san["unexpected_recompiles"],
    )
    correct = bool(
        identical and frozen and fused_clean and dispatched and retired == 0
    )
    gate.update(
        tokens_identical=identical, compiles_frozen=frozen,
        fused_fallback_free=fused_clean, fused_kernel_dispatched=dispatched,
        retired_fallbacks=retired,
    )
    gate["enforced"] = bool(gate["enforced"] or not correct)
    gate["ok"] = gate["ok"] and correct
    return {
        "metric": "fused_vs_gather_decode_speedup",
        "value": round(ratio, 3),
        "unit": "x",
        "requests": n_req,
        "fused_tokens_per_sec": round(fused["rate"], 1),
        "gather_tokens_per_sec": round(gather["rate"], 1),
        "tokens_identical": identical,
        "fused_pallas_calls": fused["pallas_calls"],
        "fused_fallbacks": fused["fallbacks"],
        "compiles_frozen": frozen,
        "sanitizer": san,
        "gate": gate,
        "note": "same paged engine + greedy stream, decode_kernel fused vs "
        "gather; fused reads the arena through the page tables in-kernel "
        "(no materialized per-step KV copy); CPU runs the fused kernel via "
        "interpret=True so the speedup bar binds on TPU only",
    }


def bench_tp_decode():
    """Tensor-parallel serving (ISSUE 14): the SAME weights and greedy
    request stream through a TP=1 engine and a TP=4 engine (column/row-
    sharded projections, mesh-sharded KV arena, decode kernel over local
    heads — all inside the one compiled decode step).  Correctness bars on
    both tiers: token-identical outputs, compile counts frozen at warmup on
    BOTH engines, zero unexpected recompiles/host-syncs under the
    sanitizer.  The throughput bar — TP=4 >= 1.6x TP=1 decode tokens/s,
    the 4-way weight/KV bandwidth split converted to speed — binds on the
    MULTICHIP rig only: on CPU the 4 "devices" are threads of one host
    sharing a memory bus, so TP=4 proves layout correctness, not speed."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = _on_tpu()
    tp = 4
    if len(jax.devices()) < tp:
        return {
            "skipped": f"needs {tp} devices, found {len(jax.devices())}; "
            "CPU tier runs under XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 (see ci.sh)",
        }

    def _cfg(tp_deg):
        if on_tpu:
            return LlamaConfig(
                vocab_size=32000,
                hidden_size=2048,
                intermediate_size=5632,
                num_hidden_layers=12,
                num_attention_heads=16,
                num_key_value_heads=16,
                max_position_embeddings=2048,
                tensor_parallel_degree=tp_deg,
            )
        return LlamaConfig.tiny(tensor_parallel_degree=tp_deg)

    if on_tpu:
        prompt_len, n_req, lo, hi, slots, page_size = 64, 32, 32, 128, 4, 32
    else:
        prompt_len, n_req, lo, hi, slots, page_size = 8, 10, 3, 8, 3, 8
    max_len = prompt_len + hi + 8

    paddle.seed(0)
    model1 = LlamaForCausalLM(_cfg(1))
    model4 = LlamaForCausalLM(_cfg(4))
    model4.set_state_dict(model1.state_dict())
    if on_tpu:
        model1 = paddle.amp.decorate(model1, level="O2", dtype="bfloat16")
        model4 = paddle.amp.decorate(model4, level="O2", dtype="bfloat16")

    rng = np.random.RandomState(0)
    vocab = _cfg(1).vocab_size
    prompts = [
        rng.randint(1, vocab, (prompt_len,)).astype(np.int32)
        for _ in range(n_req)
    ]
    new_toks = rng.randint(lo, hi + 1, size=n_req)

    def _run(model, tp_deg):
        eng = ContinuousBatchingEngine(
            model, slots=slots, max_len=max_len,
            prefill_buckets=[prompt_len], queue_depth=n_req, seed=0,
            paged=True, page_size=page_size, tp=tp_deg,
        )
        eng.warmup()
        warm = eng.compile_counts()
        handles = []
        t0 = time.perf_counter()
        for i in range(n_req):
            handles.append(
                eng.submit(prompts[i], max_new_tokens=int(new_toks[i]))
            )
        eng.run_until_idle()
        for h in handles:
            h.wait(timeout=600)
        wall = time.perf_counter() - t0
        return {
            "rate": sum(len(h.tokens) for h in handles) / wall,
            "tokens": [list(h.tokens) for h in handles],
            "compiles_frozen": eng.compile_counts() == warm,
        }

    prev_mesh = _mesh.get_mesh()
    try:
        with _sanitized_serving() as _san:
            # TP=1 first: its executables trace before any mesh exists, so
            # the baseline leg cannot see the TP leg's device placement
            tp1 = _run(model1, 1)
            tp4 = _run(model4, tp)
        san = _sanitizer_summary(_san)
    finally:
        _mesh.set_mesh(prev_mesh)

    identical = tp4["tokens"] == tp1["tokens"]
    frozen = bool(tp4["compiles_frozen"] and tp1["compiles_frozen"])
    ratio = tp4["rate"] / max(tp1["rate"], 1e-9)
    gate = throughput_gate(
        ratio, 1.6, on_tpu, key="min_tp4_speedup",
        unexpected_recompiles=san["unexpected_recompiles"],
    )
    correct = bool(identical and frozen)
    gate.update(tokens_identical=identical, compiles_frozen=frozen)
    gate["enforced"] = bool(gate["enforced"] or not correct)
    gate["ok"] = gate["ok"] and correct
    return {
        "metric": "tp4_vs_tp1_decode_speedup",
        "value": round(ratio, 3),
        "unit": "x",
        "requests": n_req,
        "tp4_tokens_per_sec": round(tp4["rate"], 1),
        "tp1_tokens_per_sec": round(tp1["rate"], 1),
        "tokens_identical": identical,
        "compiles_frozen": frozen,
        "sanitizer": san,
        "gate": gate,
        "note": "same weights (state_dict copy) + greedy stream at tp=1 vs "
        "tp=4; tp=4 shards projections column/row, the paged KV arena, and "
        "the decode kernel over the 'mp' mesh inside one compiled step; "
        "the 1.6x bar binds on the multichip rig only",
    }


def bench_kv_quant_serving():
    """Quantized KV serving (ISSUE 18): the SAME weights and greedy request
    stream through a full-precision paged engine and an int8 engine whose
    page pool is sized to the SAME HBM byte budget — the int8 arena packs
    kv_page_bytes(none)/kv_page_bytes(int8) times the pages into those
    bytes (~1.94x at bf16 head_dim=128), so under page-bound admission it
    holds proportionally more concurrent sequences.  Gates: peak concurrent
    sequences >= 1.8x (enforced on BOTH tiers — capacity is byte math, not
    throughput noise), per-request token match vs full precision >= 0.95,
    zero unexpected recompiles under the sanitizer; TTFT p50 within 10% of
    the full-precision leg binds on TPU only (CPU latency is noise)."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.inference.paging import kv_page_bytes
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=12,
            num_attention_heads=16,
            num_key_value_heads=16,
            max_position_embeddings=2048,
        )
        prompt_len, n_req, new_toks, page_size = 64, 32, 96, 32
        full_pool = 41  # 40 usable pages: 8 concurrent 160-token spans
    else:
        cfg = LlamaConfig.tiny()
        prompt_len, n_req, new_toks, page_size = 8, 12, 24, 8
        full_pool = 13  # 12 usable pages: 3 concurrent 32-token spans

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    dtype_bytes = np.dtype(str(model.lm_head.weight.dtype)).itemsize
    full_page_b = kv_page_bytes(
        page_size, cfg.num_key_value_heads, head_dim, dtype_bytes, "none"
    )
    q8_page_b = kv_page_bytes(
        page_size, cfg.num_key_value_heads, head_dim, dtype_bytes, "int8"
    )
    budget_bytes = full_pool * full_page_b
    q8_pool = budget_bytes // q8_page_b  # same HBM bytes, more pages
    max_len = prompt_len + new_toks + 8

    rng = np.random.RandomState(0)
    prompts = [  # distinct prompts: no prefix sharing masking the capacity
        rng.randint(1, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        for _ in range(n_req)
    ]

    def _run(quant, pool_pages):
        eng = ContinuousBatchingEngine(
            model, slots=n_req, max_len=max_len,
            prefill_buckets=[prompt_len], queue_depth=n_req, seed=0,
            paged=True, page_size=page_size, pool_pages=pool_pages,
            kv_quant=quant,
        )
        eng.warmup()
        warm = eng.compile_counts()
        # occupancy gauges accumulate per decode tick; reset AFTER warmup so
        # peak concurrency measures only the stream (slots = n_req, so the
        # page pool — not the slot table — is what bounds admission)
        profiler.reset_serving()
        handles = []
        t0 = time.perf_counter()
        for i in range(n_req):
            handles.append(eng.submit(prompts[i], max_new_tokens=new_toks))
        eng.run_until_idle()
        for h in handles:
            h.wait(timeout=600)
        wall = time.perf_counter() - t0
        g = profiler.metrics_snapshot()["serving"]
        ttfts = sorted(g["ttfts_s"])
        return {
            "rate": sum(len(h.tokens) for h in handles) / wall,
            "tokens": [list(h.tokens) for h in handles],
            "peak_concurrent": int(round(g["occupancy_peak"] * n_req)),
            "ttft_p50_s": ttfts[len(ttfts) // 2] if ttfts else 0.0,
            "compiles_frozen": eng.compile_counts() == warm,
            "pool_pages": eng.pool_pages,
        }

    with _sanitized_serving() as _san:
        full = _run("none", full_pool)
        q8 = _run("int8", int(q8_pool))
    san = _sanitizer_summary(_san)
    kvq = profiler.metrics_snapshot()["kv_quant"]

    def _match(a, b):
        n = min(len(a), len(b))
        return float(np.mean(np.asarray(a[:n]) == np.asarray(b[:n]))) if n else 1.0

    match = float(np.mean([
        _match(a, b) for a, b in zip(full["tokens"], q8["tokens"])
    ]))
    ratio = q8["peak_concurrent"] / max(full["peak_concurrent"], 1)
    ttft_ok = bool(
        q8["ttft_p50_s"] <= full["ttft_p50_s"] * 1.10 or not on_tpu
    )
    frozen = bool(full["compiles_frozen"] and q8["compiles_frozen"])
    gate = throughput_gate(
        ratio, 1.8, True, key="min_concurrency_ratio",
        unexpected_recompiles=san["unexpected_recompiles"],
    )
    correct = bool(match >= 0.95 and frozen and ttft_ok)
    gate.update(
        min_token_match=0.95, token_match=round(match, 4),
        compiles_frozen=frozen, ttft_within_10pct=ttft_ok,
    )
    gate["enforced"] = bool(gate["enforced"] or not correct)
    gate["ok"] = gate["ok"] and correct
    return {
        "metric": "int8_vs_full_peak_concurrency_same_hbm",
        "value": round(ratio, 3),
        "unit": "x",
        "requests": n_req,
        "hbm_page_budget_bytes": int(budget_bytes * cfg.num_hidden_layers),
        "full_pool_pages": full["pool_pages"],
        "int8_pool_pages": q8["pool_pages"],
        "full_peak_concurrent": full["peak_concurrent"],
        "int8_peak_concurrent": q8["peak_concurrent"],
        "token_match": round(match, 4),
        "full_ttft_p50_s": round(full["ttft_p50_s"], 4),
        "int8_ttft_p50_s": round(q8["ttft_p50_s"], 4),
        "full_tokens_per_sec": round(full["rate"], 1),
        "int8_tokens_per_sec": round(q8["rate"], 1),
        "kv_quant_gauges": {
            "arena_bytes": kvq["arena_bytes"], "scale_bytes": kvq["scale_bytes"],
            "quantize_ops": kvq["quantize"], "dequantize_ops": kvq["dequantize"],
        },
        "compiles_frozen": frozen,
        "sanitizer": san,
        "gate": gate,
        "note": "same weights + greedy stream, full-precision vs int8 page "
        "arena holding the SAME HBM page-byte budget; slots = n_req so the "
        "page pool bounds admission — peak concurrent sequences is the "
        "capacity the bytes buy; token match >= 0.95 is the quality bar, "
        "TTFT p50 within 10% binds on TPU",
    }


def bench_router():
    """Multi-replica router failover (ISSUE 9): the same greedy request
    stream posted directly to one undisturbed replica, then routed over a
    2-replica fleet whose preferred replica is stopped mid-stream.  The
    router's contract is robustness at near-zero cost, so the gate is the
    correctness pair — every routed request resolves exactly once (all 200)
    and the outputs are bit-identical to the direct run, failover included —
    while the routed-minus-direct p50 latency is the reported metric."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference import serve
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Router

    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    n_req, prompt_len, new_toks = 16, 8, 8
    prompts = rng.randint(0, cfg.vocab_size, (n_req, prompt_len)).astype(np.int32)

    def _replica():
        eng = ContinuousBatchingEngine(
            model, slots=2, max_len=prompt_len + new_toks + 8,
            prefill_buckets=[prompt_len], queue_depth=n_req, seed=0,
        )
        eng.warmup()
        srv = serve(eng, port=0, block=False, supervise=False,
                    handle_signals=False)
        return srv, f"http://127.0.0.1:{srv.server_address[1]}"

    def _stop(srv):
        try:
            srv.engine.stop()
        except Exception:
            pass
        srv.shutdown()
        srv.server_close()

    def _post_direct(url, body):
        import urllib.request

        req = urllib.request.Request(
            url + "/generate", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    srv_a, url_a = _replica()
    srv_b, url_b = _replica()
    router = None
    a_stopped = False
    try:
        # direct baseline on the SURVIVOR replica: same weights (shared
        # model), greedy decode — its outputs are the bit-exact reference
        direct_lat, ref_tokens = [], []
        for row in prompts:
            t0 = time.perf_counter()
            out = _post_direct(url_b, {"input_ids": row.tolist(),
                                       "max_new_tokens": new_toks})
            direct_lat.append(time.perf_counter() - t0)
            ref_tokens.append(out["tokens"])

        profiler.reset_router()
        router = Router([url_a, url_b])
        router.start()
        routed_lat, routed_tokens, statuses = [], [], []
        for i, row in enumerate(prompts):
            if i == n_req // 2:
                # kill the preferred replica (index 0 wins score ties) so
                # the second half of the stream must fail over to B
                _stop(srv_a)
                a_stopped = True
            t0 = time.perf_counter()
            status, body, _hdrs = router.handle_generate(
                {"input_ids": row.tolist(), "max_new_tokens": new_toks}
            )
            routed_lat.append(time.perf_counter() - t0)
            statuses.append(status)
            routed_tokens.append(body.get("tokens"))
        gauges = profiler.router_summary()
    finally:
        if router is not None:
            router.stop()
        if not a_stopped:
            _stop(srv_a)
        _stop(srv_b)

    exactly_once = len(statuses) == n_req and all(s == 200 for s in statuses)
    bit_identical = bool(
        exactly_once
        and all(rt == ref for rt, ref in zip(routed_tokens, ref_tokens))
    )
    d_p50 = float(np.percentile(direct_lat, 50)) * 1e3
    r_p50 = float(np.percentile(routed_lat, 50)) * 1e3
    r_p95 = float(np.percentile(routed_lat, 95)) * 1e3
    return {
        "metric": "router_overhead_p50_ms",
        "value": round(r_p50 - d_p50, 2),
        "unit": "ms",
        "requests": n_req,
        "direct_p50_ms": round(d_p50, 2),
        "routed_p50_ms": round(r_p50, 2),
        "routed_p95_ms": round(r_p95, 2),
        "retries": gauges["retries"],
        "failovers": gauges["failovers"],
        "breaker_trips": gauges["breaker_trips"],
        "exactly_once": exactly_once,
        "greedy_outputs_match": bit_identical,
        "gate": {
            # correctness gate, enforced everywhere: kill-mid-stream must
            # not drop a request or perturb a single token
            "exactly_once": exactly_once,
            "bit_identical": bit_identical,
            "enforced": True,
            "ok": exactly_once and bit_identical,
        },
        "note": "2 in-process replicas sharing seed-matched weights; the "
        "preferred replica's server is stopped at the stream midpoint, so "
        "the tail fails over; p50 overhead = routed - direct on the "
        "undisturbed survivor",
    }


def bench_soak():
    """Closed-loop autoscaler chaos mini-soak (ISSUE 16): a saturating
    step-function burst of mixed organic + adversarial traffic through the
    router while the autoscaler grows the fleet 1 -> 2 THROUGH a
    failed-spawn drill and a poisoned decode step.  Headline is
    requests/s/chip over the whole soak (CPU: informational); the enforced
    gate is the robustness contract — every offered request resolves
    exactly once, every adversarial kind lands its typed outcome, organic
    traffic holds the SLO, and the loop actually scaled through the
    chaos."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.fault import injection as finj
    from paddle_tpu.inference import serve
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Replica, Router
    from paddle_tpu.serving.autoscaler import Autoscaler
    from paddle_tpu.serving.workload import Workload, run_soak

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    servers = {}

    def _replica(rid, warm=False):
        eng = ContinuousBatchingEngine(
            model, slots=2, max_len=64, prefill_buckets=[8],
            queue_depth=16, seed=0,
        )
        if warm:  # spawns stay cold: their compiles are process-cached and
            eng.warmup()  # the router only routes to them after probe-ready
        srv = serve(eng, port=0, block=False, supervise=False,
                    handle_signals=False)
        servers[rid] = srv
        return Replica(rid, f"http://127.0.0.1:{srv.server_address[1]}")

    def _stop(srv):
        try:
            srv.engine.stop()
        except Exception:
            pass
        srv.shutdown()
        srv.server_close()

    profiler.reset_router()
    profiler.reset_autoscale()
    router = Router([_replica("r0", warm=True)], probe_interval=0.05,
                    retry_backoff=0.02)
    asc = None
    try:
        router.start()
        deadline = time.monotonic() + 60
        while (time.monotonic() < deadline
               and router.replicas[0].state != "ready"):
            time.sleep(0.05)
        asc = Autoscaler(
            router,
            spawn_fn=lambda i, tp: _replica(f"as{i}"),
            stop_fn=lambda rep: _stop(servers.pop(rep.rid)),
            min_replicas=1, max_replicas=2, interval=0.05, up_ticks=2,
            down_ticks=4, up_cooldown=0.2, down_cooldown=0.3,
            up_drain_s=10.0, up_queue_depth=1.0, up_miss_rate=0.5,
            min_page_free=0.0, down_drain_s=10.0, tp_max=1,
            devices_total=1, drain_grace=5.0,
        ).start()
        wl = Workload(
            rate_hz=500.0, duration_s=60.0, requests=400, seed=7,
            steps=((0.0, 1.0), (0.2, 4.0)), prompt_len=(4, 8),
            max_new_tokens=4, deadline_s=60.0, frac_over_deadline=0.03,
            frac_unknown_adapter=0.03, frac_over_bucket=0.03,
            max_len_hint=64,
        )
        report = run_soak(
            router, wl, threads=4, realtime=False,
            faults=((0.05, "autoscale.spawn:1,serve.decode.nan:1"),),
        )
        asc.stop()  # join the control thread: an in-flight spawn completes
        gauges = profiler.autoscale_summary()
    finally:
        finj.disarm()
        if asc is not None:
            asc.stop()
        router.stop()
        for srv in servers.values():
            _stop(srv)

    s = report.summary()
    chips = max(1, jax.device_count())
    typed_ok = all(
        s["kind_counts"].get(k, {"unexpected": 0})["unexpected"] == 0
        for k in ("unknown_adapter", "over_bucket", "over_deadline")
    )
    okc = s["kind_counts"].get("ok", {"n": 0, "unexpected": 0})
    organic_ok = (
        okc["unexpected"] <= max(3, okc["n"] // 20)
        and report.miss_rate <= 0.05
    )
    scaled = (
        gauges.get("scale_ups", 0) >= 1
        and gauges.get("spawn_failures", 0) >= 1
        and gauges.get("replicas_peak", 0) >= 2
    )
    ok = bool(report.exactly_once and typed_ok and organic_ok and scaled)
    return {
        "metric": "soak_requests_per_s_per_chip",
        "value": round(s["requests_per_s"] / chips, 2),
        "unit": "req/s/chip",
        "requests": s["offered"],
        "requests_per_s": s["requests_per_s"],
        "chips": chips,
        "latency_p50_ms": s["latency_p50_ms"],
        "latency_p95_ms": s["latency_p95_ms"],
        "miss_rate": s["miss_rate"],
        "exactly_once": report.exactly_once,
        "scale_ups": gauges.get("scale_ups", 0),
        "spawn_failures": gauges.get("spawn_failures", 0),
        "replicas_peak": gauges.get("replicas_peak", 0),
        "gate": {
            "exactly_once": report.exactly_once,
            "typed_adversarial_outcomes": typed_ok,
            "organic_slo": organic_ok,
            "scaled_through_chaos": scaled,
            "enforced": True,
            "ok": ok,
        },
        "note": "400 saturating requests (4x burst step, 9% adversarial "
        "mix) through the router; the autoscaler scales 1 -> 2 through an "
        "armed autoscale.spawn fault plus one serve.decode.nan poisoned "
        "step; the 10-minute acceptance soak lives in ./ci.sh soak",
    }


def bench_router_ha():
    """Crash-proof front door (ISSUE 17): the durable journal + idempotency
    cache must be invisible on the routed hot path.  The same greedy stream
    runs through a bare router, then through one carrying a journal,
    heartbeat, and per-request idempotency keys; the enforced gate holds
    the journaled p50 within 5% of bare (plus the dedupe correctness pair:
    a resubmitted key replays byte-identical without re-generating)."""
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference import serve
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Router

    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    n_req, prompt_len, new_toks = 32, 8, 8
    prompts = rng.randint(0, cfg.vocab_size, (n_req, prompt_len)).astype(np.int32)

    def _replica():
        eng = ContinuousBatchingEngine(
            model, slots=2, max_len=prompt_len + new_toks + 8,
            prefill_buckets=[prompt_len], queue_depth=n_req, seed=0,
        )
        eng.warmup()
        srv = serve(eng, port=0, block=False, supervise=False,
                    handle_signals=False)
        return srv, f"http://127.0.0.1:{srv.server_address[1]}"

    def _stop(srv):
        try:
            srv.engine.stop()
        except Exception:
            pass
        srv.shutdown()
        srv.server_close()

    def _run(router, keyed):
        lat = []
        for i, row in enumerate(prompts):
            body = {"input_ids": row.tolist(), "max_new_tokens": new_toks}
            key = f"bench-ha-{i}" if keyed else None
            t0 = time.perf_counter()
            status, out, _hdrs = router.handle_generate(body, idem_key=key)
            lat.append(time.perf_counter() - t0)
            assert status == 200, out
        return lat

    srv_a, url_a = _replica()
    srv_b, url_b = _replica()
    bare = journaled = None
    tmp = tempfile.mkdtemp(prefix="bench-router-ha-")
    try:
        # warm both replicas' caches through a throwaway pass, then the
        # bare-router baseline
        bare = Router([url_a, url_b]).start()
        _run(bare, keyed=False)
        bare_lat = _run(bare, keyed=False)
        bare.stop()

        profiler.reset_router()
        journaled = Router(
            [url_a, url_b], journal=os.path.join(tmp, "journal"),
            heartbeat=os.path.join(tmp, "hb"),
        ).start()
        keyed_lat = _run(journaled, keyed=True)
        # the dedupe correctness pair: every key resubmitted, one
        # generation each, byte-identical replays
        s1, b1, _ = journaled.handle_generate(
            {"input_ids": prompts[0].tolist(), "max_new_tokens": new_toks},
            idem_key="bench-ha-0",
        )
        replay_ok = s1 == 200 and json.dumps(b1) != ""
        s2, b2, h2 = journaled.handle_generate(
            {"input_ids": prompts[0].tolist(), "max_new_tokens": new_toks},
            idem_key="bench-ha-0",
        )
        replay_ok = (
            replay_ok and s2 == 200 and json.dumps(b1) == json.dumps(b2)
            and h2.get("X-Idempotency-Replay") == "hit"
        )
        gauges = profiler.router_summary()
    finally:
        if bare is not None:
            bare.stop()
        if journaled is not None:
            journaled.stop()
        _stop(srv_a)
        _stop(srv_b)

    bare_p50 = float(np.percentile(bare_lat, 50)) * 1e3
    keyed_p50 = float(np.percentile(keyed_lat, 50)) * 1e3
    overhead = (keyed_p50 / bare_p50 - 1.0) if bare_p50 > 0 else 0.0
    # the 5% bar rides a floor: at sub-ms p50s, scheduler noise dwarfs the
    # journal's microseconds — absolute slack keeps the gate meaningful
    within = keyed_p50 <= bare_p50 * 1.05 + 2.0
    return {
        "metric": "journaled_p50_overhead_pct",
        "value": round(overhead * 100.0, 2),
        "unit": "%",
        "requests": n_req,
        "bare_p50_ms": round(bare_p50, 2),
        "journaled_p50_ms": round(keyed_p50, 2),
        "journal_appends": gauges["journal_appends"],
        "idem_hits": gauges["idem_hits"],
        "replay_byte_identical": replay_ok,
        "gate": {
            "p50_within_5pct": within,
            "replay_byte_identical": replay_ok,
            "enforced": True,
            "ok": within and replay_ok,
        },
        "note": "same 32-request greedy stream through a bare router, then "
        "one with a durable journal + heartbeat + per-request idempotency "
        "keys; gate = journaled p50 <= 1.05x bare (+2ms scheduler-noise "
        "floor) and a resubmitted key replays byte-identical",
    }


def bench_disagg_serving():
    """Disaggregated prefill/decode serving (ISSUE 19): the SAME Poisson
    mixed long-prompt workload through two fleets of two engines each —
    a colocated pair, then 1 prefill + 1 decode worker joined by the
    paged-KV handoff — behind the topology-aware router.  TTFT is
    measured client-side on max_new_tokens=1 probe requests riding the
    stream (the whole response IS the first token), so it includes every
    queueing and handoff hop honestly.  The workload is CLOSED-LOOP: more
    concurrent background streams than the colocated fleet has seats, so
    its seats stay full for the whole window no matter how fast the
    machine is — an open-loop Poisson rate calibrated against a warm
    cache stops saturating and the queueing contrast (the thing being
    measured) disappears.  Gates: every request on both
    fleets resolves 200 with tokens bit-identical to a single undisturbed
    engine, zero unexpected recompiles on either handoff side, and the
    disagg fleet cuts probe TTFT p95 by >= 15% while holding >= 0.7x the
    colocated aggregate tokens/s (enforced on BOTH tiers: the cut is
    queueing structure — probes never park behind decode streams — not
    device speed)."""
    import threading

    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference import serve
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Router

    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    # decode-heavy background (short prompts, long streams) + long-prompt
    # TTFT probes: the mix disaggregation targets — on a colocated engine
    # the probe's expensive prefill interleaves with seated decode work,
    # on the split fleet it runs on the prefill worker's empty compute
    probe_prompt, bg_prompt, bg_new = 40, 8, 48
    n_total = 45
    rng = np.random.RandomState(0)
    reqs = []  # (payload, is_probe) — distinct prompts, no prefix sharing
    for i in range(n_total):
        probe = i % 3 == 2  # every third request is a TTFT probe
        reqs.append((
            {
                "input_ids": rng.randint(
                    1, cfg.vocab_size,
                    (probe_prompt if probe else bg_prompt,),
                ).astype(np.int32).tolist(),
                "max_new_tokens": 1 if probe else bg_new,
            },
            probe,
        ))

    def _engine(role):
        # role-sized workers, the point of disaggregation: the decode
        # worker holds the FLEET's seated streams (it spends no compute
        # on prefill), the prefill worker's slots only hold transient
        # prefill bursts; the colocated pair splits the same 8 seats
        slots = {"colocated": 4, "prefill": 4, "decode": 8}[role]
        return ContinuousBatchingEngine(
            model, slots=slots, max_len=64, prefill_buckets=[8, 48],
            queue_depth=64, seed=0, paged=True, page_size=8,
            pool_pages=512, kv_quant="int8", role=role,
        )

    # reference tokens: one undisturbed engine, closed loop
    ref_eng = _engine("colocated")
    ref_eng.warmup()
    handles = [
        ref_eng.submit(
            np.asarray(p["input_ids"], np.int32),
            max_new_tokens=p["max_new_tokens"],
        )
        for p, _ in reqs
    ]
    ref_eng.run_until_idle()
    ref_tokens = [list(h.wait(timeout=600)) for h in handles]
    ref_eng.stop()
    # 15 closed-loop client threads, request i on thread i%15: ten pure
    # background threads (> the colocated fleet's 8 seats, so its seats
    # never drain) and five probe threads whose long-prompt probes ride
    # the saturated window
    n_workers = 15

    def _run_fleet(roles):
        servers, urls = [], []
        for role in roles:
            eng = _engine(role)
            eng.warmup()
            srv = serve(eng, port=0, block=False, supervise=False,
                        handle_signals=False)
            servers.append(srv)
            urls.append(f"http://127.0.0.1:{srv.server_address[1]}")
        router = Router(urls, probe_interval=3600, retry_backoff=0.02)
        router.probe_once()
        lat = [None] * len(reqs)
        results = [None] * len(reqs)

        def _one(i):
            t_req = time.perf_counter()
            deadline = t_req + 300.0
            while True:
                status, body, headers = router.handle_generate(
                    dict(reqs[i][0])
                )
                if status == 200 or not body.get("retriable") \
                        or time.perf_counter() > deadline:
                    break
                time.sleep(min(float(headers.get("Retry-After", 1)), 0.2))
            lat[i] = time.perf_counter() - t_req
            results[i] = (status, body.get("tokens"))

        def _client(j):
            for i in range(j, len(reqs), n_workers):
                _one(i)

        t_base = time.perf_counter()
        threads = [threading.Thread(target=_client, args=(j,))
                   for j in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_base
        router.stop()
        for srv in servers:
            try:
                srv.engine.stop()
            except Exception:
                pass
            srv.shutdown()
            srv.server_close()
        ok = all(r is not None and r[0] == 200 for r in results)
        ident = ok and all(
            list(r[1]) == ref_tokens[i] for i, r in enumerate(results)
        )
        probe_lat = sorted(
            l for l, (_, probe) in zip(lat, reqs) if probe
        )
        toks = sum(len(r[1]) for r in results if r and r[1] is not None)
        return {
            "all_200": ok,
            "bit_identical": bool(ident),
            "ttft_p50_s": probe_lat[len(probe_lat) // 2],
            "ttft_p95_s": probe_lat[int(len(probe_lat) * 0.95)],
            "tokens_per_sec": toks / wall,
        }

    with _sanitized_serving() as _san:
        colo = _run_fleet(("colocated", "colocated"))
        profiler.reset_disagg()
        disagg = _run_fleet(("prefill", "decode"))
    san = _sanitizer_summary(_san)
    dis = profiler.disagg_summary()

    cut = 1.0 - disagg["ttft_p95_s"] / max(colo["ttft_p95_s"], 1e-9)
    tput_ratio = disagg["tokens_per_sec"] / max(colo["tokens_per_sec"], 1e-9)
    correct = bool(
        colo["all_200"] and disagg["all_200"]
        and colo["bit_identical"] and disagg["bit_identical"]
    )
    gate = throughput_gate(
        cut, 0.15, True, key="min_ttft_p95_cut",
        unexpected_recompiles=san["unexpected_recompiles"],
    )
    gate.update(
        min_tokens_per_sec_ratio=0.7,
        tokens_per_sec_ratio=round(tput_ratio, 3),
        bit_identical=correct,
    )
    gate["ok"] = bool(gate["ok"] and correct and tput_ratio >= 0.7)
    return {
        "metric": "disagg_ttft_p95_cut_vs_colocated",
        "value": round(cut, 3),
        "unit": "frac",
        "requests": len(reqs),
        "probes": sum(1 for _, p in reqs if p),
        "probe_prompt_len": probe_prompt,
        "background_prompt_len": bg_prompt,
        "background_new_tokens": bg_new,
        "client_threads": n_workers,
        "colocated_ttft_p50_s": round(colo["ttft_p50_s"], 4),
        "colocated_ttft_p95_s": round(colo["ttft_p95_s"], 4),
        "disagg_ttft_p50_s": round(disagg["ttft_p50_s"], 4),
        "disagg_ttft_p95_s": round(disagg["ttft_p95_s"], 4),
        "colocated_tokens_per_sec": round(colo["tokens_per_sec"], 1),
        "disagg_tokens_per_sec": round(disagg["tokens_per_sec"], 1),
        "handoff_bytes": dis["handoff_bytes"],
        "handoff_bytes_per_request": (
            dis["handoff_bytes"] // max(dis["exports"], 1)
        ),
        "pair_picks": dis["pair_picks"],
        "bit_identical": correct,
        "sanitizer": san,
        "gate": gate,
        "note": "same closed-loop decode-heavy stream (10 background "
        "client threads of short-prompt long streams — more than the "
        "colocated fleet's 8 seats, so they stay full all window — plus 5 "
        "threads of long-prompt max_new_tokens=1 TTFT probes) through 2 "
        "colocated engines, then 1 prefill + 1 role-sized decode worker "
        "joined by the int8 paged-KV handoff; gate = >= 15% probe "
        "TTFT p95 cut at >= 0.7x aggregate tokens/s, all tokens "
        "bit-identical to the undisturbed single-engine reference",
    }


def bench_trace_overhead():
    """FLAGS_trace cost on the serving hot path (ISSUE 10): the same
    Poisson workload through two identically-configured engines, span
    recording off then on.  Tracing is pure host-side bookkeeping, so the
    gate is twofold: p50 TTFT overhead <= 5% (enforced on TPU; CPU timing
    is noise) and — everywhere — ZERO unexpected recompiles or host syncs
    under the sanitizer with tracing enabled."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.framework import core as fcore
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.obs import trace as obs_trace

    on_tpu = _on_tpu()
    cfg = LlamaConfig.tiny(
        hidden_size=256, intermediate_size=512, num_hidden_layers=4,
        num_attention_heads=8, num_key_value_heads=8,
    )
    slots, n_req, prompt, lo, hi = 4, 16, 8, 4, 32
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, cfg.vocab_size, (n_req, prompt)).astype(np.int32)
    new_toks = np.exp(
        rng.uniform(np.log(lo), np.log(hi + 1), size=n_req)
    ).astype(np.int64).clip(lo, hi)
    gaps = np.random.RandomState(1).exponential(0.0005, size=n_req)

    def _leg(traced):
        fcore.set_flags({"FLAGS_trace": bool(traced)})
        obs_trace.reset()
        profiler.reset_serving()
        # fresh engine per leg (scheduler threads don't restart); both
        # share `model`, so the second leg reuses the compiled executables
        eng = ContinuousBatchingEngine(
            model, slots=slots, max_len=prompt + hi,
            prefill_buckets=[prompt], queue_depth=n_req, seed=0,
        )
        eng.warmup()
        with _sanitized_serving() as san:
            eng.start()
            handles = []
            t0 = time.perf_counter()
            for i in range(n_req):
                time.sleep(gaps[i])
                handles.append(eng.submit(
                    prompts[i], max_new_tokens=int(new_toks[i]),
                    trace=(obs_trace.new_trace_id(), None) if traced else None,
                ))
            for h in handles:
                h.wait(timeout=600)
            wall = time.perf_counter() - t0
            eng.stop()
        s = profiler.serving_summary()
        return {
            "wall_s": round(wall, 4),
            "ttft_p50_ms": round(s.get("ttft_p50_ms", 0.0), 3),
            "spans_recorded": obs_trace.stats()["spans_recorded"],
            "sanitizer": _sanitizer_summary(san),
        }

    try:
        off = _leg(False)
        on = _leg(True)
    finally:
        fcore.set_flags({"FLAGS_trace": False})
        obs_trace.reset()
    overhead = (
        on["ttft_p50_ms"] / off["ttft_p50_ms"] - 1.0
        if off["ttft_p50_ms"] > 0 else 0.0
    )
    bad = sum(
        leg["sanitizer"]["unexpected_recompiles"]
        + leg["sanitizer"]["unexpected_syncs"]
        for leg in (off, on)
    )
    return {
        "metric": "serving_trace_p50_overhead",
        "value": round(overhead, 4),
        "unit": "frac",
        "untraced": off,
        "traced": on,
        "wall_overhead_frac": round(on["wall_s"] / off["wall_s"] - 1.0, 4),
        "gate": {
            # timing bar binds on TPU; the sanitizer bar (tracing must add
            # zero recompiles and zero host syncs) binds everywhere
            "max_p50_overhead_frac": 0.05,
            "enforced": bool(on_tpu or bad > 0),
            "ok": (overhead <= 0.05 or not on_tpu) and bad == 0,
            "unexpected_recompiles": int(bad),
        },
        "note": "same Poisson workload, span recording off vs on; traced "
        "leg records engine.queue/prefill/decode/fetch spans per request",
    }


def bench_moe():
    """MoE throughput (SURVEY §2.2 EP): a GShard top-2 MoE FFN block,
    fwd+bwd+aux tokens/s on one chip (the dense dispatch path; the EP
    all-to-all path is validated on the CPU mesh + dryrun)."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.moe import MoELayer

    on_tpu = _on_tpu()
    if on_tpu:
        d_model, d_hidden, experts, batch, seq, steps = 1024, 4096, 8, 8, 1024, 12
    else:
        d_model, d_hidden, experts, batch, seq, steps = 16, 32, 4, 2, 8, 2

    paddle.seed(0)
    moe = MoELayer(d_model=d_model, d_hidden=d_hidden, num_experts=experts, top_k=2)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=moe.parameters())

    @paddle.jit.to_static
    def step(x):
        out = moe(x)
        loss = (out.astype("float32") ** 2).mean() + 0.01 * moe.aux_loss
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss, moe.aux_loss, moe.drop_stats["dropped_fraction"]

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, seq, d_model).astype(np.float32))
    cc0 = _cache_probe()
    t0 = time.perf_counter()
    out = step(x)
    out[0].numpy()
    t_first = time.perf_counter() - t0
    cc_delta = _cache_delta(cc0)
    rates = []
    for _ in range(3 if on_tpu else 1):  # median-of-3, same as the other legs
        t0 = time.perf_counter()
        for _ in range(steps):
            out = step(x)
        aux = float(out[1].numpy())  # syncs the window
        dropf = float(out[2].numpy())
        rates.append(batch * seq * steps / (time.perf_counter() - t0))
    return {
        "metric": "moe_gshard_tokens_per_sec",
        "value": round(sorted(rates)[len(rates) // 2], 1),
        "unit": "tokens/s",
        "time_to_first_step_s": round(t_first, 3),
        "compile_cache": cc_delta,
        "aux_loss": round(aux, 4),
        "dropped_fraction": round(dropf, 4),
        "note": f"{experts}-expert top-2 GShard FFN {d_model}->{d_hidden}, fwd+bwd+opt",
    }


# ---------------------------------------------------------------------------
# config 3: BERT-base (SQuAD-shaped QA head, seq 384)
# ---------------------------------------------------------------------------


def bench_bert():
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertConfig, BertForQuestionAnswering

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = BertConfig.bert_base(hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
        batch, seqlen, steps = 32, 384, 30
    else:
        cfg = BertConfig.tiny()
        batch, seqlen, steps = 4, 64, 2

    paddle.seed(0)
    model = BertForQuestionAnswering(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-5, parameters=model.parameters())
    if on_tpu:
        model, opt = paddle.amp.decorate(model, opt, level="O2", dtype="bfloat16")
    n_params = sum(p.size for p in model.parameters())

    @paddle.jit.to_static
    def train_step(ids, mask, starts, ends):
        loss, _, _ = model(
            ids, attention_mask=mask, start_positions=starts, end_positions=ends
        )
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seqlen)).astype(np.int32))
    # realistic SQuAD batch: variable lengths, padded to seqlen — the
    # padding mask rides as segment ids so the Pallas kernel stays engaged
    lens = rng.randint(seqlen // 2, seqlen + 1, (batch,))
    mask_np = (np.arange(seqlen)[None, :] < lens[:, None]).astype(np.int64)
    mask = paddle.to_tensor(mask_np)
    st = paddle.to_tensor(rng.randint(0, seqlen // 2, (batch,)).astype(np.int64))
    en = paddle.to_tensor(rng.randint(0, seqlen // 2, (batch,)).astype(np.int64))
    cc0 = _cache_probe()
    dt, t_first = _time_steps(train_step, (ids, mask, st, en), steps)
    ex_s = batch * steps / dt
    return {
        "metric": "bert_base_qa_examples_per_sec",
        "value": round(ex_s, 1),
        "unit": "examples/s",
        **_mfu_fields(n_params, batch * seqlen * steps / dt),
        "time_to_first_step_s": round(t_first, 3),
        "compile_cache": _cache_delta(cc0),
        "params": n_params,
    }


# ---------------------------------------------------------------------------
# long context: 32k-seq attention — flash vs ring building block (SURVEY §5.7)
# ---------------------------------------------------------------------------


def bench_longcontext_32k():
    """fwd+bwd attention step time at 32k tokens on one chip.

    - flash: the Pallas kernel over the full [1, 32k, h, d] sequence —
      also the per-chip cost of the Ulysses (sep) path, whose all-to-alls
      just re-shard heads around an identical kernel invocation.
    - ring(1/R): ONE device's work in an R=8 ring — q shard [1, 4k] against
      8 rotating KV blocks through the online-softmax merge (comm rides ICI
      in a real ring and overlaps).  Parity bar: ring wall time should be
      within ~1.5x of flash_total/R (the perfectly-split wall time).
    """
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.flash_attention import sdpa_array

    S, H, D, R = 32768, 8, 128, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, S, H, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, S, H, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, S, H, D), jnp.bfloat16)

    def flash_loss(q, k, v):
        out = sdpa_array(q, k, v, None, True, None)
        return (out.astype(jnp.float32) ** 2).mean()

    flash_step = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))

    def time_it(fn, *args, iters=3):
        # the window ends on a one-element host transfer, which waits for
        # the result; median of 3 windows, and the ratio metric divides two
        # of these
        np.asarray(fn(*args)[0][0, 0, 0])
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                r = fn(*args)
            np.asarray(r[0][0, 0, 0])
            rates.append((time.perf_counter() - t0) / iters)
        return sorted(rates)[1]

    t_flash = time_it(flash_step, q, k, v)

    # one CP device's work under the library's gathered-KV zig-zag layout
    # (ring_attention.py _gathered_zigzag_cp_local): q chunks (i, 2R-1-i)
    # each run ONE rectangular offset-causal Pallas kernel over the full
    # KV (2 fwd + 4 bwd launches/device, work balanced by construction).
    # The all-gather/reduce-scatter ride ICI in deployment; device R-1's
    # static schedule is materialized here — all devices are equal.
    from paddle_tpu.ops import flash_attention as fa

    c = S // (2 * R)
    scale = 1.0 / np.sqrt(D)
    qf_all = q.transpose(0, 2, 1, 3).reshape(H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(H, S, D)

    def chunk(x, i):
        return x[:, i * c : (i + 1) * c]

    qz = jnp.concatenate([chunk(qf_all, R - 1), chunk(qf_all, R)], axis=1)
    bq = fa._pick_block(c, 1024)
    starts = fa.q_block_starts([((R - 1) * c, c), (R * c, c)], bq)

    @jax.custom_vjp
    def ring_core(qz, kf, vf):
        return fa._pallas_flash_forward(
            qz, kf, vf, True, scale, q_offset=starts, block_q=bq)[0]

    def fwd_rule(qz, kf, vf):
        out, lse = fa._pallas_flash_forward(
            qz, kf, vf, True, scale, q_offset=starts, block_q=bq)
        return out, (qz, kf, vf, out, lse)

    def bwd_rule(res, g):
        qz, kf, vf, out, lse = res
        return fa._pallas_flash_backward(
            qz, kf, vf, g, out, lse, True, scale, q_offset=starts, block_q=bq)

    ring_core.defvjp(fwd_rule, bwd_rule)

    def ring_device_loss(qz, kf, vf):
        return (ring_core(qz, kf, vf).astype(jnp.float32) ** 2).mean()

    ring_step = jax.jit(jax.grad(ring_device_loss, argnums=(0, 1, 2)))
    t_ring = time_it(ring_step, qz, kf, vf)

    # balanced layout: the fair split of causal flash is t_flash / R.
    # (round-4 reported t_ring/(2*t_flash/R) for the UNBALANCED last
    # device doing ~2x the average; that convention is kept as a second
    # field for cross-round continuity)
    ratio = t_ring / (t_flash / R)
    ratio_r4 = t_ring / (2 * t_flash / R)
    return {
        "metric": "attention_32k_fwd_bwd_ms",
        "value": round(t_flash * 1000, 1),
        "unit": "ms",
        "flash_ms": round(t_flash * 1000, 1),
        "ring_per_device_ms": round(t_ring * 1000, 1),
        "ring_vs_split_flash": round(ratio, 2),
        "ring_vs_split_flash_r4_convention": round(ratio_r4, 2),
        "note": "flash == Ulysses per-chip cost; ring uses the BALANCED "
        "zig-zag chunk layout (device i holds chunks i and 2R-1-i, exactly "
        "2R+1 causal half-blocks each — the library's causal CP path), "
        "hops merge in-kernel via the (out,lse) carry, delta hop-invariant; "
        "denominator is the fair split t_flash/R of the same total work",
    }


def bench_longcontext_serving():
    """Long-context serving tier (ISSUE 20): context-parallel paged decode
    plus first-class session KV, measured end to end through the engine.

    - decode ratio: ONE request decodes greedily behind a 64k-token prompt
      on a cp=8 engine (pages round-robin across shards, online-softmax
      partials merged via pmax/psum) vs a 4k-token prompt on a cp=1
      engine.  The bar — long-context tokens/s PER CHIP >= 0.5x the 4k
      baseline — binds on TPU only: per shard the 64k context is 8k rows,
      ~2x the baseline's attention work, so 0.5x is the "sharding actually
      split the reads" line.  CPU runs a scaled proxy (96 tokens over
      cp=2 vs 24 over cp=1) for layout correctness, not speed.
    - session savings: a 12-turn conversation rides one `session_id`;
      every turn after the first must skip >= 90% of its prefill tokens
      (the committed pages are pinned, only the unshared suffix chunks
      through prefill) while staying bit-identical to a stateless engine
      replaying the full transcript.  Enforced on BOTH tiers — the saving
      is page-table math, not throughput noise.
    - zero unexpected recompiles under the sanitizer across all engines:
      session rope offsets and cp page tables are data, not shapes."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = _on_tpu()
    cp = 8 if on_tpu else 2
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=12,
            num_attention_heads=16,
            num_key_value_heads=16,
            max_position_embeddings=65536 + 256,
        )
        long_prompt, short_prompt, new_toks, page_size = 65536, 4096, 64, 32
        long_buckets, short_buckets = [512, 65536], [512, 4096]
        sess_len, sess_buckets = 1024, [64, 512]
        turn0, turn_gen, turn_extra = 256, 32, 16
    else:
        cfg = LlamaConfig.tiny()
        long_prompt, short_prompt, new_toks, page_size = 96, 24, 12, 8
        long_buckets, short_buckets = [8, 96], [8, 24]
        sess_len, sess_buckets = 192, [8, 128]
        turn0, turn_gen, turn_extra = 12, 3, 2
    turns = 12

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")

    rng = np.random.RandomState(0)

    def _decode_rate(prompt_len, buckets, cp_deg):
        """Decode-only tokens/s for ONE greedy request behind prompt_len
        context (TTFT — the chunked prefill — is reported separately, the
        ratio gates decode)."""
        eng = ContinuousBatchingEngine(
            model, slots=1, max_len=prompt_len + new_toks + 8,
            prefill_buckets=buckets, queue_depth=2, seed=0,
            paged=True, page_size=page_size,
            cp=cp_deg if cp_deg > 1 else None,
        )
        eng.warmup()
        warm = eng.compile_counts()
        profiler.reset_serving()
        prompt = rng.randint(1, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        t0 = time.perf_counter()
        h = eng.submit(prompt, max_new_tokens=new_toks)
        eng.run_until_idle()
        out = h.wait(timeout=1200)
        wall = time.perf_counter() - t0
        g = profiler.metrics_snapshot()["serving"]
        ttft = g["ttfts_s"][0] if g["ttfts_s"] else 0.0
        gen = len(out) - prompt_len
        return {
            "rate": gen / max(wall - ttft, 1e-9),
            "ttft_s": ttft,
            "generated": gen,
            "compiles_frozen": eng.compile_counts() == warm,
        }

    def _session_replay():
        """12 turns down one session vs a stateless engine replaying the
        transcript; returns (saved_frac, identical, frozen)."""
        sess = ContinuousBatchingEngine(
            model, slots=2, max_len=sess_len, prefill_buckets=sess_buckets,
            queue_depth=16, seed=0, paged=True, page_size=page_size,
        )
        sess.warmup()
        warm = sess.compile_counts()
        stateless = ContinuousBatchingEngine(
            model, slots=2, max_len=sess_len, prefill_buckets=sess_buckets,
            queue_depth=16, seed=0, paged=True, page_size=page_size,
            prefix_cache=False,
        )

        def _turn(eng, conv, sid=None):
            req = eng.submit(np.asarray(conv, np.int32),
                             max_new_tokens=turn_gen, session_id=sid)
            eng.run_until_idle()
            return req, list(req.wait(timeout=600).tolist())

        conv = rng.randint(1, cfg.vocab_size, (turn0,)).astype(np.int32)
        conv = conv.tolist()
        total = saved = 0
        identical = True
        for t in range(turns):
            req, out = _turn(sess, conv, sid="bench-conv")
            _, ref = _turn(stateless, conv)
            identical = identical and out == ref
            if t > 0:
                total += len(conv)
                saved += req.session_reused_tokens
            conv = out + rng.randint(
                1, cfg.vocab_size, (turn_extra,)).astype(np.int32).tolist()
        frozen = sess.compile_counts() == warm
        return saved / max(total, 1), identical, frozen

    cp_possible = len(jax.devices()) >= cp
    prev_mesh = _mesh.get_mesh()
    try:
        with _sanitized_serving() as _san:
            saved_frac, identical, sess_frozen = _session_replay()
            # cp=1 baseline traces BEFORE the cp engine installs a global
            # mesh, so its executables cannot see cp device placement
            short = _decode_rate(short_prompt, short_buckets, 1)
            long_ = (_decode_rate(long_prompt, long_buckets, cp)
                     if cp_possible else None)
        san = _sanitizer_summary(_san)
    finally:
        _mesh.set_mesh(prev_mesh)

    sess_gauges = profiler.metrics_snapshot()["sessions"]
    if long_ is not None:
        # per-chip: the cp engine spreads one decode over cp chips
        ratio = (long_["rate"] / cp) / max(short["rate"], 1e-9)
        frozen = bool(sess_frozen and short["compiles_frozen"]
                      and long_["compiles_frozen"])
    else:
        ratio = 0.0
        frozen = bool(sess_frozen and short["compiles_frozen"])
    gate = throughput_gate(
        ratio, 0.5, on_tpu and cp_possible,
        key="min_long_vs_short_per_chip_decode",
        unexpected_recompiles=san["unexpected_recompiles"],
    )
    correct = bool(saved_frac >= 0.90 and identical and frozen)
    gate.update(
        min_prefill_saved=0.90, prefill_saved=round(saved_frac, 4),
        session_tokens_identical=identical, compiles_frozen=frozen,
    )
    gate["enforced"] = bool(gate["enforced"] or not correct)
    gate["ok"] = gate["ok"] and correct
    return {
        "metric": "longctx_vs_short_per_chip_decode",
        "value": round(ratio, 3),
        "unit": "x",
        "cp": cp if cp_possible else 1,
        "long_prompt": long_prompt,
        "short_prompt": short_prompt,
        "long_decode_tokens_per_sec": (
            round(long_["rate"], 1) if long_ else None),
        "long_ttft_s": round(long_["ttft_s"], 3) if long_ else None,
        "short_decode_tokens_per_sec": round(short["rate"], 1),
        "short_ttft_s": round(short["ttft_s"], 3),
        "session_turns": turns,
        "session_prefill_saved": round(saved_frac, 4),
        "session_tokens_identical": identical,
        "session_gauges": {
            "binds": sess_gauges["session_binds_total"],
            "prefill_tokens_saved":
                sess_gauges["session_prefill_tokens_saved_total"],
            "evictions": sess_gauges["session_evictions_total"],
        },
        "compiles_frozen": frozen,
        "sanitizer": san,
        "gate": gate,
        **({} if cp_possible else {
            "cp_skipped": f"needs {cp} devices, found {len(jax.devices())}; "
            "CPU tier runs under XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 (see ci.sh)"}),
        "note": "decode ratio is tokens/s PER CHIP behind the long prompt "
        "(cp engine, pages round-robin across shards, softmax partials "
        "merged via pmax/psum) vs the 4k cp=1 baseline — the 0.5x bar "
        "binds on TPU; the >=90% session prefill saving and bit-identical "
        "replay bind on BOTH tiers; TTFT (the chunked prefill) is "
        "reported but not gated here",
    }


# ---------------------------------------------------------------------------
# loss-parity gates vs the CPU oracle (configs 1 and 4, tiny)
# ---------------------------------------------------------------------------


def _oracle_losses():
    """Deterministic 5-step loss curves for tiny LeNet + tiny Llama."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.vision.models import LeNet

    out = {}
    rng = np.random.RandomState(0)

    paddle.seed(0)
    lenet = LeNet()
    opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=lenet.parameters())
    ce = nn.CrossEntropyLoss()
    x = paddle.to_tensor(rng.rand(16, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (16,)).astype(np.int64))
    losses = []
    for _ in range(5):
        loss = ce(lenet(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    out["lenet"] = losses

    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (4, 64)).astype(np.int32))

    @paddle.jit.to_static
    def step(b):
        loss, _ = model(b, labels=b)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    out["tiny_llama"] = [float(step(ids).numpy()) for _ in range(5)]
    return out


def parity_gates():
    """Run the tiny curves here and in a pure-CPU subprocess; gate on match
    (SURVEY.md §6 loss-parity contract; trivially equal on CPU-only CI)."""
    mine = _oracle_losses()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    )
    repo = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--oracle"],
        env=env,
        cwd=repo,
        capture_output=True,
        text=True,
        timeout=900,
    )
    if proc.returncode != 0:
        return {"ok": False, "error": f"oracle rc={proc.returncode}: {proc.stderr[-300:]}"}
    oracle = json.loads(proc.stdout.strip().splitlines()[-1])
    report = {"ok": True}
    # fp32-on-MXU reduction order differs from the CPU oracle; convs (LeNet)
    # drift more than matmul stacks over 5 SGD steps (measured ~6e-3 rel)
    tols = {"lenet": 2e-2, "tiny_llama": 5e-3}
    for k in ("lenet", "tiny_llama"):
        a, b = np.asarray(mine[k]), np.asarray(oracle[k])
        match = bool(np.allclose(a, b, rtol=tols[k], atol=1e-4))
        report[k] = {"match": match, "max_rel_err": float(np.max(np.abs(a - b) / (np.abs(b) + 1e-9)))}
        report["ok"] = report["ok"] and match
    return report


# ---------------------------------------------------------------------------


def main():
    if "--oracle" in sys.argv:
        print(json.dumps(_oracle_losses()))
        return

    headline = bench_llama()
    configs = {}
    # lenet_eager runs BEFORE the serving legs: the r05 lenet regression
    # (65.3 -> 42.0 steps/s) was partly serving-engine process state (live
    # scheduler threads, device allocations, executable caches) bleeding
    # into the eager-dispatch measurement.  gc between configs for the same
    # reason — each config's numbers should not depend on its neighbours.
    import gc

    for name, fn in (
        ("resnet50_amp_o2", bench_resnet50),
        ("bert_base_qa", bench_bert),
        ("lenet_eager", bench_lenet_eager),
        ("llama_decode", bench_llama_decode),
        ("llama_serving", bench_llama_serving),
        ("paged_serving", bench_paged_serving),
        ("spec_decode", bench_llama_spec_decode),
        ("lora_serving", bench_lora_serving),
        ("paged_decode_kernel", bench_paged_decode_kernel),
        ("tp_decode", bench_tp_decode),
        ("kv_quant_serving", bench_kv_quant_serving),
        ("router_failover", bench_router),
        ("autoscale_soak", bench_soak),
        ("router_ha", bench_router_ha),
        ("disagg_serving", bench_disagg_serving),
        ("longcontext_serving", bench_longcontext_serving),
        ("trace_overhead", bench_trace_overhead),
        ("hapi_async", bench_hapi_async),
        ("moe_gshard", bench_moe),
    ):
        try:
            configs[name] = fn()
        except Exception as e:  # recorded, printed, then a non-zero exit
            configs[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
        finally:
            # zero every profiler counter family between legs: each
            # config's gauges must not include its neighbours' traffic
            from paddle_tpu import profiler as _prof

            _prof.reset()
            gc.collect()
    if _on_tpu():
        try:
            configs["llama_deep_remat"] = bench_llama(deep=True)
        except Exception as e:
            configs["llama_deep_remat"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        try:
            configs["attention_32k"] = bench_longcontext_32k()
        except Exception as e:
            configs["attention_32k"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    try:
        configs["loss_parity"] = parity_gates()
    except Exception as e:
        configs["loss_parity"] = {"ok": False, "error": f"{type(e).__name__}: {e}"[:300]}

    try:  # end-of-run cache totals to stderr (stdout stays one JSON line)
        from paddle_tpu.jit import cache_report

        print(cache_report(), file=sys.stderr)
    except Exception:
        pass

    # per-config throughput gates: a config may carry {"gate": {...,
    # "enforced": bool, "ok": bool}}; an enforced failing gate fails the
    # whole bench run (nonzero exit) AFTER the full matrix printed, so the
    # numbers behind the failure are always in the output
    gate_failures = [
        name for name, r in configs.items()
        if isinstance(r.get("gate"), dict)
        and r["gate"].get("enforced")
        and not r["gate"].get("ok")
    ]

    if "--all" in sys.argv:
        print(json.dumps(headline))
        for name, r in configs.items():
            print(json.dumps({"config": name, **r}))
    else:
        print(json.dumps({**headline, "configs": configs}))

    errored = [name for name, r in configs.items() if "error" in r]
    for name in gate_failures:
        print(
            f"bench gate FAILED: {name} value {configs[name].get('value')}"
            f" < {configs[name]['gate']}", file=sys.stderr,
        )
    for name in errored:
        print(f"bench leg RAISED: {name}: {configs[name]['error']}",
              file=sys.stderr)
    if gate_failures or errored:
        sys.exit(1)


if __name__ == "__main__":
    main()
