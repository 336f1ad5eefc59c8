"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

One process drives the two main paths once, through the entry points a user
calls, at the widths of a 1B-class Llama (serve) and a 2560-wide one
(train), with seeded random weights:

- serve: `LlamaForCausalLM` -> `ContinuousBatchingEngine` (its defaults:
  paged KV, page size 128, decode kernel `auto`, prefix cache on) ->
  `warmup()` -> `inference.serve()` -> concurrent `POST /generate`;
- train: `amp.decorate` O2 + `AdamW` + a `@paddle.jit.to_static` step;
- latent serve: a small `DeepseekV32ForCausalLM` (latent and indexer caches,
  top-k selection, held experts) through the same engine for a few tokens,
  one prompt in chunks;
- compile cache: where jax's persistent cache is, and its hit counters;
- four chips (when present): the serve phase at tensor-parallel degree 4
  and one hybrid dp x mp train step through `fleet.init`.

Every phase checks its own output — shapes, finiteness, frozen compile
counts, which Pallas kernels were traced, no XLA fallback, agreement with
the gather oracle — and raises on the first thing that is wrong.  Nothing is
caught and carried past: any failure is a traceback and a non-zero exit.
Without an accelerator it exits non-zero before doing any work.

On success the last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.

Sizes are arguments of the phase functions, so `tests/test_chip_smoke.py`
drives the same functions at `LlamaConfig.tiny()` on the CPU.
"""

from __future__ import annotations

import gc
import json
import sys
import threading
import time
import urllib.request

import numpy as np

SERVE_CONFIG = dict(
    vocab_size=32000, hidden_size=2048, intermediate_size=5632,
    num_hidden_layers=12, num_attention_heads=16, num_key_value_heads=16,
    max_position_embeddings=2048,
)
TRAIN_CONFIG = dict(
    vocab_size=32000, hidden_size=2560, intermediate_size=6912,
    num_hidden_layers=6, num_attention_heads=20, num_key_value_heads=20,
    max_position_embeddings=2048,
)


SERVE_SIZES = dict(
    slots=8, max_len=2048, buckets=[128, 256, 512],
    # 3 requests share a 192-token prefix (one full 128-token page plus half
    # of the next) and generate across the 256-token page boundary
    shared_prefix=192, shared_suffixes=[40, 44, 48],
    lone_lengths=[100, 300, 450], new_tokens=48,
    # random weights: a top logit flips on bf16 rounding between the Pallas
    # and the XLA reduction, so compare few tokens, not strings
    first_k=4, min_agree=0.5,
    # attention outputs of unit-variance values.  bf16 pages: both paths
    # round p and v to bf16 (2^-8) and differ in reduction order; 2e-3
    # measured.  int8 pages: both dequantize to f32, but XLA's default f32
    # matmul on the TPU is one bf16 pass and Mosaic's is not, so the ORACLE
    # is the rougher side; 1.6e-2 measured
    kernel_tol=1e-2, kernel_tol_int8=5e-2,
)
TRAIN_SIZES = dict(batch=8, seqlen=2048, steps=4)
# DeepseekV32Config at a tenth of its widths: 8 of 64 experts held, a top-k of
# 256 under contexts of 300-700, the 700-token prompt in chunks of 256
LATENT_CONFIG = dict(
    vocab_size=4096, hidden_size=1024, intermediate_size=2048, moe_intermediate_size=256,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=16,
    num_key_value_heads=16, q_lora_rank=256, kv_lora_rank=128, qk_nope_head_dim=64,
    qk_rope_head_dim=32, v_head_dim=64, index_n_heads=8, index_head_dim=64, index_topk=256,
    n_routed_experts=64, experts_held=8, max_position_embeddings=1024,
)
LATENT_SIZES = dict(slots=4, max_len=1024, buckets=[128, 256], lengths=[300, 700, 100],
                    new_tokens=8)


def log(msg):
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


_T0 = time.perf_counter()


def device_report():
    """The device as jax reports it: the `device` object of the result."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def log_memory(where):
    """Device memory at a phase boundary (the CPU backend reports none)."""
    import jax

    for d in jax.devices():
        m = d.memory_stats()
        if m:
            log(f"memory {where}: {d} in_use={m['bytes_in_use'] / 2**30:.2f}G "
                f"peak={m['peak_bytes_in_use'] / 2**30:.2f}G "
                f"limit={m['bytes_limit'] / 2**30:.2f}G")


def build_llama(config, *, amp, seed=0, tp=1, weights_from=None):
    """A seeded `LlamaForCausalLM`; bf16 through `amp.decorate` O2 when
    `amp`.  `weights_from` copies another model's weights in (the
    tensor-parallel twin of an already built model)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(seed)
    model = LlamaForCausalLM(LlamaConfig(tensor_parallel_degree=tp, **config))
    if amp:
        model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    if weights_from is not None:
        model.set_state_dict(weights_from.state_dict())
    return model


def make_prompts(vocab_size, *, shared_prefix, shared_suffixes, lone_lengths,
                 seed=0):
    """Token-id prompts: one run per entry of `shared_suffixes` that starts
    with the same `shared_prefix` tokens (prefix-cache hits, chunked prefill
    of the unshared suffix), then one unrelated prompt per `lone_lengths`."""
    rng = np.random.RandomState(seed)
    draw = lambda n: rng.randint(1, vocab_size, size=n).astype(np.int32)
    prefix = draw(shared_prefix)
    prompts = [np.concatenate([prefix, draw(n)]) for n in shared_suffixes]
    return prompts + [draw(n) for n in lone_lengths]


def post_generate(url, prompt, max_new_tokens):
    body = json.dumps({
        "input_ids": [int(t) for t in prompt],
        "max_new_tokens": int(max_new_tokens),
    }).encode()
    req = urllib.request.Request(
        url + "/generate", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=600) as r:
        return r.status, json.loads(r.read())


def serve_over_http(engine, prompts, max_new_tokens):
    """`inference.serve` in front of `engine`, every prompt POSTed at once
    from its own thread; every answer must be a 200 that echoes its prompt
    and carries `max_new_tokens` new tokens."""
    from paddle_tpu import inference

    server = inference.serve(engine, port=0, block=False)
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}"
    results = [None] * len(prompts)

    def client(i):
        try:
            results[i] = post_generate(url, prompts[i], max_new_tokens)
        except Exception as e:  # reported below, on the main thread
            results[i] = (type(e).__name__, str(e))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(len(prompts))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        check(not any(t.is_alive() for t in threads), "an HTTP client hung")
    finally:
        server.drain(grace=5.0).join(timeout=60)
        server.server_close()
    for i, (prompt, (status, body)) in enumerate(zip(prompts, results)):
        check(status == 200, f"request {i}: HTTP {status}: {body}")
        toks = body["tokens"]
        check(toks[: len(prompt)] == [int(t) for t in prompt],
              f"request {i}: response does not start with its prompt")
        check(len(toks) - len(prompt) == max_new_tokens,
              f"request {i}: {len(toks) - len(prompt)} new tokens, asked "
              f"for {max_new_tokens}")


def generate_in_order(engine, prompts, max_new_tokens):
    """The same prompts one at a time through `engine.generate`, in a fixed
    order, so two engines make the same prefix-cache decisions and differ
    only by their kernels."""
    out = []
    for p in prompts:
        toks = engine.generate(p, max_new_tokens=max_new_tokens, timeout=600)
        out.append([int(t) for t in toks[len(p):]])
    return out


def agreement(a, b, first_k):
    """Share of requests whose first `first_k` generated tokens agree."""
    same = sum(1 for x, y in zip(a, b) if x[:first_k] == y[:first_k])
    return same / len(a)


def kernel_vs_oracle(*, heads, kv_heads, head_dim, page_size, slots, max_len,
                     dtype, tol, quant):
    """The fused page-walk kernel against the gather-then-dense oracle on
    random pages at the serving width: the repo's own parity check
    (tests/test_fused_paged_attention.py), compiled instead of interpreted."""
    import jax.numpy as jnp

    from paddle_tpu.models.llama import _quantize_kv_rows
    from paddle_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(0)
    per_seq = -(-max_len // page_size)
    pages = slots * per_seq + 1
    k = rng.randn(pages, kv_heads, page_size, head_dim).astype(np.float32)
    v = rng.randn(pages, kv_heads, page_size, head_dim).astype(np.float32)
    q = jnp.asarray(rng.randn(slots, 1, heads, head_dim), dtype)
    tables = 1 + rng.permutation(slots * per_seq).reshape(slots, per_seq)
    pos = rng.randint(0, max_len, size=slots)
    pos[0], pos[-1] = 0, max_len - 1  # a fresh slot and a full one
    args = dict(tables=jnp.asarray(tables, jnp.int32),
                pos=jnp.asarray(pos, jnp.int32), max_len=max_len)
    if quant:
        kq, ks = _quantize_kv_rows(jnp.asarray(k))
        vq, vs = _quantize_kv_rows(jnp.asarray(v))
        args.update(k_scale=jnp.swapaxes(ks, 2, 3), v_scale=jnp.swapaxes(vs, 2, 3))
        ak, av = kq, vq
    else:
        ak, av = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    fused = fa.paged_decode_attention_array(q, ak, av, kernel="fused", **args)
    oracle = fa.paged_decode_attention_array(q, ak, av, kernel="gather", **args)
    fused = np.asarray(fused.astype(jnp.float32))
    oracle = np.asarray(oracle.astype(jnp.float32))
    check(fused.shape == (slots, 1, heads, head_dim), f"bad shape {fused.shape}")
    check(np.isfinite(fused).all(), "fused kernel produced non-finite values")
    err = float(np.abs(fused - oracle).max())
    check(err <= tol, f"fused vs gather oracle: max abs err {err} > {tol}")
    return err


def serve_phase(config, *, amp, slots, max_len, buckets, shared_prefix,
                shared_suffixes, lone_lengths, new_tokens, first_k, min_agree,
                kernel_tol, kernel_tol_int8, page_size=None, tp=1,
                reference=None):
    """Serve a few concurrent HTTP requests from a default-constructed
    engine and check it against the gather engine on the same weights.

    `page_size=None` keeps the engine's default.  With `tp > 1` the model is
    the tensor-parallel twin of the seeded one-chip model and `reference`
    (the one-chip engine's in-order tokens) is what the sharded engine must
    agree with.  Returns {"tokens", "model", "engine"}."""
    import jax.numpy as jnp

    from paddle_tpu import profiler
    from paddle_tpu.inference.engine import ContinuousBatchingEngine

    model = build_llama(config, amp=amp)
    if tp > 1:
        model = build_llama(config, amp=amp, tp=tp, weights_from=model)
    vocab = config["vocab_size"]
    shape = dict(shared_prefix=shared_prefix, shared_suffixes=shared_suffixes,
                 lone_lengths=lone_lengths)
    ordered = make_prompts(vocab, seed=0, **shape)  # one at a time, both engines
    traffic = make_prompts(vocab, seed=1, **shape)  # all at once, over HTTP
    common = dict(slots=slots, max_len=max_len, prefill_buckets=buckets, tp=tp)
    if page_size is not None:
        common["page_size"] = page_size

    profiler.reset_flash_pallas()
    profiler.reset_flash_fallbacks()
    profiler.reset_paging()
    engine = ContinuousBatchingEngine(model, **common)
    check(engine.decode_kernel == "auto",
          "the default engine's decode kernel is not 'auto'")
    ps = engine.page_size
    shared = traffic[: len(shared_suffixes)]
    check(max(len(p) for p in traffic) <= max(buckets),
          "a prompt outgrows the largest bucket")
    check(shared_prefix >= ps, "the shared prefix does not fill one page")
    check(all(len(p) // ps < (len(p) + new_tokens) // ps for p in shared),
          "the shared-prefix requests do not cross a page boundary")
    engine.warmup()
    warm = engine.compile_counts()
    log(f"serve tp={tp}: warmed {warm}, page_size={ps}, "
        f"pool_pages={engine.pool_pages}")

    tokens = generate_in_order(engine, ordered, new_tokens)
    serve_over_http(engine, traffic, new_tokens)
    check(engine.compile_counts() == warm,
          f"compiles moved under traffic: {warm} -> {engine.compile_counts()}")
    paging = profiler.paging_summary()
    check(paging.get("prefix_hits", 0) >= 2 and paging["prefill_tokens_saved"] > 0,
          f"no prefix-cache hits under shared-prefix traffic: {paging}")

    # every executable above was traced once, so the counters say which
    # path each one took
    kernels = profiler.flash_pallas_summary()
    check(kernels.get("paged_decode_fused", 0) > 0,
          f"the fused page-walk kernel was not traced: {kernels}")
    check(kernels.get("flash_fwd", 0) > 0,
          f"the flash prefill kernel was not traced: {kernels}")
    check(profiler.flash_fallback_summary() == {},
          f"XLA fallbacks: {profiler.flash_fallback_summary()}")
    log(f"serve tp={tp}: {len(traffic)} concurrent requests answered 200; "
        f"kernels {kernels}; prefix hits {paging['prefix_hits']}, "
        f"prefill tokens saved {paging['prefill_tokens_saved']}")

    # agreement with the gather engine: same weights, same request order
    gather = ContinuousBatchingEngine(model, decode_kernel="gather", **common)
    oracle_tokens = generate_in_order(gather.warmup(), ordered, new_tokens)
    del gather
    share = agreement(tokens, oracle_tokens, first_k)
    log(f"serve tp={tp}: first {first_k} greedy tokens agree with the gather "
        f"engine on {share:.2f} of requests (bar {min_agree})")
    check(share >= min_agree,
          f"fused and gather engines agree on {share} < {min_agree}")
    if reference is not None:
        share = agreement(tokens, reference, first_k)
        log(f"serve tp={tp}: agrees with the one-chip engine on {share:.2f}")
        check(share >= min_agree,
              f"tp={tp} and one-chip engines agree on {share} < {min_agree}")

    if tp == 1:
        # the compiled kernels against the oracle, at this width
        kw = dict(heads=config["num_attention_heads"],
                  kv_heads=config["num_key_value_heads"],
                  head_dim=config["hidden_size"] // config["num_attention_heads"],
                  page_size=ps, slots=slots, max_len=max_len,
                  dtype=jnp.bfloat16 if amp else jnp.float32)
        err = kernel_vs_oracle(quant=False, tol=kernel_tol, **kw)
        err8 = kernel_vs_oracle(quant=True, tol=kernel_tol_int8, **kw)
        log(f"serve: fused vs gather oracle max abs err {err:.2e} (tol "
            f"{kernel_tol}), int8 {err8:.2e} (tol {kernel_tol_int8})")

        # one request through an int8-KV engine: the in-VMEM dequant kernel
        profiler.reset_flash_pallas()
        q8 = ContinuousBatchingEngine(
            model, kv_quant="int8", **dict(common, prefill_buckets=buckets[-1:])
        ).warmup()
        warm8 = q8.compile_counts()
        out = generate_in_order(q8, ordered[:1], new_tokens)
        check(len(out[0]) == new_tokens, "the int8 request came back short")
        check(q8.compile_counts() == warm8, "int8 compiles moved under traffic")
        kernels8 = profiler.flash_pallas_summary()
        check(kernels8.get("paged_decode_fused_q8", 0) > 0,
              f"the int8 page-walk kernel was not traced: {kernels8}")
        check(profiler.flash_fallback_summary() == {},
              f"XLA fallbacks: {profiler.flash_fallback_summary()}")
        log(f"serve: int8-KV request done, kernels {kernels8}; first {first_k} "
            f"tokens equal the bf16-KV engine's: "
            f"{out[0][:first_k] == tokens[0][:first_k]}")
        del q8
    return {"tokens": tokens, "model": model, "engine": engine}


def train_phase(config, *, amp, batch, seqlen, steps, hybrid=None):
    """`steps` compiled AdamW steps on one seeded batch: losses finite and
    falling, one trace, the flash kernels in it.  `hybrid={"dp": n, "mp":
    m}` runs the step on a dp x mp mesh through `fleet.init`."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    profiler.reset_flash_pallas()
    profiler.reset_flash_fallbacks()
    mp = hybrid["mp"] if hybrid else 1
    if hybrid:
        from paddle_tpu.distributed import fleet

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": hybrid["dp"], "mp_degree": mp,
            "sharding_degree": 1, "pp_degree": 1,
        }
        fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(tensor_parallel_degree=mp, **config))
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters())
    if amp:
        model, opt = paddle.amp.decorate(model, opt, level="O2", dtype="bfloat16")
    step_model = model
    if hybrid:
        step_model = fleet.distributed_model(model)
        opt = fleet.distributed_optimizer(opt)

    @paddle.jit.to_static
    def train_step(ids):
        loss, _ = step_model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, config["vocab_size"], (batch, seqlen)).astype(np.int32)
    )
    if hybrid:
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.distributed import mesh as pmesh

        pmesh.shard_tensor_(ids, P("dp", None))
    losses = [float(train_step(ids).numpy()) for _ in range(steps)]
    log(f"train{' ' + str(hybrid) if hybrid else ''}: losses "
        + " ".join(f"{x:.4f}" for x in losses))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(train_step.trace_count + train_step.aot_hits == 1,
          f"{train_step.trace_count} traces for one step shape")
    kernels = profiler.flash_pallas_summary()
    check(kernels.get("flash_fwd", 0) > 0 and kernels.get("flash_bwd", 0) > 0,
          f"the flash forward/backward kernels were not traced: {kernels}")
    check(profiler.flash_fallback_summary() == {},
          f"XLA fallbacks: {profiler.flash_fallback_summary()}")
    return {"model": model, "losses": losses}


def latent_serve_phase(config, *, dtype, slots, max_len, buckets, lengths, new_tokens,
                       page_size=None):
    """Serve a few tokens from a `DeepseekV32ForCausalLM`: fresh and chunk
    prefill into the latent and indexer arenas, paged decode under top-k
    selection, the held experts.  Checks the finishes, the frozen compile
    counts and the step's own counters.  Returns the tokens."""
    import paddle_tpu as paddle
    from paddle_tpu import profiler
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models import DeepseekV32Config, DeepseekV32ForCausalLM

    paddle.seed(0)
    cfg = DeepseekV32Config(**config, dtype=dtype)
    engine = ContinuousBatchingEngine(
        DeepseekV32ForCausalLM(cfg), slots=slots, max_len=max_len, prefill_buckets=buckets,
        page_size=page_size)
    engine.warmup()
    warm = engine.compile_counts()
    check(warm["prefill"] == warm["chunk_prefill"] == len(buckets) and warm["decode"] == 1,
          f"latent engine warmed {warm}")
    profiler.reset_moe()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32) for n in lengths]
    tokens = generate_in_order(engine, prompts, new_tokens)
    check(all(len(t) == new_tokens for t in tokens), "a latent request ended short")
    check(engine.compile_counts() == warm, f"latent engine compiled under traffic: "
          f"{engine.compile_counts()} after {warm}")
    moe, sparse = profiler.moe_summary(), profiler.sparse_attn_summary()
    check(moe and moe["picks_held"] > 0, f"no pick landed on a held expert: {moe}")
    over = sum(1 for n in lengths if n > cfg.index_topk)
    check(sparse and sparse["rows_over_topk"] == over * (new_tokens - 1),
          f"selection did not bite as the lengths say: {sparse}")
    log(f"latent serve: {len(prompts)} requests, arenas {profiler.arena_summary()}, "
        f"moe {moe}, sparse {sparse}")
    return tokens


def cache_phase():
    """Where the persistent compile cache is, and what it did so far."""
    from paddle_tpu import jit

    p = jit.cache_info()["persistent"]
    check(p["dir"], "jax's persistent compile cache has no directory")
    log(f"compile cache: dir={p['dir']} requests={p['requests']} "
        f"disk_hits={p['disk_hits']} entries={p['entries']} bytes={p['bytes']}")
    return p


def on_distinct_devices(arrays, n):
    """Every array is laid out over `n` distinct devices, and each of them
    holds bytes."""
    devices = set()
    for arr in arrays:
        check(len(arr.sharding.device_set) == n,
              f"an array sits on {len(arr.sharding.device_set)} devices, not {n}")
        devices |= set(arr.sharding.device_set)
    check(len(devices) == n, f"{len(devices)} distinct devices, not {n}")
    for d in devices:
        stats = d.memory_stats()
        if stats is not None:  # the CPU backend reports none
            check(stats["bytes_in_use"] > 0, f"{d} holds no bytes")


def four_chip_phase(serve_config, train_config, reference, *, amp,
                    serve_sizes, train_sizes, tp, hybrid):
    """The serve phase tensor-parallel over `tp` devices against the
    one-chip engine's tokens, then one hybrid train step; weights and KV
    arena must really be spread over the devices."""
    from paddle_tpu.distributed import mesh as pmesh

    prev = pmesh.get_mesh()
    try:
        out = serve_phase(serve_config, amp=amp, tp=tp, reference=reference,
                          **serve_sizes)
        arena = out["engine"]._arenas[0]
        layer = out["model"].llama.layers[0]
        on_distinct_devices(
            [layer.self_attn.q_proj.weight._raw, layer.mlp.down_proj.weight._raw,
             out["model"].lm_head.weight._raw, arena.k._raw, arena.v._raw],
            tp,
        )
        log(f"four chips: tp={tp} weights and KV arena on {tp} distinct devices")
        del out, arena, layer
        gc.collect()
        pmesh.set_mesh(None)
        trained = train_phase(train_config, amp=amp, hybrid=hybrid, **train_sizes)
        on_distinct_devices(
            [trained["model"].llama.layers[0].mlp.gate_proj.weight._raw],
            hybrid["dp"] * hybrid["mp"],
        )
        log(f"four chips: hybrid {hybrid} train step on "
            f"{hybrid['dp'] * hybrid['mp']} distinct devices")
    finally:
        pmesh.set_mesh(prev)


def main():
    device = device_report()
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        sys.exit("chip_smoke: no TPU — this script proves the chip path and "
                 "does not run without one")

    from paddle_tpu import native
    from paddle_tpu.ops import flash_attention as fa

    check(not fa._FORCE_INTERPRET, "Pallas interpret mode is forced on")
    log(f"native core: {native.lib_status()}")
    one_chip = serve_phase(SERVE_CONFIG, amp=True, **SERVE_SIZES)
    log_memory("after serve")
    reference = one_chip["tokens"]
    del one_chip
    gc.collect()
    log_memory("serve released")
    train_phase(TRAIN_CONFIG, amp=True, **TRAIN_SIZES)
    log_memory("after train")
    gc.collect()
    latent_serve_phase(LATENT_CONFIG, dtype="bfloat16", **LATENT_SIZES)
    log_memory("after latent serve")
    gc.collect()
    cache_phase()
    if device["count"] >= 4:
        four_chip_phase(
            SERVE_CONFIG, TRAIN_CONFIG, reference, amp=True,
            serve_sizes=SERVE_SIZES, train_sizes=dict(TRAIN_SIZES, steps=2),
            tp=4, hybrid={"dp": 2, "mp": 2},
        )
        log_memory("after four chips")
        cache_phase()
    else:
        log(f"four chips: NOT RUN — {device['count']} device(s) present")
    check(not fa._FORCE_INTERPRET, "Pallas interpret mode was forced on")
    log("all phases passed")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
