"""Quantized KV serving (ISSUE 18): the int8 page arena with per-row
float32 scales must buy ~2x pages in the same HBM budget WITHOUT changing
what the serving stack observes — the quantized fused Pallas kernel stays
numerically interchangeable with the quantized gather oracle, scale rows
ride the SAME page tables/refcounts/COW/prefix machinery as their value
pages, speculative verify and LoRA co-batching compose unchanged, and the
quant mode is folded into every compile-cache key so flipping it can never
return a stale executable.

Kernels run in Pallas interpret mode on CPU (the same kernel code compiles
on TPU).  The module runs under the runtime sanitizer (conftest
_SANITIZED_MODULES): steady-state quantized traffic must not trace,
compile, or host-sync.
"""

import contextlib
import json

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.framework import core as _fcore
from paddle_tpu.inference.engine import ContinuousBatchingEngine
from paddle_tpu.inference.paging import (
    QuantConfigError,
    check_scale_arenas,
    kv_page_bytes,
    validate_kv_quant,
)
from paddle_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    PagedKVCache,
    _quantize_kv_rows,
)
import paddle_tpu.ops.flash_attention as fa


@pytest.fixture(scope="module", autouse=True)
def _rng_guard():
    """Model builds and engine seeds below consume the framework
    default_generator; several later test modules build weights without
    re-seeding paddle, so leave the global RNG stream exactly where a run
    without this module would have it."""
    state = np.asarray(paddle.get_rng_state())
    yield
    paddle.set_rng_state(state)


@pytest.fixture(scope="module")
def model(_rng_guard):
    np.random.seed(1234)
    return LlamaForCausalLM(LlamaConfig.tiny())


@contextlib.contextmanager
def _interpret():
    saved = fa._FORCE_INTERPRET
    fa._FORCE_INTERPRET = True
    try:
        yield
    finally:
        fa._FORCE_INTERPRET = saved


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 250, size=n).astype(np.int32)


def _paged(model, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("queue_depth", 16)
    kw.setdefault("seed", 0)
    kw.setdefault("page_size", 8)
    return ContinuousBatchingEngine(model, **kw)


def _match_rate(a, b):
    """Fraction of positions where two token sequences agree (over the
    shorter length) — the quality bar for quant-vs-full comparisons where
    bit-identity is not the contract."""
    n = min(len(a), len(b))
    if n == 0:
        return 1.0
    return float(np.mean(np.asarray(a[:n]) == np.asarray(b[:n])))


# ---------------------------------------------------------------------------
# quantizer: per-row symmetric int8 with the zero-row pin
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_error_bound():
    r = np.random.RandomState(3)
    x = jnp.asarray((r.rand(5, 7, 16) - 0.5).astype(np.float32) * 4.0)
    q, s = _quantize_kv_rows(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == (5, 7, 1)
    # symmetric round-to-nearest: each element within half a step
    err = np.abs(np.asarray(x) - np.asarray(q, np.float32) * np.asarray(s))
    assert (err <= np.asarray(s) * 0.5 + 1e-7).all()
    # zero rows pin scale to 1 so their dequant is EXACTLY zero (scratch
    # page 0 starts all-zero; its dequant must stay finite and zero)
    z = jnp.zeros((2, 3, 16), jnp.float32)
    qz, sz = _quantize_kv_rows(z)
    assert np.asarray(qz).max() == 0 and (np.asarray(sz) == 1.0).all()


def test_paged_cache_int8_layout():
    c = PagedKVCache(4, 8, 2, 16, "float32", quant="int8")
    assert c.quant == "int8"
    assert tuple(c.k.shape) == (4, 2, 8, 16) and str(c.k.dtype) == "int8"
    assert tuple(c.k_scale.shape) == (4, 2, 1, 8)
    assert str(c.k_scale.dtype) == "float32"
    full = PagedKVCache(4, 8, 2, 16, "float32")
    assert full.quant == "none" and full.k_scale is None


# ---------------------------------------------------------------------------
# array level: quantized fused kernel vs quantized gather oracle
# ---------------------------------------------------------------------------


def _quant_arena(num_pages=9, ps=8, hk=2, d=16, seed=0):
    """int8 arenas + realistic per-row scale arenas (scratch page 0 kept
    all-zero with scale 1, like the engine's freshly-allocated pool)."""
    r = np.random.RandomState(seed)
    qk = r.randint(-127, 128, size=(num_pages, hk, ps, d)).astype(np.int8)
    qv = r.randint(-127, 128, size=(num_pages, hk, ps, d)).astype(np.int8)
    sk = (r.rand(num_pages, hk, 1, ps).astype(np.float32) * 0.02) + 1e-4
    sv = (r.rand(num_pages, hk, 1, ps).astype(np.float32) * 0.02) + 1e-4
    qk[0] = 0
    qv[0] = 0
    sk[0] = 1.0
    sv[0] = 1.0
    return jnp.asarray(qk), jnp.asarray(qv), jnp.asarray(sk), jnp.asarray(sv)


def _both(q, ak, av, ks, vs, tables, pos, max_len):
    with _interpret():
        fused = fa.paged_decode_attention_array(
            q, ak, av, tables, pos, max_len, kernel="fused",
            k_scale=ks, v_scale=vs,
        )
    gather = fa.paged_decode_attention_array(
        q, ak, av, tables, pos, max_len, kernel="gather",
        k_scale=ks, v_scale=vs,
    )
    return np.asarray(fused), np.asarray(gather)


class TestQuantFusedVsGather:
    @pytest.mark.parametrize("sq", [1, 4])
    def test_ragged_gqa_parity(self, sq):
        """Mixed per-slot positions, GQA group packing, max_len below the
        table span: the in-VMEM dequant (int8 tile * per-row scale tile)
        must reproduce the gather path's dequant-then-dense math."""
        ak, av, ks, vs = _quant_arena()
        r = np.random.RandomState(7)
        q = jnp.asarray(r.rand(4, sq, 4, 16).astype(np.float32) - 0.5)
        tables = jnp.asarray(
            [[1, 2, 3, 4], [5, 6, 0, 0], [7, 0, 0, 0], [8, 3, 5, 1]],
            jnp.int32,
        )
        pos = jnp.asarray([27, 11, 3, 20], jnp.int32)
        fused, gather = _both(q, ak, av, ks, vs, tables, pos, max_len=28)
        np.testing.assert_allclose(fused, gather, rtol=2e-5, atol=2e-5)

    def test_scratch_overrun_stays_finite(self):
        """A verify window overrunning its mapped prefix reads scratch page
        0 (all-zero int8, scale 1) — dequant of garbage-free scratch is
        exactly zero, the position fence masks it, outputs stay finite and
        match the gather path."""
        ak, av, ks, vs = _quant_arena(seed=5)
        r = np.random.RandomState(13)
        q = jnp.asarray(r.rand(3, 4, 4, 16).astype(np.float32) - 0.5)
        tables = jnp.asarray(
            [[3, 5, 0, 0], [1, 2, 6, 7], [0, 0, 0, 0]], jnp.int32
        )
        pos = jnp.asarray([14, 9, 0], jnp.int32)
        fused, gather = _both(q, ak, av, ks, vs, tables, pos, max_len=32)
        assert np.isfinite(fused).all()
        np.testing.assert_allclose(fused, gather, rtol=2e-5, atol=2e-5)

    def test_shared_pages_read_identical(self):
        """Two slots mapping the SAME physical pages (prefix sharing) must
        dequantize identical K/V — same value pages, same scale rows."""
        ak, av, ks, vs = _quant_arena(seed=3)
        r = np.random.RandomState(11)
        q1 = r.rand(1, 1, 4, 16).astype(np.float32) - 0.5
        q = jnp.asarray(np.concatenate([q1, q1]))
        tables = jnp.asarray([[2, 4, 6, 0], [2, 4, 6, 0]], jnp.int32)
        fused, gather = _both(q, ak, av, ks, vs, tables, jnp.int32(17), 32)
        np.testing.assert_allclose(fused, gather, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(fused[0], fused[1], rtol=0, atol=0)

    def test_scale_args_validated_and_counted(self):
        """k_scale/v_scale must come as a pair, and the quant fused dispatch
        is counted under its OWN kernel name (the dashboards distinguish
        quantized from full-precision hot paths)."""
        ak, av, ks, vs = _quant_arena()
        q = jnp.zeros((1, 1, 4, 16), jnp.float32)
        t = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
        with pytest.raises(ValueError, match="k_scale"):
            fa.paged_decode_attention_array(
                q, ak, av, t, jnp.int32(5), 32, k_scale=ks
            )
        profiler.reset_flash_pallas()
        profiler.reset_flash_fallbacks()
        with _interpret():
            fa.paged_decode_attention_array(
                q, ak, av, t, jnp.int32(5), 32, k_scale=ks, v_scale=vs
            )
        assert profiler.flash_pallas_summary() == {"paged_decode_fused_q8": 1}
        assert profiler.flash_fallback_summary() == {}
        assert "paged_decode_fused_q8" in fa._PALLAS_KERNELS


# ---------------------------------------------------------------------------
# construction: typed config error, page-byte math, pool auto-sizing
# ---------------------------------------------------------------------------


class TestQuantConfig:
    def test_validate_kv_quant(self):
        assert validate_kv_quant(None) == "none"
        assert validate_kv_quant("INT8") == "int8"
        with pytest.raises(QuantConfigError, match="int4"):
            validate_kv_quant("int4")

    def test_engine_rejects_unknown_quant_mode(self, model):
        with pytest.raises(QuantConfigError, match="fp4"):
            _paged(model, kv_quant="fp4")

    def test_kv_page_bytes_math(self):
        # bf16 hd=128: int8+scales is ~1.94x smaller per page
        full = kv_page_bytes(8, 2, 128, 2, "none")
        q8 = kv_page_bytes(8, 2, 128, 2, "int8")
        assert full == 2 * 8 * 2 * 128 * 2
        assert q8 == 2 * 8 * 2 * (128 + 4)
        assert 1.9 < full / q8 < 2.0
        with pytest.raises(QuantConfigError):
            kv_page_bytes(8, 2, 128, 2, "int4")

    def test_pool_autosizes_to_same_hbm_budget(self, model):
        """With pool_pages unset, the int8 engine sizes its pool to what
        the FULL-precision pool's HBM budget buys at int8 page bytes —
        the same bytes hold ~2-3x the pages (exact ratio depends on the
        cache dtype and head_dim)."""
        base = _paged(model)
        q8 = _paged(model, kv_quant="int8")
        cfg = model.config
        hd = cfg.hidden_size // cfg.num_attention_heads
        dtype_b = np.dtype(
            _fcore.to_jax_dtype(_fcore.get_default_dtype())
        ).itemsize
        ratio = kv_page_bytes(8, cfg.num_key_value_heads, hd, dtype_b, "none") \
            / kv_page_bytes(8, cfg.num_key_value_heads, hd, dtype_b, "int8")
        assert q8.pool_pages > base.pool_pages
        assert q8.pool_pages == pytest.approx(base.pool_pages * ratio, rel=0.2)
        # explicit pool_pages is always honored verbatim
        assert _paged(model, kv_quant="int8", pool_pages=9).pool_pages == 9

    def test_check_scale_arenas(self):
        ok = PagedKVCache(4, 8, 2, 16, "float32", quant="int8")
        check_scale_arenas([ok], 4, 8)
        check_scale_arenas([PagedKVCache(4, 8, 2, 16, "float32")], 4, 8)
        bad = PagedKVCache(4, 8, 2, 16, "float32", quant="int8")
        bad.k_scale = None
        with pytest.raises(AssertionError, match="scale"):
            check_scale_arenas([bad], 4, 8)

    def test_quant_mode_salts_compile_caches(self):
        """Flipping FLAGS_serve_kv_quant must change BOTH the eager
        dispatch salt and the AOT snapshot fingerprint — a flag flip after
        a same-shape call can never return a stale executable."""
        from paddle_tpu.jit.cache import _flags_fingerprint
        from paddle_tpu.ops.dispatch import _dispatch_salt

        before = (_dispatch_salt(), _flags_fingerprint())
        paddle.set_flags({"FLAGS_serve_kv_quant": "int8"})
        try:
            after = (_dispatch_salt(), _flags_fingerprint())
        finally:
            paddle.set_flags({"FLAGS_serve_kv_quant": "none"})
        assert before[0] != after[0]
        assert before[1] != after[1]


# ---------------------------------------------------------------------------
# engine level: quality, sharing, speculation, LoRA, restart, recompiles
# ---------------------------------------------------------------------------


class TestQuantEngine:
    # Engine construction + warmup compiles dominate tier-1 wall-clock;
    # ci.sh runs the acceptance pair in fast mode and this whole class in
    # full mode, so tier-1 keeps only the cheap math/kernel/config tests.
    pytestmark = pytest.mark.slow

    def test_tokens_match_full_precision(self, model):
        """Greedy replay of mixed ragged traffic: the int8 engine's
        generated tokens must agree with the full-precision engine's at
        >= 0.95 per-position match (the ISSUE's quality bar)."""
        lens = [5, 12, 9, 15, 3]
        outs = {}
        for quant in ("none", "int8"):
            eng = _paged(model, slots=2, kv_quant=quant)
            reqs = [
                eng.submit(_prompt(n, seed=30 + i), max_new_tokens=6)
                for i, n in enumerate(lens)
            ]
            eng.run_until_idle()
            outs[quant] = [r.wait(1).tolist() for r in reqs]
        rates = [
            _match_rate(a, b) for a, b in zip(outs["none"], outs["int8"])
        ]
        assert float(np.mean(rates)) >= 0.95, rates

    def test_cow_tail_scale_isolation(self, model):
        """The COW drill under int8: request B copy-on-writes the shared
        tail page — VALUE page and SCALE rows both — so B's divergent
        suffix never corrupts A's dequant.  Both outputs must match a
        no-cache int8 engine bit-for-bit."""
        base = _prompt(12, seed=70)
        pa = np.concatenate([base, _prompt(4, seed=71)]).astype(np.int32)
        pb = np.concatenate([base, _prompt(4, seed=72)]).astype(np.int32)

        eng = _paged(model, kv_quant="int8")
        eng.generate(base, max_new_tokens=2)  # seed cache: full page + tail
        profiler.reset_paging()
        out_b = eng.generate(pb, max_new_tokens=6)
        pg = profiler.paging_summary()
        assert pg["prefix_hits"] == 1 and pg["cow_copies"] >= 1
        out_a = eng.generate(pa, max_new_tokens=6)  # rereads the shared tail

        fresh = _paged(model, kv_quant="int8", prefix_cache=False)
        assert np.array_equal(out_b, fresh.generate(pb, max_new_tokens=6))
        assert np.array_equal(out_a, fresh.generate(pa, max_new_tokens=6))

    def test_prefix_hit_bit_reproducible(self, model):
        """A prefix-cache hit replays QUANTIZED rows written by the earlier
        request; re-running the identical prompt must be bit-identical to
        its first run — cached int8 pages + scale rows reproduce exactly
        what the fresh prefill produced."""
        eng = _paged(model, kv_quant="int8")
        p = _prompt(14, seed=77)
        first = eng.generate(p, max_new_tokens=5)
        profiler.reset_paging()
        second = eng.generate(p, max_new_tokens=5)
        assert profiler.paging_summary()["prefix_hits"] == 1
        assert np.array_equal(first, second)

    def test_spec_and_lora_cobatch_quality(self, model):
        """spec_k=3 + 3-tenant LoRA co-batch: the verify window writes its
        draft rows through the quantizing scatter and rejected drafts roll
        back by redirect exactly as at full precision; per-request token
        match vs the full-precision engine stays >= 0.95."""
        from paddle_tpu.lora import AdapterArena, AdapterRegistry, make_random

        outs = {}
        for quant in ("none", "int8"):
            reg = AdapterRegistry(model.config)
            for i in range(3):
                make_random(reg, f"t{i + 1}", rank=4, seed=i + 1, scale=0.02)
            eng = _paged(
                model, slots=2, spec_k=3, kv_quant=quant,
                lora=AdapterArena(reg, capacity=3, rank_max=4),
            )
            reqs = [
                eng.submit(
                    np.tile(_prompt(6, seed=55 + i), 2).astype(np.int32),
                    max_new_tokens=6,
                    adapter=None if i == 0 else f"t{i}",
                )
                for i in range(4)
            ]
            eng.run_until_idle()
            outs[quant] = [r.wait(1).tolist() for r in reqs]
        rates = [
            _match_rate(a, b) for a, b in zip(outs["none"], outs["int8"])
        ]
        assert float(np.mean(rates)) >= 0.95, rates

    def test_zero_recompiles_and_fused_token_identity(self, model):
        """decode_kernel='fused' vs 'gather' on the SAME int8 arena must be
        token-identical (the gather path is the parity oracle), with zero
        recompiles after warmup — quantize-on-write and the scale operands
        are part of the warmed executables, tables stay traced data."""
        outs = {}
        for kern in ("gather", "fused"):
            ctx = _interpret() if kern == "fused" else contextlib.nullcontext()
            with ctx:
                eng = _paged(model, slots=2, kv_quant="int8",
                             decode_kernel=kern)
                eng.warmup()
                warm = eng.compile_counts()
                base = _prompt(12, seed=60)
                reqs = [
                    eng.submit(_prompt(n, seed=30 + i), max_new_tokens=4)
                    for i, n in enumerate([5, 12, 9])
                ]
                reqs += [
                    eng.submit(
                        np.concatenate([base, _prompt(3, seed=45 + i)])
                        .astype(np.int32),
                        max_new_tokens=3,
                    )
                    for i in range(2)
                ]
                eng.run_until_idle()
                outs[kern] = [r.wait(1).tolist() for r in reqs]
                assert eng.compile_counts() == warm
        assert outs["fused"] == outs["gather"]

    def test_warm_restart_survives_quant(self, model):
        """restart() keeps the pool, prefix cache, arenas AND scale arenas:
        the restarted engine still serves int8 with zero fresh compiles and
        a prefix hit on the pre-restart prompt."""
        eng = _paged(model, kv_quant="int8")
        eng.warmup()
        base = _prompt(12, seed=100)
        eng.generate(base, max_new_tokens=2)
        warm = eng.compile_counts()
        eng.restart(reason="drill")
        assert eng.kv_quant == "int8"
        assert eng._arenas[0].quant == "int8"
        assert eng._arenas[0].k_scale is not None
        profiler.reset_paging()
        out = eng.generate(
            np.concatenate([base, _prompt(4, seed=101)]).astype(np.int32),
            max_new_tokens=4,
        )
        assert out.size == 16 + 4
        assert profiler.paging_summary()["prefix_hits"] == 1
        assert eng.compile_counts() == warm

    def test_debug_invariants_audit_scale_arenas(self, model):
        """FLAGS_serve_debug_invariants audits scale-arena congruence each
        step; stripping a scale arena from a live int8 engine trips it."""
        paddle.set_flags({"FLAGS_serve_debug_invariants": True})
        try:
            eng = _paged(model, kv_quant="int8")
            eng.generate(_prompt(10, seed=70), max_new_tokens=2)
            with eng._mu:
                eng._check_page_invariants_locked()  # clean pass
                saved = eng._arenas[0].v_scale
                eng._arenas[0].v_scale = None
                with pytest.raises(AssertionError, match="scale"):
                    eng._check_page_invariants_locked()
                eng._arenas[0].v_scale = saved
        finally:
            paddle.set_flags({"FLAGS_serve_debug_invariants": False})


# ---------------------------------------------------------------------------
# observability: /metrics family, /healthz, flight header, router scoring
# ---------------------------------------------------------------------------


class TestQuantObservability:
    @pytest.mark.slow
    def test_metrics_family_and_healthz(self, model):
        from paddle_tpu.obs import metrics

        profiler.reset()
        eng = _paged(model, kv_quant="int8")
        eng.generate(_prompt(10, seed=5), max_new_tokens=4)
        h = eng.healthz()
        assert h["kv_quant"] == "int8"
        # page_free_frac stays a fraction of the replica's OWN usable pages
        # — the router's scoring needs no quant awareness
        assert 0.0 <= h["page_free_frac"] <= 1.0
        snap = profiler.metrics_snapshot()["kv_quant"]
        assert snap["mode"] == "int8"
        assert snap["arena_bytes"] > 0 and snap["scale_bytes"] > 0
        assert snap["quantize"] > 0 and snap["dequantize"] > 0
        text = metrics.render()
        assert 'paddle_kv_quant_mode{mode="int8"} 1' in text
        assert "paddle_kv_quant_arena_bytes" in text
        assert "paddle_kv_quant_scale_bytes" in text
        assert 'paddle_kv_quant_page_ops_total{op="quantize"}' in text
        assert 'paddle_kv_quant_page_ops_total{op="dequantize"}' in text

    def test_metrics_zero_render_without_quant(self):
        """The family's metric NAMES are stable before any quant traffic —
        mode renders 'none', counters render 0 (never absent series)."""
        from paddle_tpu.obs import metrics

        profiler.reset()
        text = metrics.render()
        assert 'paddle_kv_quant_mode{mode="none"} 1' in text
        assert 'paddle_kv_quant_page_ops_total{op="quantize"} 0' in text

    @pytest.mark.slow
    def test_flight_header_carries_kv_quant(self, model, tmp_path):
        from paddle_tpu.obs import flight

        profiler.reset()
        eng = _paged(model, kv_quant="int8")
        eng.generate(_prompt(8, seed=6), max_new_tokens=2)
        p = flight.dump("unit", path=str(tmp_path / "flight-kvq.jsonl"))
        with open(p) as f:
            header = json.loads(f.readline())
        assert header["kv_quant"]["mode"] == "int8"
        assert header["kv_quant"]["arena_bytes"] > 0
        # a full-precision process omits the section (like mesh/lora)
        profiler.reset()
        _paged(model)
        p2 = flight.dump("unit", path=str(tmp_path / "flight-none.jsonl"))
        with open(p2) as f:
            h2 = json.loads(f.readline())
        assert "kv_quant" not in h2
