"""The grouped-expert kernel of a small step (ISSUE 36), interpreted on the
CPU in float32: against `_routed_experts`' loop (the XLA form a step takes off
the TPU) and against a plain float32 sum over picks, over step sizes, hit
patterns, idle rows, a held range in the middle of the router's, and both
routers; the counters a traced decode step leaves."""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import profiler  # noqa: E402
from paddle_tpu.inference.engine import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models import Ling3Config, Ling3ForCausalLM, Mellum2Config, Mellum2ForCausalLM  # noqa: E402
from paddle_tpu.models import deepseek_v32 as dsv  # noqa: E402
from paddle_tpu.ops import flash_attention as fa  # noqa: E402
from paddle_tpu.ops import grouped_experts as ge  # noqa: E402

D, I = 32, 16


@pytest.fixture(scope="module", autouse=True)
def _rng_guard():
    """Model builds consume the framework's default generator; later modules
    build weights without re-seeding it."""
    state = np.asarray(paddle.get_rng_state())
    yield
    paddle.set_rng_state(state)


@pytest.fixture
def interpret():
    fa._FORCE_INTERPRET = True
    try:
        yield
    finally:
        fa._FORCE_INTERPRET = False


def router(T):
    """The router's width E, the picks a token K, the held range: one token
    covers a router of 4 with its 4 picks, 5 tokens and more one of 16."""
    E, held, offset = (4, 2, 1) if T == 1 else (16, 8, 4)
    return SimpleNamespace(n_routed_experts=E, num_experts_per_tok=4, expert_offset=offset, n_group=2,
                           topk_group=2, norm_topk_prob=True, routed_scaling_factor=2.5), held


def matrices(rng, held):
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
    return mk(held, D, I), mk(held, D, I), mk(held, I, D)


def plain_sum(cfg, x, experts, wts, live, w1, w3, w2):
    """Every pick on a held expert, one at a time, in float32."""
    held = w1.shape[0]
    local = np.asarray(experts) - cfg.expert_offset
    y = np.zeros(x.shape, np.float32)
    for t, k in zip(*np.nonzero((local >= 0) & (local < held) & np.asarray(live)[:, None])):
        e = local[t, k]
        mid = jax.nn.silu(x[t] @ w1[e]) * (x[t] @ w3[e])
        y[t] += float(wts[t, k]) * np.asarray(mid @ w2[e])
    return y


def three_forms(cfg, x, experts, wts, live, mats):
    """(the kernel, the loop, the plain sum), and the kernel's stats."""
    assert x.shape[0] * experts.shape[1] >= cfg.n_routed_experts  # a step the dispatch sends to the kernel
    before = profiler.flash_pallas_summary().get("grouped_experts", 0)
    loop, loop_stats = dsv._routed_experts(cfg, x, experts, wts, live, *mats)
    assert profiler.flash_pallas_summary().get("grouped_experts", 0) == before  # off the TPU: the loop
    fa._FORCE_INTERPRET = True
    try:
        got, stats = jax.jit(lambda *a: dsv._routed_experts(cfg, *a))(x, experts, wts, live, *mats)
    finally:
        fa._FORCE_INTERPRET = False
    assert profiler.flash_pallas_summary()["grouped_experts"] == before + 1
    np.testing.assert_array_equal(stats, loop_stats)
    return np.asarray(got), np.asarray(loop), plain_sum(cfg, x, experts, wts, live, *mats), np.asarray(stats)


def picks(pattern, T, cfg, held, rng):
    """-> (experts [T, K] distinct a token, live [T])."""
    E, K, off = cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.expert_offset
    live = np.ones(T, bool)
    outside = [e for e in range(E) if not off <= e < off + held]
    experts = np.stack([rng.permutation(E)[:K] for _ in range(T)])
    if pattern == "no_expert_hit":
        if len(outside) >= K:
            experts = np.stack([rng.permutation(outside)[:K] for _ in range(T)])
        else:  # one token's 4 picks cover a router of 4: then nobody is live
            live[:] = False
    elif pattern == "every_expert_hit" and T > 1:
        experts = np.asarray([[off + (t * K + k) % held for k in range(K)] for t in range(T)])
    elif pattern == "one_expert_picked_by_every_token" and T > 1:
        experts = np.asarray([[off + 2] + list(rng.permutation(outside)[:K - 1]) for _ in range(T)])
    elif pattern == "idle_slots_and_padding_rows":
        live = rng.random(T) < 0.5
        live[-1] = False  # the rows a padded step adds are the last
    elif pattern == "picks_on_both_sides_of_the_held_range" and T > 1:
        experts = np.asarray([[off - 1 - t % 2, off + t % held, off + held - 1, off + held + t % 3] for t in range(T)])
    return jnp.asarray(experts, jnp.int32), jnp.asarray(live)


PATTERNS = ["no_expert_hit", "every_expert_hit", "one_expert_picked_by_every_token",
            "idle_slots_and_padding_rows", "picks_on_both_sides_of_the_held_range"]


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("T", [1, 5, 64, 128])
def test_kernel_is_the_loops_sum_and_the_plain_sum(T, pattern):
    rng = np.random.default_rng(T * 31 + len(pattern))
    cfg, held = router(T)
    experts, live = picks(pattern, T, cfg, held, rng)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    wts = jnp.asarray(rng.random((T, cfg.num_experts_per_tok)) + 0.1, jnp.float32)
    got, loop, plain, stats = three_forms(cfg, x, experts, wts, live, matrices(rng, held))
    np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got, loop, rtol=2e-5, atol=2e-6)
    assert not got[~np.asarray(live)].any()  # a row that is not live routes nowhere
    local = np.asarray(experts) - cfg.expert_offset
    mine = (local >= 0) & (local < held) & np.asarray(live)[:, None]
    assert [int(v) for v in stats] == [int(np.asarray(live).sum()), int(mine.sum()), len(set(local[mine])),
                                       max(np.bincount(local[mine], minlength=1))]
    if pattern == "no_expert_hit":
        assert stats[2] == 0 and not got.any()
    if pattern == "every_expert_hit":
        assert stats[2] == held
    if pattern == "one_expert_picked_by_every_token" and T > 1:
        assert (stats[2], stats[3]) == (1, T)


@pytest.mark.parametrize("T", [5, 64])
@pytest.mark.parametrize("form", ["sigmoid_groups", "softmax"])
def test_kernel_takes_both_routers_outputs(form, T):
    rng = np.random.default_rng(T)
    cfg, held = router(T)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(D, cfg.n_routed_experts)), jnp.float32)
    if form == "softmax":
        experts, wts = dsv._route_softmax(cfg, x, gate)
    else:
        experts, wts = dsv._route(cfg, x, gate, jnp.asarray(rng.normal(size=cfg.n_routed_experts) * 0.01, jnp.float32))
    live = jnp.ones(T, bool).at[T // 2].set(False)
    got, loop, plain, stats = three_forms(cfg, x, experts, wts, live, matrices(rng, held))
    np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got, loop, rtol=2e-5, atol=2e-6)
    assert 0 < stats[1] < T * cfg.num_experts_per_tok  # picks land on both sides of the held range


def test_bfloat16_operands_accumulate_in_float32(interpret):
    """The precision a served configuration states: bf16 weights and
    activations; the sum over experts is float32 and is not rounded between
    experts."""
    rng = np.random.default_rng(3)
    cfg, held = router(64)
    experts, live = picks("every_expert_hit", 64, cfg, held, rng)
    x = jnp.asarray(rng.normal(size=(64, D)), jnp.bfloat16)
    wts = jnp.asarray(rng.random((64, 4)) + 0.1, jnp.float32)
    mats = tuple(m.astype(jnp.bfloat16) for m in matrices(rng, held))
    got, _ = dsv._routed_experts(cfg, x, experts, wts, live, *mats)
    assert got.dtype == jnp.float32
    plain = plain_sum(cfg, x.astype(jnp.float32), experts, wts, live, *(m.astype(jnp.float32) for m in mats))
    assert np.abs(np.asarray(got) - plain).max() < 0.02 * np.abs(plain).max()


def test_hit_list_is_the_hit_experts_ascending():
    counts = jnp.asarray([0, 3, 0, 0, 1, 7, 0, 2], jnp.int32)
    ids, n = ge.hit_list(counts)
    assert int(n[0]) == 4 and [int(v) for v in ids[:4]] == [1, 4, 5, 7] and int(jnp.max(ids)) <= 7
    ids, n = ge.hit_list(jnp.zeros(8, jnp.int32))
    assert int(n[0]) == 0 and int(jnp.max(ids)) <= 7
    ids, n = ge.hit_list(jnp.ones(8, jnp.int32))
    assert int(n[0]) == 8 and [int(v) for v in ids] == list(range(8))


@pytest.mark.parametrize("shape,why", [
    ((64, 2560, 768), None), ((32, 2304, 896), None), ((64, 64, 32), "whole lanes"), ((129, 2560, 768), "tokens"),
    ((64, 8192, 4096), "VMEM")])
def test_refusal_names_what_the_chip_cannot_take(shape, why):
    T, d, i = shape
    x, w1 = jax.ShapeDtypeStruct((T, d), jnp.bfloat16), jax.ShapeDtypeStruct((8, d, i), jnp.bfloat16)
    reason = ge.refusal(x, w1)
    assert (reason is None) if why is None else (why in reason)


def test_a_refused_shape_on_the_tpu_counts_as_a_fallback_and_takes_the_loop(monkeypatch):
    """Both serving cells hold `flash_fallbacks` to 0: a run in which the
    kernel did not engage reads `correct: false`."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    profiler.reset_flash_fallbacks()
    rng = np.random.default_rng(9)
    cfg, held = router(5)
    experts, live = picks("every_expert_hit", 5, cfg, held, rng)
    x = jnp.asarray(rng.normal(size=(5, D)), jnp.float32)
    wts = jnp.asarray(rng.random((5, 4)), jnp.float32)
    mats = matrices(rng, held)
    before = profiler.flash_pallas_summary().get("grouped_experts", 0)
    got, _ = dsv._routed_experts(cfg, x, experts, wts, live, *mats)
    assert profiler.flash_pallas_summary().get("grouped_experts", 0) == before
    (reason, n), = profiler.flash_fallback_summary().items()
    assert reason.startswith("grouped_experts: ") and "whole lanes" in reason and n == 1
    np.testing.assert_allclose(got, plain_sum(cfg, x, experts, wts, live, *mats), rtol=2e-5, atol=2e-6)
    profiler.reset_flash_fallbacks()


def test_a_larger_step_keeps_the_loop(interpret):
    """Every prefill chunk, and a decode step whose picks do not cover the
    router's width (`dsv32_serve.longctx16`), run not one changed line."""
    rng = np.random.default_rng(4)
    cfg, held = router(64)
    mats = matrices(rng, held)
    before = profiler.flash_pallas_summary().get("grouped_experts", 0)
    for T in (3, 136):  # 12 picks under the router's 16; more rows than a block
        experts, live = picks("idle_slots_and_padding_rows", T, cfg, held, rng)
        x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
        wts = jnp.asarray(rng.random((T, 4)), jnp.float32)
        got, _ = dsv._routed_experts(cfg, x, experts, wts, live, *mats)
        np.testing.assert_allclose(got, plain_sum(cfg, x, experts, wts, live, *mats), rtol=2e-5, atol=2e-6)
    assert profiler.flash_pallas_summary().get("grouped_experts", 0) == before


@pytest.mark.parametrize("family", ["ling3", "mellum2"])
def test_a_traced_decode_step_counts_the_kernel_and_its_geometry(interpret, family):
    """Four slots cover either tiny router (4 x 4 >= 16, 4 x 2 >= 8), so the
    engine's decode step takes the kernel in every expert layer (so does a
    prefill bucket of 16 rows here; the serving cells' chunks are 512 rows
    and more, and loop); nothing falls back."""
    profiler.reset()
    if family == "ling3":
        cfg = Ling3Config.tiny(experts_held=4, expert_offset=4)
        model, layers, held, inter = Ling3ForCausalLM(cfg), 6, 4, cfg.moe_intermediate_size
    else:
        cfg = Mellum2Config.tiny()
        model, layers, held, inter = Mellum2ForCausalLM(cfg), 8, 8, cfg.moe_intermediate_size
    eng = ContinuousBatchingEngine(model, slots=4, max_len=64, prefill_buckets=[16], page_size=8)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(1, 250, size=n).astype(np.int32), max_new_tokens=3) for n in (5, 9)]
    eng.run_until_idle()
    assert all(r.finish_reason == "length" and r.error is None for r in reqs)
    calls = profiler.flash_pallas_summary()
    assert calls["grouped_experts"] % layers == 0 and calls["grouped_experts"] >= layers  # a count of traces
    assert profiler.flash_fallback_summary() == {}
    assert {"tokens": 4, "held": held, "expert_bytes": 3 * cfg.hidden_size * inter * 4,
            "experts_in_flight": ge.EXPERTS_IN_FLIGHT, "grid_steps": 1} in profiler.grouped_experts_summary()
    moe = profiler.moe_summary()
    assert moe["steps"] >= 2 and 0 < moe["experts_hit"] <= moe["steps"] * layers * held


# -- the benchmark's reader of the kernel's roofline share ----------------------------------

def _reader_ctx(calls, seconds, walks, moe):
    import json

    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "ling-3.0-flash-ep4-serve7.json")))
    peaks = json.load(open(os.path.join(ROOT, "benchmarks", "peaks.json")))["TPU v5 lite"]
    names = {"%grouped_experts.3 = f32[64,2560]{1,0} custom-call(...)": (calls, seconds),
             "%paged_walk_decode.1 = bf16[64,1,32,640]{3,2,1,0} custom-call(...)": (walks, 0.09),
             # what reads a kernel's output holds its name among the operands
             "%fusion.7 = bf16[64,2560]{1,0} fusion(f32[64,2560]{1,0} %grouped_experts.3, ...)": (calls, 0.001),
             "%fusion.9 = bf16[64,32,640]{2,1,0} fusion(bf16[64,1,32,640]{3,2,1,0} %paged_walk_decode.1)": (walks, 0.001),
             "%fusion.43 = bf16[128,64,768]{2,1,0} fusion(...)": (walks, 0.08)}
    trace = {"ops": {n: s for n, (c, s) in names.items() if c}, "op_counts": {n: c for n, (c, _) in names.items() if c}}
    return SimpleNamespace(cfg=cfg, peaks=peaks, counters={"moe": moe} if moe else {}, trace=trace, log=lambda line: None)


@pytest.mark.parametrize("case,calls,walks,moe,reads", [
    ("a_decode_window", 720, 120, {"steps": 100, "experts_hit": 100 * 360, "picks_held": 100 * 768}, True),
    ("edges_cut_two_steps", 708, 120, {"steps": 100, "experts_hit": 100 * 360, "picks_held": 100 * 768}, True),
    ("another_kernel_in_the_match", 840, 120, {"steps": 100, "experts_hit": 100 * 360, "picks_held": 100 * 768}, False),
    ("a_program_without_the_kernel", 0, 120, {"steps": 100, "experts_hit": 100 * 360, "picks_held": 100 * 768}, False),
    ("a_program_without_the_counters", 720, 120, None, False),
    ("no_step_marks", 720, 0, {"steps": 100, "experts_hit": 100 * 360, "picks_held": 100 * 768}, False),
])
def test_roofline_reader_reads_needed_bytes_over_the_kernels_mean_time(case, calls, walks, moe, reads):
    """60 experts of 3 x 2560 x 768 x 2 B hit a layer a step: 0.864 ms at 819
    GB/s; calls of 1.0 ms read 86.4%.  A parent without the kernel, a kind
    without the counters or a match that holds another kernel reads nothing
    and raises nothing."""
    from benchmarks.readers import grouped_experts_roofline as reader

    args = {"match": ["grouped_experts"], "step_marks": ["paged_walk_decode"]}
    got = reader.read(_reader_ctx(calls, calls * 1.0e-3, walks, moe), args)
    if reads:
        assert got == pytest.approx(100 * 60 * 3 * 2560 * 768 * 2 / 819e9 / 1.0e-3, rel=1e-6)
    else:
        assert got is None
    assert reader.read(SimpleNamespace(counters={"moe": moe}, trace=None), args) is None  # an untraced run
