"""Mellum2 on the serving path (ISSUE 35), tiny on the CPU in float32: sliding
layers among full ones on two page groups in one cache manager, the page walk
that starts at the window, a rope table per layer type, the softmax router with
every expert held, each against the plain reference
(`benchmarks/reference_mellum2.py`) or dense masked attention; the window
group's pages (released behind the window, reused, never over the bound), the
grown `cache_layers()` contract, and what the model refuses."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from benchmarks import flops_mellum2 as F  # noqa: E402
from benchmarks import reference_mellum2 as ref  # noqa: E402
from benchmarks import weights_mellum2 as W  # noqa: E402
from benchmarks.reference import f32_linear  # noqa: E402
from paddle_tpu import profiler  # noqa: E402
from paddle_tpu.framework import core  # noqa: E402
from paddle_tpu.inference import engine as E  # noqa: E402
from paddle_tpu.inference.engine import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.inference.paging import WindowPages  # noqa: E402
from paddle_tpu.models import DeepseekV32Config, DeepseekV32ForCausalLM  # noqa: E402
from paddle_tpu.models import Ling3Config, Ling3ForCausalLM  # noqa: E402
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.models import Mellum2Config, Mellum2ForCausalLM  # noqa: E402
from paddle_tpu.models import deepseek_v32 as dsv  # noqa: E402
from paddle_tpu.models import mellum2 as M  # noqa: E402
from paddle_tpu.ops import flash_attention as fa  # noqa: E402

SEED = 3_500_000_011  # past 2**31, as the driver's seeds are
INIT = {"matrix_std": 0.05}
TYPES = [M.SLIDING, M.SLIDING, M.SLIDING, M.FULL] * 2


@pytest.fixture(scope="module", autouse=True)
def _rng_guard():
    """Model builds consume the framework's default generator; later modules
    build weights without re-seeding it."""
    state = np.asarray(paddle.get_rng_state())
    yield
    paddle.set_rng_state(state)


def as_dict(cfg):
    return dict(vars(cfg), init=INIT)


def seeded_model(cfg, seed=SEED):
    model = Mellum2ForCausalLM(cfg)
    d = as_dict(cfg)
    made = W.make(seed, d, W.all_leaves(d), jnp.float32)
    named = dict(model.named_parameters())
    assert set(named) == set(made)
    for n, p in named.items():
        assert tuple(p.shape) == tuple(made[n].shape), n
        p._data = made[n]
    return model


def layer_leaves(cfg, layer, seed=SEED):
    d = as_dict(cfg)
    pre = f"model.layers.{layer}."
    return {n[len(pre):]: a for n, a in W.make(seed, d, W.layer_leaves(d, layer), jnp.float32).items()}


def engine(model, **kw):
    kw = {"slots": 3, "max_len": 256, "prefill_buckets": [16, 32], "page_size": 8, "queue_depth": 8, **kw}
    return ContinuousBatchingEngine(model, **kw)


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, size=n).astype(np.int32) for n in lengths]


def serve(eng, ps, new=10):
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, new if isinstance(new, list) else [new] * len(ps))]
    eng.run_until_idle()
    assert all(r.finish_reason == "length" and r.error is None for r in reqs)
    return [list(r.tokens) for r in reqs]


def normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


@pytest.fixture
def invariants():
    core.set_flags({"FLAGS_serve_debug_invariants": True})
    yield
    core.set_flags({"FLAGS_serve_debug_invariants": False})


@pytest.fixture
def interpret():
    fa._FORCE_INTERPRET = True
    try:
        yield
    finally:
        fa._FORCE_INTERPRET = False


# -- the configuration ----------------------------------------------------------------

def test_layer_types_follow_the_published_period_and_odd_switches_are_refused():
    whole = Mellum2Config()
    assert whole.layer_types == [M.FULL if (i + 1) % 4 == 0 else M.SLIDING for i in range(28)]
    assert [whole.window(i) for i in range(4)] == [1024, 1024, 1024, None]
    assert (whole.n_routed_experts, whole.experts_held, whole.expert_offset) == (64, 64, 0)
    assert Mellum2Config.tiny().layer_types == TYPES
    for bad in (dict(layer_types=[M.FULL]), dict(mlp_layer_types=["dense"] * 8), dict(attention_bias=True),
                dict(tie_word_embeddings=True), dict(use_sliding_window=False), dict(num_key_value_heads=3),
                dict(layer_types=["chunked_attention"] * 8)):
        with pytest.raises(ValueError):
            Mellum2Config.tiny(**bad)
    bad_rope = M._published_rope()
    bad_rope[M.FULL]["rope_type"] = "longrope"
    with pytest.raises(ValueError, match="longrope"):
        Mellum2Config.tiny(rope_parameters=bad_rope)


# -- rope: a table per layer type -------------------------------------------------------

@pytest.mark.parametrize("kind", [M.SLIDING, M.FULL])
@pytest.mark.parametrize("cfg", [Mellum2Config.tiny(), Mellum2Config(num_hidden_layers=4, max_position_embeddings=512)],
                         ids=["tiny", "published"])
def test_rope_tables_of_each_layer_type_are_the_references(kind, cfg):
    inv, factor = M.rope_inv_freq(cfg, kind)
    want_inv, want_factor = ref.inv_freq(as_dict(cfg), kind)
    np.testing.assert_allclose(inv, want_inv, rtol=1e-12)
    assert factor == want_factor == (1.0 if kind == M.SLIDING else 1.2772588722239782)
    cos, sin = M._rope_tables(cfg, kind)
    rc, rs = ref.rope_tables(as_dict(cfg), kind, cfg.max_position_embeddings)
    np.testing.assert_allclose(cos._data, rc, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(sin._data, rs, rtol=1e-6, atol=1e-7)
    plain = 1.0 / (500000.0 ** (np.arange(0, cfg.head_dim, 2) / cfg.head_dim))
    if kind == M.SLIDING:
        np.testing.assert_allclose(inv, plain, rtol=1e-12)
    else:  # YaRN bites: the fast dimensions are kept, the slow ones interpolated by the factor
        f = cfg.rope_parameters[M.FULL]["factor"]
        assert inv[0] == plain[0] and inv[-1] == pytest.approx(plain[-1] / f) and (inv <= plain).all()


def test_yarn_is_the_shared_function_and_a_cut_context_does_not_rescale_it():
    cfg = Mellum2Config(num_hidden_layers=4)
    cut = Mellum2Config(num_hidden_layers=4, max_position_embeddings=4096)  # below the original 8192
    np.testing.assert_array_equal(M.rope_inv_freq(cfg, M.FULL)[0], M.rope_inv_freq(cut, M.FULL)[0])
    assert M.yarn_inv_freq is dsv.yarn_inv_freq


# -- the router and the expert sum ------------------------------------------------------

def test_softmax_router_picks_and_weights_as_the_reference_does():
    cfg = Mellum2Config.tiny()
    lw = layer_leaves(cfg, 1)
    x = normal(3, 40, cfg.hidden_size)
    experts, wts = dsv._route_softmax(cfg, x, lw["mlp.gate.weight"])
    want = np.asarray(ref.route(as_dict(cfg), f32_linear, lw, x))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(experts), np.asarray(wts), axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
    assert experts.shape == (40, 2) and np.allclose(np.asarray(wts).sum(1), 1.0, atol=1e-6)
    loose = Mellum2Config.tiny(norm_topk_prob=False)
    _, raw = dsv._route_softmax(loose, x, lw["mlp.gate.weight"])
    assert (np.asarray(raw).sum(1) < 1.0).all()


@pytest.mark.parametrize("tokens,kernel", [(3, False), (3, True), (40, False)],
                         ids=["small_step_off_the_tpu", "small_step_through_the_kernel", "the_block_loop"])
def test_expert_sum_with_all_experts_held_is_the_references(tokens, kernel):
    """3 tokens x 2 picks < 8 experts takes the loop; 4 x 2 >= 8 is a small
    step, which takes the `grouped_experts` kernel (interpreted here; off
    the TPU the loop as well): all are the reference's sum."""
    cfg = Mellum2Config.tiny()
    lw = layer_leaves(cfg, 2)
    w = {k[len("mlp."):]: v for k, v in lw.items() if k.startswith("mlp.")}
    calls = lambda: profiler.flash_pallas_summary().get("grouped_experts", 0)
    fa._FORCE_INTERPRET = kernel
    try:
        for n in (tokens, tokens + 1):
            x = normal(n, n, cfg.hidden_size)
            live = jnp.ones((n,), bool).at[0].set(False)
            before = calls()
            y, stats = M._moe(cfg, w, x, live)
            assert calls() - before == (kernel and n == 4)
            want = np.asarray(ref.moe(as_dict(cfg), f32_linear, lw, x))
            np.testing.assert_allclose(np.asarray(y)[1:], want[1:], rtol=2e-4, atol=2e-6)
            assert np.abs(np.asarray(y)[0]).max() == 0  # a row that is not live routes nowhere
            assert int(stats[0]) == n - 1 and int(stats[1]) == 2 * (n - 1)
    finally:
        fa._FORCE_INTERPRET = False


# -- the walk that starts at the window ---------------------------------------------------

def _paged(rng, b, hk, d, ps, P, pos, first):
    pages = 1 + sum(int(p) // ps - int(f) // ps + 1 for p, f in zip(pos, first))
    ak, av = (jnp.asarray(rng.normal(size=(pages, hk, ps, d)), jnp.float32) for _ in range(2))
    tables, nxt = np.zeros((b, P), np.int32), 1
    for s in range(b):
        for col in range(int(first[s]) // ps, int(pos[s]) // ps + 1):  # only what is in reach is mapped
            tables[s, col] = nxt
            nxt += 1
    return ak, av, jnp.asarray(tables)


@pytest.mark.parametrize("pages_per_step", [1, 2, None], ids=["1_page_a_copy", "2_pages_a_copy", "picked"])
def test_windowed_walk_equals_dense_masked_attention(interpret, monkeypatch, pages_per_step):
    """A start that is not page-aligned, `pos < window`, a window that ends
    on a page's last row, an idle slot; columns before the first visible page
    hold NOTHING (page 0), as the page manager leaves them."""
    if pages_per_step:
        monkeypatch.setattr(fa, "_pick_pages_per_step", lambda *a: pages_per_step)
    rng = np.random.default_rng(5)
    b, h, hk, d, ps, P, window = 5, 8, 2, 16, 8, 16, 20
    pos = np.array([5, 37, 90, 0, 63], np.int32)
    first = np.maximum(pos - window + 1, 0).astype(np.int32)
    assert first[1] % ps and (first[4] + window) % ps == 0
    ak, av, tables = _paged(rng, b, hk, d, ps, P, pos, first)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    got = fa._fused_paged_decode_window(q, ak, av, tables, jnp.asarray(pos), jnp.asarray(first), P * ps,
                                        d ** -0.5, True)
    k, v = fa.paged_gather_kv(ak, tables, P * ps), fa.paged_gather_kv(av, tables, P * ps)
    want = M._attend_dense(q[:, 0], k, v, jnp.asarray(pos), jnp.asarray(first), d ** -0.5)
    np.testing.assert_allclose(got[:, 0], want, rtol=2e-5, atol=2e-6)
    assert np.isfinite(np.asarray(got)).all()
    # with first = 0 the windowed walk is the plain one
    full_k, full_v, full_t = _paged(rng, b, hk, d, ps, P, pos, np.zeros_like(pos))
    plain = fa._fused_paged_decode(q, full_k, full_v, full_t, jnp.asarray(pos), P * ps, d ** -0.5, True)
    zero = fa._fused_paged_decode_window(q, full_k, full_v, full_t, jnp.asarray(pos), jnp.zeros(b, jnp.int32),
                                         P * ps, d ** -0.5, True)
    np.testing.assert_array_equal(plain, zero)


def test_walk_without_a_window_traces_no_third_operand(interpret):
    """`chat32` and `reason64` compile the kernel they compiled before: two
    scalar-prefetch operands, no compare against a first position."""
    q, arena = jnp.zeros((2, 1, 4, 16)), jnp.zeros((5, 2, 8, 16))
    tables, pos = jnp.zeros((2, 4), jnp.int32), jnp.zeros(2, jnp.int32)
    plain = jax.make_jaxpr(lambda *a: fa._fused_paged_decode_forward(*a, 32, 0.25, True))(
        q, arena, arena, tables, pos)
    windowed = jax.make_jaxpr(lambda *a: fa._fused_paged_decode_forward(*a[:5], 32, 0.25, True, first=a[5]))(
        q, arena, arena, tables, pos, pos)

    def prefetch(jaxpr):
        eqn = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"][0]
        return eqn.params["grid_mapping"].num_index_operands

    assert (prefetch(plain), prefetch(windowed)) == (2, 3)
    with pytest.raises(ValueError, match="windowed"):  # a chunk's rows keep the old grid: no window there
        fa._fused_paged_decode_forward(jnp.zeros((2, 40, 8, 16)), arena, arena, tables, pos, 32, 0.25, True, first=pos)


@pytest.mark.parametrize("window", [None, 16])
def test_chunk_attention_through_the_page_table_reads_only_what_its_rows_see(window):
    """A chunk at an offset that is no multiple of the key block, against
    dense masked attention; under a window the pages behind the first row's
    reach are NOT mapped (page 0), and the answer does not change."""
    rng = np.random.default_rng(9)
    H, KV, d, ps, P, s, start, n = 4, 2, 16, 8, 16, 24, 52, 20
    k_all, v_all = normal(1, 128, KV, d), normal(2, 128, KV, d)
    q = normal(3, s, H, d)
    table = np.arange(1, P + 1, dtype=np.int32)
    if window:
        table[: (start - window + 1) // ps] = 0
    put = lambda rows: jnp.zeros((P + 1, KV, ps, d)).at[1:].set(
        jnp.moveaxis(rows.reshape(P, ps, KV, d), 2, 1))
    q_pos = start + jnp.arange(s, dtype=jnp.int32)
    got = M._attend_pages(q, put(k_all), put(v_all), jnp.asarray(table), q_pos, jnp.int32(start + n), window,
                          d ** -0.5, key_rows=32, q_rows=8)
    rows = jnp.broadcast_to(k_all[None], (s, 128, KV, d)), jnp.broadcast_to(v_all[None], (s, 128, KV, d))
    want = M._attend_dense(q, *rows, q_pos, M.first_visible(q_pos, window), d ** -0.5)
    np.testing.assert_allclose(got.reshape(s, H, d)[:n], want[:n], rtol=2e-5, atol=2e-6)


# -- a layer, and the whole served path, against the plain reference -------------------------

@pytest.fixture(scope="module")
def served():
    """Three prompts, one several windows and chunks long (150 = 4 x 32 + 22;
    the window is 16, a page 8 rows), decoded 40 tokens on: every decode step
    crosses a page somewhere, and every slot releases pages behind its
    window."""
    profiler.reset_moe()
    core.set_flags({"FLAGS_serve_debug_invariants": True})
    try:
        cfg = Mellum2Config.tiny()
        eng = engine(seeded_model(cfg))
        ps = prompts((20, 150, 45))
        toks = serve(eng, ps, new=40)
    finally:
        core.set_flags({"FLAGS_serve_debug_invariants": False})
    seqs = [np.concatenate([p, np.asarray(t, np.int32)]) for p, t in zip(ps, toks)]
    logits = ref.served_logit_gaps(as_dict(cfg), SEED, seqs, [len(p) for p in ps], pad_to=256)
    return eng, ps, toks, logits, seqs


def test_prefill_then_paged_decode_agrees_with_the_references_full_forward(served):
    """Float32 on both sides: a served token's logit lies below the
    reference's best by summation order alone."""
    eng, _, toks, logits, _ = served
    for (best, got, first, _), t in zip(logits, toks):
        assert float(np.max(best - got)) < 1e-3
        assert (first == np.asarray(t)).mean() == 1.0
    # 20 and the first 32 of the others: the fresh program of one bucket; 150 = 4 x 32 + 22 and 45 = 32 + 13
    assert eng.compile_counts() == {"prefill": 1, "decode": 1, "aot_hits": 0, "chunk_prefill": 2, "copy": 0}


def test_a_reference_without_the_window_reads_other_logits(served):
    """The comparison can tell: with every layer full the reference's best
    token differs from the served one somewhere, by a real gap."""
    _, ps, toks, _, seqs = served
    cfg = as_dict(Mellum2Config.tiny())
    off = ref.served_logit_gaps(cfg, SEED, seqs[1:2], [len(ps[1])], pad_to=256, window=False)
    best, got, _, _ = off[0]
    assert float(np.mean(best - got)) > 20 * 1e-3


def test_counters_of_the_step_and_the_page_groups(served):
    eng = served[0]
    moe, win = profiler.moe_summary(), profiler.window_cache_summary()
    assert moe["steps"] == 39 and moe["tokens"] == 39 * 3 * 8  # 3 slots, 8 expert layers
    assert moe["picks_held"] == 2 * moe["tokens"]  # every expert is held: no pick lands elsewhere
    ctx = [np.arange(n + 1, n + 40) for n in (20, 150, 45)]  # a step's context, its own token among it
    assert win["decode"] == {
        "steps": 39, "live_slots": 117,
        "rows_in_reach_full": 2 * int(sum(c.sum() for c in ctx)),
        "rows_in_reach_window": 6 * int(sum(np.minimum(c, 16).sum() for c in ctx))}
    w, f = win["window"], win["full"]
    assert (w["reach"], w["pool_pages"], f["reach"], f["pool_pages"]) == (16, 3 * 3 + 7 + 1, None, 3 * 32 + 1)
    assert w["slot_pages_peak"] == 3 and w["prefill_pages_peak"] <= 7  # 15 rows back and 32 on: 7 pages
    assert f["slot_pages_peak"] == 24  # 150 + 40 rows of 8
    assert w["released_behind"] >= (150 + 39 - 15) // 8 and w["pages_live"] == 0
    # arenas by kind AND group, each group's at its own pool's size
    rows = 2 * 8 * 16 * 4  # a page of one kind: 2 KV heads x 8 rows x 16 wide, float32
    assert profiler.arena_summary() == {"k": 2 * 97 * rows, "v": 2 * 97 * rows,
                                        "k.window": 6 * 17 * rows, "v.window": 6 * 17 * rows}
    for a, t in zip(eng._arenas, TYPES):
        assert a.k.shape[0] == (17 if t == M.SLIDING else 97)


def test_a_long_prompt_in_chunks_equals_the_same_prompt_whole(served):
    _, ps, toks, _, _ = served
    cfg = Mellum2Config.tiny()
    whole = engine(seeded_model(cfg), prefill_buckets=[160])
    assert serve(whole, [ps[1]], new=40) == [toks[1]]
    assert whole.compile_counts()["chunk_prefill"] == 0 and whole._window.chunk_pages == 23


def test_the_walk_serves_the_same_tokens_as_the_plain_path(interpret, served):
    """The engine with the Pallas walk (interpreted) on both page groups
    against the engine's plain path and the reference."""
    _, ps, toks, _, _ = served
    profiler.reset_flash_pallas()
    profiler.reset_flash_fallbacks()
    eng = engine(seeded_model(Mellum2Config.tiny()))
    assert serve(eng, [ps[0], ps[2]], new=24) == [toks[0][:24], toks[2][:24]]
    calls = profiler.flash_pallas_summary()["paged_decode_fused"]  # a count of traces: every layer's walk
    assert calls and calls % 8 == 0 and not profiler.flash_fallback_summary()
    walks = profiler.paged_walk_summary()
    # the summary keeps every walk traced in this process: the engine's is 3 slots, one row, K and V
    assert (3, 1, 2) in {(w["b"], w["sq"], w["kv_operands"]) for w in walks}


# -- the window group's pages ---------------------------------------------------------------

def test_window_pages_bounds_and_arithmetic():
    w = WindowPages(slots=32, pages_per_seq=256, page_size=128, reach=1024, chunk_rows=2048)
    assert (w.slot_pages, w.chunk_pages, w.pool_pages) == (9, 25, 32 * 9 + 25 + 1)  # ISSUE 35's count
    assert w.pages_for(300) == 3 and w.pages_for(28_672) == 25
    assert w.map_range(0, 0, 2047) and w.held(0) == 16
    assert w.map_range(0, w.first_visible(2048), 4095) and w.held(0) == 24  # rows 1025 .. 4095
    assert w.released_behind == 8 and w.pool.used_count() == 24
    assert not w.map_range(0, w.first_visible(2048), 4095)  # nothing moved: no upload
    w.map_range(0, w.first_visible(4096), 4096)
    assert w.held(0) == 9 and np.flatnonzero(w.table[0]).tolist() == list(range(24, 33))
    w.check([4097] + [None] * 31)
    w.table[0, 3] = w.table[0, 30]  # a page mapped twice, and one behind the window
    with pytest.raises(AssertionError, match="window invariant"):
        w.check([4097] + [None] * 31)
    w.table[0, 3] = 0
    w.release(0)
    assert w.pool.used_count() == 0 and not w.table.any()
    with pytest.raises(ValueError):
        WindowPages(2, 8, 8, 0, 16)


def test_a_page_one_slot_released_is_reused_by_another_while_the_first_decodes(invariants):
    """The pool is LIFO-free: what slot 0 gives back behind its window is
    what slot 1's admission and slot 0's own next page take.  Outputs are
    those of each request served alone, and of the reference."""
    cfg = Mellum2Config.tiny()
    a, b = prompts((40, 30), seed=11)
    alone = [serve(engine(seeded_model(cfg)), [p], new=n)[0] for p, n in ((a, 60), (b, 30))]
    eng = engine(seeded_model(cfg), slots=2)
    win = eng._window
    ra = eng.submit(a, max_new_tokens=60)
    seen_by_a, owner = set(), {}
    for _ in range(20):
        eng.step()
        seen_by_a |= {int(p) for p in win.table[0] if p}
    rb = eng.submit(b, max_new_tokens=30)
    reused = set()
    while eng.has_work():
        eng.step()
        assert win.held(0) <= win.slot_pages and win.held(1) <= win.slot_pages
        reused |= {int(p) for p in win.table[1] if p} & seen_by_a
        live = [int(p) for s in (0, 1) for p in win.table[s] if p]
        assert len(live) == len(set(live))  # never two holders at once
    assert reused and not ra.finished.is_set() is False
    assert [list(ra.tokens), list(rb.tokens)] == alone
    assert win.pool.used_count() == 0 and eng._pool.used_count() == 0


def test_join_finish_and_release_compile_nothing_and_hold_the_bound(invariants):
    eng = engine(seeded_model(Mellum2Config.tiny())).warmup()
    warm = eng.compile_counts()
    assert warm == {"prefill": 2, "decode": 1, "aot_hits": 0, "chunk_prefill": 2, "copy": 1}
    ps = prompts((12, 90, 33, 64, 7, 120), seed=4)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, (30, 12, 50, 5, 70, 20))]
    peak = 0
    while eng.has_work():
        eng.step()
        peak = max(peak, max(eng._window.held(s) for s in range(3)))
        assert eng._window.pool.used_count() <= 3 * eng._window.slot_pages
    assert all(r.finish_reason == "length" for r in reqs) and peak == eng._window.slot_pages == 3
    assert eng.compile_counts() == warm
    h = eng.healthz()
    assert h["window_page_free_frac"] == 1.0 and h["page_free_frac"] == 1.0
    r = eng.submit(ps[1], max_new_tokens=40)
    for _ in range(6):
        eng.step()
    eng.restart("test")  # the restart gives both groups' pages back
    eng.run_until_idle()
    assert r.finished.is_set() and eng._window.pool.used_count() == 0 and eng.compile_counts() == warm


def test_a_request_the_window_group_can_never_seat_is_refused_at_submit():
    eng = engine(seeded_model(Mellum2Config.tiny()))
    eng._window.pool._free_by_shard[0] = eng._window.pool._free_by_shard[0][:2]
    eng._window.pool.num_pages = 3
    with pytest.raises(E.QueueFull, match="window group"):
        eng.submit(prompts((100,))[0], max_new_tokens=4)


# -- the contract, and what the model refuses -----------------------------------------------

@pytest.mark.parametrize("kwargs,feature", [
    ({"tp": 2}, "tp"), ({"cp": 2}, "cp"), ({"kv_quant": "int8"}, "kv_quant"),
    ({"lora": object()}, "lora"), ({"spec_k": 2}, "spec_k"), ({"role": "decode"}, "role"),
    ({"role": "prefill"}, "role"), ({"prefix_cache": True}, "prefix_cache")])
def test_what_the_model_cannot_do_is_refused_at_construction(kwargs, feature):
    model = Mellum2ForCausalLM(Mellum2Config.tiny(num_hidden_layers=4))
    with pytest.raises(E.UnsupportedByModel) as err:
        engine(model, **kwargs)
    assert err.value.feature == feature and isinstance(err.value, ValueError)
    assert feature in Mellum2ForCausalLM.engine_unsupported and len(Mellum2ForCausalLM.engine_unsupported) == 7


@pytest.mark.parametrize("kwargs,feature", [
    ({"prefix_cache": True}, "prefix_cache"), ({"spec_k": 2}, "spec_k"), ({"role": "decode"}, "role"),
    ({"kv_quant": "int8"}, "kv_quant")])
def test_the_engine_itself_refuses_what_rests_on_one_page_group(monkeypatch, kwargs, feature):
    """Whatever a model with windowed layers says of itself."""
    monkeypatch.setattr(Mellum2ForCausalLM, "engine_unsupported", frozenset())
    with pytest.raises(E.UnsupportedByModel) as err:
        engine(Mellum2ForCausalLM(Mellum2Config.tiny(num_hidden_layers=4)), **kwargs)
    assert err.value.feature == feature


def test_two_reaches_in_one_model_are_refused(monkeypatch):
    model = Mellum2ForCausalLM(Mellum2Config.tiny(num_hidden_layers=4))
    layers = model.cache_layers()
    monkeypatch.setattr(model, "cache_layers", lambda: [layers[0], (layers[1][0], [], 8)] + layers[2:])
    with pytest.raises(ValueError, match="one reach"):
        engine(model)


@pytest.mark.parametrize("asked", [None, False])
def test_prefix_cache_left_to_the_flag_resolves_to_off(asked):
    assert core.flag("FLAGS_serve_prefix_cache")  # the flag's default is on
    eng = engine(Mellum2ForCausalLM(Mellum2Config.tiny(num_hidden_layers=4)), prefix_cache=asked)
    assert eng._prefix is None and eng._sessions is None


def test_model_is_created_in_its_dtype_and_declares_its_windows():
    model = Mellum2ForCausalLM(Mellum2Config.tiny(dtype="bfloat16"))
    leaves = dict(model.named_parameters())
    assert leaves["model.layers.1.mlp.experts.up_proj"]._data.dtype == jnp.bfloat16
    assert leaves["model.layers.3.self_attn.k_proj.weight"]._data.dtype == jnp.bfloat16
    assert leaves["model.layers.1.input_layernorm.weight"]._data.dtype == jnp.float32
    assert all(p.stop_gradient for p in leaves.values())
    assert not any("shared" in n or "bias" in n for n in leaves)
    assert [r[:3] for r in model.cache_rows()] == [("k", 2, 16), ("v", 2, 16)]
    assert [(bool(r), st, reach) for r, st, reach in model.cache_layers()] == [
        (True, [], 16 if t == M.SLIDING else None) for t in TYPES]
    with pytest.raises(NotImplementedError):
        model(paddle.to_tensor(np.zeros((1, 4), np.int32)))


@pytest.mark.parametrize("family", ["llama", "deepseek_v32", "ling3"])
def test_other_models_build_one_page_group_as_before(family):
    """Nothing new is declared, nothing new is built: one pool, one table as
    the decode step's operand, `arena_summary()` by kind alone."""
    np.random.seed(1234)
    paddle.seed(79)
    model = {
        "llama": lambda: LlamaForCausalLM(LlamaConfig.tiny(num_key_value_heads=2)),
        "deepseek_v32": lambda: DeepseekV32ForCausalLM(DeepseekV32Config.tiny(experts_held=4, expert_offset=4)),
        "ling3": lambda: Ling3ForCausalLM(Ling3Config.tiny(experts_held=4, expert_offset=4)),
    }[family]()
    eng = ContinuousBatchingEngine(model, slots=2, max_len=64, prefill_buckets=[16], page_size=8)
    assert eng._window is None and set(eng._layer_group) == {0}
    assert "window_page_free_frac" not in eng.healthz() and profiler.window_cache_summary() == {}
    assert not any(k.endswith(".window") for k in profiler.arena_summary())
    toks = serve(eng, prompts((9, 21), seed=3), new=6)
    assert tuple(eng._tables_t.shape) == (2, 8) and all(len(t) == 6 for t in toks)


def test_limits_read_the_geometry_of_the_engines_own_group(monkeypatch):
    """ROADMAP 3.1's rule: the fused kernel's head-size limit spoke of the
    FIRST declared row whatever group it was in."""
    model = Mellum2ForCausalLM(Mellum2Config.tiny(num_hidden_layers=4))
    layers = model.cache_layers()
    wide = [("k", 2, 512, "float32"), ("v", 2, 512, "float32")]
    # a windowed layer that declares rows 512 wide does not bind the full group's limit
    monkeypatch.setattr(model, "cache_layers", lambda: [(wide, [], 16)] + layers[1:])
    monkeypatch.setattr(model, "cache_rows", lambda: wide)
    assert engine(model, decode_kernel="fused")._head_dim == 16


# -- operations and bytes ------------------------------------------------------------------

def test_operation_and_byte_counts_follow_the_window():
    cfg = as_dict(Mellum2Config.tiny())
    d = [F.forward_flops_decode(cfg, n) for n in (10, 11, 30, 31)]
    pair = 4 * 4 * 16
    assert d[1] - d[0] == 8 * pair and d[3] - d[2] == 2 * pair  # past the window only the full layers grow
    assert F.rows_in_reach(cfg, 9) == (9, 9) and F.rows_in_reach(cfg, 90) == (90, 16)
    assert F.visible_pairs(40) == 820 and F.visible_pairs(40, 16) == 136 + 24 * 16
    assert F.forward_flops_prompt(cfg, 40) == 40 * F.token_flops(cfg) + 2 * 64 * 256 + pair * (
        2 * 820 + 6 * (136 + 24 * 16))
    p = F.param_counts(cfg)
    assert (p["layers"], p["sliding_layers"], p["full_layers"]) == (8, 6, 2)
    assert F.kv_row_bytes(cfg) == 2 * 2 * 16 * 2
    assert F.decode_bytes(cfg, 2, 5, 100, 60) == 2 * (2 * p["non_expert"] + 5 * p["expert"]) + 160 * 128
    assert F.walk_bytes(cfg, 10) == 1280 and F.walk_flops(cfg, 10) == 10 * pair
    whole = as_dict(Mellum2Config(num_hidden_layers=8))
    assert round(F.param_counts(whole)["held"] * 2 / 1e7) == 759  # ISSUE 35: 7.59 GB in bfloat16
    assert F.kv_row_bytes(whole) == 2048
