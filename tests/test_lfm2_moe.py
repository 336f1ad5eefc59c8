"""LFM2-8B-A1B on the serving path, tiny on the CPU in float32: the gated
short convolution's decode recurrence and its chunks against the full
convolution, the router against the published form, the packed `[k | v]`
walk against plain attention, the whole served path against the plain
reference (`benchmarks/reference_lfm2.py`) and its float8 control, the tails
a slot owns, the counters of the step, the engine's rule on whole-lane rows,
and what the model refuses.  Every expert is held (one pipeline stage holds
whole layers), so there is no share of the expert layer to tie to the uncut
one."""

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
from benchmarks import reference_lfm2 as ref  # noqa: E402
from benchmarks import weights_lfm2 as W  # noqa: E402
from benchmarks.reference import f32_linear, fp8_linear  # noqa: E402
from paddle_tpu import profiler  # noqa: E402
from paddle_tpu.inference import engine as E  # noqa: E402
from paddle_tpu.inference.engine import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM  # noqa: E402
from paddle_tpu.models import deepseek_v32 as dsv  # noqa: E402
from paddle_tpu.models import lfm2_moe as M  # noqa: E402
from paddle_tpu.models import mellum2 as M2  # noqa: E402
from paddle_tpu.ops import flash_attention as fa  # noqa: E402

SEED = 3_900_000_043  # past 2**31, as the driver's seeds are
INIT = {"matrix_std": 0.05, "router_bias_std": 0.01, "conv_std": 0.5}
KINDS = ["conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv"]


@pytest.fixture(scope="module", autouse=True)
def _rng_guard():
    """Model builds consume the framework's default generator; later modules
    build weights without re-seeding it."""
    state = np.asarray(paddle.get_rng_state())
    yield
    paddle.set_rng_state(state)


@pytest.fixture()
def interpret():
    old, fa._FORCE_INTERPRET = fa._FORCE_INTERPRET, True
    try:
        yield
    finally:
        fa._FORCE_INTERPRET = old


def as_dict(cfg):
    return W.model_cfg(dict(vars(cfg), init=INIT))


def seeded_model(cfg, seed=SEED):
    model = Lfm2MoeForCausalLM(cfg)
    d = as_dict(cfg)
    made = W.make(seed, d, W.all_leaves(d), jnp.float32)
    named = dict(model.named_parameters())
    assert set(named) == set(made)
    for n, p in named.items():
        assert tuple(p.shape) == tuple(made[n].shape), n
        p._data = made[n]
    return model


def layer_leaves(cfg, layer, seed=SEED):
    return ref.layer_weights(seed, as_dict(cfg), layer)


def sub(lw, pre):
    return {k[len(pre):]: v for k, v in lw.items() if k.startswith(pre)}


def engine(model, **kw):
    kw = {"slots": 3, "max_len": 128, "prefill_buckets": [16, 32], "page_size": 8, **kw}
    return ContinuousBatchingEngine(model, **kw)


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, size=n).astype(np.int32) for n in lengths]


def serve(eng, ps, new=10):
    reqs = [eng.submit(p, max_new_tokens=new) for p in ps]
    eng.run_until_idle()
    assert all(r.finish_reason == "length" and r.error is None for r in reqs)
    return [list(r.tokens) for r in reqs]


def normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), jnp.float32)


# -- the configuration ----------------------------------------------------------------

def test_layer_kinds_and_widths_are_the_published_ones():
    whole = Lfm2MoeConfig()
    assert whole.layer_types.count("conv") == 18 and whole.layer_types.count("full_attention") == 6
    assert (whole.head_dim, whole.num_key_value_heads, whole.n_routed_experts, whole.num_experts_per_tok) == (
        64, 8, 32, 4)
    assert [whole.is_moe(i) for i in range(3)] == [False, False, True]
    cut = Lfm2MoeConfig(num_hidden_layers=9, layer_types=whole.layer_types[1:10], num_dense_layers=1)
    assert cut.layer_types == KINDS
    assert [W.layer_kind(as_dict(cut), i) for i in range(9)] == KINDS
    assert cut.topk_norm_eps == 1e-6 and cut.n_group == cut.topk_group == 1


@pytest.mark.parametrize("bad,why", [
    (dict(conv_bias=True), "conv_bias"), (dict(use_expert_bias=False), "use_expert_bias"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"), (dict(num_hidden_layers=8), "every layer"),
    (dict(layer_types=["conv"] * 8 + ["sliding_attention"]), "a layer is"), (dict(conv_L_cache=1), "one tap")])
def test_the_config_refuses_what_is_not_written(bad, why):
    with pytest.raises(ValueError, match=why):
        Lfm2MoeConfig.tiny(**bad)


# -- the short convolution ------------------------------------------------------------

def test_conv_decode_recurrence_is_the_full_convolution():
    """One row at a time through the tail, from a zero tail, against the
    reference's causal convolution over the whole sequence: float32 on both
    sides, 1e-5 on outputs of order 1 (summation order alone)."""
    cfg = Lfm2MoeConfig.tiny()
    lw = layer_leaves(cfg, 2)
    w = sub(lw, "conv.")
    x = normal(1, 20, cfg.hidden_size)
    want = ref.conv(as_dict(cfg), f32_linear, lw, x, 20)
    tail = jnp.zeros((1, cfg.conv_L_cache - 1, cfg.hidden_size), jnp.float32)
    got = []
    for t in range(20):
        out, tail = M._conv_decode(w, x[t:t + 1], tail, jnp.ones((1,), bool))
        got.append(out[0])
    np.testing.assert_allclose(np.stack(got), want, rtol=1e-5, atol=1e-5)
    # an idle slot's tail stays
    _, kept = M._conv_decode(w, x[:1], tail, jnp.zeros((1,), bool))
    np.testing.assert_array_equal(kept, tail)


@pytest.mark.parametrize("cut", [1, 2, 13])
def test_a_prompt_in_two_chunks_resumes_the_tail_as_one_chunk_would(cut):
    """The second chunk starts from the tail the first left in the slot (its
    last L - 1 real rows, padding after `true_len` left out); the slot beside
    it is untouched."""
    cfg = Lfm2MoeConfig.tiny()
    w = sub(layer_leaves(cfg, 3), "conv.")
    x = normal(2, 24, cfg.hidden_size)
    tail = normal(3, 2, cfg.conv_L_cache - 1, cfg.hidden_size)
    whole, t_whole = M._conv_prefill(cfg, w, x, tail, 1, 24, fresh=True)
    pad = jnp.concatenate([x[:cut], normal(4, 16 - cut, cfg.hidden_size)])  # a bucket of 16, padded
    a, t_a = M._conv_prefill(cfg, w, pad, tail, 1, cut, fresh=True)
    b, t_b = M._conv_prefill(cfg, w, x[cut:], t_a, 1, 24 - cut, fresh=False)
    np.testing.assert_allclose(np.concatenate([a[:cut], b]), whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(t_b, t_whole, rtol=1e-6)
    np.testing.assert_array_equal(t_b[0], tail[0])
    np.testing.assert_allclose(t_whole[1], M._conv_gates(w, x[22:24])[1], rtol=1e-6)


# -- the router -----------------------------------------------------------------------

def published_route(logits, bias, k, scale):
    """The published `Lfm2MoeSparseMoeBlock.route_tokens_to_experts`,
    transcribed in numpy: sigmoid, the bias for the choice only, top-k, the
    chosen unbiased scores over their sum + 1e-6, times the scale."""
    s = 1.0 / (1.0 + np.exp(-logits))
    chosen = np.argsort(-(s + bias), axis=1, kind="stable")[:, :k]
    w = np.take_along_axis(s, chosen, axis=1)
    return chosen, w / (w.sum(axis=1, keepdims=True) + 1e-6) * scale


def test_router_is_the_published_form():
    cfg = Lfm2MoeConfig.tiny(routed_scaling_factor=1.5)
    x = normal(5, 64, cfg.hidden_size)
    gate = normal(6, cfg.hidden_size, cfg.num_experts) * 0.1
    bias = normal(7, cfg.num_experts) * 0.3  # large enough to change choices
    experts, wts = dsv._route(cfg, x, gate, bias)
    chosen, want = published_route(np.asarray(x @ gate), np.asarray(bias), cfg.num_experts_per_tok, 1.5)
    assert (np.sort(np.asarray(experts), 1) == np.sort(chosen, 1)).all()
    order = lambda e, v: np.take_along_axis(v, np.argsort(e, 1), 1)
    np.testing.assert_allclose(order(np.asarray(experts), np.asarray(wts)), order(chosen, want), rtol=1e-5)
    unbiased = np.argsort(-np.asarray(x @ gate), axis=1)[:, :4]
    assert (np.sort(unbiased, 1) != np.sort(chosen, 1)).any()  # the bias did choose
    # the epsilon is LFM2's alone: a config without it keeps the plain quotient
    plain = dsv.DeepseekV32Config.tiny()
    assert not hasattr(plain, "topk_norm_eps")
    ref_w = np.asarray(ref.route(dict(as_dict(cfg), routed_scaling_factor=1.5), f32_linear,
                                 {"feed_forward.gate.weight": gate, "feed_forward.expert_bias": bias}, x))
    np.testing.assert_allclose(np.sort(ref_w, 1)[:, -4:], np.sort(want, 1), rtol=1e-5)


# -- attention over the packed rows ---------------------------------------------------

def packed_arena(seed, pages, kvh, ps, d):
    k, v = normal(seed, pages, kvh, ps, d), normal(seed + 1, pages, kvh, ps, d)
    return k, v, jnp.concatenate([k, v], -1)


@pytest.mark.parametrize("pos", [[0, 9, 30], [31, 5, 17]])
def test_packed_walk_is_plain_attention_over_k_and_v(interpret, pos):
    """`[q | 0]` against `[k | v]` rows in the single-operand walk: the second
    half of the output is softmax(q . k * d^-0.5) v.  1e-5 (float32,
    summation order)."""
    S, H, kvh, d, ps = 3, 4, 2, 16, 8
    k, v, kv = packed_arena(10, 13, kvh, ps, d)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 8, 9, 10]], jnp.int32)
    q = normal(12, S, H, d)
    p = jnp.asarray(pos, jnp.int32)
    qa = jnp.concatenate([q, jnp.zeros_like(q)], -1)[:, None]
    got = fa._fused_paged_decode(qa, kv, None, tables, p, 32, d ** -0.5, True)[:, 0, :, d:]
    want = M2._attend_dense(q, fa.paged_gather_kv(k, tables, 32), fa.paged_gather_kv(v, tables, 32), p,
                            jnp.zeros_like(p), d ** -0.5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_prefill_over_packed_pages_is_attention_over_k_and_v():
    """`_attend_pages` with `av=None` reads K and V from the halves of each
    packed row; the same call over the two arenas is the plain form."""
    H, kvh, d, ps = 4, 2, 16, 8
    k, v, kv = packed_arena(20, 9, kvh, ps, d)
    table = jnp.asarray([3, 1, 4, 5, 8, 2, 0, 0], jnp.int32)
    q = normal(22, 10, H, d)
    q_pos = 23 + jnp.arange(10, dtype=jnp.int32)
    got = M2._attend_pages(q, kv, None, table, q_pos, jnp.int32(33), None, d ** -0.5, key_rows=16, q_rows=8)
    want = M2._attend_pages(q, k, v, table, q_pos, jnp.int32(33), None, d ** -0.5, key_rows=16, q_rows=8)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# -- the served path ------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """One engine, three prompts (one in three chunks, each of which resumes
    the slot's tails), and the reference's logits over prompt + served
    tokens, in float32 and on the float8 grid."""
    profiler.reset_moe()
    cfg = Lfm2MoeConfig.tiny()
    eng = engine(seeded_model(cfg))
    ps = prompts((20, 75, 40))
    toks = serve(eng, ps, new=12)
    seqs = [np.concatenate([p, np.asarray(t, np.int32)]) for p, t in zip(ps, toks)]
    starts = [len(p) for p in ps]
    logits = ref.served_logit_gaps(as_dict(cfg), SEED, seqs, starts, pad_to=128)
    low = ref.served_logit_gaps(as_dict(cfg), SEED, seqs, starts, linear=fp8_linear, pad_to=128)
    return eng, ps, toks, logits, low


def test_prefill_then_paged_decode_agrees_with_the_references_full_forward(served):
    """Tolerance 1e-3 on the gap of a served token's logit below the
    reference's best, logits of order 1: float32 on both sides, so only
    summation order parts them (read: 0 to 1e-6).  The reference on the
    float8 e4m3 grid (the control's precision) puts another token first and
    fails it: a gap of 1e-2 or more."""
    eng, _, toks, logits, low = served
    for (best, got, first, _), t in zip(logits, toks):
        assert float(np.max(best - got)) < 1e-3
        assert (first == np.asarray(t)).mean() == 1.0
    assert max(float(np.max(best - got)) for best, got, _, _ in low) > 1e-2
    # 75 = 32 + 32 + 11: the fresh program once a bucket, the chunk program twice
    assert eng.compile_counts() == {"prefill": 1, "decode": 1, "aot_hits": 0, "chunk_prefill": 2, "copy": 0}


def test_counters_of_the_step_come_with_its_tokens(served):
    """Eleven decode steps of three slots: the K/V rows in reach over the two
    attention layers (`pos + 1` a slot a layer) and the tails of the seven
    convolution layers, counted inside the step."""
    eng, ps, _, _, _ = served
    moe, conv, walk = profiler.moe_summary(), profiler.short_conv_summary(), profiler.kv_walk_summary()
    assert moe["steps"] == 11 and moe["tokens"] == 11 * 3 * 8  # 3 slots, 8 expert layers
    per_slot = 7 * 2 * 64 * 4  # seven tails of 2 rows of 64, float32 here
    assert eng.model.tail_bytes_per_slot() == per_slot
    assert conv == {"steps": 11, "live_slots": 33, "tail_bytes_read": 33 * per_slot,
                    "tail_bytes_written": 33 * per_slot, "prefill_rows": 20 + 75 + 40, "chunks_resumed": 2 + 1}
    rows = 2 * sum(n + i for n in (20, 75, 40) for i in range(1, 12))
    assert walk == {"steps": 11, "live_slots": 33, "rows_in_reach": rows, "rows_per_step": rows / 11}
    assert profiler.linear_attn_summary() == {}
    assert profiler.arena_summary() == {"kv": 2 * 49 * 2 * 8 * 32 * 4, "conv_tail": 7 * 3 * 2 * 64 * 4}
    for a, kind in zip(eng._arenas, KINDS):
        assert (a.row_names, a.state_names) == ((("kv",), ()) if kind != "conv" else ((), ("conv_tail",)))


def test_the_kernels_path_serves_what_the_plain_path_serves(interpret):
    """Four slots reach the `grouped_experts` kernel (4 x 4 picks >= 8
    experts) and the walk, both interpreted: the same tokens as the XLA
    path, every expert layer through the kernel.  One attention layer and
    one convolution, both with experts, keep the interpreter's time down."""
    cfg = Lfm2MoeConfig.tiny(num_hidden_layers=2, layer_types=["full_attention", "conv"], num_dense_layers=0)
    ps = prompts((9, 40, 17, 26), seed=3)
    profiler.reset_flash_pallas()
    got = serve(engine(seeded_model(cfg), slots=4), ps, new=5)
    calls = profiler.flash_pallas_summary()
    fa._FORCE_INTERPRET = False
    assert got == serve(engine(seeded_model(cfg), slots=4), ps, new=5)
    assert calls.get("grouped_experts", 0) >= 1 and calls.get("paged_decode_fused", 0) >= 1


def test_an_idle_slots_tail_is_untouched_and_a_reseated_slot_starts_from_zero():
    cfg = Lfm2MoeConfig.tiny()
    eng = engine(seeded_model(cfg))
    a, c, d = prompts((30, 12, 25), seed=7)
    ra, rc = eng.submit(a, max_new_tokens=14), eng.submit(c, max_new_tokens=3)
    while not rc.finished.is_set():
        eng.step()
    assert not ra.finished.is_set()
    conv = [x for x in eng._arenas if x.state_names]
    left = [np.asarray(x.state_buffers()[0]._data[s]) for x in conv for s in (1, 2)]
    eng.run_until_idle()
    after = [np.asarray(x.state_buffers()[0]._data[s]) for x in conv for s in (1, 2)]
    for was, now in zip(left, after):  # slot 1 as its request left it, slot 2 never seated: zeros
        np.testing.assert_array_equal(was, now)
    assert all(np.abs(x).max() > 0 for x in left[0::2]) and all(np.abs(x).max() == 0 for x in left[1::2])
    got = serve(eng, [d], new=8)  # a slot that still holds its predecessor's tails
    assert got == serve(engine(seeded_model(cfg)), [d], new=8)


# -- the contract, and what the model refuses -----------------------------------------

@pytest.mark.parametrize("kwargs,feature", [
    ({"tp": 2}, "tp"), ({"cp": 2}, "cp"), ({"kv_quant": "int8"}, "kv_quant"),
    ({"lora": object()}, "lora"), ({"spec_k": 2}, "spec_k"), ({"role": "decode"}, "role"),
    ({"prefix_cache": True}, "prefix_cache")])
def test_what_the_model_cannot_do_is_refused_at_construction(kwargs, feature):
    model = Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny(num_hidden_layers=2, layer_types=["conv", "full_attention"]))
    with pytest.raises(E.UnsupportedByModel) as err:
        engine(model, **kwargs)
    assert err.value.feature == feature


def test_the_engine_refuses_a_row_that_is_not_whole_lanes_on_the_tpu(monkeypatch):
    """The rule is the engine's, for every model: at head size 16 LFM2's
    packed row is 32 wide and refused; at head size 64 (the published) it is
    128 and taken; a DeepSeek indexer row of 64 is refused the same way."""
    monkeypatch.setattr(E, "_rows_need_whole_lanes", lambda: True)
    narrow = Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny(num_hidden_layers=2, layer_types=["conv", "full_attention"]))
    with pytest.raises(ValueError, match="'kv' 32 wide.*whole lanes"):
        engine(narrow)
    wide = Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny(
        num_hidden_layers=2, layer_types=["conv", "full_attention"], hidden_size=128, num_attention_heads=2,
        num_key_value_heads=1, moe_intermediate_size=16, intermediate_size=32))
    eng = engine(wide, slots=1, max_len=32, prefill_buckets=[16])
    assert eng._arenas[1].kv.shape[-1] == 128
    dsv_model = dsv.DeepseekV32ForCausalLM(dsv.DeepseekV32Config.tiny())
    assert any(w % 128 for _, _, w, _ in dsv_model.cache_rows())
    with pytest.raises(ValueError, match="whole lanes"):
        engine(dsv_model)
    monkeypatch.undo()
    assert not E._rows_need_whole_lanes()  # the CPU takes any width


def test_model_is_created_in_its_dtype_with_a_tied_head_and_takes_no_gradient():
    model = Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny(dtype="bfloat16"))
    leaves = dict(model.named_parameters())
    assert not any("lm_head" in n for n in leaves)
    assert leaves["model.layers.1.feed_forward.experts.w1"]._data.dtype == jnp.bfloat16
    assert leaves["model.layers.0.conv.conv.weight"]._data.dtype == jnp.bfloat16
    for name in ("feed_forward.expert_bias", "self_attn.q_layernorm.weight", "self_attn.k_layernorm.weight"):
        assert leaves[f"model.layers.1.{name}"]._data.dtype == jnp.float32
    assert all(p.stop_gradient for p in leaves.values())
    assert [bool(r) for r, _ in model.cache_layers()] == [k != "conv" for k in KINDS]
    assert model.cache_rows() == [("kv", 2, 32, "bfloat16")]
    assert model.cache_state() == [("conv_tail", (2, 64), "bfloat16")]
    x = paddle.to_tensor(np.ones((1, 2, 64), np.float32)).astype("bfloat16")
    emb = leaves["model.embed_tokens.weight"]._data
    np.testing.assert_allclose(np.asarray(model.lm_head(x)._data, np.float32),
                               np.asarray(jnp.ones((1, 2, 64), jnp.bfloat16) @ emb.T, np.float32))
    with pytest.raises(NotImplementedError):
        model(paddle.to_tensor(np.zeros((1, 4), np.int32)))
