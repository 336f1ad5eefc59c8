"""DeepSeek-V3.2 on the serving path (ISSUE 29), tiny on the CPU: each layer
kind and the whole served path against the plain reference
(`benchmarks/reference_deepseek_v32.py`), the engine's contract with a served
model, and what the model refuses."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax.numpy as jnp  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from benchmarks import reference_deepseek_v32 as ref  # noqa: E402
from benchmarks import weights_deepseek_v32 as W  # noqa: E402
from benchmarks.reference import f32_linear  # noqa: E402
from paddle_tpu import profiler  # noqa: E402
from paddle_tpu.inference import engine as E  # noqa: E402
from paddle_tpu.inference.engine import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models import DeepseekV32Config, DeepseekV32ForCausalLM  # noqa: E402
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.models import deepseek_v32 as dsv  # noqa: E402

SEED = 2_900_000_123  # past 2**31, as the driver's seeds are


@pytest.fixture(scope="module", autouse=True)
def _rng_guard():
    """Model builds consume the framework's default generator; later modules
    build weights without re-seeding it."""
    state = np.asarray(paddle.get_rng_state())
    yield
    paddle.set_rng_state(state)


def config(**over):
    return DeepseekV32Config.tiny(experts_held=4, expert_offset=4, **over)


def as_dict(cfg, std=0.05):
    d = dict(vars(cfg))
    d["init"] = {"matrix_std": std, "router_bias_std": 0.01}
    return d


def seeded_model(cfg, seed=SEED):
    model = DeepseekV32ForCausalLM(cfg)
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


# -- each layer kind against the reference ------------------------------------------

def test_dense_mlp_and_expert_layer_match_the_reference():
    cfg = config()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(24, cfg.hidden_size)), jnp.float32)
    lw = layer_leaves(cfg, 0)
    got = dsv._swiglu(x, lw["mlp.gate_proj.weight"], lw["mlp.up_proj.weight"], lw["mlp.down_proj.weight"])
    want = ref.feed_forward(as_dict(cfg), f32_linear, lw, x, 24)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    lw = layer_leaves(cfg, 1)
    w = {k[len("mlp."):].removesuffix(".weight") if "shared" in k else k[len("mlp."):]: v
         for k, v in lw.items() if k.startswith("mlp.")}
    live = jnp.arange(24) < 20  # four rows of padding route nowhere
    got, stats = dsv._moe(cfg, w, x, live)
    want = ref.moe(as_dict(cfg), f32_linear, lw, x)
    np.testing.assert_allclose(got[:20], want[:20], rtol=1e-4, atol=1e-5)
    tokens, picks, hit, load = (int(v) for v in stats)
    weights = np.asarray(ref.route(as_dict(cfg), f32_linear, lw, x))[:20, 4:8]
    assert tokens == 20 and picks == int((weights > 0).sum())
    assert hit == int(((weights > 0).sum(0) > 0).sum()) and load == int((weights > 0).sum(0).max())
    # every token picks top-k experts of the best groups, weights sum to the scaling factor
    full = np.asarray(ref.route(as_dict(cfg), f32_linear, lw, x))
    assert ((full > 0).sum(1) == cfg.num_experts_per_tok).all()
    np.testing.assert_allclose(full.sum(1), cfg.routed_scaling_factor, rtol=1e-5)


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Four chips of four experts each: what each computes of the routed sum,
    with the shared expert counted once, is the layer with all 16 experts."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(40, 64)), jnp.float32)
    live = jnp.ones(40, bool)
    uncut = DeepseekV32Config.tiny()
    want = ref.moe(as_dict(uncut), f32_linear, layer_leaves(uncut, 1), x)
    shared = None
    routed = 0.0
    for share in range(4):
        cfg = DeepseekV32Config.tiny(experts_held=4, expert_offset=4 * share)
        lw = layer_leaves(cfg, 1)
        np.testing.assert_array_equal(  # a share draws the uncut model's experts at its indices
            lw["mlp.experts.up_proj"], layer_leaves(uncut, 1)["mlp.experts.up_proj"][4 * share:4 * share + 4])
        w = {k[len("mlp."):].removesuffix(".weight") if "shared" in k else k[len("mlp."):]: v
             for k, v in lw.items() if k.startswith("mlp.")}
        out, _ = dsv._moe(cfg, w, x, live)
        shared = dsv._swiglu(x, w["shared_experts.gate_proj"], w["shared_experts.up_proj"],
                             w["shared_experts.down_proj"])
        routed = routed + (out - shared)
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("start", [0, 19])
def test_attention_layer_matches_the_reference_where_selection_bites(start):
    """One chunk of a sequence through the latent and indexer caches against
    the reference's cache-free block: 45 keys in context, a top-k of 16."""
    cfg = config()
    n, ps = 45, 8
    x = jnp.asarray(np.random.default_rng(3).normal(size=(n, cfg.hidden_size)), jnp.float32)
    lw = layer_leaves(cfg, 1)
    w = {k[len("self_attn."):].removesuffix(".weight") if "k_norm" not in k else k[len("self_attn."):]: v
         for k, v in lw.items() if k.startswith("self_attn.")}
    cos, sin = ref.rope_tables(as_dict(cfg), 48)
    want = ref.attention(as_dict(cfg), f32_linear, lw, jnp.pad(x, ((0, 3), (0, 0))), cos, sin, n)[:n]
    lat = jnp.zeros((8, 1, ps, dsv.latent_width(cfg)), jnp.float32)
    idx = jnp.zeros((8, 1, ps, cfg.index_head_dim), jnp.float32)
    table = jnp.asarray([3, 1, 5, 2, 7, 4], jnp.int32)
    outs = []
    for s0, rows in ((0, start), (start, n - start)):
        if not rows:
            continue
        pad = 48 - rows  # a bucket with padding rows past true_len
        out, lat, idx = dsv._prefill_attention(
            cfg, w, jnp.pad(x[s0:s0 + rows], ((0, pad), (0, 0))), jnp.pad(cos[s0:s0 + rows], ((0, pad), (0, 0))),
            jnp.pad(sin[s0:s0 + rows], ((0, pad), (0, 0))), lat, idx, table,
            jnp.asarray([s0], jnp.int32), jnp.int32(rows))
        outs.append(out[:rows])
    np.testing.assert_allclose(jnp.concatenate(outs), want, rtol=2e-4, atol=2e-5)
    # and one decoded token on top of that cache, latent-space attention
    xt = jnp.asarray(np.random.default_rng(4).normal(size=(1, cfg.hidden_size)), jnp.float32)
    full = jnp.pad(jnp.concatenate([x, xt]), ((0, 2), (0, 0)))
    want = ref.attention(as_dict(cfg), f32_linear, lw, full, cos, sin, n + 1)[n]
    pos = jnp.asarray([n], jnp.int32)
    got, _, _, sel = dsv._decode_attention(cfg, w, xt, cos[pos], sin[pos], lat, idx, table[None], pos)
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    assert int(sel[0]) == cfg.index_topk < n


def test_select_mask_is_exact_with_ties_to_the_lower_position():
    score = jnp.asarray([[1.0, 3.0, 3.0, 3.0, -jnp.inf, 0.0], [2.0, -jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf]])
    assert np.asarray(dsv._select_mask(score, 2)).tolist() == [
        [False, True, True, False, False, False], [True, False, False, False, False, False]]
    rng = np.random.default_rng(5)
    s = jnp.asarray(np.round(rng.normal(size=(7, 40)), 1), jnp.float32)  # many ties
    np.testing.assert_array_equal(dsv._select_mask(s, 9), ref.top_mask(s, 9))


def test_indexer_selection_alone_equals_the_references():
    cfg = config()
    n = 70
    x = jnp.asarray(np.random.default_rng(6).normal(size=(n, cfg.hidden_size)), jnp.float32)
    lw = layer_leaves(cfg, 0)
    w = {k[len("self_attn."):].removesuffix(".weight") if "k_norm" not in k else k[len("self_attn."):]: v
         for k, v in lw.items() if k.startswith("self_attn.")}
    cos, sin = ref.rope_tables(as_dict(cfg), n)
    rows = [3, 15, 16, 40, 69]
    got = np.asarray(dsv.indexer_selection(cfg, w, x, cos, sin, rows))
    want = np.asarray(ref.indexer_selection(as_dict(cfg), f32_linear, lw, x, cos, sin, rows))
    np.testing.assert_array_equal(got, want)
    assert (got[0] >= 0).sum() == 4 and (got[-1] >= 0).sum() == cfg.index_topk  # t + 1, then top-k


# -- the served path ----------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """One engine, three prompts (one in three chunks, contexts of up to five
    times the top-k), and the reference's logits over prompt + served tokens."""
    profiler.reset_moe()
    cfg = config()
    eng = engine(seeded_model(cfg))
    ps = prompts((20, 75, 40))
    toks = serve(eng, ps, new=12)
    seqs = [np.concatenate([p, np.asarray(t, np.int32)]) for p, t in zip(ps, toks)]
    logits = ref.served_logit_gaps(as_dict(cfg), SEED, seqs, [len(p) for p in ps], pad_to=128)
    return eng, ps, toks, logits


def test_prefill_then_paged_decode_agrees_with_the_references_full_forward(served):
    eng, _, toks, logits = served
    for (best, got, first, _), t in zip(logits, toks):
        assert float(np.max(best - got)) < 1e-3
        assert (first == np.asarray(t)).mean() == 1.0
    # 75 = 32 + 32 + 11: the fresh program once a bucket, the chunk program twice
    assert eng.compile_counts() == {"prefill": 1, "decode": 1, "aot_hits": 0, "chunk_prefill": 2, "copy": 0}


def test_counters_of_the_step_come_with_its_tokens(served):
    moe, sparse = profiler.moe_summary(), profiler.sparse_attn_summary()
    assert moe["steps"] == 11 and moe["tokens"] == 11 * 3 * 2  # 3 slots, 2 expert layers
    assert 0 < moe["picks_held"] < moe["tokens"] * 4 and 0 < moe["experts_hit_per_step"] <= 8
    assert sparse["rows"] == 33 and sparse["rows_over_topk"] == 33
    assert sparse["selected"] == 33 * 16 and 0 < sparse["selected_share"] < 1
    assert profiler.arena_summary() == {
        "latent": 3 * 49 * 8 * 128 * 4, "index_key": 3 * 49 * 8 * 16 * 4}  # 24 values in whole lanes


def test_a_long_prompt_in_chunks_equals_the_same_prompt_whole(served):
    _, ps, toks, _ = served
    whole = engine(seeded_model(config()), prefill_buckets=[96])
    assert serve(whole, ps, new=12) == toks
    assert whole.compile_counts()["chunk_prefill"] == 0


def test_prefix_cache_hit_copies_both_row_kinds_and_changes_no_token():
    model = seeded_model(config())
    base = prompts((52,), seed=9)[0]
    ps = [base, np.concatenate([base, prompts((15,), seed=10)[0]])]
    plain = engine(model, prefix_cache=False, prefill_buckets=[64])
    want = [serve(plain, [p], new=6)[0] for p in ps]
    profiler.reset_paging()
    cached = engine(model, prefix_cache=True, prefill_buckets=[64])
    assert [serve(cached, [p], new=6)[0] for p in ps] == want
    paging = profiler.paging_summary()
    assert paging["prefix_hits"] == 1 and paging["cow_copies"] == 1  # 52 = 6 pages + 4 rows
    assert cached.compile_counts()["chunk_prefill"] == 1 and cached.compile_counts()["copy"] == 1


# -- the contract, and what the model refuses -----------------------------------------

@pytest.mark.parametrize("kwargs,feature", [
    ({"tp": 2}, "tp"), ({"cp": 2}, "cp"), ({"kv_quant": "int8"}, "kv_quant"),
    ({"lora": object()}, "lora"), ({"spec_k": 2}, "spec_k"), ({"role": "decode"}, "role"),
    ({"role": "prefill"}, "role")])
def test_what_the_model_cannot_do_is_refused_at_construction(kwargs, feature):
    model = DeepseekV32ForCausalLM(config(num_hidden_layers=2))
    with pytest.raises(E.UnsupportedByModel) as err:
        engine(model, **kwargs)
    assert err.value.feature == feature and isinstance(err.value, ValueError)


def test_model_is_created_in_its_dtype_and_takes_no_gradient():
    model = DeepseekV32ForCausalLM(config(num_hidden_layers=2, dtype="bfloat16"))
    leaves = dict(model.named_parameters())
    assert leaves["model.layers.1.mlp.experts.up_proj"]._data.dtype == jnp.bfloat16
    assert leaves["model.layers.1.mlp.gate.e_score_correction_bias"]._data.dtype == jnp.float32
    assert all(p.stop_gradient for p in leaves.values())
    assert [r[:3] for r in model.cache_rows()] == [("latent", 1, 128), ("index_key", 1, 16)]
    with pytest.raises(ValueError):
        DeepseekV32Config.tiny(num_nextn_predict_layers=1)
    with pytest.raises(ValueError):
        DeepseekV32Config.tiny(experts_held=8, expert_offset=12)


def test_llama_through_the_contract_compiles_and_decodes_as_before():
    """Llama declares its K and V rows and is called through `backbone`: the
    same arenas, the same compile counts, the tokens of its own `generate`."""
    np.random.seed(1234)
    paddle.seed(77)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_key_value_heads=2))
    assert model.backbone is model.llama and not hasattr(model, "engine_unsupported")
    assert [r[:3] for r in model.cache_rows()] == [("k", 2, 16), ("v", 2, 16)]
    eng = ContinuousBatchingEngine(model, slots=2, max_len=64, prefill_buckets=[16, 32],
                                   page_size=8).warmup()
    warm = eng.compile_counts()
    assert warm == {"prefill": 2, "decode": 1, "aot_hits": 0, "chunk_prefill": 2, "copy": 1}
    arena = eng._arenas[0]
    assert arena.row_names == ("k", "v") and len(arena.buffers()) == 2
    assert tuple(arena.k.shape) == tuple(arena.v.shape) == (2 * 8 + 1, 2, 8, 16)
    ps = prompts((9, 30), seed=3)
    toks = serve(eng, ps, new=8)
    for p, t in zip(ps, toks):
        want = np.asarray(model.generate(paddle.to_tensor(p[None]), max_new_tokens=8).numpy())[0]
        assert t == want[len(p):].tolist()
    assert eng.compile_counts() == warm
    # a prompt past the largest bucket goes in chunks and compiles nothing
    long = prompts((45,), seed=4)
    got = serve(eng, long, new=4)[0]
    want = np.asarray(model.generate(paddle.to_tensor(long[0][None]), max_new_tokens=4).numpy())[0]
    assert got == want[45:].tolist() and eng.compile_counts() == warm
