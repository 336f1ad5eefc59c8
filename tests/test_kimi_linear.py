"""Kimi-Linear-48B-A3B on the serving path, tiny on the CPU in float32: each
layer kind and the whole served path against the plain reference
(`benchmarks/reference_kimi_linear.py`), the chunked delta-rule scan against
the recurrence under Kimi Linear's unbounded decays, the layer kinds read from
the published lists, the chip's share of the expert layer, the state a slot
owns, the latent rows counted inside the step, and what the model refuses.
The tiny config's `full_attn_layers` [3, 7] ends in a period of four after
one of three: no modulus gives it."""

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
from benchmarks import reference_kimi_linear as ref  # noqa: E402
from benchmarks import reference_ling3 as ref_ling3  # noqa: E402
from benchmarks import weights_kimi_linear as W  # noqa: E402
from benchmarks.reference import f32_linear  # noqa: E402
from paddle_tpu import profiler  # noqa: E402
from paddle_tpu.inference import engine as E  # noqa: E402
from paddle_tpu.inference.engine import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models import KimiLinearConfig, KimiLinearForCausalLM  # noqa: E402
from paddle_tpu.models import deepseek_v32 as dsv  # noqa: E402
from paddle_tpu.models import ling3 as L  # noqa: E402
from paddle_tpu.ops import flash_attention as fa  # noqa: E402

SEED = 3_900_000_017  # past 2**31, as the driver's seeds are
# the benchmark configuration's draws (A_log uniform(0, ln 16), dt_bias uniform(-6, 3)); larger matrices
INIT = {"matrix_std": 0.05, "router_bias_std": 0.01, "conv_std": 0.5, "kda_A_log_max": float(np.log(16.0)),
        "kda_dt_bias_min": -6.0, "kda_dt_bias_max": 3.0}
KINDS = ["kda", "kda", "mla", "kda", "kda", "kda", "mla"]


@pytest.fixture(scope="module", autouse=True)
def _rng_guard():
    """Model builds consume the framework's default generator; later modules
    build weights without re-seeding it."""
    state = np.asarray(paddle.get_rng_state())
    yield
    paddle.set_rng_state(state)


def config(**over):
    return KimiLinearConfig.tiny(experts_held=4, expert_offset=4, **over)


def as_dict(cfg):
    return W.model_cfg(dict(vars(cfg), init=INIT))


def seeded_model(cfg, seed=SEED):
    model = KimiLinearForCausalLM(cfg)
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


def attn_weights(lw):
    return {k[len("self_attn."):]: v for k, v in lw.items() if k.startswith("self_attn.")}


def moe_weights(lw):
    return {k[len("mlp."):].removesuffix(".weight") if "shared" in k else k[len("mlp."):]: v
            for k, v in lw.items() if k.startswith("mlp.")}


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

def test_layer_kinds_are_read_from_the_published_lists():
    cfg = config()
    assert [cfg.layer_kind(i) for i in range(7)] == KINDS
    assert [W.layer_kind(as_dict(cfg), i) for i in range(7)] == KINDS
    assert [cfg.is_moe(i) for i in range(7)] == [False] + [True] * 6
    whole = KimiLinearConfig()
    assert [i + 1 for i in range(27) if whole.layer_kind(i) == "mla"] == [4, 8, 12, 16, 20, 24, 27]
    assert sum(whole.is_moe(i) for i in range(27)) == 26
    assert (whole.n_routed_experts, whole.num_experts_per_tok, whole.n_group, whole.kda_head_dim) == (256, 8, 1, 128)
    assert whole.max_position_embeddings == 1048576 and not hasattr(whole, "head_dim")  # 72, read by no layer
    cut = KimiLinearConfig(num_hidden_layers=5, linear_attn_config=dict(
        whole.linear_attn_config, full_attn_layers=[4], kda_layers=[1, 2, 3, 5]))
    assert [cut.layer_kind(i) for i in range(5)] == ["kda", "kda", "kda", "mla", "kda"]


@pytest.mark.parametrize("bad,why", [
    (dict(q_lora_rank=64), "low-rank"), (dict(mla_use_nope=False), "rope"),
    (dict(moe_router_activation_func="softmax"), "sigmoid"), (dict(tie_word_embeddings=True), "tied"),
    (dict(experts_held=8, expert_offset=12), "range"),
    (dict(linear_attn_config={"full_attn_layers": [3, 6], "kda_layers": [1, 2, 4, 5, 6], "head_dim": 16,
                              "num_heads": 4, "short_conv_kernel_size": 4}), "once"),
    (dict(linear_attn_config={"full_attn_layers": [3], "kda_layers": [1, 2, 4, 5, 6], "head_dim": 16,
                              "num_heads": 4, "short_conv_kernel_size": 4}), "once")])
def test_the_config_refuses_what_is_not_written(bad, why):
    with pytest.raises(ValueError, match=why):
        KimiLinearConfig.tiny(**bad)


# -- each layer kind against the reference ------------------------------------------

def kda_through_the_cache(cfg, w, x, n, cut):
    """x[:n] through `_kda_prefill` in two chunks (the second resumes the
    slot's state and tail) and x[n] through `_kda_decode`, in slot 1 of 3."""
    H, d, K = cfg.kda_heads, cfg.kda_head_dim, cfg.short_conv_kernel_size
    state = jnp.full((3, H, d, d), 7.0, jnp.float32)  # what a predecessor left: a fresh prefill ignores it
    tail = jnp.full((3, K - 1, 3 * H * d), 7.0, jnp.float32)
    outs = []
    for s0, rows in ((0, cut), (cut, n - cut)):
        chunk = jnp.pad(x[s0:s0 + rows], ((0, 48 - rows), (0, 0)))  # a bucket with padding rows
        out, state, tail = L._kda_prefill(cfg, w, chunk, state, tail, jnp.int32(1), jnp.int32(rows), s0 == 0)
        outs.append(out[:rows])
    np.testing.assert_array_equal(state[0], 7.0)  # other slots' state and tails are theirs
    np.testing.assert_array_equal(tail[2], 7.0)
    live = jnp.asarray([False, True, False])
    out, state2, tail2 = L._kda_decode(cfg, w, jnp.broadcast_to(x[n], (3, x.shape[1])), state, tail, live)
    np.testing.assert_array_equal(state2[0], state[0])  # an idle slot keeps its state and tail
    np.testing.assert_array_equal(tail2[2], tail[2])
    return jnp.concatenate(outs + [out[1:2]])


@pytest.mark.parametrize("cut", [3, 19])
def test_kda_layer_matches_the_reference_through_chunks_and_a_decoded_token(cut):
    """Tolerance: float32 on both sides, the chunked form against the
    recurrence differs by summation order alone (1e-6 of an output of order
    0.1); one gate for every channel, or the decay left out, is 100 times
    outside."""
    cfg = config()
    n = 45
    x = normal(3, n + 1, cfg.hidden_size)
    lw = layer_leaves(cfg, 1)
    want = ref.kda(as_dict(cfg), f32_linear, lw, jnp.pad(x, ((0, 2), (0, 0))), n + 1)[:n + 1]
    w = attn_weights(lw)
    got = kda_through_the_cache(cfg, w, x, n, cut)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    assert float(jnp.max(jnp.abs(want))) > 0.02
    g = L._kda_inputs(cfg, w, x, jnp.zeros((n + 1, 4, 3 * 64)))[3]
    assert float(jnp.min(g)) < -5.0  # past Ling-3's bound, as the benchmark seeds it
    if cut == 19:
        flat = dict(w, **{"g_b_proj.weight": jnp.zeros_like(w["g_b_proj.weight"])})  # one gate, 0.5, everywhere
        off = kda_through_the_cache(cfg, flat, x, n, cut)
        assert float(jnp.max(jnp.abs(off - want))) > 100 * 2e-6
        no_decay = dict(w, **{"dt_bias": jnp.full((64,), -40.0)})  # softplus -> 0: nothing fades
        off = kda_through_the_cache(cfg, no_decay, x, n, cut)
        assert float(jnp.max(jnp.abs(off - want))) > 100 * 2e-6


def test_the_references_kda_in_blocks_of_positions_is_the_recurrence_whole(monkeypatch):
    """At 64k positions the reference takes its KDA inputs a block at a time
    and carries the state and the convolution's rows across; at 16 rows a
    block it gives what one block gives."""
    cfg = as_dict(config())
    lw = layer_leaves(config(), 1)
    x = normal(4, 64, 64)
    whole = ref.kda(cfg, f32_linear, lw, x, 53)
    monkeypatch.setattr(ref, "TOKEN_BLOCK", 16)
    np.testing.assert_allclose(ref.kda(cfg, f32_linear, lw, x, 53), whole, rtol=1e-5, atol=1e-6)
    assert not np.asarray(whole[53:]).any()


def recurrence(q, k, v, g, beta, s0):
    S, out = np.asarray(s0, np.float64), []
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    for t in range(q.shape[0]):
        Sp = np.exp(g[t])[:, :, None] * S
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", Sp, k[t]))
        S = Sp + k[t][:, :, None] * u[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), S


@pytest.mark.parametrize("true_len", [1, 63, 65, 128])
@pytest.mark.parametrize("decays", ["down_to_e-20", "a_channel_at_e-20", "mixed_rows"])
def test_chunked_scan_equals_the_recurrence_under_unbounded_decays(true_len, decays):
    """128 rows in two chunks of 64 with decays Kimi Linear's gate reaches
    (down to e^-20 a row, 64 rows of which sum to e^-1280): the pairwise
    form `exp(G_i - G_j)` holds and `exp(G)` falls to its true limit, 0."""
    rng = np.random.default_rng(true_len)
    n, H, d = 128, 2, 16
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = unit(rng.normal(size=(n, H, d))), unit(rng.normal(size=(n, H, d)))
    v, beta = rng.normal(size=(n, H, d)), rng.uniform(0.05, 1.0, size=(n, H))
    g = {"down_to_e-20": rng.uniform(-20.0, -1e-3, size=(n, H, d)),
         "a_channel_at_e-20": np.where(np.arange(d) % 4 == 0, -20.0, rng.uniform(-0.05, 0.0, size=(n, H, d))),
         "mixed_rows": np.where(rng.random((n, 1, 1)) < 0.2, -20.0, -0.01) * np.ones((n, H, d))}[decays]
    valid = np.arange(n) < true_len
    g, beta = g * valid[:, None, None], beta * valid[:, None]
    s0 = rng.normal(size=(H, d, d))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    o, S = jax.jit(L._kda_scan)(f32(q), f32(k), f32(v), f32(g), f32(beta), f32(s0))
    want_o, want_S = recurrence(q[:true_len], k[:true_len], v[:true_len], g[:true_len], beta[:true_len], s0)
    np.testing.assert_allclose(o[:true_len], want_o, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(S, want_S, rtol=1e-4, atol=2e-5)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()


@pytest.mark.parametrize("start", [0, 19])
def test_nope_mla_layer_matches_the_reference_through_the_latent_pages(start):
    """One chunk of a sequence, or two, through the paged latent cache against
    the reference's cache-free block, then one decoded token that reaches its
    45 keys through the page table; no rope tables anywhere, no gate."""
    cfg = config()
    n, ps = 45, 8
    x = normal(3, n, cfg.hidden_size)
    lw = layer_leaves(cfg, 2)
    w = attn_weights(lw)
    assert "g_proj.weight" not in w
    want = ref.mla(as_dict(cfg), f32_linear, lw, jnp.pad(x, ((0, 3), (0, 0))), n)[:n]
    lat = jnp.zeros((8, 1, ps, dsv.latent_width(cfg)), jnp.float32)
    table = jnp.asarray([3, 1, 5, 2, 7, 4], jnp.int32)
    outs = []
    for s0, rows in ((0, start), (start, n - start)):
        if not rows:
            continue
        pad = ((0, 48 - rows), (0, 0))  # a bucket with padding rows past true_len
        out, lat = L._mla_prefill(cfg, w, jnp.pad(x[s0:s0 + rows], pad), None, None, lat, table,
                                  jnp.asarray([s0], jnp.int32), jnp.int32(rows))
        outs.append(out[:rows])
    np.testing.assert_allclose(jnp.concatenate(outs), want, rtol=2e-4, atol=2e-5)
    xt = normal(4, 1, cfg.hidden_size)
    full = jnp.pad(jnp.concatenate([x, xt]), ((0, 2), (0, 0)))
    want = ref.mla(as_dict(cfg), f32_linear, lw, full, n + 1)[n]
    pos = jnp.asarray([n], jnp.int32)
    got, _ = L._mla_decode(cfg, w, xt, None, None, lat, table[None], pos, 48)
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    # the keys of two pages swapped in the table: no position enters a score, so the token reads the same
    swapped = table[jnp.asarray([1, 0, 2, 3, 4, 5])]
    again, _ = L._mla_decode(cfg, w, xt, None, None, lat, swapped[None], pos, 48)
    np.testing.assert_allclose(again, got, rtol=1e-5, atol=1e-6)


def test_nope_mla_decode_walks_the_pages_in_the_kernel_as_it_gathers_them():
    """The Pallas page walk (interpreted here) over the one latent arena gives
    what the gathered context gives, without rope."""
    cfg = config()
    w = attn_weights(layer_leaves(cfg, 2))
    lat = normal(5, 9, 1, 8, dsv.latent_width(cfg)) * 0.3
    tables = jnp.asarray([[3, 1, 5, 2, 7, 4, 0, 0], [6, 8, 0, 0, 0, 0, 0, 0], [0] * 8], jnp.int32)
    pos = jnp.asarray([44, 9, 0], jnp.int32)
    x = normal(6, 3, cfg.hidden_size)
    want, _ = L._mla_decode(cfg, w, x, None, None, lat, tables, pos, 64)
    old, fa._FORCE_INTERPRET = fa._FORCE_INTERPRET, True
    try:
        got, _ = L._mla_decode(cfg, w, x, None, None, lat, tables, pos, 64)
    finally:
        fa._FORCE_INTERPRET = old
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Two chips of eight experts each (EP2): what each computes of the routed
    sum, with the shared expert counted once, is the layer with all 16; the
    router renormalises and scales by 2.446."""
    x = normal(2, 40, 64)
    live = jnp.ones(40, bool)
    uncut = KimiLinearConfig.tiny()
    want = ref_ling3.moe(as_dict(uncut), f32_linear, layer_leaves(uncut, 1), x)
    shared, routed = None, 0.0
    for share in range(2):
        cfg = KimiLinearConfig.tiny(experts_held=8, expert_offset=8 * share)
        lw = layer_leaves(cfg, 1)
        np.testing.assert_array_equal(  # a share draws the uncut model's experts at its indices
            lw["mlp.experts.up_proj"], layer_leaves(uncut, 1)["mlp.experts.up_proj"][8 * share:8 * share + 8])
        w = moe_weights(lw)
        out, _ = dsv._moe(cfg, w, x, live)
        shared = dsv._swiglu(x, w["shared_experts.gate_proj"], w["shared_experts.up_proj"],
                             w["shared_experts.down_proj"])
        routed = routed + (out - shared)
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=1e-5)
    weights = np.asarray(ref_ling3.route(as_dict(uncut), f32_linear, layer_leaves(uncut, 1), x))
    assert ((weights > 0).sum(1) == 4).all()
    np.testing.assert_allclose(weights.sum(1), 2.446, rtol=1e-5)


# -- the served path ----------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """One engine, three prompts (one in three chunks, each of which resumes
    the slot's state), and the reference's logits over prompt + served tokens."""
    profiler.reset_moe()
    cfg = config()
    eng = engine(seeded_model(cfg))
    ps = prompts((20, 75, 40))
    toks = serve(eng, ps, new=12)
    seqs = [np.concatenate([p, np.asarray(t, np.int32)]) for p, t in zip(ps, toks)]
    logits = ref.served_logit_gaps(as_dict(cfg), SEED, seqs, [len(p) for p in ps], pad_to=128)
    return eng, ps, toks, logits


def test_prefill_then_paged_decode_agrees_with_the_references_full_forward(served):
    """Tolerance 1e-3 on logits of order 1: float32 on both sides, so a served
    token's logit lies below the reference's best by summation order alone
    (read: 0 to 1e-6); the layer tests above show what a gate a head or a
    decay left out does to a layer's output, 100 times their tolerance."""
    eng, _, toks, logits = served
    for (best, got, first, _), t in zip(logits, toks):
        assert float(np.max(best - got)) < 1e-3
        assert (first == np.asarray(t)).mean() == 1.0
    # 75 = 32 + 32 + 11: the fresh program once a bucket, the chunk program twice
    assert eng.compile_counts() == {"prefill": 1, "decode": 1, "aot_hits": 0, "chunk_prefill": 2, "copy": 0}


def test_counters_of_the_step_come_with_its_tokens(served):
    """Eleven decode steps of three slots over two MLA layers: the latent rows
    in reach (`pos + 1` a slot a layer) counted inside the step."""
    eng, ps, _, _ = served
    moe, linear, walk = profiler.moe_summary(), profiler.linear_attn_summary(), profiler.latent_walk_summary()
    assert moe["steps"] == 11 and moe["tokens"] == 11 * 3 * 6  # 3 slots, 6 expert layers
    per_slot = 5 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)  # five KDA layers: a state and a tail, float32 here
    assert eng.model.state_bytes_per_slot() == per_slot
    assert linear["steps"] == 11 and linear["live_slots"] == 33
    assert linear["state_bytes_read"] == 33 * per_slot
    # the decode step after a prompt of n writes position n + i - 1 for i = 1 .. 11
    rows = 2 * sum(n + i for n in (20, 75, 40) for i in range(1, 12))
    assert walk == {"steps": 11, "live_slots": 33, "rows_in_reach": rows, "rows_per_step": rows / 11}
    assert profiler.arena_summary() == {
        "latent": 2 * 49 * 8 * 128 * 4, "kda_state": 5 * 3 * 4 * 16 * 16 * 4, "conv_tail": 5 * 3 * 3 * 192 * 4}
    for a, kind in zip(eng._arenas, KINDS):
        assert (a.row_names, a.state_names) == ((("latent",), ()) if kind == "mla" else
                                                ((), ("kda_state", "conv_tail")))


def test_an_idle_slots_state_is_untouched_and_a_reseated_slot_starts_from_zero():
    cfg = config()
    eng = engine(seeded_model(cfg))
    a, c, d = prompts((30, 12, 25), seed=7)
    ra, rc = eng.submit(a, max_new_tokens=14), eng.submit(c, max_new_tokens=3)
    while not rc.finished.is_set():
        eng.step()
    assert not ra.finished.is_set()
    kda = [x for x in eng._arenas if x.state_names]
    left = [[np.asarray(t._data[s]) for t in x.state_buffers()] for x in kda for s in (1, 2)]
    eng.run_until_idle()
    after = [[np.asarray(t._data[s]) for t in x.state_buffers()] for x in kda for s in (1, 2)]
    for was, now in zip(left, after):  # slot 1 as its request left it, slot 2 never seated: zeros
        for x, y in zip(was, now):
            np.testing.assert_array_equal(x, y)
    assert all(np.abs(x[0]).max() > 0 for x in left[0::2]) and all(np.abs(x[0]).max() == 0 for x in left[1::2])
    got = serve(eng, [d], new=8)  # a slot that still holds its predecessor's state
    assert got == serve(engine(seeded_model(cfg)), [d], new=8)


# -- the contract, and what the model refuses -----------------------------------------

@pytest.mark.parametrize("kwargs,feature", [
    ({"tp": 2}, "tp"), ({"cp": 2}, "cp"), ({"kv_quant": "int8"}, "kv_quant"),
    ({"lora": object()}, "lora"), ({"spec_k": 2}, "spec_k"), ({"role": "decode"}, "role"),
    ({"prefix_cache": True}, "prefix_cache")])
def test_what_the_model_cannot_do_is_refused_at_construction(kwargs, feature):
    model = KimiLinearForCausalLM(config(num_hidden_layers=3, linear_attn_config={
        "full_attn_layers": [3], "kda_layers": [1, 2], "head_dim": 16, "num_heads": 4,
        "short_conv_kernel_size": 4}))
    with pytest.raises(E.UnsupportedByModel) as err:
        engine(model, **kwargs)
    assert err.value.feature == feature and KimiLinearForCausalLM.engine_unsupported == L.Ling3ForCausalLM.engine_unsupported


def test_model_is_created_in_its_dtype_without_rope_tables_and_takes_no_gradient():
    model = KimiLinearForCausalLM(config(dtype="bfloat16"))
    leaves = dict(model.named_parameters())
    assert leaves["model.layers.1.mlp.experts.up_proj"]._data.dtype == jnp.bfloat16
    assert leaves["model.layers.1.self_attn.f_b_proj.weight"]._data.dtype == jnp.bfloat16
    for name in ("mlp.gate.e_score_correction_bias", "self_attn.A_log", "self_attn.dt_bias",
                 "self_attn.o_norm.weight"):
        assert leaves[f"model.layers.1.{name}"]._data.dtype == jnp.float32
    assert all(p.stop_gradient for p in leaves.values())
    assert model.model.layers[2].self_attn.rope_cos is None
    assert [bool(r) for r, _ in model.cache_layers()] == [k == "mla" for k in KINDS]
    assert model.cache_state() == [("kda_state", (4, 16, 16), "float32"), ("conv_tail", (3, 192), "bfloat16")]
    with pytest.raises(NotImplementedError):
        model(paddle.to_tensor(np.zeros((1, 4), np.int32)))
