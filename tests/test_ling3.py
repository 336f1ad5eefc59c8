"""Ling-3.0-flash on the serving path (ISSUE 33), tiny on the CPU in float32:
each layer kind and the whole served path against the plain reference
(`benchmarks/reference_ling3.py`), the chunked delta-rule scan against the
recurrence, the state a slot owns, the engine's grown contract with a served
model (rows per token AND state per slot, per layer), and what the model
refuses."""

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
from benchmarks import reference_ling3 as ref  # noqa: E402
from benchmarks import weights_ling3 as W  # noqa: E402
from benchmarks.reference import f32_linear  # noqa: E402
from paddle_tpu import profiler  # noqa: E402
from paddle_tpu.inference import engine as E  # noqa: E402
from paddle_tpu.inference.engine import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models import DeepseekV32Config, DeepseekV32ForCausalLM  # noqa: E402
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.models import Ling3Config, Ling3ForCausalLM  # noqa: E402
from paddle_tpu.models import deepseek_v32 as dsv  # noqa: E402
from paddle_tpu.models import ling3 as L  # noqa: E402
from paddle_tpu.ops import flash_attention as fa  # noqa: E402

SEED = 3_300_000_017  # past 2**31, as the driver's seeds are
INIT = {"matrix_std": 0.05, "router_bias_std": 0.01, "conv_std": 0.5, "kda_A_log_max": 1.386,
        "kda_f_bias_std": 2.0}
KINDS = ["kda", "kda", "kda", "kda", "mla", "kda", "kda"]


@pytest.fixture(scope="module", autouse=True)
def _rng_guard():
    """Model builds consume the framework's default generator; later modules
    build weights without re-seeding it."""
    state = np.asarray(paddle.get_rng_state())
    yield
    paddle.set_rng_state(state)


def config(**over):
    return Ling3Config.tiny(experts_held=4, expert_offset=4, **over)


def as_dict(cfg):
    return dict(vars(cfg), init=INIT)


def seeded_model(cfg, seed=SEED):
    model = Ling3ForCausalLM(cfg)
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

def test_a_kept_layers_kind_follows_its_published_index():
    cfg = config()
    assert [cfg.layer_kind(i) for i in range(7)] == KINDS
    assert [cfg.is_moe(i) for i in range(7)] == [False] + [True] * 6
    assert [cfg.published_index(i) for i in (0, 6)] == [1, 7]
    whole = Ling3Config()
    assert [i for i in range(42) if whole.layer_kind(i) == "mla"] == [5, 11, 17, 23, 29, 35, 41]
    assert sum(whole.is_moe(i) for i in range(42)) == 40 and whole.n_routed_experts == 512
    for bad in (dict(q_lora_rank=64), dict(kda_safe_gate=False), dict(experts_held=8, expert_offset=12),
                dict(dense_layers_kept=3), dict(expert_swiglu_limit_list=[0, 0, 4, 0, 0, 0, 0, 0])):
        with pytest.raises(ValueError):
            Ling3Config.tiny(**bad)


# -- each layer kind against the reference ------------------------------------------

def test_dense_mlp_and_expert_layer_match_the_reference():
    cfg = config()
    x = normal(1, 24, cfg.hidden_size)
    lw = layer_leaves(cfg, 0)
    got = dsv._swiglu(x, lw["mlp.gate_proj.weight"], lw["mlp.up_proj.weight"], lw["mlp.down_proj.weight"])
    np.testing.assert_allclose(got, ref.feed_forward(as_dict(cfg), f32_linear, lw, x, 24), rtol=1e-4, atol=1e-5)
    lw = layer_leaves(cfg, 1)
    live = jnp.arange(24) < 20  # four rows of padding route nowhere
    got, stats = dsv._moe(cfg, moe_weights(lw), x, live)
    np.testing.assert_allclose(got[:20], ref.moe(as_dict(cfg), f32_linear, lw, x)[:20], rtol=1e-4, atol=1e-5)
    weights = np.asarray(ref.route(as_dict(cfg), f32_linear, lw, x))
    assert [int(v) for v in stats[:2]] == [20, int((weights[:20, 4:8] > 0).sum())]
    assert ((weights > 0).sum(1) == cfg.num_experts_per_tok).all()
    np.testing.assert_allclose(weights.sum(1), cfg.routed_scaling_factor, rtol=1e-5)


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Four chips of four experts each: what each computes of the routed sum,
    with the shared expert counted once, is the layer with all 16 experts."""
    x = normal(2, 40, 64)
    live = jnp.ones(40, bool)
    uncut = Ling3Config.tiny()
    want = ref.moe(as_dict(uncut), f32_linear, layer_leaves(uncut, 1), x)
    shared, routed = None, 0.0
    for share in range(4):
        cfg = Ling3Config.tiny(experts_held=4, expert_offset=4 * share)
        lw = layer_leaves(cfg, 1)
        np.testing.assert_array_equal(  # a share draws the uncut model's experts at its indices
            lw["mlp.experts.up_proj"], layer_leaves(uncut, 1)["mlp.experts.up_proj"][4 * share:4 * share + 4])
        w = moe_weights(lw)
        out, _ = dsv._moe(cfg, w, x, live)
        shared = dsv._swiglu(x, w["shared_experts.gate_proj"], w["shared_experts.up_proj"],
                             w["shared_experts.down_proj"])
        routed = routed + (out - shared)
    np.testing.assert_allclose(routed + shared, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("small_step", ["the_loop_off_the_tpu", "the_kernel"])
@pytest.mark.parametrize("rows", [4, 24])
def test_the_two_forms_of_the_expert_layer_give_one_sum(rows, small_step):
    """A small step whose picks cover the router's width takes the
    `grouped_experts` kernel (interpreted here; off the TPU the loop), any
    other step loops over the blocks in use: the same 24 tokens through one
    form and, three at a time, through the other."""
    cfg = config()
    x = normal(8, 24, cfg.hidden_size)
    w = moe_weights(layer_leaves(cfg, 2))
    live = jnp.arange(24) != 5  # an idle slot routes nowhere in either form
    experts, wts = dsv._route(cfg, x, w["gate.weight"], w["gate.e_score_correction_bias"])
    mats = (w["experts.gate_proj"], w["experts.up_proj"], w["experts.down_proj"])
    assert 3 * cfg.num_experts_per_tok < cfg.num_experts <= rows * cfg.num_experts_per_tok
    calls = lambda: profiler.flash_pallas_summary().get("grouped_experts", 0)
    old, fa._FORCE_INTERPRET = fa._FORCE_INTERPRET, small_step == "the_kernel"
    try:
        before = calls()
        whole, stats = dsv._routed_experts(cfg, x, experts, wts, live, *mats)
        assert calls() - before == (small_step == "the_kernel")
        parts = [dsv._routed_experts(cfg, x[i:i + 3], experts[i:i + 3], wts[i:i + 3], live[i:i + 3], *mats)[0]
                 for i in range(0, 24, 3)]
        assert calls() - before == (small_step == "the_kernel")  # three tokens' picks do not cover the router
        np.testing.assert_allclose(whole, jnp.concatenate(parts), rtol=1e-5, atol=1e-6)
        held = np.asarray((experts >= 4) & (experts < 8) & live[:, None])
        assert [int(v) for v in stats[:2]] == [23, int(held.sum())] and not np.asarray(whole[5]).any()
        if rows == 4:  # the form is chosen from the step's static shape alone
            few = dsv._routed_experts(cfg, x[:4], experts[:4], wts[:4], live[:4], *mats)[0]
            np.testing.assert_allclose(few, whole[:4], rtol=1e-5, atol=1e-6)
    finally:
        fa._FORCE_INTERPRET = old


def kda_through_the_cache(cfg, w, x, n, cut, fault=None):
    """x[:n] through `_kda_prefill` in two chunks (the second resumes the
    slot's state and tail) and x[n] through `_kda_decode`, in slot 1 of 3."""
    H, d, K = cfg.num_attention_heads, cfg.head_dim, cfg.short_conv_kernel_size
    state = jnp.full((3, H, d, d), 7.0, jnp.float32)  # what a predecessor left: a fresh prefill ignores it
    tail = jnp.full((3, K - 1, 3 * H * d), 7.0, jnp.float32)
    outs = []
    for s0, rows in ((0, cut), (cut, n - cut)):
        chunk = jnp.pad(x[s0:s0 + rows], ((0, 48 - rows), (0, 0)))  # a bucket with padding rows
        out, state, tail = L._kda_prefill(cfg, w, chunk, state, tail, jnp.int32(1), jnp.int32(rows), s0 == 0)
        if fault == "bfloat16 state":
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        outs.append(out[:rows])
    np.testing.assert_array_equal(state[0], 7.0)  # other slots' state and tails are theirs
    np.testing.assert_array_equal(tail[2], 7.0)
    live = jnp.asarray([False, True, False])
    out, state2, tail2 = L._kda_decode(cfg, w, jnp.broadcast_to(x[n], (3, x.shape[1])), state, tail, live)
    np.testing.assert_array_equal(state2[0], state[0])  # an idle slot keeps its state and tail
    np.testing.assert_array_equal(tail2[2], tail[2])
    assert not np.array_equal(state2[1], state[1])
    np.testing.assert_array_equal(tail2[1, :-1], tail[1, 1:])
    return jnp.concatenate(outs + [out[1:2]])


@pytest.mark.parametrize("cut", [3, 19])
def test_kda_layer_matches_the_reference_through_chunks_and_a_decoded_token(cut):
    """Tolerance: float32 on both sides, the chunked form against the
    recurrence differs by summation order alone (1e-6 of an output of order
    0.1); a state held in bfloat16 or a decay left out is 100 times outside."""
    cfg = config()
    n = 45
    x = normal(3, n + 1, cfg.hidden_size)
    lw = layer_leaves(cfg, 1)
    want = ref.kda(as_dict(cfg), f32_linear, lw, jnp.pad(x, ((0, 2), (0, 0))), n + 1)[:n + 1]
    got = kda_through_the_cache(cfg, attn_weights(lw), x, n, cut)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    assert float(jnp.max(jnp.abs(want))) > 0.02
    if cut == 19:
        low = kda_through_the_cache(cfg, attn_weights(lw), x, n, cut, fault="bfloat16 state")
        assert float(jnp.max(jnp.abs(low - want))) > 100 * 2e-6
        no_decay = dict(attn_weights(lw), **{"f_proj.bias": jnp.full((64,), -40.0)})  # g = 0: nothing fades
        off = kda_through_the_cache(cfg, no_decay, x, n, cut)
        assert float(jnp.max(jnp.abs(off - want))) > 100 * 2e-6


def recurrence(q, k, v, g, beta, s0):
    S, out = np.asarray(s0, np.float64), []
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    for t in range(q.shape[0]):
        Sp = np.exp(g[t])[:, :, None] * S
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", Sp, k[t]))
        S = Sp + k[t][:, :, None] * u[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), S


@pytest.mark.parametrize("true_len", [1, 63, 65, 100, 127, 128])
@pytest.mark.parametrize("decays", ["both ends", "slow", "fast"])
def test_chunked_scan_equals_the_recurrence(true_len, decays):
    """128 rows in two chunks of 64, the rows past `true_len` with g = 0 and
    beta = 0, decays at both ends of (e^-5, 1): the pairwise form holds where
    a factored `exp(-G)` would have left float32 (64 rows of -5 are e^-320)."""
    rng = np.random.default_rng(true_len)
    n, H, d = 128, 2, 16
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = unit(rng.normal(size=(n, H, d))), unit(rng.normal(size=(n, H, d)))
    v, beta = rng.normal(size=(n, H, d)), rng.uniform(0.05, 1.0, size=(n, H))
    g = {"both ends": np.where(rng.random((n, H, d)) < 0.5, -4.999, -1e-4),
         "slow": rng.uniform(-0.01, 0.0, size=(n, H, d)),
         "fast": rng.uniform(-5.0, -4.0, size=(n, H, d))}[decays]
    valid = np.arange(n) < true_len
    g, beta = g * valid[:, None, None], beta * valid[:, None]
    s0 = rng.normal(size=(H, d, d))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    o, S = jax.jit(L._kda_scan)(f32(q), f32(k), f32(v), f32(g), f32(beta), f32(s0))
    want_o, want_S = recurrence(q[:true_len], k[:true_len], v[:true_len], g[:true_len], beta[:true_len], s0)
    np.testing.assert_allclose(o[:true_len], want_o, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(S, want_S, rtol=1e-4, atol=2e-5)
    assert np.isfinite(np.asarray(o)).all()


def test_chunked_scan_holds_on_a_constant_sequence():
    """One key again and again (what a model of random weights decodes into):
    `(I + A)` is then far from the identity, and a power series for its
    inverse would cancel catastrophically where the triangular solve does not."""
    n, H, d = 128, 1, 16
    k = np.zeros((n, H, d)); k[..., 0] = 1.0
    v = np.random.default_rng(0).normal(size=(n, H, d))
    g, beta = np.full((n, H, d), -1e-3), np.full((n, H), 0.9)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    o, S = L._kda_scan(f32(k), f32(k), f32(v), f32(g), f32(beta), jnp.zeros((H, d, d), jnp.float32))
    want_o, want_S = recurrence(k, k, v, g, beta, np.zeros((H, d, d)))
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(S, want_S, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("start", [0, 19])
def test_mla_layer_matches_the_reference_through_the_latent_pages(start):
    """One chunk of a sequence, or two, through the paged latent cache against
    the reference's cache-free block, then one decoded token that reaches its
    45 keys through the page table."""
    cfg = config()
    n, ps = 45, 8
    x = normal(3, n, cfg.hidden_size)
    lw = layer_leaves(cfg, 4)
    w = attn_weights(lw)
    cos, sin = ref.rope_tables(as_dict(cfg), 48)
    want = ref.mla(as_dict(cfg), f32_linear, lw, jnp.pad(x, ((0, 3), (0, 0))), cos, sin, n)[:n]
    lat = jnp.zeros((8, 1, ps, dsv.latent_width(cfg)), jnp.float32)
    table = jnp.asarray([3, 1, 5, 2, 7, 4], jnp.int32)
    outs = []
    for s0, rows in ((0, start), (start, n - start)):
        if not rows:
            continue
        pad = ((0, 48 - rows), (0, 0))  # a bucket with padding rows past true_len
        out, lat = L._mla_prefill(cfg, w, jnp.pad(x[s0:s0 + rows], pad), jnp.pad(cos[s0:s0 + rows], pad),
                                  jnp.pad(sin[s0:s0 + rows], pad), lat, table,
                                  jnp.asarray([s0], jnp.int32), jnp.int32(rows))
        outs.append(out[:rows])
    np.testing.assert_allclose(jnp.concatenate(outs), want, rtol=2e-4, atol=2e-5)
    xt = normal(4, 1, cfg.hidden_size)
    full = jnp.pad(jnp.concatenate([x, xt]), ((0, 2), (0, 0)))
    want = ref.mla(as_dict(cfg), f32_linear, lw, full, cos, sin, n + 1)[n]
    pos = jnp.asarray([n], jnp.int32)
    got, _ = L._mla_decode(cfg, w, xt, cos[pos], sin[pos], lat, table[None], pos, 48)
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)


def test_mla_decode_walks_the_pages_in_the_kernel_as_it_gathers_them():
    """The Pallas page walk (interpreted here) over the one latent arena, its
    rows the keys and the values (`arena_v=None`), gives what the gathered
    context gives."""
    cfg = config()
    w = attn_weights(layer_leaves(cfg, 4))
    cos, sin = ref.rope_tables(as_dict(cfg), 64)
    lat = normal(5, 9, 1, 8, dsv.latent_width(cfg)) * 0.3
    tables = jnp.asarray([[3, 1, 5, 2, 7, 4, 0, 0], [6, 8, 0, 0, 0, 0, 0, 0], [0] * 8], jnp.int32)
    pos = jnp.asarray([44, 9, 0], jnp.int32)
    x = normal(6, 3, cfg.hidden_size)
    want, _ = L._mla_decode(cfg, w, x, cos[pos], sin[pos], lat, tables, pos, 64)
    old, fa._FORCE_INTERPRET = fa._FORCE_INTERPRET, True
    try:
        got, _ = L._mla_decode(cfg, w, x, cos[pos], sin[pos], lat, tables, pos, 64)
    finally:
        fa._FORCE_INTERPRET = old
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


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
    (read: 0 to 1e-6); the layer tests above show what a bfloat16 state or a
    dropped decay does to a layer's output, 100 times their tolerance."""
    eng, _, toks, logits = served
    for (best, got, first, _), t in zip(logits, toks):
        assert float(np.max(best - got)) < 1e-3
        assert (first == np.asarray(t)).mean() == 1.0
    # 75 = 32 + 32 + 11: the fresh program once a bucket, the chunk program twice
    assert eng.compile_counts() == {"prefill": 1, "decode": 1, "aot_hits": 0, "chunk_prefill": 2, "copy": 0}


def test_counters_of_the_step_come_with_its_tokens(served):
    eng = served[0]
    moe, linear = profiler.moe_summary(), profiler.linear_attn_summary()
    assert moe["steps"] == 11 and moe["tokens"] == 11 * 3 * 6  # 3 slots, 6 expert layers
    assert 0 < moe["picks_held"] < moe["tokens"] * 4
    per_slot = 6 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)  # six KDA layers: a state and a tail, float32 here
    assert eng.model.state_bytes_per_slot() == per_slot
    assert linear["steps"] == 11 and linear["live_slots"] == 33
    assert linear["state_bytes_read"] == linear["state_bytes_written"] == 33 * per_slot
    assert linear["prefill_rows"] == 20 + 75 + 40 and linear["chunks_resumed"] == 3  # 75 = 32 + 32 + 11 and 40 = 32 + 8
    # arenas only where a layer has rows, state only where it has state: both kinds, by name
    assert profiler.arena_summary() == {
        "latent": 1 * 49 * 8 * 128 * 4, "kda_state": 6 * 3 * 4 * 16 * 16 * 4, "conv_tail": 6 * 3 * 3 * 192 * 4}
    for a, kind in zip(eng._arenas, KINDS):
        assert (a.row_names, a.state_names) == ((("latent",), ()) if kind == "mla" else
                                                ((), ("kda_state", "conv_tail")))
        assert len(a.buffers()) == (kind == "mla") and len(a.state_buffers()) == 2 * (kind == "kda")


def test_a_long_prompt_in_chunks_equals_the_same_prompt_whole(served):
    """Tokens, and what the slot holds when the request is done: the state and
    the tail of every KDA layer."""
    _, ps, toks, _ = served
    cfg = config()
    chunked, whole = engine(seeded_model(cfg)), engine(seeded_model(cfg), prefill_buckets=[96])
    assert serve(chunked, [ps[1]], new=12) == serve(whole, [ps[1]], new=12) == [toks[1]]
    assert whole.compile_counts()["chunk_prefill"] == 0 and chunked.compile_counts()["chunk_prefill"] == 2
    for a, b in zip(chunked._arenas, whole._arenas):
        for x, y in zip(a.state_buffers(), b.state_buffers()):
            assert float(jnp.max(jnp.abs(x._data[0]))) > 0
            np.testing.assert_allclose(x._data[0], y._data[0], rtol=1e-4, atol=1e-6)


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
    busy = [np.asarray(x.kda_state._data[0]) for x in kda]
    eng.run_until_idle()
    after = [[np.asarray(t._data[s]) for t in x.state_buffers()] for x in kda for s in (1, 2)]
    for was, now in zip(left, after):  # slot 1 as its request left it, slot 2 never seated: zeros
        for x, y in zip(was, now):
            np.testing.assert_array_equal(x, y)
    assert all(np.abs(x[0]).max() > 0 for x in left[0::2]) and all(np.abs(x[0]).max() == 0 for x in left[1::2])
    assert all(not np.array_equal(x, np.asarray(k.kda_state._data[0])) for x, k in zip(busy, kda))
    # a new request takes a slot whose buffers still hold its predecessor's state
    got = serve(eng, [d], new=8)
    assert got == serve(engine(seeded_model(cfg)), [d], new=8)


# -- the contract, and what the model refuses -----------------------------------------

@pytest.mark.parametrize("kwargs,feature", [
    ({"tp": 2}, "tp"), ({"cp": 2}, "cp"), ({"kv_quant": "int8"}, "kv_quant"),
    ({"lora": object()}, "lora"), ({"spec_k": 2}, "spec_k"), ({"role": "decode"}, "role"),
    ({"role": "prefill"}, "role"), ({"prefix_cache": True}, "prefix_cache")])
def test_what_the_model_cannot_do_is_refused_at_construction(kwargs, feature):
    model = Ling3ForCausalLM(config(num_hidden_layers=2))
    with pytest.raises(E.UnsupportedByModel) as err:
        engine(model, **kwargs)
    assert err.value.feature == feature and isinstance(err.value, ValueError)
    assert feature in Ling3ForCausalLM.engine_unsupported and len(Ling3ForCausalLM.engine_unsupported) == 7


@pytest.mark.parametrize("asked", [None, False])
def test_prefix_cache_left_to_the_flag_resolves_to_off(asked):
    from paddle_tpu.framework import core

    assert core.flag("FLAGS_serve_prefix_cache")  # the flag's default is on
    eng = engine(Ling3ForCausalLM(config(num_hidden_layers=2)), prefix_cache=asked)
    assert eng._prefix is None and eng._sessions is None and eng.healthz()["prefix_cache_size"] == 0


def test_model_is_created_in_its_dtype_and_takes_no_gradient():
    model = Ling3ForCausalLM(config(dtype="bfloat16"))
    leaves = dict(model.named_parameters())
    assert leaves["model.layers.1.mlp.experts.up_proj"]._data.dtype == jnp.bfloat16
    assert leaves["model.layers.1.self_attn.conv.weight"]._data.dtype == jnp.bfloat16
    for name in ("mlp.gate.e_score_correction_bias", "self_attn.A_log", "self_attn.f_proj.bias",
                 "self_attn.o_norm.weight"):
        assert leaves[f"model.layers.1.{name}"]._data.dtype == jnp.float32
    assert all(p.stop_gradient for p in leaves.values())
    assert [r[:3] for r in model.cache_rows()] == [("latent", 1, 128)]
    assert model.cache_state() == [("kda_state", (4, 16, 16), "float32"), ("conv_tail", (3, 192), "bfloat16")]
    assert [bool(r) for r, _ in model.cache_layers()] == [k == "mla" for k in KINDS]
    with pytest.raises(NotImplementedError):
        model(paddle.to_tensor(np.zeros((1, 4), np.int32)))


def test_deepseek_v32_through_the_grown_contract_keeps_its_programs_and_arenas():
    np.random.seed(1234)
    paddle.seed(78)
    model = DeepseekV32ForCausalLM(DeepseekV32Config.tiny(experts_held=4, expert_offset=4))
    assert not hasattr(model, "cache_layers")
    eng = engine(model, prefix_cache=True).warmup()
    assert eng.compile_counts() == {"prefill": 2, "decode": 1, "aot_hits": 0, "chunk_prefill": 2, "copy": 1}
    assert eng._has_state is False and eng._prefix is not None
    for a in eng._arenas:
        assert a.row_names == ("latent", "index_key") and a.state_names == () and len(a.buffers()) == 2
        assert tuple(a.latent.shape) == (3 * 16 + 1, 1, 8, 128)
    assert profiler.arena_summary() == {"latent": 3 * 49 * 8 * 128 * 4, "index_key": 3 * 49 * 8 * 16 * 4}


def test_llama_through_the_grown_contract_compiles_and_decodes_as_before():
    """Llama declares its K and V rows and nothing per slot: the same arenas,
    the same compile counts, the tokens of its own `generate`."""
    np.random.seed(1234)
    paddle.seed(77)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_key_value_heads=2))
    assert not hasattr(model, "cache_layers") and not hasattr(model, "engine_unsupported")
    eng = ContinuousBatchingEngine(model, slots=2, max_len=64, prefill_buckets=[16, 32],
                                   page_size=8).warmup()
    warm = eng.compile_counts()
    assert warm == {"prefill": 2, "decode": 1, "aot_hits": 0, "chunk_prefill": 2, "copy": 1}
    assert eng._has_state is False and eng._prefix is not None  # the flag's default, as before
    for a in eng._arenas:
        assert a.row_names == ("k", "v") and a.state_names == () and a.state_buffers() == []
        assert tuple(a.k.shape) == tuple(a.v.shape) == (2 * 8 + 1, 2, 8, 16)
    assert profiler.arena_summary() == {"k": 2 * 17 * 2 * 8 * 16 * 4, "v": 2 * 17 * 2 * 8 * 16 * 4}
    ps = prompts((9, 30), seed=3)
    toks = serve(eng, ps, new=8)
    for p, t in zip(ps, toks):
        want = np.asarray(model.generate(paddle.to_tensor(p[None]), max_new_tokens=8).numpy())[0]
        assert t == want[len(p):].tolist()
    assert eng.compile_counts() == warm
