"""The KDA decode recurrence as one Pallas kernel (ISSUE 38), interpreted on
the CPU: against `_kda_recurrence`'s XLA form (the form off the TPU) over slot
and head counts and both state widths, decays drawn as `weights_ling3.py`
draws them, idle slots whose state must come back bit for bit; the dispatch
on a refused shape; the counters a traced decode step leaves; the
benchmark's reader of the kernel's roofline share."""

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
from benchmarks import weights_kimi_linear as WK  # noqa: E402
from benchmarks import weights_ling3 as W  # noqa: E402
from paddle_tpu import profiler  # noqa: E402
from paddle_tpu.inference.engine import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.models import KimiLinearConfig, Ling3Config, Ling3ForCausalLM  # noqa: E402
from paddle_tpu.models import ling3 as L  # noqa: E402
from paddle_tpu.ops import flash_attention as fa  # noqa: E402
from paddle_tpu.ops import kda_decode as kd  # noqa: E402

# the benchmark configuration's draws: A_log uniform(0, ln 4) a head, f_proj.bias normal(0, 2) a channel
INIT = {"matrix_std": 0.05, "router_bias_std": 0.01, "conv_std": 0.5, "kda_A_log_max": 1.3862943611198906,
        "kda_f_bias_std": 2.0}


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


def step_inputs(S, H, d, seed=0):
    """A decode step's q, k, v, g, beta from a KDA layer's own weights (as the
    benchmark seeds them) over random normed inputs, a state of order one,
    and a live mask with idle slots."""
    cfg = Ling3Config.tiny(num_attention_heads=H, num_key_value_heads=H, head_dim=d, experts_held=4,
                           expert_offset=4)
    spec = dict(vars(cfg), init=INIT)
    pre = "model.layers.1.self_attn."
    w = {n[len(pre):]: a for n, a in W.make(seed, spec, W.layer_leaves(spec, 1), jnp.float32).items()
         if n.startswith(pre)}
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(S, cfg.hidden_size)), jnp.float32)
    conv_in = jnp.asarray(rng.normal(size=(S, cfg.short_conv_kernel_size, 3 * H * d)), jnp.float32)
    q, k, v, g, beta = L._kda_inputs(cfg, w, x, conv_in)
    state = jnp.asarray(rng.normal(size=(S, H, d, d)) * 0.5, jnp.float32)
    live = jnp.asarray(np.arange(S) % 3 != 1)
    return q, k, v, g, beta, state, live


def assert_the_xla_form(got, want, state, live):
    """`o` and the state within float32 rounding of the XLA form; an idle
    slot's state exactly as it was."""
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6 * float(jnp.max(jnp.abs(b))))
    idle = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(got[1])[idle], np.asarray(state)[idle])
    assert not np.array_equal(np.asarray(got[1])[~idle], np.asarray(state)[~idle])


@pytest.mark.parametrize("d", [16, 128])
@pytest.mark.parametrize("H", [2, 4])
@pytest.mark.parametrize("S", [3, 8])
def test_kernel_is_the_xla_recurrence(interpret, S, H, d):
    q, k, v, g, beta, state, live = step_inputs(S, H, d)
    a = np.exp(np.asarray(g))
    assert a.min() < np.exp(-4.0) and a.max() > np.exp(-0.5)  # decays over the whole of (e^-5, 1)
    fa._FORCE_INTERPRET = False
    want = L._kda_recurrence(q, k, v, g, beta, state, live)
    fa._FORCE_INTERPRET = True
    before = profiler.flash_pallas_summary().get("kda_state_step", 0)
    got = jax.jit(lambda *a: L._kda_recurrence(*a))(q, k, v, g, beta, state, live)  # a trace of its own
    assert profiler.flash_pallas_summary().get("kda_state_step", 0) == before + 1
    assert_the_xla_form(got, want, state, live)


def test_kernel_is_the_xla_recurrence_at_32_slots_under_kimi_linears_unbounded_decays(interpret):
    """Kimi Linear's decay (`-exp(A) * softplus(x W_fa W_fb + dt_bias)`, the
    benchmark's draws: A_log uniform(0, ln 16), dt_bias uniform(-6, 3)) goes far
    below Ling-3's e^-5: the kernel takes `g` however it was formed."""
    S, H, d = 32, 2, 128
    lin = {"full_attn_layers": [2], "kda_layers": [1], "head_dim": d, "num_heads": H, "short_conv_kernel_size": 4}
    cfg = KimiLinearConfig.tiny(num_hidden_layers=2, num_attention_heads=H, linear_attn_config=lin,
                                experts_held=4, expert_offset=4)
    spec = dict(vars(cfg), init={"matrix_std": 0.02, "router_bias_std": 0.01, "conv_std": 0.5,
                                 "kda_A_log_max": float(np.log(16.0)), "kda_dt_bias_min": -6.0,
                                 "kda_dt_bias_max": 3.0})
    pre = "model.layers.0.self_attn."
    w = {n[len(pre):]: a for n, a in WK.make(5, spec, WK.layer_leaves(spec, 0), jnp.float32).items()
         if n.startswith(pre)}
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(S, cfg.hidden_size)), jnp.float32)
    conv_in = jnp.asarray(rng.normal(size=(S, 4, 3 * H * d)), jnp.float32)
    q, k, v, g, beta = L._kda_inputs(cfg, w, x, conv_in)
    state = jnp.asarray(rng.normal(size=(S, H, d, d)) * 0.5, jnp.float32)
    live = jnp.asarray(np.arange(S) % 5 != 2)
    assert float(jnp.min(g)) < -20.0 and float(jnp.max(g)) > np.log(0.95)  # from a full memory to none
    fa._FORCE_INTERPRET = False
    want = L._kda_recurrence(q, k, v, g, beta, state, live)
    fa._FORCE_INTERPRET = True
    got = jax.jit(lambda *a: L._kda_recurrence(*a))(q, k, v, g, beta, state, live)
    assert_the_xla_form(got, want, state, live)
    gone = np.asarray(g)[np.asarray(live)] < -20.0  # channels whose past is e^-20 away: the update alone
    assert gone.any()


@pytest.mark.parametrize("shape,dtype,why", [
    ((64, 32, 128, 128), jnp.float32, None), ((8, 4, 16, 16), jnp.float32, "whole lanes"),
    ((8, 4, 128, 64), jnp.float32, "whole lanes"), ((64, 32, 128, 128), jnp.bfloat16, "dtype"),
    ((4, 512, 128, 128), jnp.float32, "VMEM")])
def test_refusal_names_what_the_chip_cannot_take(shape, dtype, why):
    reason = kd.refusal(jax.ShapeDtypeStruct(shape, dtype))
    assert (reason is None) if why is None else (why in reason)


def test_a_refused_shape_on_the_tpu_counts_as_a_fallback_and_keeps_the_xla_form(monkeypatch):
    """`reason64` holds `flash_fallbacks` to 0: a run in which the kernel did
    not engage reads `correct: false`."""
    q, k, v, g, beta, state, live = step_inputs(3, 2, 16)
    want = L._kda_recurrence(q, k, v, g, beta, state, live)
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    profiler.reset_flash_fallbacks()
    before = profiler.flash_pallas_summary().get("kda_state_step", 0)
    got = L._kda_recurrence(q, k, v, g, beta, state, live)
    assert profiler.flash_pallas_summary().get("kda_state_step", 0) == before
    (reason, n), = profiler.flash_fallback_summary().items()
    assert reason.startswith("kda_state_step: ") and "whole lanes" in reason and n == 1
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    profiler.reset_flash_fallbacks()


def test_off_the_tpu_the_step_is_the_xla_form():
    q, k, v, g, beta, state, live = step_inputs(3, 2, 16)
    before = profiler.flash_pallas_summary().get("kda_state_step", 0)
    jaxpr = jax.make_jaxpr(lambda *a: L._kda_recurrence(*a))(q, k, v, g, beta, state, live)
    assert "pallas_call" not in str(jaxpr)
    assert profiler.flash_pallas_summary().get("kda_state_step", 0) == before


def test_a_traced_decode_step_records_the_kernel_and_its_geometry(interpret):
    """The engine's decode step takes the kernel in each of the six KDA
    layers; the prefill keeps the chunked scan; nothing falls back."""
    profiler.reset()
    cfg = Ling3Config.tiny(experts_held=4, expert_offset=4)
    eng = ContinuousBatchingEngine(Ling3ForCausalLM(cfg), slots=3, max_len=64, prefill_buckets=[16], page_size=8)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(1, 250, size=n).astype(np.int32), max_new_tokens=3) for n in (5, 9)]
    eng.run_until_idle()
    assert all(r.finish_reason == "length" and r.error is None for r in reqs)
    calls = profiler.flash_pallas_summary()["kda_state_step"]
    assert calls % 6 == 0 and calls >= 6  # a count of traces: six KDA layers a decode step
    assert profiler.flash_fallback_summary() == {}
    H, d = cfg.num_attention_heads, cfg.head_dim
    assert profiler.kda_decode_summary() == [{"slots": 3, "heads": H, "dk": d, "dv": d, "heads_per_step": H,
                                              "grid_steps": 3, "state_bytes_per_step": H * d * d * 4}]
    assert profiler.linear_attn_summary()["steps"] >= 2


# -- the benchmark's reader of the kernel's roofline share ----------------------------------

def _reader_ctx(calls, seconds, walks, linear):
    import json

    cfg = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "ling-3.0-flash-ep4-serve7.json")))
    peaks = json.load(open(os.path.join(ROOT, "benchmarks", "peaks.json")))["TPU v5 lite"]
    names = {"%kda_state_step.1 = (f32[64,32,128]{2,1,0}, f32[64,32,128,128]{3,2,1,0}) custom-call(...)":
             (calls, seconds),
             "%paged_walk_decode.1 = bf16[64,1,32,640]{3,2,1,0} custom-call(...)": (walks, 0.09),
             # what reads the kernel's output holds its name among the operands
             "%fusion.12 = bf16[64,4096]{1,0} fusion(f32[64,32,128]{2,1,0} %get-tuple-element.3 "
             "(kda_state_step.1), ...)": (calls, 0.001),
             "%fusion.43 = bf16[128,64,768]{2,1,0} fusion(...)": (walks, 0.08)}
    trace = {"ops": {n: s for n, (c, s) in names.items() if c}, "op_counts": {n: c for n, (c, _) in names.items() if c}}
    return SimpleNamespace(cfg=cfg, peaks=peaks, counters={"linear_attn": linear} if linear else {}, trace=trace,
                           log=lambda line: None)


WINDOW = {"steps": 100, "live_slots": 100 * 64, "state_bytes_read": 1, "state_bytes_written": 1,
          "prefill_rows": 0, "chunks_resumed": 0}


@pytest.mark.parametrize("case,calls,walks,linear,reads", [
    ("a_decode_window", 720, 120, WINDOW, True),
    ("edges_cut_two_steps", 708, 120, WINDOW, True),
    ("another_kernel_in_the_match", 740, 120, WINDOW, False),
    ("a_program_without_the_kernel", 0, 120, WINDOW, False),
    ("a_program_without_the_counters", 720, 120, None, False),
    ("no_step_marks", 720, 0, WINDOW, False),
])
def test_roofline_reader_reads_the_states_bytes_over_the_kernels_mean_time(case, calls, walks, linear, reads):
    """64 live slots' state of 32 x 128 x 128 float32, read and written once:
    268 MB, 0.328 ms at 819 GB/s; calls of 0.4 ms read 81.9%.  A parent
    without the kernel, a kind without the counters, a count of calls off by
    more than two steps, or the fusion that reads the kernel's output, reads
    nothing of it and raises nothing."""
    from benchmarks.readers import kda_state_roofline as reader

    args = {"match": ["kda_state_step"], "step_marks": ["paged_walk_decode"]}
    got = reader.read(_reader_ctx(calls, calls * 0.4e-3, walks, linear), args)
    if reads:
        assert got == pytest.approx(100 * 2 * 64 * 32 * 128 * 128 * 4 / 819e9 / 0.4e-3, rel=1e-6)
    else:
        assert got is None
    assert reader.read(SimpleNamespace(counters={"linear_attn": linear}, trace=None), args) is None  # untraced
