"""Speculative decoding on the paged engine (ISSUE 11): n-gram drafting +
batched verify must be token-identical to the plain engine under greedy
(acceptance only reorders WHEN tokens land, never WHICH tokens), keep the
compiled budget at exactly one extra executable under acceptance-rate churn,
right-trim EOS inside an accepted window, co-batch speculative and plain
slots, rebuild drafter state across warm restarts, and surface acceptance
in the profiler / drain estimate / trace spans.

Runs under the runtime sanitizer (conftest _SANITIZED_MODULES): any fresh
trace or unexpected host sync a speculation step introduced inside the
steady-state zone fails these tests directly.

All CPU: same executable shapes as TPU minus the Pallas kernel choice.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference.engine import ContinuousBatchingEngine
from paddle_tpu.inference.paging import spec_write_pages
from paddle_tpu.inference.spec import NgramDrafter
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.obs import flight, metrics, trace


@pytest.fixture(scope="module")
def model():
    np.random.seed(1234)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 250, size=n).astype(np.int32)


def _cycle_prompt(n=20, period=6, seed=7):
    """Repetitive prompt: prompt-lookup drafting exploits exactly this."""
    pat = _prompt(period, seed=seed)
    return np.tile(pat, -(-n // period))[:n].astype(np.int32)


def _paged(model, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8, 32])
    kw.setdefault("queue_depth", 16)
    kw.setdefault("seed", 0)
    kw.setdefault("page_size", 8)
    return ContinuousBatchingEngine(model, **kw)


# ---------------------------------------------------------------------------
# drafter unit behavior: back-off, short history, self-match skip
# ---------------------------------------------------------------------------


def test_drafter_matches_longest_order_first():
    d = NgramDrafter(3)
    d.reset([1, 2, 3, 1, 2])
    # 3-gram suffix (3,1,2) only occurs at the end (self-match, skipped);
    # 2-gram (1,2) recurs at start -> continuation [3, 1, 2], extrapolated
    # cyclically (the match hypothesizes period 3) out to k
    assert d.propose(4) == [3, 1, 2, 3]
    assert d.propose(2) == [3, 1]
    assert d.propose(0) == []


def test_drafter_prompt_shorter_than_n_backs_off():
    d = NgramDrafter(3)
    d.reset([7])  # shorter than max_ngram: only order 1 exists, no repeat yet
    assert d.propose(3) == []
    d.extend(7)  # now (7,) recurs -> 1-gram draft despite the tiny history;
    # the period-1 match extrapolates to a constant-run draft of length k
    assert d.propose(3) == [7, 7, 7]


def test_drafter_miss_and_reset():
    d = NgramDrafter(3)
    d.reset([1, 2, 3, 4])
    assert d.propose(3) == []  # nothing recurs
    d.reset([5, 6, 5, 6])
    assert len(d) == 4
    assert d.propose(2) == [5, 6]


def test_spec_write_pages_split():
    in_table, overrun = spec_write_pages(13, 4, 8, 2)  # rows 13..16
    assert in_table == [1] and overrun == [2]
    assert spec_write_pages(0, 4, 8, 1) == ([0], [])
    assert spec_write_pages(5, 0, 8, 1) == ([], [])


# ---------------------------------------------------------------------------
# greedy equivalence: spec output is bit-identical to the plain engine
# ---------------------------------------------------------------------------


def test_spec_greedy_token_identical_to_plain(model):
    p = _cycle_prompt()
    plain = _paged(model)
    r0 = plain.submit(p, max_new_tokens=24)
    plain.run_until_idle()
    out_plain = r0.wait(1).tolist()

    profiler.reset_speculation()
    spec = _paged(model, spec_k=3)
    spec.warmup()
    warm = spec.compile_counts()
    assert warm["verify"] == 1  # exactly one extra executable
    r1 = spec.submit(p, max_new_tokens=24)
    spec.run_until_idle()
    assert r1.wait(1).tolist() == out_plain
    assert spec.compile_counts() == warm  # acceptance churn is data
    s = profiler.speculation_summary()
    assert s["accepted"] > 0  # speculation actually fired
    raw = profiler.metrics_snapshot()["speculation"]
    assert raw["emitted"] == raw["accepted"] + raw["slot_steps"]  # n_emit=n_acc+1
    assert s["tokens_per_step"] > 1.0


def test_spec_k0_is_the_plain_engine(model):
    """FLAGS_serve_spec_k=0 (the default) must BE the non-speculative
    engine: no verify executable, plain decode path, identical tokens."""
    p = _prompt(10, seed=11)
    base = _paged(model)
    out = base.generate(p, max_new_tokens=8).tolist()
    k0 = _paged(model, spec_k=0)
    assert not k0._spec_on
    assert "verify" not in k0.compile_counts()
    assert k0.generate(p, max_new_tokens=8).tolist() == out


def test_per_request_opt_out_rides_verify_bit_identical(model):
    """spec_k=0 on the REQUEST while the engine speculates: the row rides
    the verify executable at draft length 0 and must still match plain."""
    p = _cycle_prompt(n=14)
    base = _paged(model)
    out = base.generate(p, max_new_tokens=10).tolist()
    spec = _paged(model, spec_k=3)
    r = spec.submit(p, max_new_tokens=10, spec_k=0)
    spec.run_until_idle()
    assert r.wait(1).tolist() == out
    assert spec._drafters == [None] * spec.slots  # opt-out never drafted


def test_mixed_spec_plain_slots_cobatched_bit_identical(model):
    """Greedy speculative slots co-batched with a sampled slot and a
    spec_k=0 opt-out: the greedy outputs must match the plain engine
    token-for-token (rows are independent; sampling rides column 0 on its
    own key schedule and cannot perturb a greedy neighbour)."""
    pg, po, ps_ = _cycle_prompt(), _prompt(9, seed=3), _prompt(7, seed=4)
    outs = {}
    for tag, eng in (("plain", _paged(model)), ("spec", _paged(model, spec_k=3))):
        r_g = eng.submit(pg, max_new_tokens=14)
        r_o = eng.submit(po, max_new_tokens=10, spec_k=0)
        r_s = eng.submit(ps_, max_new_tokens=8, temperature=0.8)
        eng.run_until_idle()
        outs[tag] = (r_g.wait(1).tolist(), r_o.wait(1).tolist())
        assert len(r_s.wait(1)) == ps_.size + 8  # sampled slot completes
    assert outs["spec"] == outs["plain"]


# ---------------------------------------------------------------------------
# EOS inside an accepted window right-trims; length bound never overshoots
# ---------------------------------------------------------------------------


def test_eos_inside_accepted_window_right_trims(model):
    """Calibrate deterministically: replay the spec run step-by-step to find
    a token whose FIRST occurrence lands strictly inside a multi-token
    accepted burst, then rerun with that token as EOS — the request must
    finish at it exactly, with the burst's trailing tokens discarded."""
    p = _cycle_prompt()
    eng = _paged(model, spec_k=3)
    r = eng.submit(p, max_new_tokens=24)
    bursts, full = [], []
    while eng.has_work():
        before = len(r.tokens)
        eng.step()
        if len(r.tokens) > before:
            bursts.append(list(r.tokens[before:]))
    full = list(r.tokens)
    eos = None
    seen = set()
    for b in bursts:
        for j, t in enumerate(b):
            if t not in seen and j < len(b) - 1:
                eos = t  # first occurrence, with accepted tokens after it
                break
            seen.add(t)
        if eos is not None:
            break
    if eos is None:
        pytest.skip("no multi-token accepted burst on this model/seed")
    cut = full.index(eos)
    eng2 = _paged(model, spec_k=3)
    r2 = eng2.submit(p, max_new_tokens=24, eos_token_id=int(eos))
    eng2.run_until_idle()
    assert r2.wait(1).tolist() == p.tolist() + full[: cut + 1]
    assert r2.finish_reason == "eos"


def test_length_bound_never_overshoots(model):
    """The draft budget clamp (<= remaining-1) guarantees a verify window
    can never emit past max_new_tokens, whatever the acceptance."""
    p = _cycle_prompt()
    eng = _paged(model, spec_k=3)
    for want in (1, 2, 3, 5):
        r = eng.submit(p, max_new_tokens=want)
        eng.run_until_idle()
        assert len(r.wait(1)) == p.size + want
        assert r.finish_reason == "length"


# ---------------------------------------------------------------------------
# compile/recompile contract under churn; warm restart
# ---------------------------------------------------------------------------


def test_zero_recompiles_under_acceptance_churn(model):
    """Joins, finishes, recycles, drafter hits AND misses, per-request caps:
    every shape is [slots, k+1], so the warmed counts never move.  The
    module-level sanitizer additionally fails on any fresh trace or
    unexpected host sync inside the steady-state step."""
    eng = _paged(model, spec_k=3)
    eng.warmup()
    warm = eng.compile_counts()
    reqs = []
    for i in range(7):
        prompt = _cycle_prompt(n=12 + i) if i % 2 else _prompt(9 + i, seed=40 + i)
        reqs.append(
            eng.submit(
                prompt, max_new_tokens=3 + (i % 6),
                spec_k=None if i % 3 else 1,
                temperature=0.0 if i != 5 else 0.6,
            )
        )
    eng.run_until_idle()
    for r in reqs:
        assert r.wait(1) is not None
    assert eng.compile_counts() == warm


def test_warm_restart_rebuilds_drafter_state(model):
    """restart() drops every per-slot drafter with the slot table (host
    n-gram state must not survive a slot reassignment) and the next
    admission rebuilds one from prompt + first token — zero fresh compiles,
    tokens still identical to the plain engine."""
    p = _cycle_prompt()
    plain = _paged(model)
    out_ref = plain.generate(p, max_new_tokens=12).tolist()

    eng = _paged(model, spec_k=3)
    eng.warmup()
    r = eng.submit(p, max_new_tokens=12)
    for _ in range(3):  # give the drafter live state
        eng.step()
    assert any(d is not None for d in eng._drafters)
    warm = eng.compile_counts()
    eng.restart(reason="drill")
    assert eng._drafters == [None] * eng.slots
    with pytest.raises(Exception):
        r.wait(1)  # streamed already -> EngineRestarted
    r2 = eng.submit(p, max_new_tokens=12)
    eng.run_until_idle()
    assert r2.wait(1).tolist() == out_ref
    assert eng.compile_counts() == warm


# ---------------------------------------------------------------------------
# observability: drain estimate, healthz, profiler, /metrics, flight, spans
# ---------------------------------------------------------------------------


def test_drain_estimate_scales_with_token_rate(model):
    """The admission/drain EWMA priced every step at 1 token (the r05 bug):
    with speculation emitting >1 token/step the estimate must shrink by the
    observed rate, or deadlines over-reject on exactly the fast replicas."""
    eng = _paged(model, spec_k=3)
    eng._step_ewma_s = 0.1
    r = eng.submit(_prompt(6, seed=9), max_new_tokens=40)
    base = eng.estimate_drain_s()  # rate EWMA starts at 1.0
    assert base == pytest.approx(np.ceil(40 / 3) * 0.1)
    eng._tok_rate_ewma = 2.0
    fast = eng.estimate_drain_s()
    assert fast == pytest.approx(np.ceil(40 / 6) * 0.1)
    assert fast < base
    r.cancel()
    eng.run_until_idle()


def test_speculation_observability_surfaces(model, tmp_path):
    """One spec run must show up everywhere the issue names: healthz
    tokens_per_step, serving_summary().speculation, stable /metrics names
    (zero-rendered before traffic), and the flight-recorder dump header."""
    profiler.reset()
    text = metrics.render()
    for name in (
        "paddle_spec_steps_total 0",
        "paddle_spec_proposed_tokens_total 0",
        "paddle_spec_accepted_tokens_total 0",
        "paddle_spec_emitted_tokens_total 0",
        "paddle_spec_acceptance_rate 0",
        "paddle_spec_tokens_per_step 0",
    ):
        assert name in text  # scrape-stable: zeros render, names never vary

    eng = _paged(model, spec_k=3)
    eng.generate(_cycle_prompt(), max_new_tokens=16)
    s = profiler.serving_summary()
    assert s["speculation"]["proposed"] > 0
    assert 0.0 <= s["speculation"]["acceptance_rate"] <= 1.0
    h = eng.healthz()
    assert h["tokens_per_step"] >= 1.0
    text = metrics.render()
    assert "paddle_spec_steps_total 0" not in text

    import json

    path = flight.dump("spec-test", path=str(tmp_path / "f.jsonl"))
    header = json.loads(open(path).read().splitlines()[0])
    assert header["speculation"]["proposed"] > 0


def test_engine_verify_span_carries_acceptance(model):
    paddle.set_flags({"FLAGS_trace": True})
    trace.reset()
    try:
        tid = trace.new_trace_id()
        eng = _paged(model, spec_k=3)
        eng.warmup()
        r = eng.submit(_cycle_prompt(), max_new_tokens=16, trace=(tid, "a" * 16))
        eng.run_until_idle()
        r.wait(1)
        spans = [s for s in trace.spans(tid) if s["name"] == "engine.verify"]
        assert spans
        proposed = sum(s["attrs"]["proposed"] for s in spans)
        accepted = sum(s["attrs"]["accepted"] for s in spans)
        assert proposed > 0
        assert 0 <= accepted <= proposed
    finally:
        paddle.set_flags({"FLAGS_trace": False})
        trace.reset()


def test_page_invariants_hold_under_speculation(model):
    """FLAGS_serve_debug_invariants with the spec extension: every verify
    window's overrun entries must be scratch redirects, refcounts stay
    audited across accepted-run page-frontier advances."""
    paddle.set_flags({"FLAGS_serve_debug_invariants": True})
    try:
        eng = _paged(model, slots=2, spec_k=3, pool_pages=12)
        for i in range(4):
            eng.generate(_cycle_prompt(n=10 + i), max_new_tokens=8)
        with eng._mu:
            eng._check_page_invariants_locked()
        if eng._prefix is not None:
            eng._prefix.clear(eng._pool)
        assert eng._pool.free_count() == eng._pool.usable_pages
    finally:
        paddle.set_flags({"FLAGS_serve_debug_invariants": False})
