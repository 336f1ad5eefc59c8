"""Continuous-batching engine (ISSUE 5): slot-pooled static KV cache, one
compiled decode step for every occupancy, bucketed prefill, slot recycling
without leakage, EOS handling, and the serve() admission-queue contract.

All CPU: the engine's decode rides the dense flash_decode path (sq=1), the
same executable shape as TPU minus the Pallas kernel choice.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.inference.engine import ContinuousBatchingEngine, QueueFull
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def model():
    # a module's fixture is built before the first test's `_seeded`: without a
    # seed of its own the weights follow whatever file this worker ran before
    # (one whole run drew weights whose greedy output repeats a token early:
    # test_generate_eos_stops_and_trims read 7 columns for 8)
    paddle.seed(1234)
    np.random.seed(1234)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 250, size=n).astype(np.int32)


def _engine(model, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("queue_depth", 16)
    kw.setdefault("seed", 0)
    return ContinuousBatchingEngine(model, **kw)


def _post(port, body, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        json.dumps(body).encode(),
        {"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# ---------------------------------------------------------------------------
# correctness: engine vs lock-step generate
# ---------------------------------------------------------------------------


def test_engine_matches_lockstep_generate(model):
    p = _prompt(5, seed=7)
    eng = _engine(model)
    out = eng.generate(p, max_new_tokens=6)
    ref = model.generate(
        paddle.to_tensor(p[None]), max_new_tokens=6
    ).numpy()[0]
    assert np.array_equal(out, ref)


def test_slot_recycling_no_leakage(model):
    """A slot recycled from finished request A must give request B the exact
    tokens a fresh engine (and the lock-step path) gives: the stale rows A
    left beyond B's prefill are never attended (decode overwrites row pos
    before masking j <= pos)."""
    pa, pb = _prompt(14, seed=1), _prompt(5, seed=2)
    dirty = _engine(model, slots=1)  # one slot: B MUST reuse A's slot
    ra = dirty.submit(pa, max_new_tokens=20)  # long: fills rows well past B's
    dirty.run_until_idle()
    ra.wait(1)
    out_dirty = dirty.generate(pb, max_new_tokens=8)

    fresh = _engine(model, slots=1)
    out_fresh = fresh.generate(pb, max_new_tokens=8)
    assert np.array_equal(out_dirty, out_fresh)

    ref = model.generate(paddle.to_tensor(pb[None]), max_new_tokens=8).numpy()[0]
    assert np.array_equal(out_dirty, ref)


def test_per_slot_temperature_is_data(model):
    """A sampled request decoding next to a greedy one must not perturb the
    greedy tokens (temperature is per-slot data; rows are independent)."""
    pg, ps = _prompt(5, seed=3), _prompt(9, seed=4)
    eng = _engine(model)
    rg = eng.submit(pg, max_new_tokens=6, temperature=0.0)
    rs = eng.submit(ps, max_new_tokens=6, temperature=0.9)
    eng.run_until_idle()
    ref = model.generate(paddle.to_tensor(pg[None]), max_new_tokens=6).numpy()[0]
    assert np.array_equal(rg.wait(1), ref)
    assert len(rs.wait(1)) == 9 + 6


# ---------------------------------------------------------------------------
# compile-count contract: buckets + 1, zero recompiles after warmup
# ---------------------------------------------------------------------------


def test_mixed_length_compile_count(model):
    """Total compiled executables == distinct prefill buckets used + 1
    decode, across joins, finishes, and recycling."""
    eng = _engine(model, slots=2, prefill_buckets=[8, 16, 32])
    lens = [5, 12, 20, 3, 30, 8]  # buckets 8, 16, 32, 8, 32, 8
    reqs = [
        eng.submit(_prompt(n, seed=10 + i), max_new_tokens=4 + (i % 3))
        for i, n in enumerate(lens)
    ]
    eng.run_until_idle()
    for r in reqs:
        r.wait(1)
    counts = eng.compile_counts()
    assert counts["prefill"] == 3  # buckets 8, 16, 32 each traced once
    assert counts["decode"] == 1


def test_zero_recompiles_after_warmup(model):
    eng = _engine(model)
    eng.warmup()
    warm = eng.compile_counts()
    assert warm["prefill"] == len(eng.prefill_buckets)
    assert warm["decode"] == 1
    # overlapping traffic with different lengths, finishes, recycling
    reqs = [
        eng.submit(_prompt(3 + 2 * i, seed=20 + i), max_new_tokens=2 + i)
        for i in range(5)
    ]
    eng.run_until_idle()
    for r in reqs:
        assert r.wait(1) is not None
        assert r.finish_reason == "length"
    assert eng.compile_counts() == warm  # 0 recompiles under traffic


# ---------------------------------------------------------------------------
# EOS satellite: per-sequence stop + right-trimmed outputs
# ---------------------------------------------------------------------------


def test_generate_eos_stops_and_trims(model):
    p = _prompt(5, seed=5)[None]
    full = model.generate(paddle.to_tensor(p), max_new_tokens=8).numpy()
    eos = int(full[0, 5 + 2])  # greedy emits this at generation step 3
    out = model.generate(
        paddle.to_tensor(p), max_new_tokens=8, eos_token_id=eos
    ).numpy()
    assert out.shape[1] == 5 + 3  # right-trimmed at the eos column
    assert np.array_equal(out[0], full[0, : 5 + 3])
    assert out[0, -1] == eos


def test_generate_eos_mixed_batch_pads_finished_rows(model):
    p = np.stack([_prompt(5, seed=5), _prompt(5, seed=6)])
    full = model.generate(paddle.to_tensor(p), max_new_tokens=8).numpy()
    eos = int(full[0, 5])  # row 0 finishes on its FIRST generated token
    assert eos not in full[1, 5:], "need a row that never emits eos"
    out = model.generate(
        paddle.to_tensor(p), max_new_tokens=8, eos_token_id=eos
    ).numpy()
    assert out.shape[1] == 5 + 8  # row 1 runs to max_new_tokens
    assert (out[0, 5:] == eos).all()  # finished row rides along as eos
    assert np.array_equal(out[1], full[1])


def test_generation_predictor_forwards_eos(model):
    p = _prompt(5, seed=5)
    pred = inference.GenerationPredictor(model, max_new_tokens=8)
    full = pred.generate(p)
    eos = int(full[0, 5 + 1])
    keep = int(np.argmax(full[0, 5:] == eos)) + 1  # first eos hit stops it
    out = pred.generate(p, eos_token_id=eos)
    assert out.shape[1] == 5 + keep
    assert out[0, -1] == eos


def test_engine_eos_finishes_slot_early(model):
    p = _prompt(5, seed=7)
    eng = _engine(model)
    full = eng.generate(p, max_new_tokens=8)
    eos = int(full[5 + 1])
    keep = int(np.argmax(full[5:] == eos)) + 1
    out = eng.generate(p, max_new_tokens=8, eos_token_id=eos)
    assert out.tolist() == full[: 5 + keep].tolist()
    # finish_reason is per-request: resubmit to inspect the handle
    r = eng.submit(p, max_new_tokens=8, eos_token_id=eos)
    eng.run_until_idle()
    r.wait(1)
    assert r.finish_reason == "eos"


# ---------------------------------------------------------------------------
# scheduler: streaming, admission queue, threaded serve()
# ---------------------------------------------------------------------------


def test_streaming_token_callbacks(model):
    p = _prompt(5, seed=8)
    eng = _engine(model)
    stream = []
    r = eng.submit(p, max_new_tokens=5, on_token=stream.append)
    eng.run_until_idle()
    out = r.wait(1)
    assert stream == out[-5:].tolist()  # streamed in generation order


def _drains():
    return paddle.profiler.serving_summary()["drains"]


def test_streaming_runs_one_step_behind_the_device(model):
    """A streaming callback gets step N's token while step N + 1 is in
    flight, and a change of membership does not end that: a request admitted
    midway is prefilled BEHIND the step in flight with nothing fetched first,
    its first token comes with the tick's flush and before any of its decode
    tokens, a slot at its length bound leaves by count and finishes a tick
    later, and the tokens and their order are those of a request nobody
    streams.  An EOS watch, whose values decide membership, is still fetched
    in its own tick."""
    p, q = _prompt(5, seed=8), _prompt(6, seed=9)
    want_p = _engine(model).generate(p, max_new_tokens=9)[-9:].tolist()
    want_q = _engine(model).generate(q, max_new_tokens=4)[-4:].tolist()
    eng = _engine(model)
    paddle.profiler.reset_serving()
    order = []
    r = eng.submit(p, max_new_tokens=9, on_token=lambda t: order.append(("p", t)))
    eng.step()  # the prefill and one decode step behind it: the first token, the step kept
    assert (len(order), len(eng._pending_fetch)) == (1, 1)
    eng.step()
    assert (len(order), len(eng._pending_fetch)) == (2, 1)
    r2 = eng.submit(q, max_new_tokens=4, on_token=lambda t: order.append(("q", t)))
    eng.step()  # q's prefill goes in behind p's step in flight; q's first token after p's third
    assert order[2:] == [("p", want_p[2]), ("q", want_q[0])]
    assert len(eng._pending_fetch) == 1 and r2.ttft_s is not None
    ticks = 3
    while not r2.finished.is_set():
        eng.step()
        ticks += 1
    # q's fourth token is its third decode step's, delivered a tick after
    # the step was dispatched: the slot left the mask by count meanwhile
    assert ticks == 3 + 3 and not r.finished.is_set()
    eng.run_until_idle()
    assert [t for w, t in order if w == "p"] == want_p == list(r.tokens)
    assert [t for w, t in order if w == "q"] == want_q == list(r2.tokens)
    assert not eng._pending_fetch
    s = paddle.profiler.serving_summary()
    assert s["membership_changes"] == 4
    assert not any(s["drains"].values())  # nothing drained the device
    r3 = eng.submit(p, max_new_tokens=9, eos_token_id=-1, on_token=lambda t: None)
    eng.step()
    assert len(r3.tokens) == 2 and not eng._pending_fetch
    assert _drains()["eos_watch"] == 1


_CHURN = [  # (prompt length, max_new_tokens): 20 is longer than the largest bucket
    (5, 7), (9, 3), (20, 5), (4, 1), (12, 9), (7, 2), (15, 6), (6, 4),
]


@pytest.mark.parametrize("stream,eos", [(True, None), (False, None), (True, -1)],
                         ids=["streamed", "unstreamed", "eos-watch"])
def test_membership_churn_keeps_tokens_on_the_device(model, stream, eos):
    """3 slots, 8 requests of staggered lengths: slots end and are reseated
    while others decode, one prompt goes in chunks, one request is its
    prefill's token alone.  Every request's tokens (and its callbacks, in
    order) are lock-step generate's, nothing compiles, and neither a finish,
    an admission nor a first token drains the device; a batch that watches
    for EOS is fetched every tick, and says so."""
    prompts = [_prompt(n, seed=60 + i) for i, (n, _) in enumerate(_CHURN)]
    want = [
        model.generate(paddle.to_tensor(p[None]), max_new_tokens=n).numpy()[0, -n:].tolist()
        for p, (_, n) in zip(prompts, _CHURN)
    ]
    eng = _engine(model).warmup()
    warm = eng.compile_counts()
    paddle.profiler.reset_serving()
    streams = [[] for _ in _CHURN]
    reqs = [
        eng.submit(p, max_new_tokens=n, eos_token_id=eos,
                   on_token=streams[i].append if stream else None)
        for i, (p, (_, n)) in enumerate(zip(prompts, _CHURN))
    ]
    depth = 0
    while eng.has_work():
        eng.step()
        depth = max(depth, len(eng._pending_fetch))
    assert [list(r.tokens) for r in reqs] == want
    assert all(r.finish_reason == "length" for r in reqs)
    if stream:
        assert streams == want
    assert eng.compile_counts() == warm
    assert not eng._pending_fetch
    s = paddle.profiler.serving_summary()
    assert s["requests"] == 8 and s["membership_changes"] == 16
    d = s["drains"]
    assert (d["length"], d["admission"], d["first_token"]) == (0, 0, 0)
    if eos is None:
        assert not any(d.values())
        # unstreamed, a step is fetched when a slot's last token or a first
        # token is behind it: never deeper than the longest answer
        assert depth <= (2 if stream else max(n for _, n in _CHURN))
    else:
        assert d["eos_watch"] > 0 and depth == 0
    # a slot that sat out a step between its last and its finish wrote
    # nothing into its own pages: its prompt's, committed to the prefix
    # cache, serve the same prompt again to the same tokens
    kept = [i for i, p in enumerate(prompts) if eng._prefix.lookup(p)[0] >= 8]
    assert kept  # the pool is 3 pages: the last to finish are still cached
    hits = paddle.profiler.paging_summary()["prefix_hits"]
    for i in kept:
        again = eng.submit(prompts[i], max_new_tokens=_CHURN[i][1])
        eng.run_until_idle()
        assert list(again.tokens) == want[i]
    assert paddle.profiler.paging_summary()["prefix_hits"] == hits + len(kept)


def test_a_late_entry_of_a_departed_request_never_reaches_the_tenant(model):
    """An unfetched step belongs to the (slot, request) it was dispatched
    for.  A request leaves its slot a step late (here a logit window found
    non-finite when the step is fetched, one tick on) while a further step of
    its is in flight; the slot is reseated before that step is fetched.  Its
    token goes to nobody: the predecessor keeps what it had when it erred,
    the tenant's tokens are generate's.  And on the plain path, one slot and
    two requests back to back: the first gets its last token, the second
    every one of its own."""
    pa, pb = _prompt(5, seed=21), _prompt(7, seed=22)
    want_a = _engine(model).generate(pa, max_new_tokens=6)[-6:].tolist()
    want_b = _engine(model).generate(pb, max_new_tokens=5)[-5:].tolist()
    eng = _engine(model, slots=1)
    sa, sb = [], []
    ra = eng.submit(pa, max_new_tokens=6, on_token=sa.append)
    rb = eng.submit(pb, max_new_tokens=5, on_token=sb.append)
    eng.run_until_idle()
    assert (sa, sb) == (want_a, want_b) and ra.finish_reason == rb.finish_reason == "length"

    eng = _engine(model, slots=1)
    real, calls = eng._decode_fn, []

    def decode(*a):
        out = list(real(*a))
        calls.append(len(calls))
        if len(calls) == 2:  # the second decode step: slot 0's window reads non-finite
            out[2] = paddle.to_tensor(np.zeros(eng.slots, bool))
        return tuple(out)

    eng._decode_fn = decode
    sa, sb = [], []
    ra = eng.submit(pa, max_new_tokens=6, on_token=sa.append)
    for _ in range(3):  # steps 1-3 dispatched; step 2 fetched, and a erred by it
        eng.step()
    assert ra.finish_reason == "error" and sa == want_a[:2]
    assert [r.id for e in eng._pending_fetch for _, r in e.pairs] == [ra.id]
    rb = eng.submit(pb, max_new_tokens=5, on_token=sb.append)
    eng.run_until_idle()
    assert sa == want_a[:2] == list(ra.tokens)
    assert sb == want_b == list(rb.tokens) and rb.finish_reason == "length"
    assert not eng._pending_fetch


def test_a_slot_that_sits_out_a_step_writes_nothing_into_its_pages(model):
    """Between a slot's last step and its finish, a tick later, the others
    run a step in which it is inactive: that step must write to the scratch
    page and not through the slot's table row, whose first page holds the
    prompt the prefix cache has committed.  The same prompt served again
    from those pages gives the same tokens."""
    pa, pb = _prompt(12, seed=31), _prompt(6, seed=32)
    want_a = _engine(model).generate(pa, max_new_tokens=3)[-3:].tolist()
    eng = _engine(model, slots=2)
    ra = eng.submit(pa, max_new_tokens=3, on_token=lambda t: None)
    rb = eng.submit(pb, max_new_tokens=12, on_token=lambda t: None)
    while not ra.finished.is_set():
        eng.step()
    assert not rb.finished.is_set() and list(ra.tokens) == want_a
    hits = paddle.profiler.paging_summary().get("prefix_hits", 0)
    again = eng.submit(pa, max_new_tokens=3)
    eng.run_until_idle()
    assert paddle.profiler.paging_summary()["prefix_hits"] == hits + 1
    assert list(again.tokens) == want_a


def test_the_engine_takes_no_paged_argument(model):
    """There is one engine: its selector is refused, not ignored."""
    with pytest.raises(TypeError, match="paged"):
        ContinuousBatchingEngine(model, paged=False)
    assert _engine(model).decode_kernel == "auto"


@pytest.mark.parametrize("name", ["FLAGS_serve_paged_kv", "FLAGS_serve_decode_kernel"])
def test_the_flag_that_chose_an_engine_or_a_kernel_is_gone(name):
    with pytest.raises(KeyError, match=name):
        paddle.get_flags([name])
    with pytest.raises(KeyError, match=name):
        paddle.set_flags({name: "auto"})


def test_submit_queue_full_raises(model):
    eng = _engine(model, queue_depth=2)  # scheduler not running
    eng.submit(_prompt(4), max_new_tokens=2)
    eng.submit(_prompt(4), max_new_tokens=2)
    with pytest.raises(QueueFull):
        eng.submit(_prompt(4), max_new_tokens=2)


def test_serve_engine_http_roundtrip_and_503(model):
    # queue bound >= concurrent requests: the roundtrip half must not shed
    eng = _engine(model, slots=2, queue_depth=4)
    eng.warmup()
    srv = inference.serve(eng, port=0, block=False)
    port = srv.server_address[1]
    try:
        # overlapping requests with different lengths all complete
        results = {}

        def hit(i, n, mnt):
            results[i] = _post(
                port, {"input_ids": _prompt(n, seed=30 + i).tolist(),
                       "max_new_tokens": mnt},
            )

        ts = [
            threading.Thread(target=hit, args=(i, 3 + 4 * i, 3 + i))
            for i in range(4)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert sorted(s for s, _ in results.values()) == [200] * 4
        for i, (_, body) in results.items():
            assert len(body["tokens"]) == (3 + 4 * i) + (3 + i)
            ref = model.generate(
                paddle.to_tensor(_prompt(3 + 4 * i, seed=30 + i)[None]),
                max_new_tokens=3 + i,
            ).numpy()[0]
            assert body["tokens"] == ref.tolist()

        # freeze the scheduler, fill the admission queue, and the next
        # request must shed with 503 + JSON error body
        eng.stop()
        for _ in range(eng.queue_depth):
            eng.submit(_prompt(4), max_new_tokens=2)
        status, body = _post(port, {"input_ids": _prompt(4).tolist(),
                                    "max_new_tokens": 2})
        assert status == 503
        assert "error" in body
        eng.start()  # drain the queued requests before shutdown
    finally:
        srv.shutdown()
        eng.stop()


def test_serving_profiler_gauges(model):
    paddle.profiler.reset_serving()
    eng = _engine(model, slots=2)
    reqs = [
        eng.submit(_prompt(4 + i, seed=40 + i), max_new_tokens=3)
        for i in range(3)
    ]
    eng.run_until_idle()
    for r in reqs:
        r.wait(1)
    s = paddle.profiler.serving_summary()
    assert s["requests"] == 3
    assert s["tokens"] == 9
    assert s["tokens_per_s"] > 0
    assert 0 < s["occupancy_mean"] <= 1.0
    assert s["ttft_p50_ms"] > 0 and s["ttft_p95_ms"] >= s["ttft_p50_ms"]
