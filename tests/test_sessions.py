"""First-class session KV (ISSUE 20): a `session_id` pins the finished
turn's committed pages in the prefix cache, so turn N+1 chunk-prefills only
the unshared suffix at its true rope offsets — bit-identical to stateless
replay, with >= 90% of multi-turn prefill work skipped and zero fresh
compiles.  Sessions evict LRU-whole under page pressure (the next turn
falls back to a stateless re-prefill), survive warm restart(), and pin
router traffic to the replica holding their pages.

Also here: the typed ContextOverflow 400 (admission-time, before any page
is reserved) and the session clauses of the debug-invariants audit.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference import serve
from paddle_tpu.inference.engine import (
    ContextOverflow,
    ContinuousBatchingEngine,
)
from paddle_tpu.inference.paging import PagePool, PrefixCache, SessionStore
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import Router


@pytest.fixture(scope="module", autouse=True)
def _rng_guard():
    state = np.asarray(paddle.get_rng_state())
    yield
    paddle.set_rng_state(state)


@pytest.fixture(scope="module")
def model(_rng_guard):
    np.random.seed(1234)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 250, size=n).astype(np.int32)


def _paged(model, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 192)
    kw.setdefault("prefill_buckets", [8, 128])
    kw.setdefault("queue_depth", 16)
    kw.setdefault("seed", 0)
    kw.setdefault("page_size", 8)
    return ContinuousBatchingEngine(model, **kw)


def _turn(eng, prompt, n=3, sid=None):
    req = eng.submit(np.asarray(prompt, np.int32), max_new_tokens=n,
                     session_id=sid)
    eng.run_until_idle()
    return req, list(req.wait(1).tolist())


def _replica_server(model, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8, 64])
    kw.setdefault("queue_depth", 16)
    kw.setdefault("seed", 0)
    eng = ContinuousBatchingEngine(model, **kw)
    srv = serve(eng, port=0, block=False, supervise=False,
                handle_signals=False)
    return srv, eng, f"http://127.0.0.1:{srv.server_address[1]}"


def _stop_server(srv):
    try:
        srv.engine.stop()
    except Exception:
        pass
    srv.shutdown()
    srv.server_close()


def _post(url, body, headers=None, timeout=60):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


# ---------------------------------------------------------------------------
# store unit: pin/unpin lifecycle over real cache entries
# ---------------------------------------------------------------------------


def _committed_chain(pool, cache, tokens):
    pages = [pool.alloc() for _ in range(-(-len(tokens) // cache.page_size))]
    cache.commit(np.asarray(tokens, np.int32), pages, pool)
    for p in pages:  # the slot mapping these stood in for is gone
        pool.decref(p)
    entries, covered = cache.chain(np.asarray(tokens, np.int32))
    assert covered == len(tokens)
    return entries


def test_session_store_pin_lifecycle_and_lru():
    pool = PagePool(16)
    cache = PrefixCache(8)
    store = SessionStore(capacity=2)
    seq_a = list(range(1, 17))
    entries = _committed_chain(pool, cache, seq_a)
    assert store.bind("a", seq_a, entries) == []
    assert store.pages_pinned() == len(entries)
    assert all(e.pinned == 1 for e in entries)
    # pinned entries never evict, even under direct pressure
    assert cache.evict_one(pool) is None
    # rebind with a LONGER chain pins-new-before-unpin: shared links never
    # transit zero
    seq_a2 = seq_a + list(range(17, 25))
    entries2 = _committed_chain(pool, cache, seq_a2)
    store.bind("a", seq_a2, entries2)
    assert all(e.pinned == 1 for e in entries2)
    assert store.get("a")["turns"] == 2
    # capacity 2: binding c evicts the LRU of {a, b}
    seq_b = list(range(30, 46))
    store.bind("b", seq_b, _committed_chain(pool, cache, seq_b))
    store.touch("a")  # b becomes LRU
    seq_c = list(range(50, 66))
    evicted = store.bind("c", seq_c, _committed_chain(pool, cache, seq_c))
    assert evicted == ["b"]
    st = store.stats()
    assert st["sessions_resident"] == 2
    assert st["session_evictions_total"] == 1
    assert st["session_binds_total"] == 4
    store.check(cache, pool)  # pins == session holds
    # release drops every pin; the chain becomes ordinary LRU-evictable
    store.release("a")
    store.release("c")
    assert store.pages_pinned() == 0
    assert cache.evict_one(pool) is not None


def test_session_check_catches_pin_drift():
    pool = PagePool(8)
    cache = PrefixCache(8)
    store = SessionStore()
    entries = _committed_chain(pool, cache, list(range(1, 9)))
    store.bind("s", list(range(1, 9)), entries)
    entries[0].pinned += 1  # a leak the audit must name
    with pytest.raises(AssertionError, match="session invariant"):
        store.check(cache, pool)


# ---------------------------------------------------------------------------
# engine replay: 20 turns, bit-identical, >= 90% prefill skipped, 0 compiles
# ---------------------------------------------------------------------------


def test_20_turn_session_replay_bit_identical_90pct_saved(model):
    """A 20-turn conversation through one engine with a session_id must
    emit, turn for turn, the exact tokens a stateless engine (no prefix
    cache at all) produces from the full transcript — while skipping >=90%
    of the turns-2..20 prefill tokens and compiling NOTHING after warmup."""
    sess = _paged(model)
    sess.warmup()
    warm = sess.compile_counts()
    stateless = _paged(model, prefix_cache=False)

    conv = _prompt(12, seed=10).tolist()
    total_prompt = saved = 0
    for t in range(20):
        req, out = _turn(sess, conv, n=3, sid="conv-0")
        _, ref = _turn(stateless, conv, n=3)
        assert out == ref, f"turn {t} diverged from stateless replay"
        if t > 0:
            total_prompt += len(conv)
            saved += req.session_reused_tokens
        conv = out + _prompt(2, seed=100 + t).tolist()
    assert saved / total_prompt >= 0.90
    assert sess.compile_counts() == warm  # rope offsets/tables are data
    st = sess._sessions.stats()
    assert st["sessions_resident"] == 1
    assert st["session_binds_total"] == 20
    assert st["session_prefill_tokens_saved_total"] == saved
    # the audit's session clause holds with a live pinned chain
    with sess._mu:
        sess._check_page_invariants_locked()


def test_session_eviction_under_page_pressure_falls_back_stateless(model):
    """A small pool: sessionless traffic must be able to evict an idle
    session LRU-whole to get pages; the evicted session's next turn still
    answers bit-identically via a stateless re-prefill."""
    paddle.set_flags({"FLAGS_serve_debug_invariants": True})
    try:
        eng = _paged(model, max_len=64, prefill_buckets=[8, 64],
                     pool_pages=6)  # 5 usable pages
        turn1 = _prompt(14, seed=20).tolist()
        _, out1 = _turn(eng, turn1, n=3, sid="victim")
        assert eng._sessions.stats()["sessions_resident"] == 1
        assert eng._sessions.pages_pinned() == 2  # 16 committed rows
        # flood: sessionless prompts spanning 4 pages each — with only 3
        # unpinned pages in the pool, admission must count the pinned chain
        # as reachable headroom and the allocator must evict the session
        for i in range(3):
            _turn(eng, _prompt(26, seed=30 + i).tolist(), n=4)
        st = eng._sessions.stats()
        assert st["sessions_resident"] == 0
        assert st["session_evictions_total"] == 1
        # next turn: stateless re-prefill, same tokens as a fresh engine
        conv = out1 + _prompt(2, seed=21).tolist()
        _, out2 = _turn(eng, conv, n=3, sid="victim")
        fresh = _paged(model, max_len=64, prefill_buckets=[8, 64],
                       prefix_cache=False)
        _, ref = _turn(fresh, conv, n=3)
        assert out2 == ref
        with eng._mu:
            eng._check_page_invariants_locked()
    finally:
        paddle.set_flags({"FLAGS_serve_debug_invariants": False})


def test_sessions_survive_warm_restart(model):
    eng = _paged(model)
    eng.warmup()
    warm = eng.compile_counts()
    conv = _prompt(16, seed=40).tolist()
    _, out1 = _turn(eng, conv, n=3, sid="s")
    eng.restart(reason="drill")
    assert eng._sessions.stats()["sessions_resident"] == 1
    conv2 = out1 + _prompt(2, seed=41).tolist()
    req, out2 = _turn(eng, conv2, n=3, sid="s")
    # pinned KV survived: everything but the last emitted token (whose KV
    # was never written) came from the session chain
    assert req.session_reused_tokens == len(out1) - 1
    fresh = _paged(model, prefix_cache=False)
    _, ref = _turn(fresh, conv2, n=3)
    assert out2 == ref
    assert eng.compile_counts() == warm


# ---------------------------------------------------------------------------
# ContextOverflow: typed 400 at admission, before any page moves
# ---------------------------------------------------------------------------


def test_context_overflow_typed_at_admission(model):
    eng = _paged(model, max_len=32, prefill_buckets=[8, 32])
    free_before = eng._pool.free_count()
    with pytest.raises(ContextOverflow) as ei:
        eng.submit(_prompt(40, seed=50), max_new_tokens=2)
    body = ei.value.body()
    assert body["prompt_len"] == 40 and body["max_len"] == 32
    assert body["cp"] == 1
    assert eng._pool.free_count() == free_before
    # the engine still serves: the reject consumed nothing
    assert eng.generate(_prompt(6, seed=51), max_new_tokens=2).size == 8


def test_context_overflow_http_400_with_capacity_body(model):
    srv, eng, url = _replica_server(model, max_len=32,
                                    prefill_buckets=[8, 32])
    try:
        status, body, _ = _post(
            url, {"input_ids": _prompt(40, seed=52).tolist(),
                  "max_new_tokens": 2})
        assert status == 400
        assert body["type"] == "ContextOverflow"
        assert body["retriable"] is False
        assert body["capacity"]["prompt_len"] == 40
        assert body["capacity"]["max_len"] == 32
        assert "cp" in body["capacity"]
    finally:
        _stop_server(srv)


# ---------------------------------------------------------------------------
# router: session -> replica pinning, repin drill on replica death
# ---------------------------------------------------------------------------


def test_router_pins_sessions_and_repins_after_death(model):
    srv_a, eng_a, url_a = _replica_server(model)
    srv_b, eng_b, url_b = _replica_server(model)
    router = Router([url_a, url_b], probe_interval=3600, retry_backoff=0.01)
    prof_before = profiler.router_summary()
    try:
        router.probe_once()
        conv = _prompt(10, seed=60).tolist()
        status, body, _ = router.handle_generate(
            {"input_ids": conv, "max_new_tokens": 3, "session_id": "c1"})
        assert status == 200
        h = router.healthz()
        assert h["session_pins"] == 1
        pinned_rid = next(iter(h["session_pins_by_replica"]))
        # a session rides the colocated path even in a role-split fleet
        assert router._disagg_eligible(
            {"input_ids": [1, 2], "session_id": "c1"}) is False

        # turn 2 routes BACK to the pinned replica (and only it holds the
        # session), even though least-loaded scoring alone could tie
        conv2 = body["tokens"] + _prompt(2, seed=61).tolist()
        status, body2, _ = router.handle_generate(
            {"input_ids": conv2, "max_new_tokens": 3, "session_id": "c1"})
        assert status == 200
        pinned_eng = eng_a if pinned_rid == "r0" else eng_b
        other_eng = eng_b if pinned_rid == "r0" else eng_a
        assert "c1" in pinned_eng._sessions
        assert "c1" not in other_eng._sessions
        assert profiler.router_summary().get("session_pin_hits", 0) >= 1

        # kill the pinned replica mid-session: the next turn unpins, falls
        # back to the survivor, re-prefills STATELESSLY, and answers with
        # the exact tokens an undisturbed engine produces — exactly once
        _stop_server(srv_a if pinned_rid == "r0" else srv_b)
        conv3 = body2["tokens"] + _prompt(2, seed=62).tolist()
        status, body3, _ = router.handle_generate(
            {"input_ids": conv3, "max_new_tokens": 3, "session_id": "c1"})
        assert status == 200
        fresh = _paged(model, max_len=64, prefill_buckets=[8, 64],
                       prefix_cache=False)
        _, ref = _turn(fresh, conv3, n=3)
        assert body3["tokens"] == ref
        assert profiler.router_summary().get("session_repins", 0) >= 1
        h = router.healthz()
        survivor_rid = "r1" if pinned_rid == "r0" else "r0"
        assert h["session_pins_by_replica"] == {survivor_rid: 1}
    finally:
        router.stop()
        for srv in (srv_a, srv_b):
            try:
                _stop_server(srv)
            except Exception:
                pass
        profiler.reset_router()


# ---------------------------------------------------------------------------
# observability: metric families + flight-recorder header
# ---------------------------------------------------------------------------


def test_session_metrics_families_and_flight_header(model, tmp_path):
    from paddle_tpu.obs import flight, metrics

    eng = _paged(model)
    conv = _prompt(12, seed=70).tolist()
    _, out = _turn(eng, conv, n=3, sid="m1")
    _turn(eng, out + _prompt(2, seed=71).tolist(), n=3, sid="m1")

    snap = profiler.metrics_snapshot()["sessions"]
    assert snap["sessions_resident"] == 1
    assert snap["session_binds_total"] >= 2
    assert snap["session_prefill_tokens_saved_total"] > 0

    text = metrics.render()
    for fam in ("paddle_session_resident", "paddle_session_pages_pinned",
                "paddle_session_binds_total", "paddle_session_evictions_total",
                "paddle_session_prefill_tokens_saved_total",
                "paddle_session_pin_hits_total", "paddle_session_repins_total",
                "paddle_cp_degree", "paddle_cp_decode_compiles_total"):
        assert fam in text, fam

    path = flight.dump("test", path=str(tmp_path / "f.jsonl"))
    header = json.loads(open(path).readline())
    assert "sessions" in header
    assert header["sessions"]["sessions_resident"] == 1
