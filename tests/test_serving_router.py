"""Multi-replica serving router (ISSUE 9): health-checked failover,
deadline propagation through two hops, circuit-breaker lifecycle, brownout
shedding, rolling drain with zero dropped requests, and the kill -9 chaos
drill.

The fast tests run the REAL router over in-process serve() instances that
share one tiny model (identical weights across replicas is the property
failover relies on: greedy outputs are bit-identical whichever replica
answers).  The slow drill runs router-MANAGED subprocess replicas through
the launch Container — the production process topology — and kills one with
SIGKILL under Poisson load.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler as prof
from paddle_tpu.fault import injection as finj
from paddle_tpu.inference import serve
from paddle_tpu.inference.engine import ContinuousBatchingEngine, QueueFull
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import Replica, ReplicaProcess, Router, serve_router


@pytest.fixture(scope="module")
def model():
    np.random.seed(1234)
    return LlamaForCausalLM(LlamaConfig.tiny())


@pytest.fixture(autouse=True)
def _clean_router_state():
    prof.reset_router()
    yield
    finj.disarm()
    prof.reset_router()
    paddle.set_flags({"FLAGS_fault_hang_sec": 3600.0})


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 250, size=n).astype(np.int32)


def _ref(model, p, n):
    return model.generate(paddle.to_tensor(p[None]), max_new_tokens=n).numpy()[0]


def _replica_server(model, **kw):
    """One in-process replica: engine + serve() on an ephemeral port."""
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8])
    kw.setdefault("queue_depth", 16)
    kw.setdefault("seed", 0)
    eng = ContinuousBatchingEngine(model, **kw)
    srv = serve(eng, port=0, block=False, supervise=False, handle_signals=False)
    port = srv.server_address[1]
    return srv, eng, f"http://127.0.0.1:{port}"


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
# satellite 1: engine.healthz() load fields, forwarded by serve()
# ---------------------------------------------------------------------------


def test_healthz_exports_router_load_fields(model):
    srv, eng, url = _replica_server(model)
    try:
        h = eng.healthz()
        for k in ("page_free_frac", "prefix_cache_size", "decode_ewma_ms"):
            assert k in h
        assert 0.0 <= h["page_free_frac"] <= 1.0
        # serve() forwards the engine dict verbatim over /healthz
        with urllib.request.urlopen(url + "/healthz", timeout=5) as r:
            wire = json.loads(r.read())
        for k in ("page_free_frac", "prefix_cache_size", "decode_ewma_ms",
                  "drain_estimate_s", "queue_depth"):
            assert k in wire
    finally:
        _stop_server(srv)


def test_engine_reports_page_free_fraction(model):
    eng = ContinuousBatchingEngine(
        model, slots=2, max_len=64, prefill_buckets=[8], queue_depth=4,
        seed=0, page_size=8, prefix_cache=False,
    )
    assert eng.healthz()["page_free_frac"] == 1.0
    eng.submit(_prompt(4), max_new_tokens=4)
    eng.step()  # admit into a slot: its pages leave the free list
    assert 0.0 < eng.healthz()["page_free_frac"] < 1.0
    eng.run_until_idle()
    # nothing holds a page once the request is done (no prefix cache)
    assert eng.healthz()["page_free_frac"] == 1.0


# ---------------------------------------------------------------------------
# satellite 2: uniformly typed error JSON (retriable + Retry-After driven)
# ---------------------------------------------------------------------------


def test_serve_errors_are_typed_json(model):
    srv, eng, url = _replica_server(model)
    try:
        eng._step_ewma_s = 0.01  # evidence for a nonzero Retry-After
        eng.submit(_prompt(4), max_new_tokens=8)
        srv.drain(grace=0.5)
        time.sleep(0.05)
        status, body, headers = _post(url, {"input_ids": [1, 2, 3]})
        assert status == 503
        assert body["type"] == "Draining"
        assert body["retriable"] is True
        assert "error" in body
    finally:
        _stop_server(srv)


def test_spent_deadline_header_is_non_retriable_504(model):
    srv, eng, url = _replica_server(model)
    try:
        status, body, _ = _post(
            url, {"input_ids": [1, 2, 3]}, headers={"X-Deadline-Ms": "0"}
        )
        assert status == 504
        assert body["type"] == "DeadlineExceeded"
        assert body["retriable"] is False
    finally:
        _stop_server(srv)


def test_unattainable_deadline_is_retriable_504(model, monkeypatch):
    srv, eng, url = _replica_server(model)
    try:
        # pin the backlog estimate (the live scheduler would relax it)
        monkeypatch.setattr(eng, "estimate_drain_s", lambda: 10.0)
        status, body, headers = _post(
            url, {"input_ids": [1, 2, 3], "deadline_s": 0.05}
        )
        assert status == 504
        assert body["type"] == "DeadlineUnattainable"
        # retriable: a LESS LOADED replica may still meet the deadline —
        # this is what lets the router fail over instead of giving up
        assert body["retriable"] is True
        assert int(headers.get("Retry-After", 0)) >= 1
    finally:
        _stop_server(srv)


# ---------------------------------------------------------------------------
# satellite 3: QueueFull Retry-After clamped by the request deadline
# ---------------------------------------------------------------------------


def test_queuefull_retry_after_clamped_by_deadline(model, monkeypatch):
    eng = ContinuousBatchingEngine(
        model, slots=2, max_len=64, prefill_buckets=[8], queue_depth=1, seed=0
    )
    eng.submit(_prompt(4), max_new_tokens=8)  # fill the queue (no scheduler)
    # simulate the admission race the clamp exists for: the drain estimate
    # is small at the deadline gate but has grown (concurrent admissions)
    # by the time the queue insert fails
    ests = iter([0.0, 50.0])
    monkeypatch.setattr(eng, "estimate_drain_s", lambda: next(ests, 50.0))
    with pytest.raises(QueueFull) as ei:
        eng.submit(_prompt(4), max_new_tokens=8, deadline_s=2.0)
    # never told to retry after its own deadline
    assert ei.value.retry_after_s == 2.0
    # without a deadline the raw estimate passes through
    with pytest.raises(QueueFull) as ei:
        eng.submit(_prompt(4), max_new_tokens=8)
    assert ei.value.retry_after_s == 50.0


# ---------------------------------------------------------------------------
# deadline propagation: client -> router hop -> serve() hop -> engine
# ---------------------------------------------------------------------------


def test_deadline_header_reaches_engine_submit(model, monkeypatch):
    srv, eng, url = _replica_server(model)
    seen = []
    orig = eng.submit

    def spy(*a, **kw):
        seen.append(kw.get("deadline_s"))
        return orig(*a, **kw)

    monkeypatch.setattr(eng, "submit", spy)
    try:
        status, body, _ = _post(
            url, {"input_ids": _prompt(4).tolist(), "max_new_tokens": 2},
            headers={"X-Deadline-Ms": "30000"},
        )
        assert status == 200
        assert seen and seen[0] == pytest.approx(30.0, abs=0.5)
    finally:
        _stop_server(srv)


def test_two_hop_deadline_propagation_shrinks_budget(model, monkeypatch):
    """client --X-Deadline-Ms--> router --X-Deadline-Ms(remaining)-->
    serve() --deadline_s--> engine.submit: each hop sees a strictly
    bounded, shrinking budget."""
    srv, eng, url = _replica_server(model)
    seen = []
    orig = eng.submit

    def spy(*a, **kw):
        seen.append(kw.get("deadline_s"))
        return orig(*a, **kw)

    monkeypatch.setattr(eng, "submit", spy)
    front = serve_router([url], port=0, block=False, probe=False)
    front.router.probe_once()
    fport = front.server_address[1]
    try:
        status, body, _ = _post(
            f"http://127.0.0.1:{fport}",
            {"input_ids": _prompt(4).tolist(), "max_new_tokens": 2},
            headers={"X-Deadline-Ms": "30000"},
        )
        assert status == 200
        # the engine saw the REMAINING budget: positive, below the
        # client's 30s by the router+serve hop overhead
        assert seen and 0 < seen[0] <= 30.0
        # body deadline_s is equivalent client syntax at the router
        seen.clear()
        status, _, _ = _post(
            f"http://127.0.0.1:{fport}",
            {"input_ids": _prompt(4).tolist(), "max_new_tokens": 2,
             "deadline_s": 25.0},
        )
        assert status == 200
        assert seen and 0 < seen[0] <= 25.0
    finally:
        front.stop_router()
        front.server_close()
        _stop_server(srv)


# ---------------------------------------------------------------------------
# circuit breaker: closed -> open -> half-open trial -> closed
# ---------------------------------------------------------------------------


def test_breaker_open_half_open_close_cycle():
    rep = Replica("r0", "http://127.0.0.1:9", breaker_threshold=3,
                  breaker_cooldown=0.05)
    assert rep.breaker == "closed" and rep.allow()
    rep.record_failure("x")
    rep.record_failure("x")
    assert rep.breaker == "closed"  # below threshold
    rep.record_failure("x")
    assert rep.breaker == "open"  # consecutive failures tripped it
    assert not rep.allow()  # open: traffic blocked during cooldown
    time.sleep(0.06)
    assert rep.allow()  # cooldown elapsed -> half-open, ONE trial
    assert rep.breaker == "half_open"
    assert not rep.allow()  # second caller blocked while the trial flies
    rep.record_failure("trial failed")
    assert rep.breaker == "open"  # failed trial re-opens
    time.sleep(0.06)
    assert rep.allow()
    rep.record_success(0.01)
    assert rep.breaker == "closed"  # successful trial closes
    assert rep.allow()
    g = prof.router_summary()
    # two trips: consecutive-failure open + the failed half-open trial
    assert g["breaker_trips"] == 2
    assert g["breaker_half_open"] == 2
    assert g["breaker_closes"] == 1


def test_breaker_half_open_race_single_transition():
    """ISSUE 16 satellite: a probe success and a request failure landing
    CONCURRENTLY on a half-open replica must serialize under the replica
    lock into coherent transitions — whichever order wins, the breaker
    ends closed (threshold 2: one stale failure after a close cannot
    re-trip), the half-open trial slot is released exactly once, and the
    close is counted exactly once."""
    for _ in range(30):
        prof.reset_router()
        rep = Replica("r0", "http://127.0.0.1:9", breaker_threshold=2,
                      breaker_cooldown=60.0)
        rep.record_failure("x")
        rep.record_failure("x")
        assert rep.breaker == "open"
        # explicit clock: past the cooldown -> half_open, trial in flight
        assert rep.allow(now=time.monotonic() + 61.0)
        assert rep.breaker == "half_open"
        barrier = threading.Barrier(2)

        def _probe_ok():
            barrier.wait()
            rep.record_success(0.01)

        def _request_fail():
            barrier.wait()
            rep.record_failure("concurrent request failure")

        threads = [threading.Thread(target=_probe_ok),
                   threading.Thread(target=_request_fail)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # either serialization ends closed: success-first absorbs the late
        # failure below threshold; failure-first re-opens then the success
        # closes.  A torn interleave (stuck trial, double transition,
        # half_open limbo) fails here.
        assert rep.breaker == "closed"
        assert rep._trial_inflight is False
        assert rep.allow()  # the trial slot was released, traffic flows
        g = prof.router_summary()
        assert g["breaker_closes"] == 1  # exactly one close transition
        assert g["breaker_trips"] in (1, 2)  # initial trip (+ failed trial)


def test_error_retry_after_zero_still_emits_header():
    """ISSUE 16 satellite: a truthy-zero retry_after (0 / 0.0, e.g. a
    deadline-clamped drain estimate) must still emit Retry-After with the
    >= 1s rounding — only None (no evidence) omits the header."""
    for zero in (0, 0.0):
        status, body, headers = Router._error(
            503, "RouterOverloaded", "gate full", True, retry_after=zero,
        )
        assert status == 503
        assert headers["Retry-After"] == "1"
        assert body["retry_after_s"] == 0
    # rounding is preserved for real estimates
    _, _, headers = Router._error(503, "x", "m", True, retry_after=2.6)
    assert headers["Retry-After"] == "3"
    # None still means "no header"
    _, _, headers = Router._error(504, "DeadlineExhausted", "m", False)
    assert "Retry-After" not in headers


def test_probe_flap_opens_breaker_then_recovers(model):
    srv, eng, url = _replica_server(model)
    router = Router([url], probe_interval=3600, retry_backoff=0.01)
    try:
        router.probe_once()
        assert router.replicas[0].state == "ready"
        finj.arm("router.replica.flap:3")
        for _ in range(3):
            router.probe_once()
        rep = router.replicas[0]
        assert rep.state == "down"
        assert rep.breaker == "open"
        assert router.pick() is None  # a flapping replica takes no traffic
        finj.disarm()
        router.probe_once()  # healthy probe recovers state AND breaker
        assert rep.state == "ready"
        assert rep.breaker == "closed"
        assert router.pick() is rep
    finally:
        router.stop()
        _stop_server(srv)


# ---------------------------------------------------------------------------
# failover: retry on another replica, exactly-once, bit-identical
# ---------------------------------------------------------------------------


def test_failover_retries_on_survivor_bit_identical(model):
    srv_a, eng_a, url_a = _replica_server(model)
    srv_b, eng_b, url_b = _replica_server(model)
    router = Router([url_a, url_b], probe_interval=3600, retry_backoff=0.01)
    try:
        router.probe_once()  # both ready; ties break toward index 0
        _stop_server(srv_a)  # replica A dies AFTER the probe marked it ready
        prompts = [_prompt(6, seed=i) for i in range(6)]
        for i, p in enumerate(prompts):
            status, body, _ = router.handle_generate(
                {"input_ids": p.tolist(), "max_new_tokens": 6}
            )
            # every request resolves exactly once, on the survivor, with
            # the exact tokens an undisturbed run produces
            assert status == 200, body
            assert np.array_equal(body["tokens"], _ref(model, p, 6))
        g = prof.router_summary()
        # the first breaker_threshold requests hit dead A then failed over;
        # once the breaker opened, B was picked directly
        assert g["retries"] >= 3
        assert g["failovers"] >= 3
        assert router.replicas[0].breaker == "open"
        assert g["requests"] == len(prompts)
    finally:
        router.stop()
        _stop_server(srv_b)


def test_failover_leaves_single_trace_with_aborted_hop(model):
    """ISSUE 10 drill: kill replica A between the probe and the request.
    The whole two-hop story — dead attempt AND surviving retry — must land
    under ONE trace id: an ``aborted`` replica.forward for A, an ``ok`` one
    for B with the survivor's serve.handle parented on it."""
    from paddle_tpu.obs import trace as obs_trace

    srv_a, eng_a, url_a = _replica_server(model)
    srv_b, eng_b, url_b = _replica_server(model)
    router = Router([url_a, url_b], probe_interval=3600, retry_backoff=0.01)
    paddle.set_flags({"FLAGS_trace": True})
    obs_trace.reset()
    try:
        router.probe_once()  # both ready; ties break toward index 0
        _stop_server(srv_a)  # A dies AFTER the probe marked it ready
        p = _prompt(6, seed=3)
        status, body, _ = router.handle_generate(
            {"input_ids": p.tolist(), "max_new_tokens": 4}
        )
        assert status == 200
        assert np.array_equal(body["tokens"], _ref(model, p, 4))

        # the surviving engine's own trace (its tick spans) is no request's
        tids = {s["trace_id"] for s in obs_trace.spans()} - {eng_b.trace_id}
        assert len(tids) == 1  # ONE trace spans the failure and the retry
        tid = tids.pop()
        fwd = [s for s in obs_trace.spans(tid)
               if s["name"] == "replica.forward"]
        assert [s["status"] for s in fwd] == ["aborted", "ok"]
        assert fwd[0]["attrs"]["replica"] == "r0"
        assert fwd[0]["attrs"]["error"]  # why the hop died
        assert fwd[1]["attrs"]["replica"] == "r1"
        assert fwd[1]["attrs"]["http_status"] == 200
        # the survivor's serve() hop joined the trace via X-Parent-Span,
        # parented on ITS forward attempt (not the aborted one)
        handles = [s for s in obs_trace.spans(tid)
                   if s["name"] == "serve.handle"]
        assert len(handles) == 1
        assert handles[0]["parent_id"] == fwd[1]["span_id"]
        # one admit root owns one pick per attempt
        admit = [s for s in obs_trace.spans(tid)
                 if s["name"] == "router.admit"]
        assert len(admit) == 1 and admit[0]["status"] == "ok"
        picks = [s for s in obs_trace.spans(tid)
                 if s["name"] == "router.pick"]
        assert len(picks) == 2
        assert all(s["parent_id"] == admit[0]["span_id"] for s in picks)
    finally:
        paddle.set_flags({"FLAGS_trace": False})
        obs_trace.reset()
        router.stop()
        _stop_server(srv_b)


def test_hedged_dispatch_wins_over_hung_replica(model):
    srv_a, eng_a, url_a = _replica_server(model)
    srv_b, eng_b, url_b = _replica_server(model)
    router = Router([url_a, url_b], probe_interval=3600,
                    retry_backoff=0.01, hedge_s=0.05)
    try:
        router.probe_once()
        # warm both replicas (first request pays the compile) so the wall
        # bound below measures routing, not tracing
        for u in (url_a, url_b):
            st, _, _ = _post(u, {"input_ids": [1, 2, 3], "max_new_tokens": 2})
            assert st == 200
        paddle.set_flags({"FLAGS_fault_hang_sec": 2.0})
        finj.arm("router.replica.hang:1")  # wedge the primary dispatch
        p = _prompt(6, seed=9)
        t0 = time.monotonic()
        status, body, _ = router.handle_generate(
            {"input_ids": p.tolist(), "max_new_tokens": 4}
        )
        wall = time.monotonic() - t0
        assert status == 200
        assert np.array_equal(body["tokens"], _ref(model, p, 4))
        assert wall < 2.0  # the hedge answered; the hang did not gate us
        g = prof.router_summary()
        assert g["hedges"] == 1
        assert g["hedge_wins"] == 1
    finally:
        router.stop()
        _stop_server(srv_a)
        _stop_server(srv_b)


# ---------------------------------------------------------------------------
# brownout: bounded admission + shed over-deadline work first
# ---------------------------------------------------------------------------


def test_admission_gate_full_sheds_with_retry_after(model):
    srv, eng, url = _replica_server(model)
    router = Router([url], probe_interval=3600, max_inflight=0)
    try:
        router.probe_once()
        status, body, headers = router.handle_generate(
            {"input_ids": [1, 2, 3]}
        )
        assert status == 503
        assert body["type"] == "RouterOverloaded"
        assert body["retriable"] is True
        assert prof.router_summary()["brownout_sheds"] == 1
    finally:
        router.stop()
        _stop_server(srv)


def test_brownout_sheds_over_deadline_work_first():
    # a replica whose advertised backlog already exceeds the deadline:
    # the router sheds without queueing (over-deadline work first), with
    # Retry-After surfaced from the healthiest replica's drain estimate
    rep = Replica("r0", "http://127.0.0.1:9")
    rep._note_healthz({
        "status": "ready", "queue_depth": 8, "active_slots": 2,
        "drain_estimate_s": 50.0,
    })
    router = Router([rep], probe_interval=3600)
    status, body, headers = router.handle_generate(
        {"input_ids": [1, 2, 3]}, deadline_ms=1000
    )
    assert status == 504
    assert body["type"] == "DeadlineUnattainable"
    assert body["retriable"] is False
    # the drain estimate, spread by the router's own ±25% herd jitter
    # (FLAGS_router_retry_after_jitter); the header is the body's float, rounded
    assert 37.5 <= body["retry_after_s"] <= 62.5
    assert int(headers["Retry-After"]) == int(body["retry_after_s"] + 0.5)
    assert prof.router_summary()["brownout_sheds"] == 1
    # the same fleet still accepts work with no deadline (it would need a
    # live endpoint to finish; shedding is deadline-driven, not global)
    status, body, _ = router.handle_generate({"input_ids": [1, 2, 3]})
    assert body["type"] != "DeadlineUnattainable"


def test_no_ready_replica_is_typed_503():
    rep = Replica("r0", "http://127.0.0.1:9")  # never probed ok: connecting
    router = Router([rep], probe_interval=3600)
    status, body, _ = router.handle_generate({"input_ids": [1]})
    assert status == 503
    assert body["type"] == "NoReadyReplica"
    assert body["retriable"] is True
    assert prof.router_summary()["no_replica"] == 1


# ---------------------------------------------------------------------------
# rolling drain/restart: zero dropped requests
# ---------------------------------------------------------------------------


def test_rolling_drain_zero_dropped_requests(model):
    srv_a, eng_a, url_a = _replica_server(model)
    srv_b, eng_b, url_b = _replica_server(model)
    router = Router([url_a, url_b], probe_interval=0.05, retry_backoff=0.01)
    restarted = []

    def _warm_restart(rep, grace):
        # in-process stand-in for the launch Container respawn: a warm
        # engine restart behind the same HTTP front
        eng = eng_a if rep.rid == "r0" else eng_b
        eng.restart()
        restarted.append(rep.rid)

    results = []
    results_mu = threading.Lock()
    stop = threading.Event()

    def _client(seed):
        i = 0
        while not stop.is_set():
            p = _prompt(6, seed=seed * 100 + i)
            status, body, _ = router.handle_generate(
                {"input_ids": p.tolist(), "max_new_tokens": 4}
            )
            with results_mu:
                results.append((p, status, body))
            i += 1
            time.sleep(0.02)  # bound the request count (each is verified)
        return i

    try:
        router.start()
        threads = [
            threading.Thread(target=_client, args=(s,), daemon=True)
            for s in range(3)
        ]
        for t in threads:
            t.start()
        time.sleep(0.3)  # steady load flowing before the upgrade starts
        report = router.rolling_restart(grace=10.0, ready_timeout=10.0,
                                        restart_fn=_warm_restart)
        time.sleep(0.3)  # load continues after the fleet upgrade
        stop.set()
        for t in threads:
            t.join(30)
        assert restarted == ["r0", "r1"]
        assert all(r["drained"] and r["ready"] for r in report)
        # ZERO dropped requests: every routed request during the rolling
        # upgrade resolved 200 with the exact undisturbed-run tokens
        assert len(results) > 0
        for p, status, body in results:
            assert status == 200, body
            assert np.array_equal(body["tokens"], _ref(model, p, 4))
        # both replicas re-admitted and serving
        assert {r.state for r in router.replicas} == {"ready"}
    finally:
        stop.set()
        router.stop()
        _stop_server(srv_a)
        _stop_server(srv_b)


# ---------------------------------------------------------------------------
# router gauges surface in profiler.summary()
# ---------------------------------------------------------------------------


def test_router_gauges_in_profiler_summary(model, capsys):
    srv, eng, url = _replica_server(model)
    router = Router([url], probe_interval=3600)
    try:
        router.probe_once()
        p = _prompt(4)
        status, _, _ = router.handle_generate(
            {"input_ids": p.tolist(), "max_new_tokens": 2}
        )
        assert status == 200
        prof.Profiler().summary()
        out = capsys.readouterr().out
        assert "router:" in out
        assert "breaker trips" in out
        assert "r0=ready" in out
        g = prof.router_summary()
        assert g["requests"] == 1
        assert g["replica_states"] == {"r0": "ready"}
    finally:
        router.stop()
        _stop_server(srv)


# ---------------------------------------------------------------------------
# chaos drill (slow): kill -9 one subprocess replica under Poisson load
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_kill9_chaos_drill_exactly_once(model, tmp_path, monkeypatch):
    """Two router-managed subprocess replicas (launch Container topology).
    Under Poisson load, the injected router.replica.kill SIGKILLs one
    replica.  Every submitted request must resolve exactly once — retried
    on the survivor or failed typed — and every 200 must be bit-identical
    to an undisturbed run.  Afterwards a rolling restart revives the dead
    replica through the Container respawn path and the fleet is whole.

    ISSUE 10 rides the drill: tracing is on in every process, so the kill
    must leave a single trace joining the dead hop to its surviving retry,
    and the SIGTERM drains plus the breaker transition must land in
    flight-recorder dumps under $PADDLE_OBS_DIR."""
    from paddle_tpu.obs import flight, trace as obs_trace

    obs_dir = tmp_path / "flightrec"
    monkeypatch.setenv("PADDLE_OBS_DIR", str(obs_dir))
    monkeypatch.setenv("PADDLE_TRACE", "1")  # subprocess replicas inherit
    paddle.set_flags({"FLAGS_trace": True})
    obs_trace.reset()
    flight.reset()
    procs = [
        ReplicaProcess(i, _free_port(), log_dir=str(tmp_path / "logs")).start()
        for i in range(2)
    ]
    reps = [
        Replica(f"r{i}", rp.url, process=rp) for i, rp in enumerate(procs)
    ]
    router = Router(reps, probe_interval=0.1, retry_backoff=0.02)
    try:
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            router.probe_once()
            if all(r.state == "ready" for r in reps):
                break
            time.sleep(0.5)
        assert all(r.state == "ready" for r in reps), "replicas never booted"
        router.start()

        n_requests = 24
        results = []
        results_mu = threading.Lock()
        rng = np.random.RandomState(7)

        def _load():
            for i in range(n_requests):
                time.sleep(float(rng.exponential(0.05)))  # Poisson arrivals
                p = _prompt(6, seed=1000 + i)
                status, body, _ = router.handle_generate(
                    {"input_ids": p.tolist(), "max_new_tokens": 4}
                )
                with results_mu:
                    results.append((p, status, body))

        threads = [threading.Thread(target=_load, daemon=True) for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.3)  # load in flight...
        finj.arm("router.replica.kill:1")  # ...then SIGKILL one replica
        for t in threads:
            t.join(300)
        assert not any(t.is_alive() for t in threads)

        # exactly once: one resolution per submitted request
        assert len(results) == 2 * n_requests
        ok = typed = 0
        for p, status, body in results:
            if status == 200:
                ok += 1
                # the survivor's greedy output is bit-identical to an
                # undisturbed run (same seed -> same weights everywhere)
                assert np.array_equal(body["tokens"], _ref(model, p, 4))
            else:
                typed += 1
                assert body.get("type"), body  # failed TYPED, never silent
        assert ok >= len(results) - 4  # zero-token retries recover the rest
        killed = [rp for rp in procs if not rp.alive()]
        assert len(killed) == 1  # the fault killed exactly one replica

        # freeze probing and replay the production race the trace exists to
        # explain: the router acts on STALE health state — it still believes
        # the SIGKILLed replica is ready — so one request must leave BOTH
        # hops in one trace: the aborted forward and the survivor's retry
        router.stop()
        dead_rep = next(r for r in reps if not r.process.alive())
        live_rep = next(r for r in reps if r.process.alive())
        dead_rep._note_healthz({"status": "ready", "queue_depth": 0,
                                "active_slots": 0, "drain_estimate_s": 0.0})
        live_rep._note_healthz({"status": "ready", "queue_depth": 1,
                                "active_slots": 1, "drain_estimate_s": 0.5})
        p = _prompt(6, seed=55)
        status, body, _ = router.handle_generate(
            {"input_ids": p.tolist(), "max_new_tokens": 4}
        )
        assert status == 200
        assert np.array_equal(body["tokens"], _ref(model, p, 4))
        by_tid = {}
        for s in obs_trace.spans():
            if s["name"] == "replica.forward":
                by_tid.setdefault(s["trace_id"], []).append(s)
        joined = [
            hops for hops in by_tid.values()
            if any(h["status"] == "aborted" for h in hops)
            and any(h["status"] == "ok" for h in hops)
        ]
        assert joined, "no trace joins the dead hop to its surviving retry"
        hops = joined[-1]  # the stale-state request is the newest
        dead = next(h for h in hops if h["status"] == "aborted")
        live = next(h for h in hops if h["status"] == "ok")
        assert dead["attrs"]["replica"] == dead_rep.rid
        assert live["attrs"]["replica"] == live_rep.rid

        # the breaker transition reached the flight ring; a post-mortem
        # dump carries it (one JSON object per line, header first)
        dump_path = flight.dump("chaos-drill")
        assert dump_path and str(obs_dir) in dump_path
        with open(dump_path) as f:
            lines = [json.loads(ln) for ln in f]
        assert lines[0]["kind"] == "header"
        assert lines[0]["reason"] == "chaos-drill"
        assert any(
            e.get("kind") == "breaker" and "open" in e.get("detail", "")
            for e in lines[1:]
        ), "flight dump is missing the breaker transition"

        # rolling restart revives the dead replica via Container respawn
        # and re-admits it only after /healthz reports ready
        report = router.rolling_restart(grace=10.0, ready_timeout=180.0)
        assert all(r["ready"] for r in report), report
        assert all(rp.alive() for rp in procs)
        p = _prompt(6, seed=77)
        status, body, _ = router.handle_generate(
            {"input_ids": p.tolist(), "max_new_tokens": 4}
        )
        assert status == 200
        assert np.array_equal(body["tokens"], _ref(model, p, 4))

        # the rolling restart's SIGTERM drain dumped the survivor's flight
        # ring into $PADDLE_OBS_DIR from inside the subprocess
        drains = [p_ for p_ in obs_dir.iterdir() if "serve-drain" in p_.name]
        assert drains, "SIGTERM drain left no flight-recorder dump"
    finally:
        paddle.set_flags({"FLAGS_trace": False})
        obs_trace.reset()
        flight.reset()
        router.stop()
        for rp in procs:
            rp.terminate()


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# chaos drill (slow): kill -9 under MIXED-ADAPTER load (ISSUE 12)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_kill9_chaos_drill_mixed_adapters(model, tmp_path):
    """ISSUE 12 rides the kill -9 drill: both subprocess replicas boot with
    ``--lora a1,a2,a3,a4`` (identical spec string -> position-seeded,
    bit-identical adapter weights fleet-wide), the Poisson load cycles the
    four tenants, and the injected SIGKILL takes one replica mid-stream.
    Every request resolves exactly once; every 200 is bit-identical to a
    single-process LoRA engine serving the same tenant (the failover
    contract extends to adapter outputs); after the kill the survivor
    advertises its resident tenants through /healthz so adapter-aware
    ``pick()`` keeps scoring residency; an unknown tenant fails typed —
    404 AdapterUnknown, retriable=false, no retry storm."""
    from paddle_tpu.lora import AdapterArena, AdapterRegistry, make_random

    adapters = ["a1", "a2", "a3", "a4"]

    # single-process reference engine: the same registration order + seeds
    # the workers derive from the identical --lora string
    reg = AdapterRegistry(model.config)
    for i, name in enumerate(adapters):
        make_random(reg, name, rank=4, seed=i + 1)
    ref_eng = ContinuousBatchingEngine(
        model, slots=2, max_len=64, prefill_buckets=[8, 16], queue_depth=32,
        seed=0, page_size=8, lora=AdapterArena(reg),
    )
    n_requests = 16
    refs = []
    for i in range(n_requests):
        p = _prompt(6, seed=1000 + i)
        refs.append(ref_eng.generate(p, max_new_tokens=4,
                                     adapter=adapters[i % len(adapters)]))

    procs = [
        ReplicaProcess(i, _free_port(), log_dir=str(tmp_path / "logs"),
                       extra_args=("--lora", ",".join(adapters))).start()
        for i in range(2)
    ]
    reps = [Replica(f"r{i}", rp.url, process=rp) for i, rp in enumerate(procs)]
    router = Router(reps, probe_interval=0.1, retry_backoff=0.02)
    try:
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            router.probe_once()
            if all(r.state == "ready" for r in reps):
                break
            time.sleep(0.5)
        assert all(r.state == "ready" for r in reps), "replicas never booted"
        router.start()

        results = []
        results_mu = threading.Lock()
        rng = np.random.RandomState(7)

        def _load():
            for i in range(n_requests):
                time.sleep(float(rng.exponential(0.05)))  # Poisson arrivals
                p = _prompt(6, seed=1000 + i)
                status, body, _ = router.handle_generate(
                    {"input_ids": p.tolist(), "max_new_tokens": 4,
                     "adapter": adapters[i % len(adapters)]}
                )
                with results_mu:
                    results.append((i, status, body))

        threads = [threading.Thread(target=_load, daemon=True) for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.3)  # mixed-tenant load in flight...
        finj.arm("router.replica.kill:1")  # ...then SIGKILL one replica
        for t in threads:
            t.join(300)
        assert not any(t.is_alive() for t in threads)

        # exactly once: one resolution per submitted request
        assert len(results) == 2 * n_requests
        ok = 0
        for i, status, body in results:
            if status == 200:
                ok += 1
                # whichever replica answered, the tenant's greedy output is
                # bit-identical to the single-process LoRA reference
                assert np.array_equal(body["tokens"], refs[i]), (i, body)
            else:
                assert body.get("type"), body  # failed TYPED, never silent
        assert ok >= len(results) - 4  # zero-token retries recover the rest
        killed = [rp for rp in procs if not rp.alive()]
        assert len(killed) == 1  # the fault killed exactly one replica

        # the survivor's /healthz advertises its resident tenants; the
        # router snapshot carries them and adapter-aware pick() scores them
        router.stop()
        router.probe_once()
        survivor = next(r for r in reps if r.process.alive())
        resident = set(survivor.snapshot()["lora_adapters"])
        assert resident & set(adapters), resident
        target = sorted(resident & set(adapters))[0]
        assert router.pick(adapter=target).rid == survivor.rid

        # unknown tenant: typed 404 straight through the router — the
        # retriable=false field stops the failover loop (no retry storm)
        p = _prompt(6, seed=55)
        status, body, _ = router.handle_generate(
            {"input_ids": p.tolist(), "max_new_tokens": 2, "adapter": "ghost"}
        )
        assert status == 404
        assert body["type"] == "AdapterUnknown"
        assert body["retriable"] is False

        # after the drill a known tenant still answers bit-identically
        p0 = _prompt(6, seed=1000)
        status, body, _ = router.handle_generate(
            {"input_ids": p0.tolist(), "max_new_tokens": 4,
             "adapter": adapters[0]}
        )
        assert status == 200
        assert np.array_equal(body["tokens"], refs[0])
    finally:
        router.stop()
        for rp in procs:
            rp.terminate()
