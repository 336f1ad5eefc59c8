"""Observability surface (ISSUE 10): distributed request tracing, the
Prometheus /metrics exposition, and the crash flight recorder.

Runs under the runtime sanitizer (conftest _SANITIZED_MODULES): tracing is
pure host-side bookkeeping, so any recompile or host sync it introduced
inside a steady-state zone would fail these tests directly.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler as prof
from paddle_tpu.fault import injection as finj
from paddle_tpu.inference import serve
from paddle_tpu.inference.engine import ContinuousBatchingEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.obs import flight, metrics, trace
from paddle_tpu.serving import serve_router


@pytest.fixture(scope="module")
def model():
    np.random.seed(1234)
    return LlamaForCausalLM(LlamaConfig.tiny())


@pytest.fixture(autouse=True)
def _traced():
    """Span recording on, both ring buffers clean, flags restored."""
    paddle.set_flags({"FLAGS_trace": True})
    trace.reset()
    flight.reset()
    prof.reset()
    yield
    paddle.set_flags({
        "FLAGS_trace": False,
        "FLAGS_obs_buffer_events": 4096,
    })
    trace.reset()
    flight.reset()
    finj.disarm()


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 250, size=n).astype(np.int32)


def _replica_server(model, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8])
    kw.setdefault("queue_depth", 16)
    kw.setdefault("seed", 0)
    eng = ContinuousBatchingEngine(model, **kw)
    srv = serve(eng, port=0, block=False, supervise=False, handle_signals=False)
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


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


# ---------------------------------------------------------------------------
# trace core: flag gating, bounded buffer, tree/export shape
# ---------------------------------------------------------------------------


def test_recording_gated_on_flag_minting_always_on():
    paddle.set_flags({"FLAGS_trace": False})
    t0 = time.perf_counter()
    sid = trace.record("x", trace.new_trace_id(), t0=t0, t1=t0 + 0.001)
    assert sid == ""  # no-op without the flag...
    assert trace.stats()["spans_recorded"] == 0
    assert len(trace.new_trace_id()) == 16  # ...but ids still mint
    paddle.set_flags({"FLAGS_trace": True})
    tid = trace.new_trace_id()
    trace.record("x", tid, t0=t0, t1=t0 + 0.001)
    assert trace.stats()["spans_recorded"] == 1
    assert trace.spans(tid)[0]["dur_s"] == pytest.approx(0.001)


def test_span_buffer_bounded_by_flag():
    paddle.set_flags({"FLAGS_obs_buffer_events": 32})
    tid = trace.new_trace_id()
    t0 = time.perf_counter()
    for i in range(100):
        trace.record("tick", tid, t0=t0, t1=t0, i=i)
    s = trace.stats()
    assert s["spans_buffered"] == 32  # ring capacity holds
    assert s["spans_recorded"] == 100
    assert s["spans_dropped"] == 100 - 32
    # oldest evicted, newest kept
    assert trace.spans(tid)[-1]["attrs"]["i"] == 99


def test_span_context_manager_marks_errors():
    tid = trace.new_trace_id()
    with pytest.raises(ValueError):
        with trace.span("outer", tid) as s:
            with trace.span("inner", tid, parent_id=s.span_id):
                pass
            raise ValueError("boom")
    roots = trace.tree(tid)
    assert [r["name"] for r in roots] == ["outer"]
    assert roots[0]["status"] == "error"
    assert [c["name"] for c in roots[0]["children"]] == ["inner"]
    assert roots[0]["children"][0]["status"] == "ok"
    ev = trace.chrome_trace(tid)["traceEvents"]
    assert {e["name"] for e in ev} == {"outer", "inner"}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in ev)


# ---------------------------------------------------------------------------
# serve(): hop headers in, span tree + X-Trace-Id out, /trace round trip
# ---------------------------------------------------------------------------


def test_serve_trace_http_round_trip(model):
    srv, eng, url = _replica_server(model)
    try:
        tid = trace.new_trace_id()
        status, body, headers = _post(
            url, {"input_ids": _prompt(6).tolist(), "max_new_tokens": 3},
            headers={"X-Trace-Id": tid, "X-Parent-Span": "c" * 16},
        )
        assert status == 200
        assert headers["X-Trace-Id"] == tid  # the hop echoes the trace id
        code, text, _ = _get(url + f"/trace/{tid}")
        assert code == 200
        doc = json.loads(text)
        assert doc["trace_id"] == tid
        (handle,) = doc["spans"]  # one root: the serve.handle span
        assert handle["name"] == "serve.handle"
        assert handle["parent_id"] == "c" * 16
        names = [c["name"] for c in handle["children"]]
        # engine stages parent on the pre-minted handle span id
        assert names[:2] == ["engine.queue", "engine.prefill"]
        assert "engine.decode" in names and "engine.fetch" in names
        code, text, _ = _get(url + "/trace/deadbeefdeadbeef")
        assert code == 404
    finally:
        _stop_server(srv)


def test_serve_error_body_carries_trace_id(model):
    srv, eng, url = _replica_server(model)
    try:
        tid = trace.new_trace_id()
        status, body, headers = _post(
            url, {"input_ids": [1, 2, 3]},
            headers={"X-Trace-Id": tid, "X-Deadline-Ms": "0"},
        )
        assert status == 504
        assert body["type"] == "DeadlineExceeded"
        assert body["trace_id"] == tid  # a 504 joins its span tree
        assert headers["X-Trace-Id"] == tid
        # without a client header the replica mints its own root id
        status, body, _ = _post(
            url, {"input_ids": [1, 2, 3]}, headers={"X-Deadline-Ms": "0"}
        )
        assert len(body["trace_id"]) == 16 and body["trace_id"] != tid
    finally:
        _stop_server(srv)


# ---------------------------------------------------------------------------
# /metrics: Prometheus text exposition with stable names
# ---------------------------------------------------------------------------

STABLE_METRICS = (
    "paddle_serving_requests_total",
    "paddle_serving_tokens_total",
    "paddle_serving_ttft_seconds",
    "paddle_paging_prefix_hits_total",
    "paddle_router_requests_total",
    "paddle_router_breaker_trips_total",
    "paddle_train_steps_total",
    "paddle_sanitizer_unexpected_traces_total",
    "paddle_obs_spans_recorded_total",
    "paddle_flight_events_total",
)


def test_metrics_scrape_stable_names_and_format(model):
    srv, eng, url = _replica_server(model)
    try:
        status, _, _ = _post(
            url, {"input_ids": _prompt(6).tolist(), "max_new_tokens": 3}
        )
        assert status == 200
        code, text, headers = _get(url + "/metrics")
        assert code == 200
        assert headers["Content-Type"].startswith("text/plain")
        samples = {}
        for line in text.splitlines():
            if line.startswith("#"):
                continue  # HELP/TYPE lines
            name_labels, val = line.rsplit(" ", 1)
            float(val)  # every sample value parses
            samples[name_labels] = float(val)
        # stable names: renames break dashboards, so they break this test
        for m in STABLE_METRICS:
            assert any(k.startswith(m) for k in samples), m
        port = srv.server_address[1]
        req_key = (
            f'paddle_serving_requests_total{{replica="127.0.0.1:{port}"}}'
        )
        assert samples[req_key] >= 1.0
        # zero-valued counters are exported, never omitted
        assert any(
            k.startswith("paddle_router_breaker_trips_total") and v == 0.0
            for k, v in samples.items()
        )
    finally:
        _stop_server(srv)


def test_router_metrics_endpoint_has_role_label(model):
    srv, eng, url = _replica_server(model)
    front = serve_router([url], port=0, block=False, probe=False)
    front.router.probe_once()
    fURL = f"http://127.0.0.1:{front.server_address[1]}"
    try:
        status, _, _ = _post(
            fURL, {"input_ids": _prompt(6).tolist(), "max_new_tokens": 2}
        )
        assert status == 200
        code, text, _ = _get(fURL + "/metrics")
        assert code == 200
        line = next(
            l for l in text.splitlines()
            if l.startswith("paddle_router_requests_total{")
        )
        assert 'role="router"' in line
        assert line.endswith(" 1")
        # the router-side span tree is also served on the front door
        # the request's trace: the engine's own (its tick spans) is newer
        tid = [t for t in trace.trace_ids() if t != eng.trace_id][-1]
        code, text, _ = _get(fURL + f"/trace/{tid}")
        assert code == 200
        names = [s["name"] for s in json.loads(text)["spans"]]
        assert "router.admit" in names
    finally:
        front.stop_router()
        front.server_close()
        _stop_server(srv)


def test_metrics_render_offline_includes_trace_and_flight_counters():
    tid = trace.new_trace_id()
    t0 = time.perf_counter()
    trace.record("x", tid, t0=t0, t1=t0)
    flight.record("unit", "event")
    text = metrics.render(labels={"replica": "unit"})
    assert 'paddle_obs_spans_recorded_total{replica="unit"} 1' in text
    assert 'paddle_flight_events_total{replica="unit"}' in text
    assert "# HELP" in text and "# TYPE" in text


# ---------------------------------------------------------------------------
# profiler.reset(): every counter family zeroed in one shot
# ---------------------------------------------------------------------------


def test_profiler_reset_zeroes_every_family():
    prof.record_step(dispatch_s=0.1, host_blocked_s=0.0, inflight=1, wall_s=0.1)
    prof.record_serving_request(ttft_s=0.01, tokens=4, wall_s=0.1)
    prof.record_paging_event("prefix_hits")
    prof.record_router_event("requests")
    prof.record_router_replica_state("r0", "ready")
    prof.record_flash_fallback("unit")
    snap = prof.metrics_snapshot()
    assert snap["step"]["steps"] == 1 and snap["router"]["requests"] == 1
    prof.reset()
    snap = prof.metrics_snapshot()
    assert snap["step"]["steps"] == 0
    assert snap["serving"]["requests"] == 0 and snap["serving"]["ttfts_s"] == []
    assert snap["paging"]["prefix_hits"] == 0
    assert snap["router"]["requests"] == 0
    assert snap["router"]["replica_states"] == {}
    assert snap["flash_fallbacks"] == {}


def test_serving_summary_names_membership_changes_and_drains_by_cause():
    """The two fields by which a benchmark's counters say whether a change
    of membership cost the device a drain: every cause rendered, at 0 too,
    and both zeroed by reset_serving()."""
    causes = ("eos_watch", "length", "admission", "first_token", "evict",
              "rebuild", "stop", "poison")
    prof.reset_serving()
    s = prof.serving_summary()
    assert s["membership_changes"] == 0
    assert s["drains"] == dict.fromkeys(causes, 0)
    prof.record_membership_change()
    prof.record_membership_change()
    prof.record_serving_drain("evict")
    s = prof.serving_summary()
    assert s["membership_changes"] == 2
    assert s["drains"] == dict(dict.fromkeys(causes, 0), evict=1)
    assert prof.metrics_snapshot()["serving"]["drains"] == {"evict": 1}
    prof.reset_serving()
    s = prof.serving_summary()
    assert (s["membership_changes"], sum(s["drains"].values())) == (0, 0)


# ---------------------------------------------------------------------------
# the scheduler's tick by phase (ISSUE 37): always-on counters in
# serving_summary()["tick"], one engine.tick.<phase> span a phase a tick under
# FLAGS_trace, engine.fetch folded to one an epoch
# ---------------------------------------------------------------------------

PHASES = ("evict", "admit", "prepare", "dispatch", "wait", "deliver", "other")


def _engine(model, **kw):
    kw = {"slots": 4, "max_len": 64, "prefill_buckets": [8],
          "queue_depth": 16, "seed": 0, **kw}
    return ContinuousBatchingEngine(model, **kw)


def _drive(eng):
    """step() until idle, synchronously; the number of ticks it took."""
    n = 0
    while eng.has_work():
        eng.step()
        n += 1
    return n


def _submit(eng, n_new, seed=0, on_token=lambda tok: None, **kw):
    return eng.submit(_prompt(6, seed), max_new_tokens=n_new,
                      on_token=on_token, trace=(trace.new_trace_id(), ""), **kw)


def test_tick_phases_are_disjoint_and_add_up_to_the_ticks_wall_time(model):
    eng = _engine(model)
    for i, n in enumerate((5, 9, 12)):
        _submit(eng, n, seed=i)
    t_before = time.perf_counter()
    ticks = _drive(eng)
    t_after = time.perf_counter()
    tick = prof.serving_summary()["tick"]
    assert tuple(tick["phases_s"]) == PHASES
    assert all(v >= 0.0 for v in tick["phases_s"].values())  # `other` too
    assert sum(tick["phases_s"].values()) == pytest.approx(tick["wall_s"], rel=1e-6)
    assert 0.0 < tick["wall_s"] <= t_after - t_before
    # every step() counts in the wall time; `steps` only those that dispatched
    # (the last tick delivers the last token and dispatches nothing)
    raw = prof.metrics_snapshot()["serving"]
    assert tick["steps"] == raw["ticks"] == 11 < ticks
    assert raw["tick"] == tick
    assert tick["host_s"] == pytest.approx(tick["wall_s"] - tick["phases_s"]["wait"])
    assert tick["host_ms_mean"] == pytest.approx(1e3 * tick["host_s"] / tick["steps"])
    assert tick["wait_share"] == pytest.approx(tick["phases_s"]["wait"] / tick["wall_s"])
    assert tick["phases_s"]["wait"] > 0.0 and tick["phases_s"]["admit"] > 0.0
    longest = tick["longest"]
    assert len(longest) == min(8, ticks)
    assert [t["ms"] for t in longest] == sorted((t["ms"] for t in longest), reverse=True)
    for t in longest:
        assert tuple(t["phases_ms"]) == PHASES
        assert sum(t["phases_ms"].values()) == pytest.approx(t["ms"], rel=1e-6)
        assert t_before < t["at_s"] <= t_after  # perf_counter at the tick's end
    # the first tick compiles: the prefill in `admit`, the step in `dispatch`
    assert max(longest[0]["phases_ms"], key=longest[0]["phases_ms"].get) in ("admit", "dispatch")


def test_a_slow_on_token_moves_deliver_and_nothing_else(model):
    """A client whose callback sleeps 5 ms costs `deliver` 5 ms a token; the
    host's other phases and the wait on the device stay where they were."""
    eng = _engine(model)
    _submit(eng, 4)
    _drive(eng)  # every program compiled

    def run(on_token):
        prof.reset_serving()
        for i in range(2):
            _submit(eng, 16, seed=i, on_token=on_token)
        _drive(eng)
        return prof.serving_summary()["tick"]["phases_s"]

    quick = run(lambda tok: None)
    slow = run(lambda tok: time.sleep(0.005))
    slept = 0.005 * 32
    assert slow["deliver"] - quick["deliver"] >= slept
    for phase in ("wait", "prepare", "dispatch", "evict"):
        assert abs(slow[phase] - quick[phase]) < 0.25 * slept, phase


def test_a_flush_inside_an_eviction_is_wait_and_deliver_not_evict(model):
    """A cancelled request is evicted after what was dispatched is flushed:
    the flush's fetch and its callbacks go to `wait` and `deliver` and come
    out of `evict`, the phase that enclosed them."""
    eng = _engine(model)
    victim = _submit(eng, 30)
    _submit(eng, 30, seed=1, on_token=lambda tok: time.sleep(0.02))
    for _ in range(4):
        eng.step()
    victim.cancel()
    prof.reset_serving()
    eng.step()  # this tick evicts: its flush delivers the step in flight
    s = prof.serving_summary()
    assert s["drains"]["evict"] == 1 and victim.finished.is_set()
    phases = s["tick"]["phases_s"]
    assert phases["deliver"] >= 0.02  # the other stream's callback slept in it
    assert phases["wait"] > 0.0
    assert phases["evict"] < 0.01
    assert sum(phases.values()) == pytest.approx(s["tick"]["wall_s"], rel=1e-6)
    eng.stop()


def test_reset_serving_clears_the_tick(model):
    eng = _engine(model)
    _submit(eng, 4)
    _drive(eng)
    assert prof.serving_summary()["tick"]["steps"] == 3
    prof.reset_serving()
    for tick in (prof.serving_summary()["tick"], prof.metrics_snapshot()["serving"]["tick"]):
        assert tick == {
            "steps": 0, "wall_s": 0.0, "phases_s": dict.fromkeys(PHASES, 0.0),
            "host_s": 0.0, "host_ms_mean": None, "wait_share": None, "longest": [],
        }


def test_tick_spans_exist_only_under_the_flag_and_under_the_engines_trace(model):
    eng = _engine(model)
    paddle.set_flags({"FLAGS_trace": False})
    _submit(eng, 6)
    _drive(eng)
    assert trace.spans() == []  # the counters ran all the same
    assert prof.serving_summary()["tick"]["steps"] == 5
    paddle.set_flags({"FLAGS_trace": True})
    prof.reset_serving()
    reqs = [_submit(eng, n, seed=n) for n in (6, 10)]
    ticks = _drive(eng)
    tick = prof.serving_summary()["tick"]
    own = trace.spans(eng.trace_id)
    assert own and {s["name"] for s in trace.spans()
                    if s["name"].startswith("engine.tick.")} == {s["name"] for s in own}
    assert {s["name"] for s in own} <= {"engine.tick." + p for p in PHASES[:6]}
    assert all(s["parent_id"] == "" for s in own)  # siblings: no span encloses a tick
    by_name = {}
    for s in own:
        by_name.setdefault(s["name"][len("engine.tick."):], []).append(s)
    # exactly one dispatch span for each tick that dispatched, each its own tick
    dispatched = [s["attrs"]["tick"] for s in by_name["dispatch"]]
    assert len(dispatched) == len(set(dispatched)) == tick["steps"] == 9
    assert {s["attrs"]["live"] for s in by_name["dispatch"]} == {1, 2}
    per_tick = {}
    for s in own:
        per_tick.setdefault(s["attrs"]["tick"], []).append(s["name"])
    assert len(per_tick) == ticks
    assert all(len(names) == len(set(names)) <= 6 for names in per_tick.values())
    # one admission tick, no eviction: those two phases record only their work
    assert len(by_name["admit"]) == 1 and "evict" not in by_name
    # from the same stamps as the counters
    for phase in ("prepare", "dispatch", "wait", "deliver"):
        assert sum(s["dur_s"] for s in by_name[phase]) == pytest.approx(
            tick["phases_s"][phase], rel=1e-4)
    for r in reqs:  # a request's tree holds its own stages and no tick
        names = [s["name"] for s in trace.spans(r.trace[0])]
        assert not any(n.startswith("engine.tick.") for n in names)
        assert {"engine.queue", "engine.prefill", "engine.decode", "engine.fetch"} <= set(names)


def test_600_streamed_tokens_fit_the_default_ring_with_one_fetch_an_epoch():
    """What used to be a record a request a tick (4,800 here, the ring's
    4,096 lost the head) is one engine.fetch beside each engine.decode."""
    np.random.seed(4321)
    long_model = LlamaForCausalLM(LlamaConfig.tiny(max_position_embeddings=1024))
    eng = _engine(long_model, slots=8, max_len=640)
    first = _submit(eng, 600)
    others = [_submit(eng, 40 + 60 * i, seed=i) for i in range(1, 8)]
    ticks = _drive(eng)
    assert len(first.tokens) == 600 and all(o.finished.is_set() for o in others)
    assert ticks == 600  # the prefill's token, then 599 steps a tick behind
    st = trace.stats()
    assert st["spans_dropped"] == 0 and st["spans_recorded"] < 4096
    tree = trace.spans(first.trace[0])
    decode = [s for s in tree if s["name"] == "engine.decode"]
    fetch = [s for s in tree if s["name"] == "engine.fetch"]
    # one beside each epoch's engine.decode: an epoch a finish of the others
    assert len(fetch) == len(decode) == 8
    assert sum(s["attrs"]["fetches"] for s in fetch) == ticks
    assert sum(s["attrs"]["steps"] for s in fetch) >= ticks
    assert all(s["attrs"]["wait_s"] >= 0.0 for s in fetch)
    assert sum(s["attrs"]["ticks"] for s in decode) <= ticks
    assert len(trace.spans(eng.trace_id)) <= 6 * ticks


def test_speculative_ticks_are_clocked_by_phase_too(model):
    eng = _engine(model, spec_k=3)
    r = _submit(eng, 12)
    _drive(eng)
    tick = prof.serving_summary()["tick"]
    assert tick["steps"] == prof.metrics_snapshot()["speculation"]["steps"] > 0
    assert sum(tick["phases_s"].values()) == pytest.approx(tick["wall_s"], rel=1e-6)
    assert min(tick["phases_s"][p] for p in ("prepare", "dispatch", "wait", "deliver")) > 0.0
    names = {s["name"] for s in trace.spans(r.trace[0])}
    assert {"engine.decode", "engine.verify", "engine.fetch"} <= names
    waits = [s for s in trace.spans(eng.trace_id) if s["name"] == "engine.tick.wait"]
    assert len(waits) >= tick["steps"]  # the verify fetch blocks in every round


def test_metrics_render_all_seven_tick_phases_before_any_traffic():
    prof.reset()
    text = metrics.render(labels={"replica": "unit"})
    for phase in PHASES:
        assert (f'paddle_serving_tick_phase_seconds_total{{phase="{phase}",'
                f'replica="unit"}} 0') in text.splitlines()
    assert "paddle_serving_busy_seconds_total" in text  # stays beside it


def test_span_ids_are_16_hex_unique_and_a_fork_draws_anew():
    ids = [trace.new_span_id() for _ in range(20000)] + [trace.new_trace_id()]
    assert len(set(ids)) == len(ids)
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)
    assert len({i[:8] for i in ids}) == 1  # the process's prefix, a counter behind it
    trace._seed_ids()  # what a forked child runs
    assert trace.new_span_id()[:8] != ids[0][:8]


# ---------------------------------------------------------------------------
# flight recorder: fault-event mirror, watchdog gauge, dump format
# ---------------------------------------------------------------------------


def test_flight_mirrors_fault_events_and_dumps_jsonl(tmp_path):
    dumps_before = flight.stats()["dumps_total"]  # monotonic across reset()
    finj.record_event("unit", "mirrored into the ring")
    flight.record("breaker", "r9 -> open: unit", fails=3)
    flight.note_arm("serve.decode", "tick 7")
    kinds = [e["kind"] for e in flight.events()]
    assert "unit" in kinds and "breaker" in kinds
    assert "serve.decode" not in kinds  # arms are a gauge, not ring events
    path = flight.dump("unit-test", path=str(tmp_path / "f.jsonl"))
    lines = [json.loads(l) for l in open(path)]
    header, events = lines[0], lines[1:]
    assert header["kind"] == "header"
    assert header["reason"] == "unit-test"
    assert header["armed"]["serve.decode"]["context"] == "tick 7"
    assert any(e["kind"] == "breaker" and e.get("fails") == 3
               for e in events)
    assert flight.stats()["dumps_total"] == dumps_before + 1
    assert flight.last_dump_path() == path


def test_flight_dump_on_engine_supervisor_restart(model, tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_OBS_DIR", str(tmp_path))
    from paddle_tpu.fault import EngineSupervisor

    eng = ContinuousBatchingEngine(
        model, slots=2, max_len=64, prefill_buckets=[8], queue_depth=4, seed=0
    )
    eng.start()
    try:
        sup = EngineSupervisor(eng, max_restarts=2, backoff=0.0)
        assert sup.restart("unit drill") is True
        dumps = sorted(tmp_path.glob("flight-*.jsonl"))
        assert dumps, "supervisor restart left no flight dump"
        header = json.loads(dumps[-1].read_text().splitlines()[0])
        assert header["reason"] == "engine-restart-1"
        # the engine restart event itself flowed through the injection
        # mirror into the live ring (the dump was cut just before it)
        assert any(
            e["kind"] == "engine" and "restart" in e["detail"]
            for e in flight.events()
        )
    finally:
        eng.stop()


def test_span_completions_noted_in_flight_ring(model):
    srv, eng, url = _replica_server(model)
    try:
        status, _, _ = _post(
            url, {"input_ids": _prompt(6).tolist(), "max_new_tokens": 2}
        )
        assert status == 200
        # the handler closes serve.handle AFTER it has written the response:
        # the client can be back here first
        deadline = time.time() + 5
        while True:
            spans = [e for e in flight.events() if e["kind"] == "span"]
            if any(e["detail"] == "serve.handle" for e in spans) or time.time() > deadline:
                break
            time.sleep(0.01)
        # serve.handle is a flight-noted kind; engine.* stage spans are not
        # (they would flood the ring)
        assert any(e["detail"] == "serve.handle" for e in spans)
        assert not any(e["detail"].startswith("engine.") for e in spans)
    finally:
        _stop_server(srv)


# ---------------------------------------------------------------------------
# training joins the same trace surface: fit.step under fit.window
# ---------------------------------------------------------------------------


class _Data:
    def __init__(self, n=16, d=4, c=2):
        r = np.random.RandomState(0)
        self.x = r.rand(n, d).astype(np.float32)
        self.y = r.randint(0, c, (n,)).astype(np.int64)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def test_fit_records_step_and_window_spans():
    import paddle_tpu.nn as nn

    net = nn.Linear(4, 2)
    m = paddle.Model(net)
    m.prepare(
        paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters()),
        nn.CrossEntropyLoss(),
    )
    m.fit(_Data(), batch_size=4, epochs=1, log_freq=2, verbose=0)
    steps = [s for s in trace.spans() if s["name"] == "fit.step"]
    windows = [s for s in trace.spans() if s["name"] == "fit.window"]
    assert len(steps) == 4  # 16 rows / batch 4
    assert windows, "materialize boundaries record fit.window spans"
    win_ids = {w["span_id"] for w in windows}
    assert all(s["parent_id"] in win_ids for s in steps)
    assert sum(w["attrs"]["steps"] for w in windows) == len(steps)
    # one trace id stitches the whole run
    assert len({s["trace_id"] for s in steps + windows}) == 1
