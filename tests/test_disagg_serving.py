"""Disaggregated prefill/decode serving (ISSUE 19): paged-KV handoff wire
format, role-aware engines (/prefill export, /generate import, /reserve
admission holds), the router's topology-aware (prefill, decode) pair
pipeline, and the mid-handoff fault drills.

The fast tests run REAL in-process serve() instances sharing one tiny
model (identical weights across roles is what makes "disagg tokens ==
colocated tokens" a bit-identity assertion, not a statistics one).  The
slow drill boots subprocess role workers through ReplicaProcess and
kills one prefill and one decode worker with SIGKILL under load.  The
module runs under the runtime sanitizer (conftest `_SANITIZED_MODULES`):
an unexpected recompile or host sync on either handoff side is a hard
test error.
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
from paddle_tpu.inference.engine import (
    ContinuousBatchingEngine,
    QueueFull,
)
from paddle_tpu.inference.paging import (
    HANDOFF_VERSION,
    HandoffFormatError,
    deserialize_kv_handoff,
    serialize_kv_handoff,
)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import NoDecodeCapacity, Replica, Router


@pytest.fixture(scope="module")
def model():
    np.random.seed(1234)
    return LlamaForCausalLM(LlamaConfig.tiny())


@pytest.fixture(autouse=True)
def _clean_state():
    prof.reset_router()
    prof.reset_disagg()
    yield
    finj.disarm()
    prof.reset_router()
    prof.reset_disagg()
    paddle.set_flags({"FLAGS_serve_reserve_ttl_s": 30.0})


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 250, size=n).astype(np.int32)


def _ref(model, p, n):
    return model.generate(paddle.to_tensor(p[None]), max_new_tokens=n).numpy()[0]


def _engine(model, role="colocated", **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("queue_depth", 16)
    kw.setdefault("seed", 0)
    kw.setdefault("page_size", 8)
    return ContinuousBatchingEngine(model, role=role, **kw)


def _server(model, role, warm=True, **kw):
    """One in-process role replica: engine + serve() on an ephemeral port."""
    eng = _engine(model, role=role, **kw)
    if warm:
        eng.warmup()  # sanitized module: handoff traffic must not recompile
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


def _post(url, path, body, timeout=60):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture(scope="module")
def pair(model):
    """One warmed prefill + decode server pair shared by the router-path
    tests — warmup compiles dominate this module's runtime, so the pair
    boots once.  Request it through `fresh_pair`, which resets the
    cross-test decode-side state the drills assert on."""
    srv_p, eng_p, url_p = _server(model, "prefill")
    srv_d, eng_d, url_d = _server(model, "decode")
    yield {"eng_p": eng_p, "url_p": url_p, "eng_d": eng_d, "url_d": url_d}
    _stop_server(srv_p)
    _stop_server(srv_d)


@pytest.fixture
def fresh_pair(pair):
    # a prior drill's orphaned reservation (live until its 30s TTL) must
    # not leak into this test's reservation-count assertions
    pair["eng_d"]._reserved.clear()
    pair["eng_d"]._reserved_pages = 0
    return pair


# ---------------------------------------------------------------------------
# handoff wire format: roundtrip + typed rejection
# ---------------------------------------------------------------------------


def _fake_layers(L=5, kvh=4, hd=16, n_layers=2, quant="none"):
    rng = np.random.RandomState(7)
    out = []
    for _ in range(n_layers):
        if quant == "int8":
            ly = {
                "k": rng.randint(-128, 128, size=(L, kvh, hd)).astype(np.int8),
                "v": rng.randint(-128, 128, size=(L, kvh, hd)).astype(np.int8),
                "k_scale": rng.rand(L, kvh, 1).astype(np.float32),
                "v_scale": rng.rand(L, kvh, 1).astype(np.float32),
            }
        else:
            ly = {
                "k": rng.randn(L, kvh, hd).astype(np.float32),
                "v": rng.randn(L, kvh, hd).astype(np.float32),
            }
        out.append(ly)
    return out


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_handoff_wire_roundtrip_bit_identical(quant):
    layers = _fake_layers(quant=quant)
    pay = serialize_kv_handoff(layers, 5, quant, "float32")
    assert pay["version"] == HANDOFF_VERSION
    assert pay["prompt_len"] == 5
    assert pay["payload_bytes"] > 0
    # JSON-safe end to end: what crosses the router is a plain dict
    pay = json.loads(json.dumps(pay))
    got, L = deserialize_kv_handoff(pay, quant, 4, 16, 2, "float32")
    assert L == 5
    for a, b in zip(layers, got):
        for k in a:
            assert a[k].dtype == b[k].dtype
            assert np.array_equal(a[k], b[k])


def test_handoff_wire_typed_rejection():
    pay = serialize_kv_handoff(_fake_layers(), 5, "none", "float32")

    def _reject(mutate, **kw):
        bad = json.loads(json.dumps(pay))
        mutate(bad)
        with pytest.raises(HandoffFormatError):
            deserialize_kv_handoff(
                bad, kw.get("quant", "none"), kw.get("kvh", 4),
                kw.get("hd", 16), kw.get("n_layers", 2), "float32",
            )

    _reject(lambda b: b.update(version=HANDOFF_VERSION + 1))
    _reject(lambda b: None, quant="int8")          # receiver precision differs
    _reject(lambda b: None, kvh=8)                 # foreign geometry
    _reject(lambda b: None, n_layers=3)            # layer-count mismatch
    _reject(lambda b: b.update(prompt_len=0))
    _reject(lambda b: b["layers"].pop())
    _reject(lambda b: b["layers"][0].update(k=b["layers"][0]["k"][:-8]))
    with pytest.raises(HandoffFormatError):
        deserialize_kv_handoff("nope", "none", 4, 16, 2, "float32")
    with pytest.raises(HandoffFormatError):
        serialize_kv_handoff([], 5, "none", "float32")


# ---------------------------------------------------------------------------
# engine level: export -> reserve -> import, bit-identical, frozen compiles
# ---------------------------------------------------------------------------


def _handoff_passes(model, pre, dec, ref_fn):
    """Two export->reserve->import passes; the second proves 0 recompiles."""
    for i, n_new in ((0, 8), (1, 6)):
        p = _prompt(11 + 3 * i, seed=40 + i)
        ref = ref_fn(p, n_new)
        h = pre.submit(p, max_new_tokens=1, export_kv=True)
        assert h.wait(60) is not None
        pay = h.kv_export
        assert pay is not None
        assert pay["quant"] in ("none", "int8")
        assert pay["prompt_len"] == len(p)
        # the reference includes the prompt; the export's first token is
        # the first GENERATED one (the decode side re-emits it)
        assert pay["first_token"] == int(ref[len(p)])
        rsv = dec.reserve_pages(len(p), n_new)
        assert dec.healthz()["reserved_pages"] == rsv["pages"] > 0
        # the handoff rides JSON between processes in production
        pay = json.loads(json.dumps(pay))
        got = dec.submit(
            p, max_new_tokens=n_new, handoff=pay,
            reservation=rsv["reservation"],
        ).wait(60)
        assert np.array_equal(got, ref)
        assert dec.healthz()["reserved_pages"] == 0  # consumed at admit


def test_engine_handoff_bit_identical_frozen_compiles(model, fresh_pair):
    pre, dec = fresh_pair["eng_p"], fresh_pair["eng_d"]
    assert "import" in dec.compile_counts()
    warm = {e: e.compile_counts() for e in (pre, dec)}
    _handoff_passes(model, pre, dec, lambda p, n: _ref(model, p, n))
    for e in (pre, dec):
        assert e.compile_counts() == warm[e]  # frozen on BOTH sides
    g = prof.disagg_summary()
    assert g["exports"] == 2 and g["imports"] == 2
    assert g["handoff_bytes"] > 0


def test_engine_handoff_int8_bit_identical_frozen_compiles(model):
    co = _engine(model, kv_quant="int8")
    pre = _engine(model, role="prefill", kv_quant="int8")
    dec = _engine(model, role="decode", kv_quant="int8")
    for e in (co, pre, dec):
        e.warmup()
    assert "import" in dec.compile_counts()
    assert "import" not in co.compile_counts()  # colocated shape unchanged
    warm = {e: e.compile_counts() for e in (co, pre, dec)}
    try:
        for e in (co, pre, dec):
            e.start()
        # int8 numerics: the reference is a colocated int8 engine, NOT
        # model.generate — quantized KV must match quantized KV
        _handoff_passes(
            model, pre, dec,
            lambda p, n: co.submit(p, max_new_tokens=n).wait(60),
        )
        for e in (co, pre, dec):
            assert e.compile_counts() == warm[e]
        g = prof.disagg_summary()
        assert g["exports"] == 2 and g["imports"] == 2
        # int8 rows + f32 scales ship ~2x cheaper than f32 rows
        f32_rows = 2 * 2 * (11 * 4 * 16 * 4 + 14 * 4 * 16 * 4)
        assert 0 < g["handoff_bytes"] < 0.75 * f32_rows
    finally:
        for e in (co, pre, dec):
            e.stop()


def test_role_and_handoff_validation(model):
    with pytest.raises(ValueError):
        _engine(model, role="router")
    # a handoff only lands on a decode-role engine; colocated and prefill
    # engines reject it typed instead of corrupting their arenas
    for role in ("colocated", "prefill"):
        eng = _engine(model, role=role)
        with pytest.raises(ValueError):
            eng.submit(_prompt(4), max_new_tokens=2,
                       handoff={"version": HANDOFF_VERSION})


def test_reservations_gate_admission_and_expire(model):
    dec = _engine(model, role="decode")
    # stacked worst-case holds eventually exceed headroom: typed QueueFull
    with pytest.raises(QueueFull):
        for _ in range(100):
            dec.reserve_pages(56, 8)
    dec._reserved.clear()
    dec._reserved_pages = 0
    free0 = dec.healthz()["page_free_frac"]
    r = dec.reserve_pages(8, 8)
    assert dec.healthz()["page_free_frac"] < free0  # holds shrink headroom
    # TTL reclaim: an abandoned reservation returns its headroom
    paddle.set_flags({"FLAGS_serve_reserve_ttl_s": 0.05})
    r2 = dec.reserve_pages(8, 8)
    time.sleep(0.1)
    r3 = dec.reserve_pages(8, 8)  # purges r2 on entry
    assert dec._reserved_pages == r["pages"] + r3["pages"]
    assert r2["reservation"] not in dec._reserved


# ---------------------------------------------------------------------------
# serve(): /reserve and /prefill endpoints
# ---------------------------------------------------------------------------


def test_serve_reserve_endpoint(model):
    srv, eng, url = _server(model, "decode", warm=False)
    try:
        st, body = _post(url, "/reserve", {"prompt_len": 8, "max_new_tokens": 8})
        assert st == 200
        assert body["reservation"].startswith("rsv-")
        assert body["pages"] > 0 and body["ttl_s"] > 0
        for _ in range(100):  # stacked holds exhaust the pool eventually
            st, body = _post(url, "/reserve",
                             {"prompt_len": 56, "max_new_tokens": 8})
            if st != 200:
                break
        assert st == 503
        assert body["type"] == "QueueFull"
        assert body["retriable"] is True
    finally:
        _stop_server(srv)


def test_serve_prefill_endpoint(model):
    srv, eng, url = _server(model, "prefill", warm=False)
    try:
        p = _prompt(9, seed=3)
        ref = _ref(model, p, 4)
        st, body = _post(url, "/prefill", {"input_ids": p.tolist()})
        assert st == 200
        assert body["prompt_len"] == 9
        assert body["first_token"] == int(ref[len(p)])
        hand = body["handoff"]
        assert hand["version"] == HANDOFF_VERSION
        assert hand["payload_bytes"] > 0
        st, body = _post(url, "/prefill",
                         {"input_ids": [p.tolist(), p.tolist()]})
        assert st == 400  # handoffs are per-stream: no batch rows
    finally:
        _stop_server(srv)


# ---------------------------------------------------------------------------
# router: page-starved skip, pair scoring, NoDecodeCapacity
# ---------------------------------------------------------------------------


def _fake_rep(rid, role="colocated", page_free=0.5, queue=0, **h):
    rep = Replica(rid, f"http://127.0.0.1:1/{rid}")
    rep._note_healthz({
        "status": "ready", "role": role, "page_free_frac": page_free,
        "queue_depth": queue, "active_slots": 0, "drain_estimate_s": 0.0,
        "decode_ewma_ms": 1.0, **h,
    })
    return rep


def test_pick_skips_page_starved_replica_when_alternative_exists():
    starved = _fake_rep("a", page_free=0.0)
    healthy = _fake_rep("b", page_free=0.4, queue=5)  # busier, still wins
    router = Router([starved, healthy], probe_interval=3600)
    assert router.pick() is healthy
    # the starved replica is the whole fleet -> it is reconsidered
    solo = Router([_fake_rep("c", page_free=0.0)], probe_interval=3600)
    assert solo.pick().rid == "c"


def test_pick_pair_scores_compute_vs_page_headroom():
    pre_busy = _fake_rep("p0", role="prefill", queue=6)
    pre_idle = _fake_rep("p1", role="prefill", queue=0)
    dec_low = _fake_rep("d0", role="decode", page_free=0.1)
    dec_high = _fake_rep("d1", role="decode", page_free=0.9, queue=4)
    router = Router([pre_busy, pre_idle, dec_low, dec_high],
                    probe_interval=3600)
    pre, dec = router.pick_pair()
    assert pre is pre_idle          # prefill: compute backlog decides
    assert dec is dec_high          # decode: page headroom decides
    pre, dec = router.pick_pair(exclude_prefill=("p1",),
                                exclude_decode=("d1",))
    assert pre is pre_busy and dec is dec_low


def test_pick_pair_no_decode_capacity_typed_503():
    router = Router(
        [_fake_rep("p0", role="prefill"),
         _fake_rep("d0", role="decode", page_free=0.0),
         _fake_rep("d1", role="decode", page_free=0.0)],
        probe_interval=3600,
    )
    with pytest.raises(NoDecodeCapacity) as ei:
        router.pick_pair()
    assert ei.value.status == 503
    assert ei.value.retriable is True
    assert ei.value.retry_after_s is not None
    assert prof.disagg_summary()["no_decode_capacity"] == 1
    # one side missing entirely is a None slot, not an error (the caller
    # falls back to the colocated path)
    router2 = Router([_fake_rep("d0", role="decode", page_free=0.5)],
                     probe_interval=3600)
    pre, dec = router2.pick_pair()
    assert pre is None and dec.rid == "d0"


def test_router_handle_generate_maps_no_decode_capacity():
    router = Router(
        [_fake_rep("p0", role="prefill"),
         _fake_rep("d0", role="decode", page_free=0.0)],
        probe_interval=3600,
    )
    status, body, headers = router.handle_generate(
        {"input_ids": [1, 2, 3], "max_new_tokens": 4}
    )
    assert status == 503
    assert body["type"] == "NoDecodeCapacity"
    assert body["retriable"] is True
    assert float(headers["Retry-After"]) > 0


# ---------------------------------------------------------------------------
# router: disagg pipeline end to end over HTTP
# ---------------------------------------------------------------------------


def test_router_disagg_pipeline_bit_identical(model, fresh_pair):
    eng_d = fresh_pair["eng_d"]
    router = Router([fresh_pair["url_p"], fresh_pair["url_d"]],
                    probe_interval=3600, retry_backoff=0.01)
    try:
        router.probe_once()
        assert router.healthz()["roles"] == {"prefill": 1, "decode": 1}
        for i in range(3):
            p = _prompt(6 + 2 * i, seed=60 + i)
            status, body, _ = router.handle_generate(
                {"input_ids": p.tolist(), "max_new_tokens": 5}
            )
            assert status == 200, body
            assert np.array_equal(body["tokens"], _ref(model, p, 5))
        g = prof.disagg_summary()
        assert g["pair_picks"] == 3
        assert g["exports"] == 3 and g["imports"] == 3
        assert g["handoff_bytes"] > 0
        assert g["handoff_retries"] == 0
        assert eng_d.healthz()["reserved_pages"] == 0
        # requests the pipeline cannot serve ride the colocated path on
        # whichever replica pick() chooses (any role answers /generate)
        p = _prompt(6, seed=70)
        status, body, _ = router.handle_generate(
            {"input_ids": [p.tolist()], "max_new_tokens": 4}
        )
        assert status == 200
        assert np.array_equal(body["tokens"][0], _ref(model, p, 4))
        assert prof.disagg_summary()["pair_picks"] == 3  # unchanged
    finally:
        router.stop()


def test_disagg_metrics_exposition(model, fresh_pair):
    from paddle_tpu.obs import metrics as obs_metrics

    router = Router([fresh_pair["url_p"], fresh_pair["url_d"]],
                    probe_interval=3600, retry_backoff=0.01)
    try:
        router.probe_once()
        p = _prompt(6, seed=80)
        status, body, _ = router.handle_generate(
            {"input_ids": p.tolist(), "max_new_tokens": 4}
        )
        assert status == 200
        text = obs_metrics.render()
        for name in ("paddle_disagg_exports_total",
                     "paddle_disagg_imports_total",
                     "paddle_disagg_handoff_bytes_total",
                     "paddle_disagg_pair_picks_total"):
            assert name in text
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# fault drills: mid-handoff death is a zero-token retriable failover
# ---------------------------------------------------------------------------


def test_prefill_crash_drill_zero_token_failover(model, fresh_pair):
    """disagg.prefill.crash: the /prefill hop dies without a response
    byte.  Zero tokens crossed, so the pipeline retries and the final
    tokens are bit-identical to an undisturbed run (exactly-once: the
    decode side imports exactly one handoff)."""
    router = Router([fresh_pair["url_p"], fresh_pair["url_d"]],
                    probe_interval=3600, retry_backoff=0.01)
    try:
        router.probe_once()
        finj.arm("disagg.prefill.crash:1")
        p = _prompt(9, seed=90)
        status, body, _ = router.handle_generate(
            {"input_ids": p.tolist(), "max_new_tokens": 6}
        )
        assert status == 200, body
        assert np.array_equal(body["tokens"], _ref(model, p, 6))
        g = prof.disagg_summary()
        assert g["handoff_retries"] >= 1
        assert g["imports"] == 1  # the client-visible stream ran ONCE
    finally:
        router.stop()


def test_handoff_drop_drill_retries_and_ttl_reclaims(model, fresh_pair):
    """disagg.handoff.drop: the serialized payload vanishes between the
    hops.  Neither replica is blamed; the whole pipeline retries
    exactly-once; the orphaned decode-side reservation expires by TTL."""
    paddle.set_flags({"FLAGS_serve_reserve_ttl_s": 0.2})
    eng_d = fresh_pair["eng_d"]
    router = Router([fresh_pair["url_p"], fresh_pair["url_d"]],
                    probe_interval=3600, retry_backoff=0.01)
    try:
        router.probe_once()
        finj.arm("disagg.handoff.drop:1")
        p = _prompt(7, seed=91)
        status, body, _ = router.handle_generate(
            {"input_ids": p.tolist(), "max_new_tokens": 5}
        )
        assert status == 200, body
        assert np.array_equal(body["tokens"], _ref(model, p, 5))
        g = prof.disagg_summary()
        assert g["handoff_retries"] >= 1
        assert g["exports"] == 2   # prefill ran twice (first payload lost)
        assert g["imports"] == 1   # decode streamed once
        # neither replica took breaker blame for the router-side loss
        assert all(r.breaker == "closed" for r in router.replicas)
        # the first attempt's reservation is an orphan until its TTL
        time.sleep(0.25)
        eng_d.reserve_pages(1, 1)  # purge point
        assert eng_d._reserved_pages == eng_d._reserved[
            list(eng_d._reserved)[-1]][0]
        assert len(eng_d._reserved) == 1
    finally:
        router.stop()


def test_decode_death_fails_over_to_second_decode_worker(model, fresh_pair):
    # the shared pair supplies the prefill worker and the SURVIVING
    # decode worker; the victim boots fresh (it dies mid-test)
    srv_d0, eng_d0, url_d0 = _server(model, "decode", warm=False)
    router = Router([fresh_pair["url_p"], url_d0, fresh_pair["url_d"]],
                    probe_interval=3600, retry_backoff=0.01)
    try:
        router.probe_once()   # all ready; ties break toward index 1 (d0)
        _stop_server(srv_d0)  # d0 dies AFTER the probe marked it ready
        p = _prompt(8, seed=92)
        status, body, _ = router.handle_generate(
            {"input_ids": p.tolist(), "max_new_tokens": 5}
        )
        assert status == 200, body
        assert np.array_equal(body["tokens"], _ref(model, p, 5))
        g = prof.disagg_summary()
        assert g["reserve_fails"] >= 1  # dead /reserve hop, zero tokens
        assert g["handoff_retries"] >= 1
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# autoscaler: one controller per role band
# ---------------------------------------------------------------------------


def test_load_signals_fold_one_role_band():
    from paddle_tpu.serving.autoscaler import load_signals

    snaps = [
        _fake_rep("p0", role="prefill", queue=8).snapshot(),
        _fake_rep("d0", role="decode", page_free=0.05).snapshot(),
        _fake_rep("c0", role="colocated").snapshot(),
    ]
    pre = load_signals(snaps, role="prefill")
    assert pre["replicas"] == pre["ready"] == 1
    assert pre["mean_queue"] == 8.0
    assert pre["min_page_free"] == 0.5
    dec = load_signals(snaps, role="decode")
    assert dec["replicas"] == 1
    assert dec["min_page_free"] == 0.05
    assert load_signals(snaps)["replicas"] == 3  # unfiltered: whole fleet


def test_autoscaler_role_scoped_victim_and_spawn(monkeypatch):
    from paddle_tpu.serving import autoscaler as asc_mod

    reps = [
        _fake_rep("p0", role="prefill"),
        _fake_rep("d0", role="decode"),
        _fake_rep("d1", role="decode"),
    ]
    router = Router(reps, probe_interval=3600)
    asc = asc_mod.Autoscaler(
        router, spawn_fn=lambda idx, tp: None, stop_fn=lambda rep: None,
        min_replicas=1, max_replicas=4, role="decode",
        tp_max=1, devices_total=8, interval=3600,
    )
    victim = asc._pick_victim()
    assert victim is not None and victim.rid in ("d0", "d1")  # never p0

    captured = {}

    class _StubProc:
        def __init__(self, index, port, log_dir, host="127.0.0.1",
                     extra_args=()):
            captured["extra_args"] = list(extra_args)
            self.host, self.port = host, port

        @property
        def url(self):
            return f"http://{self.host}:{self.port}"

        def start(self):
            return self

    monkeypatch.setattr(asc_mod, "ReplicaProcess", _StubProc)
    asc._default_spawn(0, 1)
    assert captured["extra_args"] == ["--role", "decode"]
    asc._default_spawn(1, 2)  # a TP>1 decode worker boots sharded AND roled
    assert captured["extra_args"] == ["--tp", "2", "--role", "decode"]


# ---------------------------------------------------------------------------
# slow chaos drill: kill -9 a prefill worker mid-handoff and a decode
# worker mid-stream; every request resolves exactly-once, bit-identical
# ---------------------------------------------------------------------------


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_disagg_kill9_chaos_drill(tmp_path):
    """The production process topology: 2 prefill + 2 decode subprocess
    workers under concurrent load, the decode side TP-sharded (--tp 2
    over the virtual CPU mesh).  SIGKILL one prefill worker, then one
    decode worker.  Every request must resolve exactly once — a 200 with
    tokens bit-identical to the single-engine reference, or a typed
    retriable error — and the survivors absorb the fleet."""
    from paddle_tpu.serving import ReplicaProcess

    procs = []
    urls = []
    for i, role in enumerate(("prefill", "prefill", "decode", "decode")):
        extra = ["--role", role]
        if role == "decode":
            extra += ["--tp", "2"]  # mixed-degree fleet: same greedy tokens
        proc = ReplicaProcess(
            index=i, port=_free_port(), log_dir=str(tmp_path),
            extra_args=extra,
        ).start()
        procs.append(proc)
        urls.append(proc.url)

    router = Router(urls, probe_interval=0.2, retry_backoff=0.05)
    # subprocess workers build their weights from a fresh generator; the
    # in-process reference must match that seeding convention exactly
    paddle.seed(0)
    np.random.seed(1234)
    ref_model = LlamaForCausalLM(LlamaConfig.tiny())
    try:
        deadline = time.monotonic() + 240  # TP workers compile at boot
        while time.monotonic() < deadline:
            router.probe_once()
            snaps = [r.snapshot() for r in router.replicas]
            if sum(s["state"] == "ready" for s in snaps) == 4:
                break
            time.sleep(0.5)
        else:
            pytest.fail("subprocess fleet never became ready")
        router.start()

        prompts = [_prompt(5 + (i % 9), seed=200 + i) for i in range(24)]
        refs = [_ref(ref_model, p, 6) for p in prompts]
        results = [None] * len(prompts)

        def _one(i):
            t0 = time.monotonic()
            while True:
                try:
                    status, body, _ = router.handle_generate(
                        {"input_ids": prompts[i].tolist(),
                         "max_new_tokens": 6}
                    )
                except Exception as e:  # pragma: no cover - hard failure
                    results[i] = ("exc", repr(e))
                    return
                if status == 200:
                    results[i] = ("ok", body["tokens"])
                    return
                # typed retriable shedding is allowed while the fleet
                # convulses; clients retry until capacity returns
                if not body.get("retriable") or time.monotonic() - t0 > 90:
                    results[i] = ("err", body)
                    return
                time.sleep(0.2)

        threads = [threading.Thread(target=_one, args=(i,))
                   for i in range(len(prompts))]
        for i, t in enumerate(threads):
            t.start()
            if i == 6:
                procs[0].kill9()   # a prefill worker dies mid-handoff
            if i == 14:
                procs[2].kill9()   # a decode worker dies mid-stream
            time.sleep(0.05)
        for t in threads:
            t.join(timeout=180)

        oks = sum(1 for r in results if r and r[0] == "ok")
        assert oks == len(prompts), [r for r in results if not r or r[0] != "ok"]
        for (kind, toks), ref in zip(results, refs):
            assert np.array_equal(toks, ref)  # bit-identical, exactly once
        g = prof.disagg_summary()
        assert g["pair_picks"] >= len(prompts)
    finally:
        router.stop()
        for proc in procs:
            try:
                proc.kill9()
            except Exception:
                pass
