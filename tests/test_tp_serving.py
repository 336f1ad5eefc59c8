"""Tensor-parallel serving (ISSUE 14): the TP=4 engine must be a pure
layout change — greedy outputs token-identical to TP=1 on mixed
paged/prefix traffic, with speculative decoding and multi-tenant LoRA
composed on top, the compiled-executable budget frozen after warmup, and
warm restarts keeping the sharded arena with zero fresh compiles.

Construction-time validation (ShardingError) is tested head-on: bad
model/tp pairs must fail with a message naming the axis and degrees, not
a GSPMD shape error deep inside trace.

Runs under the runtime sanitizer (conftest _SANITIZED_MODULES) on the
CPU backend with 8 forced host devices, so every mesh/shard_map path here
is the same program a TPU slice runs minus the Pallas kernel choice.
"""

import json
import re
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler as prof
from paddle_tpu.distributed import mesh as _mesh
from paddle_tpu.distributed.sharding import ShardingError, validate_tp
from paddle_tpu.inference.engine import ContinuousBatchingEngine
from paddle_tpu.lora import AdapterArena, AdapterRegistry, make_random
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.obs import flight, metrics


@pytest.fixture(scope="module", autouse=True)
def _mesh_guard():
    """Engines below install a global 'mp' mesh; never leak it to other
    test modules."""
    prev = _mesh.get_mesh()
    yield
    _mesh.set_mesh(prev)


@pytest.fixture(scope="module")
def model():
    np.random.seed(1234)
    paddle.seed(1234)
    return LlamaForCausalLM(LlamaConfig.tiny())


@pytest.fixture(scope="module")
def tp_model(model):
    m = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel_degree=4))
    # belt and braces: identical init order makes the weights bit-equal
    # already, but the identity tests should not depend on that
    m.set_state_dict(model.state_dict())
    return m


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 250, size=n).astype(np.int32)


def _cycle_prompt(n=20, period=6, seed=7):
    """Repetitive prompt so n-gram drafting actually fires under spec."""
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


@pytest.fixture(scope="module")
def engines(model, tp_model):
    """(tp1, tp4, tp4 warm compile counts): both with spec decoding on.

    The TP=1 engine warms first so its executables trace before any mesh
    exists; the TP=4 construction then installs the serving mesh.
    """
    e1 = _paged(model, spec_k=3)
    e1.warmup()
    e4 = _paged(tp_model, spec_k=3, tp=4)
    e4.warmup()
    return e1, e4, dict(e4.compile_counts())


def _run(engine, prompts, max_new_tokens=16):
    rs = [engine.submit(p, max_new_tokens=max_new_tokens) for p in prompts]
    engine.run_until_idle()
    return [r.wait(1).tolist() for r in rs]


# ---------------------------------------------------------------------------
# construction-time validation: typed errors, not GSPMD shape failures
# ---------------------------------------------------------------------------


def _cfg(heads, kv_heads):
    return types.SimpleNamespace(
        num_attention_heads=heads, num_key_value_heads=kv_heads
    )


def test_validate_tp_rejects_indivisible_heads():
    with pytest.raises(ShardingError, match=r"num_attention_heads \(4\).*3"):
        validate_tp(_cfg(4, 4), 3)


def test_validate_tp_rejects_indivisible_kv_heads():
    # heads split fine; the KV arena axis is what cannot shard
    with pytest.raises(ShardingError, match=r"num_key_value_heads \(4\).*8"):
        validate_tp(_cfg(8, 4), 8)


def test_validate_tp_rejects_more_shards_than_devices():
    with pytest.raises(ShardingError, match="devices"):
        validate_tp(_cfg(16, 16), 16)


def test_validate_tp_rejects_nonpositive_degree():
    with pytest.raises(ShardingError, match=">= 1"):
        validate_tp(_cfg(4, 4), 0)


def test_validate_tp_divisibility_checked_before_device_count():
    # a bad model/tp pair must fail the same way on a 1-device laptop as
    # on the full slice, so the head check runs before the device check
    with pytest.raises(ShardingError, match="num_attention_heads"):
        validate_tp(_cfg(6, 6), 4, devices=[])


def test_engine_rejects_unsharded_model_at_tp(model):
    # model built without tensor_parallel_degree: plain nn.Linear
    # projections have nothing for the mesh to shard
    with pytest.raises(ShardingError, match="tensor_parallel_degree"):
        _paged(model, tp=4)


# ---------------------------------------------------------------------------
# token identity + frozen compiled budget
# ---------------------------------------------------------------------------


def test_tp4_greedy_identical_on_mixed_traffic(engines):
    e1, e4, warm = engines
    # mixed traffic: short prompt (8-token bucket), long repetitive prompt
    # (32 bucket, spec drafting fires), and a repeat of the long prompt
    # (admission-time prefix-cache hit -> paged sharing + COW)
    prompts = [_prompt(6, seed=3), _cycle_prompt(20), _cycle_prompt(20)]
    out1 = _run(e1, prompts)
    out4 = _run(e4, prompts)
    assert out1 == out4
    # the layout change costs zero extra executables: same warm budget,
    # and serving traffic compiled nothing new
    assert dict(e4.compile_counts()) == warm
    assert warm["decode"] == 1 and warm["verify"] == 1


def test_tp4_spec_acceptance_matches_tp1(engines):
    e1, e4, _ = engines
    p = _cycle_prompt(24, period=4, seed=11)
    (out1,) = _run(e1, [p], max_new_tokens=24)
    (out4,) = _run(e4, [p], max_new_tokens=24)
    assert out1 == out4


def test_tp4_warm_restart_keeps_sharded_arena(engines):
    _, e4, warm = engines
    p = _cycle_prompt(20, seed=5)
    (before,) = _run(e4, [p])
    e4.restart(reason="tp-test")
    # restart rebuilds scheduler state only: the sharded arenas and every
    # compiled executable survive — zero fresh compiles, same tokens
    assert dict(e4.compile_counts()) == warm
    (after,) = _run(e4, [p])
    assert after == before


# ---------------------------------------------------------------------------
# observability: healthz / metrics / flight recorder carry the mesh
# ---------------------------------------------------------------------------


def test_healthz_reports_mesh_topology(engines):
    e1, e4, _ = engines
    h4 = e4.healthz()
    assert h4["tp"] == 4
    assert h4["mesh_shape"] == {"mp": 4}
    h1 = e1.healthz()
    assert h1["tp"] == 1
    assert h1["mesh_shape"] == {}


def test_metrics_render_mesh_gauges(engines):
    # the TP=4 engine recorded topology last; the gauges must render with
    # stable names (zero-rendered at tp=1, so dashboards never 404)
    text = metrics.render(labels={"replica": "unit"})
    want = {
        "paddle_mesh_devices": 8.0,
        "paddle_mesh_tp_degree": 4.0,
        "paddle_mesh_allreduce_per_step": 5.0,  # 2 layers * 2 + sampling
    }
    for name, val in want.items():
        m = re.search(rf'^{name}{{replica="unit"}} (\S+)$', text, re.M)
        assert m, f"{name} missing from exposition"
        assert float(m.group(1)) == val


def test_flight_dump_header_carries_mesh(engines, tmp_path):
    path = flight.dump("tp-test", path=str(tmp_path / "f.jsonl"))
    with open(path) as f:
        header = json.loads(f.readline())
    # cp rides along since the context-parallel engine: 1 = decode not cp-sharded
    assert header["mesh"] == {
        "devices": 8, "tp": 4, "cp": 1, "allreduce_per_step": 5,
    }


# ---------------------------------------------------------------------------
# LoRA co-batch under TP
# ---------------------------------------------------------------------------


def _registry(model, n=3, rank=4, scale=0.02):
    reg = AdapterRegistry(model.config)
    for i in range(n):
        make_random(reg, f"a{i + 1}", rank=rank, seed=i + 1, scale=scale)
    return reg


def test_tp4_lora_cobatch_identical(model, tp_model):
    eL1 = _paged(model, lora=AdapterArena(_registry(model)))
    eL1.warmup()
    prompts = [_prompt(12, seed=s) for s in range(3)]

    def _tenants(engine):
        rs = [
            engine.submit(p, max_new_tokens=12, adapter=f"a{i + 1}")
            for i, p in enumerate(prompts)
        ]
        engine.run_until_idle()
        return [r.wait(1).tolist() for r in rs]

    out1 = _tenants(eL1)
    eL4 = _paged(tp_model, lora=AdapterArena(_registry(tp_model)), tp=4)
    eL4.warmup()
    warm = dict(eL4.compile_counts())
    assert _tenants(eL4) == out1
    # adapter uploads write in place into the sharded arena slabs: the
    # co-batched delta retraces nothing
    assert dict(eL4.compile_counts()) == warm


# ---------------------------------------------------------------------------
# fused kernel under shard_map: numerics vs the gather oracle
# ---------------------------------------------------------------------------


def test_fused_shard_map_matches_gather_oracle(engines):
    import jax.numpy as jnp

    from paddle_tpu.ops import flash_attention as fa

    assert _mesh.axis_size("mp") == 4
    rng = np.random.RandomState(0)
    pages, ps, hk, d, slots = 9, 8, 4, 16, 3
    ak = rng.randn(pages, hk, ps, d).astype(np.float32)
    av = rng.randn(pages, hk, ps, d).astype(np.float32)
    q = rng.randn(slots, 1, hk, d).astype(np.float32)
    tables = np.array([[1, 2, 0], [3, 4, 0], [5, 6, 0]], np.int32)
    pos = np.array([13, 9, 17], np.int32)
    args = (jnp.asarray(q), jnp.asarray(ak), jnp.asarray(av),
            jnp.asarray(tables), jnp.asarray(pos), 24)
    ref = fa.paged_decode_attention_array(*args, kernel="gather")
    prev = fa._FORCE_INTERPRET
    fa._FORCE_INTERPRET = True
    try:
        # with mp=4 installed this routes through the shard_map wrapper:
        # each device runs the kernel over its local kv_heads/4 heads
        fused = fa.paged_decode_attention_array(*args, kernel="fused")
    finally:
        fa._FORCE_INTERPRET = prev
    assert float(jnp.max(jnp.abs(fused - ref))) < 2e-6
