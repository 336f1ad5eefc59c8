"""Context-parallel paged decode (ISSUE 20): the 'cp' mesh axis shards one
sequence's KV pages round-robin across devices; each shard runs the fused
paged-decode kernel over its local page-table slice and the shards merge
via the online-softmax two-term combine (pmax of m, psum of l and acc).

Contract under test:

- the combine math equals one softmax over the union of keys (pure jnp
  reference `cp_softmax_combine`, then the shard_map'd kernel vs the
  single-device gather oracle);
- a cp=2 ENGINE is a pure layout change: greedy outputs token-identical
  to cp=1 on ragged mixed traffic, including forced-fused + int8 + spec
  decode, with the compiled-executable budget frozen;
- page bookkeeping becomes per-shard (PagePool shards, round-robin
  sequence-page placement, per-shard admission) and the debug-invariants
  audit understands the layout;
- over-capacity prompts shed with the typed ContextOverflow carrying the
  PER-SHARD geometry.

Kernels run in Pallas interpret mode on the CPU backend with 8 forced
host devices — the same shard_map program a TPU slice runs.
"""

import contextlib

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.distributed import mesh as _mesh
from paddle_tpu.inference.engine import ContextOverflow, ContinuousBatchingEngine
from paddle_tpu.inference.paging import PagePool
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM, _quantize_kv_rows
import paddle_tpu.ops.flash_attention as fa


@pytest.fixture(scope="module", autouse=True)
def _mesh_guard():
    """Engines and direct dispatches below install a global 'cp' mesh;
    never leak it to other test modules."""
    prev = _mesh.get_mesh()
    yield
    _mesh.set_mesh(prev)


@pytest.fixture(autouse=True)
def _fresh_mesh():
    """Engine construction at cp>1 installs the global mesh as a side
    effect; start every test without one so a cp=1 engine built after a
    cp=2 test sees cp=1 dispatch, like a fresh process would."""
    _mesh.set_mesh(None)
    yield


@pytest.fixture(scope="module", autouse=True)
def _rng_guard():
    state = np.asarray(paddle.get_rng_state())
    yield
    paddle.set_rng_state(state)


@pytest.fixture(scope="module")
def model(_rng_guard):
    np.random.seed(1234)
    return LlamaForCausalLM(LlamaConfig.tiny())


@contextlib.contextmanager
def _interpret():
    saved = fa._FORCE_INTERPRET
    fa._FORCE_INTERPRET = True
    try:
        yield
    finally:
        fa._FORCE_INTERPRET = saved


@contextlib.contextmanager
def _cp_mesh(cp):
    prev = _mesh.get_mesh()
    _mesh.serving_mesh(1, cp=cp)
    try:
        yield
    finally:
        _mesh.set_mesh(prev)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 250, size=n).astype(np.int32)


def _paged(model, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8, 32])
    kw.setdefault("queue_depth", 16)
    kw.setdefault("seed", 0)
    kw.setdefault("page_size", 8)
    return ContinuousBatchingEngine(model, **kw)


# ---------------------------------------------------------------------------
# combine math: per-shard online-softmax partials -> one softmax
# ---------------------------------------------------------------------------


def test_cp_softmax_combine_matches_dense_softmax():
    """Split a score row's keys into disjoint shard sets, form each shard's
    (acc, m, l) exactly as the kernel does, and check the combine equals
    softmax over the union — including a fully-masked shard (m=-inf)."""
    r = np.random.RandomState(7)
    rows, n, d = 6, 24, 8
    s = jnp.asarray(r.randn(rows, n).astype(np.float32) * 3)
    v = jnp.asarray(r.randn(n, d).astype(np.float32))
    ref = jnp.einsum("rn,nd->rd", jnp.exp(s - s.max(-1, keepdims=True)), v)
    ref = ref / jnp.exp(s - s.max(-1, keepdims=True)).sum(-1, keepdims=True)

    parts = []
    for lo, hi in ((0, 9), (9, 24), (24, 24)):  # third shard sees nothing
        sj, vj = s[:, lo:hi], v[lo:hi]
        m = (sj.max(-1, keepdims=True) if hi > lo
             else jnp.full((rows, 1), -jnp.inf))
        e = jnp.exp(sj - m) if hi > lo else jnp.zeros((rows, 0))
        parts.append((jnp.einsum("rn,nd->rd", e, vj), m,
                      e.sum(-1, keepdims=True)))
    acc = jnp.stack([p[0] for p in parts])
    m = jnp.stack([p[1] for p in parts])
    l = jnp.stack([p[2] for p in parts])
    out = fa.cp_softmax_combine(acc, m, l)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# kernel level: shard_map'd fused cp decode vs the gather oracle
# ---------------------------------------------------------------------------


def _cp_arena(cp=2, num_pages=16, ps=8, hk=2, d=16, b=3, P=4, seed=0,
              quant=False):
    """Global arena + tables in the engine's cp layout: sequence page j of
    each row lives on shard j % cp (shard s owns physical pages
    [s*per_shard, (s+1)*per_shard), page s*per_shard being scratch)."""
    r = np.random.RandomState(seed)
    per = num_pages // cp
    k = r.randn(num_pages, hk, ps, d).astype(np.float32)
    v = r.randn(num_pages, hk, ps, d).astype(np.float32)
    for s in range(cp):  # scratch pages stay zero, like a live pool
        k[s * per] = 0.0
        v[s * per] = 0.0
    nxt = [s * per + 1 for s in range(cp)]  # next unused page per shard
    tables = np.zeros((b, P), np.int32)
    for i in range(b):
        for j in range(P):
            sh = j % cp
            tables[i, j] = nxt[sh]
            nxt[sh] += 1
    assert max(nxt[s] - s * per for s in range(cp)) <= per
    ka, va = jnp.asarray(k), jnp.asarray(v)
    if not quant:
        return ka, va, jnp.asarray(tables), None, None
    kq, ks = _quantize_kv_rows(ka)  # scales [num_pages, hk, ps, 1]
    vq, vs = _quantize_kv_rows(va)
    return (kq, vq, jnp.asarray(tables), jnp.swapaxes(ks, 2, 3),
            jnp.swapaxes(vs, 2, 3))


@pytest.mark.parametrize("sq", [1, 3])  # plain decode and a verify window
def test_cp_fused_matches_gather_oracle(sq):
    ka, va, tables, _, _ = _cp_arena()
    r = np.random.RandomState(5)
    q = jnp.asarray(r.randn(3, sq, 4, 16).astype(np.float32))  # GQA rep=2
    pos = jnp.asarray([29, 11, 17 + sq], jnp.int32)
    with _interpret(), _cp_mesh(2):
        fused = fa.paged_decode_attention_array(
            q, ka, va, tables, pos, max_len=32, kernel="fused")
    oracle = fa.paged_decode_attention_array(
        q, ka, va, tables, pos, max_len=32, kernel="gather")
    # shard merge reassociates the softmax sums: allclose, not bit-equal
    np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


def test_cp_fused_q8_matches_quant_gather_oracle():
    ka, va, tables, ks, vs = _cp_arena(seed=1, quant=True)
    r = np.random.RandomState(6)
    q = jnp.asarray(r.randn(3, 1, 4, 16).astype(np.float32))
    pos = jnp.asarray([30, 9, 22], jnp.int32)
    with _interpret(), _cp_mesh(2):
        fused = fa.paged_decode_attention_array(
            q, ka, va, tables, pos, max_len=32, kernel="fused",
            k_scale=ks, v_scale=vs)
    oracle = fa.paged_decode_attention_array(
        q, ka, va, tables, pos, max_len=32, kernel="gather",
        k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


def test_cp_indivisible_shapes_fall_back_to_gather():
    """Direct callers whose tables/pool don't pack into cp shards must take
    the GSPMD gather path with the typed fallback reason — never a
    shard_map shape error."""
    r = np.random.RandomState(8)
    ka = jnp.asarray(r.randn(7, 2, 8, 16).astype(np.float32))  # 7 % 2 != 0
    va = jnp.asarray(r.randn(7, 2, 8, 16).astype(np.float32))
    tables = jnp.asarray([[1, 2, 3]], jnp.int32)
    q = jnp.asarray(r.randn(1, 1, 4, 16).astype(np.float32))
    pos = jnp.asarray([10], jnp.int32)
    profiler.reset_flash_fallbacks()
    with _interpret(), _cp_mesh(2):
        out = fa.paged_decode_attention_array(
            q, ka, va, tables, pos, max_len=24, kernel="auto")
    oracle = fa.paged_decode_attention_array(
        q, ka, va, tables, pos, max_len=24, kernel="gather")
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-6, atol=1e-6)
    fb = profiler.flash_fallback_summary()
    assert fb.get("paged tables/pool not divisible by cp", 0) >= 1
    assert "paged tables/pool not divisible by cp" in fa._FALLBACK_REASONS


# ---------------------------------------------------------------------------
# pool: per-shard free lists, scratch pinning, round-robin placement
# ---------------------------------------------------------------------------


def test_page_pool_shards_allocation_geometry():
    pool = PagePool(10, shards=2)
    assert pool.per_shard == 5
    assert pool.scratch_pages == (0, 5)
    assert pool.usable_pages == 8
    assert pool.free_count() == 8
    assert pool.free_count(0) == 4 and pool.free_count(1) == 4
    a = pool.alloc(0)
    b = pool.alloc(1)
    assert pool.shard_of(a) == 0 and 1 <= a < 5
    assert pool.shard_of(b) == 1 and 6 <= b < 10
    assert pool.free_count(0) == 3 and pool.free_count(1) == 3
    for p in pool.scratch_pages:
        assert pool.is_scratch(p) and pool.refs[p] == 1
    pool.decref(a)
    pool.decref(b)
    assert pool.free_count() == 8


# ---------------------------------------------------------------------------
# engine level: cp=2 is a pure layout change
# ---------------------------------------------------------------------------


def test_cp_engine_greedy_identical_to_cp1_and_healthz(model):
    lens = [6, 13, 9]
    base = {}
    eng1 = _paged(model, cp=1)
    for i, n in enumerate(lens):
        base[i] = eng1.generate(_prompt(n, seed=40 + i),
                                max_new_tokens=4 + i).tolist()
    eng2 = _paged(model, cp=2)
    for i, n in enumerate(lens):
        out = eng2.generate(_prompt(n, seed=40 + i),
                            max_new_tokens=4 + i).tolist()
        assert out == base[i]
    h = eng2.healthz()
    assert h["cp"] == 2
    assert len(h["page_free_by_shard"]) == 2
    assert h["mesh_shape"].get("cp") == 2
    assert profiler.mesh_summary()["cp"] == 2
    assert eng2._pool.per_shard * 2 == eng2._pool.num_pages


@pytest.mark.parametrize("axis", ["tp", "cp"])
def test_sharded_arena_store_moves_no_arena(model, axis):
    """The store under GSPMD: with kv heads split over 'mp' (tp=2) or pages
    over 'cp' (cp=2), the partitioned decode and prefill steps never hold the
    whole arena (a gather across chips would), and no collective or copy has
    a shard of it as its result: each device writes its own shard in place.
    Tokens equal the unsharded engine's."""
    from conftest import hlo_results, paged_engine_steps

    prompts = [_prompt(n, seed=60 + i) for i, n in enumerate([6, 13, 27])]
    eng1 = _paged(model)
    want = [eng1.generate(p, max_new_tokens=5).tolist() for p in prompts]
    if axis == "tp":
        sharded = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel_degree=2))
        sharded.set_state_dict(model.state_dict())
        eng = _paged(sharded, tp=2)
    else:
        eng = _paged(model, cp=2)
    arena = eng._arenas[0].k._data
    whole, shard = tuple(arena.shape), tuple(arena.sharding.shard_shape(arena.shape))
    assert shard != whole
    moves = ("copy", "all-gather", "all-reduce", "all-to-all",
             "collective-permute", "reduce-scatter")
    # bucket 8 is one page (the whole-page form), decode the three-index form
    for name, (fn, args) in paged_engine_steps(eng, 8).items():
        text = fn.lowered_text(*args)
        assert hlo_results(text, whole) == [], name
        stored = hlo_results(text, shard)
        assert any(op in ("scatter", "dynamic-update-slice") for _, op in stored), name
        assert [r for r in stored if r[1].startswith(moves)] == [], name
    assert [eng.generate(p, max_new_tokens=5).tolist() for p in prompts] == want


def test_cp_engine_forced_fused_spec_identity(model):
    """The long-context serving configuration end to end: cp=2 with the
    fused kernel REQUIRED and speculative decode — greedy outputs identical
    to the same stack at cp=1, zero recompiles after warmup on either
    engine, and the decode traffic provably on the cp Pallas kernel."""
    kw = dict(decode_kernel="fused", spec_k=2, prefill_buckets=[8, 32])
    outs = {}
    with _interpret():
        for cp in (1, 2):
            _mesh.set_mesh(None)  # each engine installs (or skips) its own
            eng = _paged(model, cp=cp, **kw)
            eng.warmup()
            warm = eng.compile_counts()
            outs[cp] = [
                eng.generate(_prompt(n, seed=90 + i),
                             max_new_tokens=5).tolist()
                for i, n in enumerate([7, 12])
            ]
            assert eng.compile_counts() == warm  # tables/offsets are data
    assert outs[2] == outs[1]
    assert profiler.flash_pallas_summary().get("paged_decode_fused_cp", 0) >= 1


def test_cp_engine_forced_fused_int8_runs_frozen(model):
    """int8 pages under cp: token-level identity to cp=1 is NOT the
    contract (the shard combine reassociates sums whose near-ties int8
    rounding already narrowed — same stance as test_kv_quant); the
    numerics bar is the kernel-level q8-vs-oracle test above.  Here: the
    quantized cp kernel actually serves the traffic, finishes, and the
    compiled budget stays frozen."""
    with _interpret():
        eng = _paged(model, cp=2, decode_kernel="fused", kv_quant="int8",
                     spec_k=2, prefill_buckets=[8, 32])
        eng.warmup()
        warm = eng.compile_counts()
        out = eng.generate(_prompt(9, seed=94), max_new_tokens=6)
        assert out.size == 15
        assert eng.compile_counts() == warm
    assert profiler.flash_pallas_summary().get(
        "paged_decode_fused_cp_q8", 0) >= 1


def test_cp_engine_debug_invariants_audit(model):
    """The per-step audit under cp understands the layout: per-shard
    refcount accounting, scratch pinned on EVERY shard, and sequence page
    j mapped on shard j % cp."""
    paddle.set_flags({"FLAGS_serve_debug_invariants": True})
    try:
        eng = _paged(model, cp=2)
        base = _prompt(12, seed=55)
        eng.generate(base, max_new_tokens=3)
        eng.generate(np.concatenate([base, _prompt(4, seed=56)]).astype(
            np.int32), max_new_tokens=3)  # prefix hit across shards
        with eng._mu:
            eng._check_page_invariants_locked()
    finally:
        paddle.set_flags({"FLAGS_serve_debug_invariants": False})


def test_cp_context_overflow_carries_per_shard_geometry(model):
    eng = _paged(model, cp=2, max_len=32)
    free_before = eng._pool.free_count()
    with pytest.raises(ContextOverflow) as ei:
        eng.submit(_prompt(40, seed=77), max_new_tokens=4)
    body = ei.value.body()
    assert body["prompt_len"] == 40 and body["max_len"] == 32
    assert body["cp"] == 2
    assert body["pages_per_shard"] == eng.pages_per_seq // 2
    assert body["tokens_per_shard"] == body["pages_per_shard"] * 8
    # typed at ADMISSION: no page was reserved or allocated for the reject
    assert eng._pool.free_count() == free_before
