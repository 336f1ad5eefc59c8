"""Paged KV cache + copy-on-write prefix sharing (ISSUE 7): the block-paged
arena must give the tokens lock-step `model.generate` gives, keep
the zero-recompile contract under join/finish/recycle AND prefix-hit traffic
(chunk prefill + page copy are warmed executables, page tables are data),
isolate shared pages through COW, and keep refcounts/eviction honest under
FLAGS_serve_debug_invariants.

All CPU: same executable shapes as TPU minus the Pallas kernel choice.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference.engine import ContinuousBatchingEngine, QueueFull
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def model():
    np.random.seed(1234)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 250, size=n).astype(np.int32)


def _paged(model, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("queue_depth", 16)
    kw.setdefault("seed", 0)
    kw.setdefault("page_size", 8)
    return ContinuousBatchingEngine(model, **kw)


# ---------------------------------------------------------------------------
# token identity: the engine vs lock-step generate on the same traffic
# ---------------------------------------------------------------------------


def test_paged_matches_lockstep_generate_mixed_traffic(model):
    """Mixed-length greedy traffic through a two-slot engine (joins,
    finishes, recycled slots): every request's tokens must be IDENTICAL to
    lock-step `model.generate` on its own prompt — paging relocates KV rows,
    it never changes what attention reads."""
    lens = [5, 12, 9, 15, 3, 11]
    eng = ContinuousBatchingEngine(
        model, slots=2, max_len=64, prefill_buckets=[8, 16],
        queue_depth=16, seed=0, page_size=8,
    )
    prompts = [_prompt(n, seed=50 + i) for i, n in enumerate(lens)]
    reqs = [
        eng.submit(p, max_new_tokens=4 + (i % 5)) for i, p in enumerate(prompts)
    ]
    eng.run_until_idle()
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        ref = model.generate(
            paddle.to_tensor(p[None]), max_new_tokens=4 + (i % 5)
        ).numpy()[0]
        assert np.array_equal(r.wait(1), ref), i


def test_cow_preserves_shared_page_and_outputs(model):
    """Two requests share a 12-token prefix whose pages sit in the cache
    with a partially-filled tail (12 = 1 full page + 4 rows at page_size 8).
    The second request must COW the tail — its own tokens match a no-cache
    engine bit-for-bit, and re-running the FIRST prompt afterwards still
    matches: the shared source page was never written through."""
    base = _prompt(12, seed=70)
    pa = np.concatenate([base, _prompt(4, seed=71)]).astype(np.int32)
    pb = np.concatenate([base, _prompt(4, seed=72)]).astype(np.int32)

    eng = _paged(model)
    eng.generate(base, max_new_tokens=2)  # seeds the cache: full page + tail
    profiler.reset_paging()
    out_b = eng.generate(pb, max_new_tokens=6)
    pg = profiler.paging_summary()
    assert pg["prefix_hits"] == 1 and pg["cow_copies"] >= 1
    out_a = eng.generate(pa, max_new_tokens=6)  # rereads the shared tail

    fresh = _paged(model, prefix_cache=False)
    assert np.array_equal(out_b, fresh.generate(pb, max_new_tokens=6))
    assert np.array_equal(out_a, fresh.generate(pa, max_new_tokens=6))


# ---------------------------------------------------------------------------
# compile-count contract with paging: chunk prefill + page copy are warmed
# ---------------------------------------------------------------------------


def test_zero_recompiles_with_prefix_traffic(model):
    eng = _paged(model)
    eng.warmup()
    warm = eng.compile_counts()
    assert warm["prefill"] == len(eng.prefill_buckets)
    assert warm["chunk_prefill"] == len(eng.prefill_buckets)
    assert warm["copy"] == 1
    assert warm["decode"] == 1

    base = _prompt(12, seed=60)
    first = eng.submit(
        np.concatenate([base, _prompt(4, seed=61)]).astype(np.int32),
        max_new_tokens=4,
    )
    eng.run_until_idle()
    first.wait(1)
    profiler.reset_paging()
    # overlapping prefix-hit traffic: COW tail copies + chunk prefills of the
    # unshared suffixes, joins/finishes/recycling — all through the warmed
    # executables (tables and rope offsets are traced data, never shapes)
    reqs = [
        eng.submit(
            np.concatenate([base, _prompt(3, seed=62 + i)]).astype(np.int32),
            max_new_tokens=3 + i,
        )
        for i in range(4)
    ]
    eng.run_until_idle()
    for r in reqs:
        assert r.wait(1) is not None
    pg = profiler.paging_summary()
    assert pg["prefix_hits"] == 4
    assert pg["cow_copies"] >= 1
    assert eng.compile_counts() == warm  # 0 recompiles under prefix traffic


def test_warm_restart_preserves_prefix_cache_no_recompile(model):
    """The chaos-serve drill's assertion, in-process: restart() drops slot
    state but keeps the page pool, the prefix cache, and every compiled
    executable — the next shared-prefix request is a cache hit served with
    zero fresh compiles."""
    eng = _paged(model)
    eng.warmup()
    base = _prompt(12, seed=100)
    eng.generate(base, max_new_tokens=2)
    warm = eng.compile_counts()
    eng.restart(reason="drill")
    profiler.reset_paging()
    out = eng.generate(
        np.concatenate([base, _prompt(4, seed=101)]).astype(np.int32),
        max_new_tokens=4,
    )
    assert out.size == 16 + 4
    assert profiler.paging_summary()["prefix_hits"] == 1
    assert eng.compile_counts() == warm


# ---------------------------------------------------------------------------
# allocator: refcounts, eviction, admission backpressure, accounting
# ---------------------------------------------------------------------------


def test_refcount_invariants_and_eviction(model):
    """Distinct prompts overflow a small pool: LRU cache eviction must kick
    in, every step's refcount audit (FLAGS_serve_debug_invariants) must hold,
    and after draining + dropping the cache the pool is fully free — no
    leaked pages anywhere."""
    paddle.set_flags({"FLAGS_serve_debug_invariants": True})
    try:
        eng = _paged(model, slots=2, pool_pages=9)  # 8 usable pages
        profiler.reset_paging()
        for i in range(6):
            eng.generate(_prompt(10 + (i % 3), seed=80 + i), max_new_tokens=6)
        assert profiler.paging_summary()["cache_evictions"] > 0
        with eng._mu:
            eng._check_page_invariants_locked()
        eng._prefix.clear(eng._pool)
        assert eng._pool.free_count() == eng._pool.usable_pages
    finally:
        paddle.set_flags({"FLAGS_serve_debug_invariants": False})


def test_submit_queue_full_when_pool_cannot_fit(model):
    """A request whose lifetime span can never fit the page pool sheds at
    submit with QueueFull + Retry-After, like queue exhaustion does; a
    request that fits is still served."""
    eng = _paged(model, pool_pages=3)  # 2 usable pages = 16 KV rows
    with pytest.raises(QueueFull) as ei:
        eng.submit(_prompt(12, seed=95), max_new_tokens=20)  # span 32 -> 4 pages
    assert ei.value.retry_after_s is not None
    out = eng.generate(_prompt(6, seed=96), max_new_tokens=4)  # 2 pages: fits
    assert out.size == 10


def test_prefix_hit_accounting(model):
    eng = _paged(model)
    profiler.reset_paging()
    base = _prompt(12, seed=90)
    eng.generate(base, max_new_tokens=2)  # compulsory miss, then committed
    eng.generate(
        np.concatenate([base, _prompt(4, seed=91)]).astype(np.int32),
        max_new_tokens=2,
    )
    pg = profiler.paging_summary()
    assert pg["prefix_lookups"] == 2
    assert pg["prefix_hits"] == 1
    assert pg["prefix_hit_rate"] == 0.5
    assert pg["prefill_tokens_saved"] == 12
    assert pg["cache_commits"] >= 1
    assert pg["pages_used_peak"] >= 1
    assert pg["pages_total"] == eng._pool.usable_pages


# ---------------------------------------------------------------------------
# the store: every writer against a plain statement of what a write means
# ---------------------------------------------------------------------------

_PS, _KVH, _D, _PAGES = 8, 2, 4, 12


def _quant_ref(x):
    """The program's own quantizer (the store is what is under test): int8
    values, float32 scales [..., 1]."""
    import jax

    from paddle_tpu.models.llama import _quantize_kv_rows

    return tuple(np.asarray(a) for a in jax.jit(_quantize_kv_rows)(x))


def _store_ref(arena, rows, tables, start, true_len, scales=False):
    """A loop over rows: `arena[page, :, row] = new` (scale buffers keep
    their rows on the last axis).  Rows at or past `true_len` and rows whose
    page entry overruns the table go to scratch page 0, which no reader sees
    and this reference leaves alone."""
    out = arena.copy()
    for n in range(rows.shape[0]):
        for i in range(min(rows.shape[1], true_len)):
            entry, row = divmod(int(start[n]) + i, _PS)
            if entry >= tables.shape[1]:
                continue
            if scales:
                out[tables[n, entry], :, 0, row] = rows[n, i]
            else:
                out[tables[n, entry], :, row] = rows[n, i]
    return out


# (id, writer, s, [table rows], [start per sequence], true_len, int8)
_STORE_CASES = [
    # three slots decode one token each: page starts, page ends, mid-page
    ("decode-sq1", "decode", 1, [[3, 4, 0], [5, 6, 7], [8, 9, 0]], [0, 23, 12], None, False),
    # a window of 4 across a page boundary; slot 1's runs off its 2-entry
    # table (rows 22, 23 land, 24 and 25 are redirected)
    ("verify-sq4-overrun", "decode", 4, [[3, 4], [5, 6], [7, 8]], [6, 14, 0], None, False),
    # slot 1 is inactive: pos 0 over an all-zero table row
    ("decode-inactive-slot", "decode", 1, [[3, 4], [0, 0], [5, 6]], [9, 0, 3], None, False),
    ("verify-inactive-slot", "decode", 4, [[3, 4], [0, 0], [5, 6]], [5, 0, 12], None, False),
    # a window as long as a page goes by whole pages, all slots at once
    ("verify-sq8-whole-pages", "decode", 8, [[3, 4], [5, 6], [0, 0]], [5, 11, 0], None, False),
    ("prefill-fresh-16-len11", "prefill", 16, [[3, 4, 5, 0]], None, 11, False),
    ("prefill-fresh-24-len17", "prefill", 24, [[6, 2, 9, 4]], None, 17, False),
    # the bucket is wider than the table: its last page has no entry
    ("prefill-fresh-24-overrun", "prefill", 24, [[6, 2]], None, 13, False),
    ("prefill-fresh-4-under-a-page", "prefill", 4, [[7, 8]], None, 3, False),
    # prefix-cache hit: the suffix starts mid-page and ends mid-page
    ("prefill-chunk-16-start13", "prefill", 16, [[3, 4, 5, 6]], [13], 9, False),
    ("prefill-chunk-8-start3-full", "prefill", 8, [[3, 4, 5, 6]], [3], 8, False),
    ("prefill-chunk-16-overrun", "prefill", 16, [[3, 4, 5]], [13], 16, False),
    ("int8-decode-sq1", "decode", 1, [[3, 4, 0], [5, 6, 7], [0, 0, 0]], [7, 16, 0], None, True),
    ("int8-verify-sq4-overrun", "decode", 4, [[3, 4], [5, 6]], [6, 14], None, True),
    ("int8-prefill-fresh-16-len11", "prefill", 16, [[3, 4, 5, 0]], None, 11, True),
    ("int8-prefill-chunk-16-start13", "prefill", 16, [[3, 4, 5, 6]], [13], 9, True),
]


@pytest.mark.parametrize("case", _STORE_CASES, ids=[c[0] for c in _STORE_CASES])
def test_store_matches_row_loop(case):
    """Each writer leaves every page but scratch page 0 byte-equal to the
    row loop's: the rows it must write AND the rows it must leave alone."""
    from paddle_tpu.models import llama

    _, writer, s, tables, start, true_len, int8 = case
    rng = np.random.RandomState(len(case[0]) * 1000 + s)
    tables = np.asarray(tables, np.int32)
    b = tables.shape[0]
    shape = (_PAGES, _KVH, _PS, _D)
    if int8:
        ak, av = (rng.randint(-127, 128, shape).astype(np.int8) for _ in range(2))
        ks, vs = (rng.rand(_PAGES, _KVH, 1, _PS).astype(np.float32) for _ in range(2))
    else:
        ak, av = (rng.randn(*shape).astype(np.float32) for _ in range(2))
    k, v = (rng.randn(b, s, _KVH, _D).astype(np.float32) for _ in range(2))
    starts = np.asarray([0] * b if start is None else start, np.int32)
    n_valid = s if true_len is None else true_len
    T = paddle.to_tensor

    if writer == "decode":
        if int8:
            new_ak, new_ks = llama._page_decode_write_quant(
                T(ak), T(ks), T(k), T(tables), T(starts))
            new_av, new_vs = llama._page_decode_write_quant(
                T(av), T(vs), T(v), T(tables), T(starts))
        else:
            new_ak = llama._page_decode_write(T(ak), T(k), T(tables), T(starts))
            new_av = llama._page_decode_write(T(av), T(v), T(tables), T(starts))
    else:
        # the fused writers rope k on the way in; cos 1, sin 0 leaves it as
        # it is, so what must land is k itself
        cos = T(np.ones((64, _D), np.float32))
        sin = T(np.zeros((64, _D), np.float32))
        q = T(rng.randn(b, s, _KVH, _D).astype(np.float32))
        tail = [T(tables[0]), T(np.asarray(true_len, np.int32))]
        tail += [] if start is None else [T(starts)]
        if int8:
            _, _, new_ak, new_av, new_ks, new_vs = llama._rope_page_scatter_quant(
                T(ak), T(av), T(ks), T(vs), q, T(k), T(v), cos, sin, *tail)
        else:
            _, _, new_ak, new_av = llama._rope_page_scatter(
                T(ak), T(av), q, T(k), T(v), cos, sin, *tail)
            # the unfused writer stores the same rows at the same addresses
            alone = llama._page_scatter(T(av), T(v), *tail)
            np.testing.assert_array_equal(alone.numpy()[1:], new_av.numpy()[1:])

    pairs = [(ak, k, new_ak), (av, v, new_av)]
    if int8:
        (qk, sk), (qv, sv) = _quant_ref(k), _quant_ref(v)
        pairs = [(ak, qk, new_ak), (av, qv, new_av)]
        for old, rows, got in ((ks, sk[..., 0], new_ks), (vs, sv[..., 0], new_vs)):
            ref = _store_ref(old, rows, tables, starts, n_valid, scales=True)
            assert got.numpy().dtype == np.float32
            np.testing.assert_array_equal(got.numpy()[1:], ref[1:])
    for old, rows, got in pairs:
        ref = _store_ref(old, rows, tables, starts, n_valid)
        assert got.numpy().dtype == old.dtype
        np.testing.assert_array_equal(got.numpy()[1:], ref[1:])
        # and the case is not vacuous: some mapped row did change
        assert (ref[1:] != old[1:]).any() or tables.max() == 0

