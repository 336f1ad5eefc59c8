"""Fused paged-decode attention (ISSUE 13): the Pallas kernel that reads
the page arena THROUGH the per-slot tables in-kernel must be numerically
interchangeable with the gather-then-dense oracle it replaces — on ragged
mixed traffic, prefix-shared pages, speculative verify windows, scratch-page
overruns, and LoRA co-batches — while the widened `_pallas_viable` gate
(pad-and-mask for non-128 sequences, in-kernel key-padding bias) keeps the
retired fallback reasons at a permanent zero.

Kernels run in Pallas interpret mode on CPU (the same kernel code compiles
on TPU).  The module runs under the runtime sanitizer (conftest
_SANITIZED_MODULES): steady-state traffic through the fused kernel must not
trace, compile, or host-sync.
"""

import contextlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference.engine import ContinuousBatchingEngine
from paddle_tpu.inference.paging import check_table_bounds
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
import paddle_tpu.ops.flash_attention as fa


@pytest.fixture(scope="module")
def model():
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
# array level: fused kernel vs gather-then-dense oracle
# ---------------------------------------------------------------------------


def _arena(num_pages=9, ps=8, hk=2, d=16, seed=0):
    r = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(r.rand(num_pages, hk, ps, d).astype(np.float32) - 0.5)
    return mk(), mk()


def _both(q, ak, av, tables, pos, max_len):
    """(the walk interpreted, gather); `av` None is ONE arena, the keys' rows
    the values too: said to the kernel as `arena_v=None`, which the
    dispatcher's K/V head-width rule never sees."""
    with _interpret():
        if av is None:
            walked = fa._fused_paged_decode(
                q, ak, None, tables, pos, max_len, q.shape[-1] ** -0.5, True)
        else:
            walked = fa.paged_decode_attention_array(
                q, ak, av, tables, pos, max_len, kernel="fused")
    gather = fa.paged_decode_attention_array(
        q, ak, ak if av is None else av, tables, pos, max_len, kernel="gather")
    return np.asarray(walked), np.asarray(gather)


@pytest.fixture
def heads_per_step(monkeypatch):
    """Force how many KV heads a grid step of the walk moves (the program
    picks it from the static shape alone; a test steers it here)."""
    def force(hb):
        def pick(hk, *shape):
            assert hk % hb == 0
            return hb

        monkeypatch.setattr(fa, "_pick_kv_heads_block", pick)

    return force


@pytest.fixture
def pages_per_step(monkeypatch):
    """Force how many pages a copy block of the looping walk holds."""
    def force(pp):
        monkeypatch.setattr(fa, "_pick_pages_per_step", lambda *shape: pp)

    return force


def _divisors(n):
    return [i for i in range(1, n + 1) if n % i == 0]


def _walk_case(name):
    """(q, arena_k, arena_v or None, tables, pos, max_len, forced pages a
    step or None, mesh degrees or None) of one class of looping walk."""
    r = np.random.RandomState(len(name))
    rnd = lambda *shape: jnp.asarray(r.rand(*shape).astype(np.float32) - 0.5)
    ps = 8

    def tables(held, P, first=1):
        """Each slot its own pages, in an order that is not the arena's; 0
        (the scratch page) in the columns it does not hold."""
        t = np.zeros((len(held), P), np.int32)
        ids = r.permutation(sum(held)) + first
        for i, n in enumerate(held):
            t[i, :n], ids = ids[:n], ids[n:]
        return jnp.asarray(t)

    if name in ("chat32", "chat32-pp4", "tp2"):
        # the serving cell's class: 8 KV heads of 128, 4 q heads each, 16 columns
        pos = np.array([0, 37, 127, 64, 9], np.int32)
        held = pos // ps + 1
        arena = lambda: rnd(int(held.sum()) + 1, 8, ps, 128)
        return (rnd(5, 1, 32, 128), arena(), arena(), tables(held, 16), jnp.asarray(pos), 128,
                4 if name == "chat32-pp4" else None, {"mp": 2} if name == "tp2" else None)
    if name in ("reason64", "reason64-pp3", "edges"):
        # the latent walk's class: one KV head, a wide row, ONE arena; 19
        # columns are 2 blocks of 8 and 3 over, 6 of 3 and 1 over; slots at 0,
        # at k * ps - 1 and k * ps, one holding every column beside one holding one
        P = 19
        pos = np.array([0, 7, 8, 63, 64, P * ps - 1, 3, 100], np.int32)
        held = pos // ps + 1
        return (rnd(8, 1, 32, 640), rnd(int(held.sum()) + 1, 1, ps, 640), None, tables(held, P),
                jnp.asarray(pos), P * ps, {"reason64": None, "reason64-pp3": 3, "edges": 1}[name], None)
    if name in ("verify3", "verify3-one-arena"):
        # the verify window: 3 rows a slot at pos, pos + 1, pos + 2; a window
        # that crosses into a page the table does not map reads scratch page 0
        pos = np.array([14, 0, 5, 29, 61], np.int32)
        held = np.array([2, 1, 1, 4, 8])  # pos 14: rows 14..16 cross into column 2, unmapped
        one = name.endswith("one-arena")
        hk = 1 if one else 2
        arena = lambda: rnd(int(held.sum()) + 1, hk, ps, 16)
        return (rnd(5, 3, 4, 16), arena(), None if one else arena(), tables(held, 8), jnp.asarray(pos), 64,
                2, None)
    raise KeyError(name)


class TestFusedVsGather:
    @pytest.mark.parametrize("sq", [1, 4])
    @pytest.mark.parametrize(
        "hk,hb", [(hk, hb) for hk in (4, 6) for hb in _divisors(hk)]
    )
    def test_heads_per_step_parity(self, heads_per_step, hk, hb, sq):
        """Every divisor of `hk` as the heads a step moves gives the gather
        oracle's result: the head is a batch dim of both dots, nothing else.
        Ragged frontiers, GQA packing (rep 2), max_len below the table span."""
        heads_per_step(hb)
        ak, av = _arena(num_pages=9, ps=8, hk=hk, d=16, seed=hk)
        r = np.random.RandomState(17 + hb)
        q = jnp.asarray(r.rand(4, sq, 2 * hk, 16).astype(np.float32) - 0.5)
        tables = jnp.asarray(
            [[1, 2, 3, 4], [5, 6, 0, 0], [7, 0, 0, 0], [8, 3, 5, 1]],
            jnp.int32,
        )
        pos = jnp.asarray([27, 11, 3, 20], jnp.int32)
        profiler.reset()
        fused, gather = _both(q, ak, av, tables, pos, max_len=28)
        np.testing.assert_allclose(fused, gather, rtol=2e-5, atol=2e-5)
        (walk,) = profiler.paged_walk_summary()
        assert walk["heads_per_step"] == hb
        assert walk["grid_steps"] == 4 * (hk // hb)  # the pages are a loop inside

    @pytest.mark.parametrize("sq", [1, 4])
    def test_stale_entries_past_the_last_page_are_inert(self, sq):
        """A table that still names pages past a slot's newest visible one
        (here pages of NaNs) reads as the table with zeros there: the walk's
        loop ends at the slot's newest visible page, so nothing is copied or
        computed for them."""
        ak, av = _arena(num_pages=9, ps=8, hk=2, d=16, seed=9)
        ak, av = (a.at[jnp.asarray([6, 8])].set(jnp.nan) for a in (ak, av))
        r = np.random.RandomState(19)
        q = jnp.asarray(r.rand(3, sq, 4, 16).astype(np.float32) - 0.5)
        clean = np.array([[1, 2, 0, 0], [5, 0, 0, 0], [7, 3, 4, 0]], np.int32)
        stale = np.array([[1, 2, 6, 8], [5, 8, 6, 8], [7, 3, 4, 6]], np.int32)
        # the newest visible row, pos + sq - 1, stays on the last clean page
        pos = jnp.asarray([12 - sq, 7 - sq, 20], jnp.int32)
        with _interpret():
            got = [
                np.asarray(fa.paged_decode_attention_array(
                    q, ak, av, jnp.asarray(t), pos, 32, kernel="fused"))
                for t in (clean, stale)
            ]
        np.testing.assert_array_equal(got[0], got[1])
        gather = fa.paged_decode_attention_array(
            q, ak, av, jnp.asarray(clean), pos, 32, kernel="gather")
        np.testing.assert_allclose(got[1], np.asarray(gather), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("case", [
        "chat32", "chat32-pp4", "reason64", "reason64-pp3", "edges", "verify3",
        "verify3-one-arena", "tp2"])
    def test_looped_walk_parity(self, pages_per_step, case):
        """The walk off the grid (one grid step a slot and head block, the
        slot's own pages in a loop, several a copy, the next block and the
        next slot's first block in flight) against the gather oracle, in the
        classes of shape the cells run and at the loop's edges."""
        from paddle_tpu.distributed import mesh as pmesh

        q, ak, av, tables, pos, max_len, pp, degrees = _walk_case(case)
        if pp is not None:
            pages_per_step(pp)
        prev = pmesh.get_mesh()
        try:
            if degrees:
                pmesh.build_mesh(devices=jax.devices()[:2], **degrees)
            profiler.reset()
            walked, gather = _both(q, ak, av, tables, pos, max_len)
        finally:
            pmesh.set_mesh(prev)
        assert np.isfinite(walked).all()
        np.testing.assert_allclose(walked, gather, rtol=2e-5, atol=2e-5)
        (walk,) = profiler.paged_walk_summary()
        local_hk = ak.shape[1] // (degrees or {}).get("mp", 1)
        assert walk["grid_steps"] == q.shape[0] * local_hk // walk["heads_per_step"]
        assert walk["kv_operands"] == (1 if av is None else 2)
        if pp is not None:
            assert walk["pages_per_step"] == pp

    @pytest.mark.parametrize("one_arena", [False, True], ids=["k-and-v", "one-arena"])
    @pytest.mark.parametrize("pp", [1, 3, None])
    def test_looped_walk_copies_no_page_past_the_last(self, pages_per_step, pp, one_arena):
        """Table columns past a slot's last page name a page of NaNs: the
        output is finite and equal to the clean table's, block by block of
        every size (a copied NaN would reach the output through a weight of
        0); the room of a block's pages that the slot does not hold is zeroed,
        not left as the new kernel's or the slot before's (interpret mode makes
        scratch of NaNs)."""
        if pp is not None:
            pages_per_step(pp)
        r = np.random.RandomState(23)
        P, ps, hk = 7, 8, 1 if one_arena else 2
        mk = lambda: jnp.asarray(r.rand(12, hk, ps, 16).astype(np.float32) - 0.5).at[11].set(jnp.nan)
        ak, av = mk(), None if one_arena else mk()
        q = jnp.asarray(r.rand(4, 1, 4, 16).astype(np.float32) - 0.5)
        clean = np.array([[1, 2, 3, 4, 5, 6, 7], [5, 6, 0, 0, 0, 0, 0],
                          [0, 0, 0, 0, 0, 0, 0], [8, 3, 5, 1, 9, 0, 0]], np.int32)
        stale = np.where(clean == 0, 11, clean)
        stale[2, 0] = 0  # the idle slot, parked on scratch page 0 at pos 0, walks that page
        pos = jnp.asarray([55, 15, 0, 39], jnp.int32)
        got = [_both(q, ak, av, jnp.asarray(t), pos, 56)[0] for t in (clean, stale)]
        assert np.isfinite(got[1]).all()
        np.testing.assert_array_equal(got[0], got[1])
        gather = _both(q, ak, av, jnp.asarray(clean), pos, 56)[1]
        np.testing.assert_allclose(got[1], gather, rtol=2e-5, atol=2e-5)

    def test_many_q_rows_keep_the_grid(self):
        """A q block of more rows than `_PAGED_WALK_LOOP_ROWS` (a chunk
        prefill) keeps a page a grid step, both arenas through the pipeline."""
        ak, av = _arena(num_pages=9, ps=8, hk=2, d=16, seed=4)
        r = np.random.RandomState(29)
        sq = fa._PAGED_WALK_LOOP_ROWS // 2 + 8  # 2 q heads a KV head
        q = jnp.asarray(r.rand(2, sq, 4, 16).astype(np.float32) - 0.5)
        tables = jnp.asarray(np.tile([[1, 2, 3, 4, 5, 6, 7, 8, 1, 2, 3, 4]], (2, 1)), jnp.int32)
        profiler.reset()
        fused, gather = _both(q, ak, av, tables, jnp.asarray([3, 20], jnp.int32), max_len=96)
        np.testing.assert_allclose(fused, gather, rtol=2e-5, atol=2e-5)
        (walk,) = profiler.paged_walk_summary()
        assert walk["grid_steps"] == 2 * (2 // walk["heads_per_step"]) * 12
        assert (walk["pages_per_step"], walk["kv_operands"]) == (1, 2)

    @pytest.mark.parametrize("sq", [1, 4])
    def test_ragged_gqa_parity(self, sq):
        """Mixed per-slot positions (including a fresh slot at pos 0 and a
        slot whose newest page is partially filled), GQA group packing
        (h=4 over hk=2), and max_len below the table span (the gather's
        [:max_len] slice must be reproduced by the in-kernel jid fence)."""
        ak, av = _arena(num_pages=9, ps=8, hk=2, d=16)
        b, h, d = 4, 4, 16
        r = np.random.RandomState(7)
        q = jnp.asarray(r.rand(b, sq, h, d).astype(np.float32) - 0.5)
        tables = jnp.asarray(
            [[1, 2, 3, 4], [5, 6, 0, 0], [7, 0, 0, 0], [8, 3, 5, 1]],
            jnp.int32,
        )
        pos = jnp.asarray([27, 11, 3, 20], jnp.int32)  # ragged frontiers
        fused, gather = _both(q, ak, av, tables, pos, max_len=28)
        np.testing.assert_allclose(fused, gather, rtol=2e-5, atol=2e-5)

    def test_shared_pages_and_scalar_pos(self):
        """Two slots mapping the SAME physical pages (prefix sharing) must
        read identical K/V; scalar pos broadcasts to every slot (the chunk
        prefill call shape)."""
        ak, av = _arena(seed=3)
        r = np.random.RandomState(11)
        q1 = r.rand(1, 1, 4, 16).astype(np.float32) - 0.5
        q = jnp.asarray(np.concatenate([q1, q1]))  # same query in both slots
        tables = jnp.asarray([[2, 4, 6, 0], [2, 4, 6, 0]], jnp.int32)
        fused, gather = _both(q, ak, av, tables, jnp.int32(17), max_len=32)
        np.testing.assert_allclose(fused, gather, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(fused[0], fused[1], rtol=0, atol=0)

    @pytest.mark.parametrize("hb", [None, 1, 2])
    def test_spec_verify_window_with_scratch_overrun(self, heads_per_step, hb):
        """The [slots, k+1] verify shape: window rows attend j <= pos + i
        per row, and a window overrunning the mapped prefix reads scratch
        page 0 through table entry 0 — exactly what the gather path reads
        for those rows, so parity covers the rejected-draft territory.
        With the program's own pick of heads per step (None) and each forced."""
        if hb is not None:
            heads_per_step(hb)
        ak, av = _arena(seed=5)
        r = np.random.RandomState(13)
        q = jnp.asarray(r.rand(3, 4, 4, 16).astype(np.float32) - 0.5)
        # slot 0's window [14, 18) crosses into entry 2 == 0 (scratch)
        tables = jnp.asarray(
            [[3, 5, 0, 0], [1, 2, 6, 7], [0, 0, 0, 0]], jnp.int32
        )
        pos = jnp.asarray([14, 9, 0], jnp.int32)  # slot 2: inactive, parked
        fused, gather = _both(q, ak, av, tables, pos, max_len=32)
        assert np.isfinite(fused).all()
        np.testing.assert_allclose(fused, gather, rtol=2e-5, atol=2e-5)

    def test_kernel_arg_validated(self):
        ak, av = _arena()
        q = jnp.zeros((1, 1, 4, 16), jnp.float32)
        t = jnp.zeros((1, 4), jnp.int32)
        with pytest.raises(ValueError, match="auto|fused|gather"):
            fa.paged_decode_attention_array(
                q, ak, av, t, jnp.int32(0), 32, kernel="dense"
            )
        # 'fused' must refuse, not silently degrade, when ineligible
        with pytest.raises(ValueError, match="fused"):
            fa.paged_decode_attention_array(
                q, ak[:, :, :4], av[:, :, :4], t, jnp.int32(0), 32, kernel="fused"
            )  # page_size 4: not sublane-aligned

    def test_auto_dispatch_counts_pallas_call(self):
        """kernel='auto' under interpret takes the fused kernel and counts
        the dispatch; off the Pallas path it falls back to gather and logs
        the reason only for genuinely ineligible shapes (eligible shapes on
        CPU just take the oracle silently — CPU has no fast path to miss)."""
        ak, av = _arena()
        q = jnp.zeros((1, 1, 4, 16), jnp.float32)
        t = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
        profiler.reset_flash_pallas()
        profiler.reset_flash_fallbacks()
        with _interpret():
            fa.paged_decode_attention_array(q, ak, av, t, jnp.int32(5), 32)
        assert profiler.flash_pallas_summary() == {"paged_decode_fused": 1}
        assert profiler.flash_fallback_summary() == {}
        with _interpret():  # ineligible page size -> counted fallback
            fa.paged_decode_attention_array(
                q, ak[:, :, :4], av[:, :, :4], t, jnp.int32(5), 16
            )
        assert (
            profiler.flash_fallback_summary()["paged page_size not 8-aligned"]
            == 1
        )

    def test_walk_geometry_follows_the_static_shape(self):
        """`paged_walk_summary()` at the two serving cells' published shapes,
        traced and not run.  Mistral widths over `chat32`'s arena: batch-32
        decode and a verify window of 5 take all 8 KV heads of a page and one
        grid step a slot (32 for 4,096 pages of table), 4 pages a copy, K and
        V; a chunk prefill, whose q rows fill VMEM, keeps one head and one
        page a grid step.  Ling-3's latent walk over `reason64`'s arena: 64
        grid steps for 16,384 columns, 8 pages a copy, ONE operand."""
        bf16 = jnp.bfloat16
        arena = jax.ShapeDtypeStruct((513, 8, 128, 128), bf16)
        profiler.reset()
        for b, sq in ((32, 1), (32, 5), (1, 256), (1, 512)):
            with _interpret():
                jax.eval_shape(
                    lambda q, ak, av, t, p: fa.paged_decode_attention_array(
                        q, ak, av, t, p, 2048),
                    jax.ShapeDtypeStruct((b, sq, 32, 128), bf16), arena, arena,
                    jax.ShapeDtypeStruct((b, 16), jnp.int32),
                    jax.ShapeDtypeStruct((b,), jnp.int32),
                )
        page = 128 * 128 * 2  # one head's K or V tile of a page, bf16
        fields = ("heads_per_step", "grid_steps", "pages_per_step", "kv_operands", "kv_bytes_per_step")
        walks = {(w["b"], w["sq"]): tuple(w[f] for f in fields) for w in profiler.paged_walk_summary()}
        for key in ((32, 1), (32, 5)):
            assert walks[key] == (8, 32, 4, 2, 2 * 4 * 8 * page)
        for key in ((1, 256), (1, 512)):
            assert walks[key] == (1, 8 * 16, 1, 2, 2 * page)
        profiler.reset()
        assert profiler.paged_walk_summary() == []
        jax.eval_shape(
            lambda q, lat, t, p: fa._fused_paged_decode(q, lat, None, t, p, 32768, 0.07, True),
            jax.ShapeDtypeStruct((64, 1, 32, 640), bf16),
            jax.ShapeDtypeStruct((16385, 1, 128, 640), bf16),
            jax.ShapeDtypeStruct((64, 256), jnp.int32),
            jax.ShapeDtypeStruct((64,), jnp.int32),
        )
        (walk,) = profiler.paged_walk_summary()
        assert walk == {"b": 64, "sq": 1, "heads_per_step": 1, "grid_steps": 64, "pages_per_step": 8,
                        "kv_operands": 1, "kv_bytes_per_step": 8 * 128 * 640 * 2}
        profiler.reset()

    @pytest.mark.parametrize(
        "hk,sq,want",
        [(8, 1, 8), (8, 5, 8), (8, 256, 1), (8, 512, 1), (2, 1, 2), (2, 256, 1)],
        ids=["decode", "verify5", "chunk256", "chunk512", "decode-tp4", "chunk256-tp4"],
    )
    def test_heads_per_step_picker(self, hk, sq, want):
        """Mistral widths (4 q heads a KV head, page 128, head dim 128, bf16);
        under tp=4 the kernel sees the local 2 KV heads."""
        qr = -(-4 * sq // 8) * 8
        assert fa._pick_kv_heads_block(hk, qr, 128, 128, 2) == want

    @pytest.mark.parametrize(
        "hb,qr,d,operands,cols,want",
        [(8, 8, 128, 2, 16, 4), (8, 24, 128, 2, 16, 4), (2, 8, 128, 2, 16, 8), (1, 32, 640, 1, 256, 8),
         (1, 32, 640, 1, 4, 4), (8, 8, 256, 2, 64, 2), (8, 128, 64, 2, 64, 4)],
        ids=["chat32", "chat32-verify5", "chat32-tp4", "reason64", "few-columns", "wide-heads", "many-rows"],
    )
    def test_pages_per_step_picker(self, hb, qr, d, operands, cols, want):
        """Pages of 128 bf16 rows: two blocks of `want` pages from each arena
        fit `_PAGED_WALK_VMEM_BUDGET`, two f32 score tiles of a block too,
        within the bound on a block's pages and the table's columns."""
        assert fa._pick_pages_per_step(hb, qr, 128, d, 2, operands, cols) == want


# ---------------------------------------------------------------------------
# engine level: decode_kernel="fused" vs "gather" token identity
# ---------------------------------------------------------------------------


class TestEngineFused:
    def test_mixed_traffic_token_identity_zero_recompiles(self, model):
        """Greedy replay of mixed ragged traffic with a shared prefix pair:
        the fused engine's tokens must be IDENTICAL to the gather engine's,
        with zero recompiles after warmup (tables stay traced data in both
        kernels) and zero fallbacks recorded on the fused leg."""
        lens = [5, 12, 9, 15, 3]
        base = _prompt(12, seed=40)
        outs = {}
        for kern in ("gather", "fused"):
            ctx = _interpret() if kern == "fused" else contextlib.nullcontext()
            with ctx:
                eng = _paged(model, slots=2, decode_kernel=kern)
                eng.warmup()
                warm = eng.compile_counts()
                profiler.reset_flash_fallbacks()
                reqs = [
                    eng.submit(_prompt(n, seed=30 + i), max_new_tokens=3 + (i % 3))
                    for i, n in enumerate(lens)
                ]
                reqs += [
                    eng.submit(
                        np.concatenate([base, _prompt(3, seed=45 + i)]).astype(
                            np.int32
                        ),
                        max_new_tokens=3,
                    )
                    for i in range(2)
                ]
                eng.run_until_idle()
                outs[kern] = [r.wait(1).tolist() for r in reqs]
                assert eng.compile_counts() == warm
                assert profiler.flash_fallback_summary() == {}
        assert outs["fused"] == outs["gather"]

    def test_spec_decode_token_identity(self, model):
        """spec_k=3: the verify body's [slots, k+1] window rides the fused
        kernel — accepted/rejected splits, and therefore tokens, must match
        the gather verify exactly."""
        outs = {}
        for kern in ("gather", "fused"):
            ctx = _interpret() if kern == "fused" else contextlib.nullcontext()
            with ctx:
                eng = _paged(model, slots=2, spec_k=3, decode_kernel=kern)
                p = np.tile(_prompt(6, seed=55), 2).astype(np.int32)  # repetitive
                reqs = [
                    eng.submit(p, max_new_tokens=8),
                    eng.submit(_prompt(9, seed=56), max_new_tokens=6),
                ]
                eng.run_until_idle()
                outs[kern] = [r.wait(1).tolist() for r in reqs]
        assert outs["fused"] == outs["gather"]

    def test_lora_cobatch_token_identity(self, model):
        """Adapter co-batching composes: LoRA deltas land in q/k/v BEFORE
        attention, so the fused kernel must be adapter-agnostic — mixed
        base + adapter traffic matches the gather engine bit-for-bit."""
        from paddle_tpu.lora import AdapterArena, AdapterRegistry, make_random

        outs = {}
        for kern in ("gather", "fused"):
            reg = AdapterRegistry(model.config)
            for i in range(3):
                make_random(reg, f"a{i + 1}", rank=4, seed=i + 1, scale=0.02)
            ctx = _interpret() if kern == "fused" else contextlib.nullcontext()
            with ctx:
                eng = _paged(
                    model, slots=2, decode_kernel=kern,
                    lora=AdapterArena(reg, capacity=3, rank_max=4),
                )
                reqs = [
                    eng.submit(
                        _prompt(8, seed=60 + i), max_new_tokens=4,
                        adapter=None if i == 0 else f"a{i}",
                    )
                    for i in range(4)
                ]
                eng.run_until_idle()
                outs[kern] = [r.wait(1).tolist() for r in reqs]
        assert outs["fused"] == outs["gather"]

    def test_fused_requires_eligible_geometry_at_construction(self, model):
        with pytest.raises(ValueError, match="fused"):
            _paged(model, decode_kernel="fused", page_size=4)
        with pytest.raises(ValueError, match="auto|fused|gather"):
            _paged(model, decode_kernel="dense")


# ---------------------------------------------------------------------------
# widened dense-kernel gate: non-128 sequences and key-padding masks now
# take Pallas — the retired fallback reasons must never fire again
# ---------------------------------------------------------------------------


def _dense_ref(q, k, v, causal, kbias=None):
    qt = jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32)
    kt = jnp.transpose(k, (0, 2, 1, 3)).astype(jnp.float32)
    vt = jnp.transpose(v, (0, 2, 1, 3)).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(q.shape[-1])
    if causal:
        ids = np.arange(q.shape[1])
        s = jnp.where(ids[:, None] >= ids[None, :], s, -1e30)
    if kbias is not None:
        s = s + kbias[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    return jnp.transpose(jnp.einsum("bhqk,bhkd->bhqd", p, vt), (0, 2, 1, 3))


class TestWidenedGate:
    def _qkv(self, s, b=2, h=2, d=32, seed=0):
        r = np.random.RandomState(seed)
        mk = lambda: jnp.asarray(r.rand(b, s, h, d).astype(np.float32) - 0.5)
        return mk(), mk(), mk()

    @pytest.mark.parametrize("s", [72, 200])
    def test_non_128_multiple_takes_pallas(self, s):
        """Ragged serving lengths pad-and-fence instead of falling back: the
        retired 'seq not a 128-multiple' reason stays at zero while the
        kernel-dispatch counter moves, and the padded rows never leak into
        real rows' softmax (parity against the dense reference)."""
        q, k, v = self._qkv(s)
        profiler.reset_flash_pallas()
        profiler.reset_flash_fallbacks()
        fa._fallback_logged = set()
        with _interpret():
            out = fa.sdpa_array(q, k, v, causal=True)
        assert profiler.flash_pallas_summary() == {"flash_fwd": 1}
        assert profiler.flash_fallback_summary() == {}
        assert not fa._fallback_logged
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_dense_ref(q, k, v, True)),
            rtol=2e-5, atol=2e-5,
        )

    def test_key_padding_mask_takes_pallas(self):
        """A plain [b,1,1,s] additive key-padding mask lowers to an
        in-kernel bias — no 'attn_mask given' fallback — and the masked
        keys carry exactly zero weight."""
        s = 72  # non-aligned AND masked: both gaps closed at once
        q, k, v = self._qkv(s, seed=2)
        keep = np.zeros((2, s), np.float32)
        keep[0, 60:] = -1e30  # batch row 0 pads keys past 60
        keep[1, 48:] = -1e30
        mask = jnp.asarray(keep[:, None, None, :])
        profiler.reset_flash_pallas()
        profiler.reset_flash_fallbacks()
        with _interpret():
            out = fa.sdpa_array(q, k, v, mask=mask)
        assert profiler.flash_pallas_summary() == {"flash_fwd": 1}
        assert profiler.flash_fallback_summary() == {}
        ref = _dense_ref(q, k, v, False, kbias=jnp.asarray(keep))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5
        )

    def test_key_padding_mask_grads(self):
        """The backward rule reconstructs kbias + padding deterministically;
        grads must match the dense reference, with pad/masked columns
        contributing nothing."""
        s = 72
        q, k, v = self._qkv(s, b=1, seed=3)
        keep = np.zeros((1, s), np.float32)
        keep[0, 64:] = -1e30
        mask = jnp.asarray(keep[:, None, None, :])

        def lp(q, k, v):
            return (fa.sdpa_array(q, k, v, mask=mask) ** 2).sum()

        def lr(q, k, v):
            return (_dense_ref(q, k, v, False, jnp.asarray(keep)) ** 2).sum()

        profiler.reset_flash_pallas()
        with _interpret():
            gp = jax.grad(lp, argnums=(0, 1, 2))(q, k, v)
        assert profiler.flash_pallas_summary() == {
            "flash_fwd": 1, "flash_bwd": 1
        }
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gp, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name} mismatch",
            )
        # masked-out key columns got exactly zero dk/dv
        assert np.abs(np.asarray(gp[1])[:, 64:]).max() == 0.0
        assert np.abs(np.asarray(gp[2])[:, 64:]).max() == 0.0

    def test_non_key_padding_mask_still_falls_back(self):
        """A full [b,1,s,s] mask is NOT lowerable — it must keep taking the
        honest fallback with the structural reason, never a retired one."""
        q, k, v = self._qkv(128, b=1, seed=4)
        mask = jnp.zeros((1, 1, 128, 128), jnp.float32)
        profiler.reset_flash_fallbacks()
        fa._fallback_logged = set()
        with _interpret():
            fa.sdpa_array(q, k, v, mask=mask)
        fb = profiler.flash_fallback_summary()
        assert fb == {"attn_mask not key-padding": 1}

    def test_flight_dump_header_carries_kernel_dispatch(self, tmp_path):
        """A crash dump must say which attention kernels the process was
        built with — the first question a perf/correctness triage asks."""
        import json

        from paddle_tpu.obs import flight

        profiler.reset_flash_pallas()
        profiler.reset_flash_fallbacks()
        fa._log_pallas_call("paged_decode_fused")
        fa._log_pallas_fallback("head_dim > 256", shape=(1, 1, 2, 512))
        p = flight.dump("unit", path=str(tmp_path / "flight-unit.jsonl"))
        with open(p) as f:
            header = json.loads(f.readline())
        assert header["kind"] == "header"
        assert header["flash"]["pallas"] == {"paged_decode_fused": 1}
        assert header["flash"]["fallbacks"] == {"head_dim > 256": 1}

    def test_retired_reasons_render_zero_in_metrics(self):
        """The retired label values stay in the exported set at 0 — the
        dashboards prove the gaps are closed by a flatline, not by a
        series disappearing."""
        from paddle_tpu.obs import metrics

        for r in ("seq not a 128-multiple", "attn_mask given"):
            assert r in fa._FALLBACK_REASONS
        profiler.reset_flash_fallbacks()
        profiler.reset_flash_pallas()
        text = metrics.render()
        assert 'paddle_flash_fallbacks_total{reason="seq not a 128-multiple"} 0' in text
        assert 'paddle_flash_fallbacks_total{reason="attn_mask given"} 0' in text
        assert 'paddle_flash_pallas_calls_total{kernel="paged_decode_fused"} 0' in text


# ---------------------------------------------------------------------------
# decode_attention_array zero-copy bugfix + table-bounds invariant
# ---------------------------------------------------------------------------


def test_decode_zero_copy_when_aligned():
    """The hoisted padding check: an already-8-aligned q chunk must reach
    the Pallas decode kernel with NO pad in the traced program and the
    kernel sees its rows as they are; a ragged one pads to the next sublane
    tile (65 -> 72 rows, no further) and is sliced back."""
    k = jnp.zeros((1, 128, 2, 32), jnp.float32)
    v = jnp.zeros((1, 128, 2, 32), jnp.float32)

    def prims(jaxpr, acc):
        """(primitive, output shape), recursing through call wrappers
        (jnp.pad hides inside one: `pjit`, named `jit` since jax 0.9) but
        NOT into the pallas kernel body."""
        for e in jaxpr.eqns:
            acc.append((e.primitive.name, tuple(e.outvars[0].aval.shape)))
            if e.primitive.name in ("pjit", "jit"):
                prims(e.params["jaxpr"].jaxpr, acc)
        return acc

    def run(sq):
        q = jnp.zeros((1, sq, 2, 32), jnp.float32)
        with _interpret():
            jx = jax.make_jaxpr(
                lambda q, k, v: fa.decode_attention_array(q, k, v, jnp.int32(0))
            )(q, k, v)
        return prims(jx.jaxpr, [])

    aligned = run(64)
    assert "pad" not in [name for name, _ in aligned]
    assert ("pallas_call", (2, 64, 32)) in aligned
    ragged = run(65)
    assert ("pad", (2, 72, 32)) in ragged
    assert ("pallas_call", (2, 72, 32)) in ragged
    assert ("slice", (2, 65, 32)) in ragged  # the padded rows never leave


def test_check_table_bounds():
    """The fused kernel indexes the arena by the RAW table entry (no clamp)
    — the host invariant must catch any out-of-range id before it reaches
    the device."""
    check_table_bounds(np.array([[0, 1, 8], [3, 0, 0]]), num_pages=9)
    check_table_bounds(np.zeros((0, 4), np.int32), num_pages=9)  # empty ok
    with pytest.raises(AssertionError, match="out of arena bounds"):
        check_table_bounds(np.array([[0, 9]]), num_pages=9)
    with pytest.raises(AssertionError, match="out of arena bounds"):
        check_table_bounds(np.array([[-1, 2]]), num_pages=9)


def test_engine_invariants_cover_table_bounds(model):
    """FLAGS_serve_debug_invariants audits the live table through
    check_table_bounds; corrupting an entry past the pool trips it."""
    paddle.set_flags({"FLAGS_serve_debug_invariants": True})
    try:
        eng = _paged(model)
        eng.generate(_prompt(10, seed=70), max_new_tokens=2)
        with eng._mu:
            eng._check_page_invariants_locked()  # clean pass
            saved = eng._page_table[0, 0]
            eng._page_table[0, 0] = eng._pool.num_pages + 3
            with pytest.raises(AssertionError, match="out of arena bounds"):
                eng._check_page_invariants_locked()
            eng._page_table[0, 0] = saved
    finally:
        paddle.set_flags({"FLAGS_serve_debug_invariants": False})
