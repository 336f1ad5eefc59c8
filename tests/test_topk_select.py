"""DeepSeek-V3.2's prefill selection as one Pallas kernel
(`ops/topk_select.py`), interpreted on the CPU, against the XLA form that
`models/deepseek_v32.py:_select_mask` runs off the TPU: the same mask, bit
for bit, on drawn scores, on scores with many ties (the prefix count taken),
with rows that see fewer than k keys, contexts that end inside a key block
and at its edge, `-0.0` beside `0.0`, and a top-k larger than the context or
the table; the dispatcher off the TPU, interpreted, and on a refused shape."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.models import deepseek_v32 as dsv
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import topk_select as ts


@pytest.fixture
def interpret():
    fa._FORCE_INTERPRET = True
    try:
        yield
    finally:
        fa._FORCE_INTERPRET = False


def draw(kind, seed, s, L):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(s, L))
    if kind == "ties":  # one decimal: dozens of keys a value, the k-th's ties overflow its room
        return np.round(x, 1)
    if kind == "signed-zeros":  # relu(a) w over heads: exact zeros of either sign, most keys among them
        return np.where(x < 0.3, np.where(rng.random((s, L)) < 0.5, -0.0, 0.0), x)
    return x


def chunk_score(kind, s, L, kb, start, true_len, seed=0):
    """A chunk's score as `_prefill_attention` leaves it: a query at `start +
    row` sees the keys up to its position inside the context's key blocks
    (padding rows past `true_len` see into the last block), -inf elsewhere.
    Returns (score, n_blocks)."""
    n_blocks = -(-(start + true_len) // kb)
    q_pos = start + np.arange(s)[:, None]
    keys = np.arange(L)[None, :]
    seen = (keys <= q_pos) & (keys < n_blocks * kb)
    return jnp.asarray(np.where(seen, draw(kind, seed, s, L), -np.inf), jnp.float32), n_blocks


CASES = [  # (draw, rows, table, key block, start, true_len, k)
    pytest.param("normal", 64, 512, 128, 0, 64, 16, id="first-chunk-rows-under-k"),
    pytest.param("normal", 64, 512, 128, 130, 64, 48, id="drawn-mid-block"),
    pytest.param("ties", 64, 512, 128, 200, 64, 40, id="ties-mid-block"),
    pytest.param("ties", 64, 512, 128, 192, 64, 40, id="ties-at-block-edge"),
    pytest.param("ties", 96, 512, 256, 160, 70, 100, id="ties-padded-tail-two-chunks-a-block"),
    pytest.param("signed-zeros", 64, 512, 128, 100, 50, 24, id="signed-zeros"),
    pytest.param("signed-zeros", 64, 512, 128, 300, 64, 200, id="signed-zeros-tie-at-zero"),
    pytest.param("normal", 64, 512, 128, 0, 40, 300, id="k-over-the-context"),
    pytest.param("ties", 32, 512, 128, 420, 32, 600, id="k-over-the-table"),
]


@pytest.mark.parametrize("form", ["eager", "jit"])
@pytest.mark.parametrize("kind,s,L,kb,start,true_len,k", CASES)
def test_the_kernel_is_the_xla_form_bit_for_bit(kind, s, L, kb, start, true_len, k, form):
    """Against the XLA form op by op and compiled as the program runs it (where
    XLA would drop a `+ 0.0` and part -0.0 from 0.0)."""
    score, n_blocks = chunk_score(kind, s, L, kb, start, true_len, seed=s + start)
    xla = dsv._select_mask if form == "eager" else jax.jit(dsv._select_mask, static_argnums=1)
    want = np.asarray(xla(score, k))
    got = np.asarray(ts.topk_select(score, k, n_blocks, kb, True))
    np.testing.assert_array_equal(got, want)
    assert not got[:, n_blocks * kb:].any()


def test_the_cases_reach_the_edges_they_name():
    """The ties cases overflow a row's room (the prefix count decides), the
    first chunk has rows under k, the signed zeros tie at the k-th value."""
    def spill(kind, s, L, kb, start, true_len, k):
        score, _ = chunk_score(kind, s, L, kb, start, true_len, seed=s + start)
        a = np.asarray(score)
        kth = -np.sort(-a, axis=1)[:, min(k, L) - 1:min(k, L)]
        above, tie = (a > kth).sum(1), ((a == kth) & (a > -np.inf)).sum(1)
        return (tie > min(k, L) - above).any(), (np.isfinite(a).sum(1) < k).any(), (kth == 0).any()

    params = {p.id: p.values for p in CASES}
    assert spill(*params["ties-mid-block"])[0] and spill(*params["ties-at-block-edge"])[0]
    assert spill(*params["signed-zeros-tie-at-zero"])[0] and spill(*params["signed-zeros-tie-at-zero"])[2]
    assert spill(*params["first-chunk-rows-under-k"])[1] and spill(*params["k-over-the-context"])[1]
    assert not spill(*params["drawn-mid-block"])[0]


def test_negative_zero_is_zero_and_the_lower_position_wins():
    """Three keys at 0 (one of them -0.0) for two places: the first two
    positions, whatever the sign; -inf never."""
    row = [1.0, -0.0, 2.0, 0.0, 0.0, -1.0] + [-np.inf] * 122
    score = jnp.asarray([row] * 32, jnp.float32)
    got = np.asarray(ts.topk_select(score, 4, 1, 128, True))
    assert got[0, :6].tolist() == [True, True, True, True, False, False] and not got[:, 6:].any()
    np.testing.assert_array_equal(got, np.asarray(dsv._select_mask(score, 4)))
    np.testing.assert_array_equal(got, np.asarray(jax.jit(dsv._select_mask, static_argnums=1)(score, 4)))


def test_differentiation_through_the_kernel_stops_at_the_mask(interpret):
    """The scores the mask keeps get their gradient, as through the XLA form:
    the mask itself passes none, and the kernel is not differentiated."""
    score, n_blocks = chunk_score("normal", 32, 256, 128, 100, 32, seed=2)
    loss = lambda x: jnp.sum(jnp.where(dsv._select_topk(x, 24, n_blocks, 128), jnp.where(x > -jnp.inf, x, 0) ** 2, 0))
    got = np.asarray(jax.grad(loss)(score))
    fa._FORCE_INTERPRET = False
    np.testing.assert_array_equal(got, np.asarray(jax.grad(loss)(score)))
    assert (got != 0).sum() == 32 * 24


def test_the_dispatcher_runs_the_loop_off_the_tpu_and_the_kernel_under_interpret():
    profiler.reset()
    score, n_blocks = chunk_score("ties", 64, 512, 128, 200, 64, seed=4)
    want = np.asarray(dsv._select_topk(score, 40, n_blocks, 128))
    assert "topk_select" not in profiler.flash_pallas_summary()  # off the TPU: the loop
    assert profiler.topk_select_summary() == []
    fa._FORCE_INTERPRET = True
    try:
        got = np.asarray(dsv._select_topk(score, 40, jnp.int32(n_blocks), 128))
    finally:
        fa._FORCE_INTERPRET = False
    np.testing.assert_array_equal(got, want)
    assert profiler.flash_pallas_summary()["topk_select"] == 1
    [geo] = profiler.topk_select_summary()
    assert (geo["rows"], geo["cols"], geo["row_tile"], geo["kb"]) == (64, 512, 32, 128) and geo["vmem_bytes"] > 0
    profiler.reset()
    assert profiler.topk_select_summary() == []


def test_a_refused_shape_on_the_tpu_takes_the_loop_and_counts_a_fallback(monkeypatch):
    """48 rows are not whole tiles of 32: on the TPU the dispatcher logs the
    refusal as a Pallas fallback and answers with the loop."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    profiler.reset()
    score, n_blocks = chunk_score("normal", 48, 512, 128, 0, 48)
    got = np.asarray(dsv._select_topk(score, 16, n_blocks, 128))
    np.testing.assert_array_equal(got, np.asarray(dsv._select_mask(score, 16)))
    assert "topk_select" not in profiler.flash_pallas_summary()
    assert any(r.startswith("topk_select: 48 rows") for r in profiler.flash_fallback_summary())
    profiler.reset()


def test_deepseek_prefill_attention_selects_through_the_kernel(interpret):
    """`_prefill_attention` at the tiny configuration takes both kernels,
    the selection and the attention, a chunk."""
    profiler.reset()
    cfg = dsv.DeepseekV32Config.tiny()
    rng = np.random.default_rng(8)
    dims = dsv._dims(cfg)
    w = {n: jnp.asarray(rng.normal(size=(dims[a], dims[b])) * 0.1, jnp.float32) for n, a, b in dsv.ATTN_MATRICES}
    w.update({"q_a_layernorm": jnp.ones(cfg.q_lora_rank), "kv_a_layernorm": jnp.ones(cfg.kv_lora_rank),
              "indexer.k_norm.weight": jnp.ones(cfg.index_head_dim), "indexer.k_norm.bias": jnp.zeros(cfg.index_head_dim)})
    cos, sin = dsv._rope_tables(cfg)
    x = jnp.asarray(rng.normal(size=(32, cfg.hidden_size)), jnp.float32)
    lat = jnp.zeros((8, 1, 8, dsv.latent_width(cfg)), jnp.float32)
    idx = jnp.zeros((8, 1, 8, cfg.index_head_dim), jnp.float32)
    dsv._prefill_attention(cfg, w, x, cos.numpy()[:32], sin.numpy()[:32], lat, idx, jnp.arange(6, dtype=jnp.int32),
                           jnp.asarray([0], jnp.int32), jnp.int32(27))
    assert profiler.flash_pallas_summary()["topk_select"] == 1
    assert profiler.topk_select_summary()[0]["cols"] == 48
    profiler.reset()
