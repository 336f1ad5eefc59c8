"""The prefill chunk's latent attention as one Pallas kernel
(`ops/mla_prefill.py`), interpreted on the CPU, against the XLA loop that
`models/deepseek_v32.py:_attend_expanded` runs off the TPU: both mask forms
(causal from the chunk's start, DeepSeek-V3.2's selected keys), a first and a
resumed chunk, a padded tail, rows with fewer keys than the top-k, a q tile
whose mask is empty, 4 and 8 heads; the two model paths through the
dispatcher; the packed mask; the counters a traced call leaves."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import profiler
from paddle_tpu.models import Ling3Config
from paddle_tpu.models import deepseek_v32 as dsv
from paddle_tpu.models import ling3 as L
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops import mla_prefill as mp

DN, DR, DV, C = 16, 8, 16, 16  # the tiny configurations' widths; the latent row is 128 wide


@pytest.fixture
def interpret():
    fa._FORCE_INTERPRET = True
    try:
        yield
    finally:
        fa._FORCE_INTERPRET = False


def normal(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape) * scale, jnp.float32)


def operands(H, s, n_keys, seed=0):
    """A chunk's q rows of H heads, a context of `n_keys` latent rows (the
    row's tail past ckv and k_pe zero, as the cache keeps it) and W_ukv."""
    cfg = dsv.DeepseekV32Config.tiny(num_attention_heads=H, num_key_value_heads=H)
    W = dsv.latent_width(cfg)
    lat = jnp.zeros((n_keys, W), jnp.float32).at[:, :C + DR].set(normal(seed + 2, n_keys, C + DR))
    return (cfg, normal(seed + 3, C, H * (DN + DV), scale=0.3), normal(seed, s, H, DN), normal(seed + 1, s, H, DR), lat)


def selection(s, n_keys, start, true_len, k, seed=0):
    """The indexer's exact top-k over a drawn score: keys up to each query's
    position and inside the context (`start + true_len`), ties to the lower
    position, every finite key where fewer than k are."""
    q_pos = start + np.arange(s)[:, None]
    keys = np.arange(n_keys)[None, :]
    score = jnp.where(jnp.asarray((keys <= q_pos) & (keys < start + true_len)), normal(seed + 7, s, n_keys), -jnp.inf)
    return dsv._select_mask(score, k)


def both_forms(cfg, w, qn, qp, lat, n_blocks, kb, qb, start, mask):
    """(the loop, the kernel) through the dispatcher, and the kernel's calls."""
    calls = lambda: profiler.flash_pallas_summary().get("mla_prefill", 0)
    before = calls()
    want = dsv._attend_expanded(cfg, w, qn, qp, lat, n_blocks, kb, qb, 0.3, start, mask)
    assert calls() == before  # off the TPU: the loop
    fa._FORCE_INTERPRET = True
    try:
        got = dsv._attend_expanded(cfg, w, qn, qp, lat, n_blocks, kb, qb, 0.3, start, mask)
    finally:
        fa._FORCE_INTERPRET = False
    assert calls() == before + 1
    return np.asarray(want), np.asarray(got)


CHUNKS = [  # (start, true_len): a first chunk, resumed off the key block, a padded tail
    pytest.param(0, 32, id="first"),
    pytest.param(19, 32, id="resumed-off-block"),
    pytest.param(19, 21, id="padded-tail"),
]


@pytest.mark.parametrize("H", [4, 8])
@pytest.mark.parametrize("start,true_len", CHUNKS)
@pytest.mark.parametrize("form", ["causal", "selected"])
def test_the_kernel_is_the_loop(form, start, true_len, H):
    """32 query rows in tiles of 8 over key blocks of 16: the same online
    softmax over the same keys, to float32 rounding, padding rows included."""
    s, n_keys, kb, qb = 32, 64, 16, 8
    cfg, w, qn, qp, lat = operands(H, s, n_keys, seed=H + start)
    n_blocks = (start + true_len + kb - 1) // kb
    mask = selection(s, n_keys, start, true_len, 12, seed=start) if form == "selected" else None
    want, got = both_forms(cfg, w, qn, qp, lat, jnp.int32(n_blocks), kb, qb, jnp.int32(start), mask)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max())


def test_rows_with_fewer_keys_than_the_top_k_attend_every_key_they_have():
    """A first chunk under a top-k of 24: the first 23 rows see fewer keys
    than k and take them all; row 0 its own key alone."""
    s, n_keys, kb, qb, H = 32, 64, 16, 8, 4
    cfg, w, qn, qp, lat = operands(H, s, n_keys, seed=11)
    mask = selection(s, n_keys, 0, s, 24, seed=3)
    counts = np.asarray(mask).sum(axis=1)
    assert counts[0] == 1 and counts[22] == 23 and (counts[23:] == 24).all()
    want, got = both_forms(cfg, w, qn, qp, lat, jnp.int32(2), kb, qb, jnp.int32(0), mask)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max())
    # row 0 under its own key alone: its value row, expanded
    v0 = (lat[0, :C] @ w).reshape(H, DN + DV)[:, DN:].reshape(-1)
    np.testing.assert_allclose(got[0], v0, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("form", ["causal", "selected"])
def test_a_q_tile_whose_mask_is_empty_is_skipped_and_changes_nothing(form, interpret):
    """Causal: a first chunk's first tile sees nothing of the second key
    block.  Selected (a chunk at 19): a tile with no chosen key in the first
    block but some in the second, and a tile with none at all (its rows come
    out zero, as the loop's do)."""
    s, n_keys, kb, qb, H = 32, 64, 16, 8, 4
    cfg, w, qn, qp, lat = operands(H, s, n_keys, seed=5)
    start, n_blocks, mask = 0, 2, None
    if form == "selected":
        start, n_blocks = 19, 4
        mask = selection(s, n_keys, start, s, 12, seed=9).at[8:16, 0:16].set(False).at[24:32].set(False)
        live = np.asarray(mp.tile_any(mask, kb, qb)).reshape(s // qb, n_keys // kb)
        assert not live[1, 0] and live[1, 1] and not live[3].any()
    fa._FORCE_INTERPRET = False
    want = np.asarray(dsv._attend_expanded(cfg, w, qn, qp, lat, jnp.int32(n_blocks), kb, qb, 0.3, jnp.int32(start),
                                           mask))
    fa._FORCE_INTERPRET = True
    got = np.asarray(mp.mla_prefill(qn, qp, lat, w, n_blocks, kb, qb, 0.3, start, mask, True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max())
    if form == "selected":
        assert not got[24:].any()


def test_the_packed_mask_holds_each_key_once_at_its_lane_and_bit():
    """pack_mask's words unpacked in numpy are the mask; at the chip's key
    block (1024 keys, 8 lane-chunks) a word holds four blocks' chunks."""
    rng = np.random.default_rng(2)
    for s, n_keys, kb in ((8, 64, 16), (16, 8192, 1024)):
        chosen = rng.random((s, n_keys)) < 0.2
        words = np.asarray(mp.pack_mask(jnp.asarray(chosen), kb)).view(np.uint32)
        cw = mp._chunk(kb)
        k = np.arange(n_keys)
        col = (k // (32 * cw)) * cw + k % cw
        back = (words[:, col] >> ((k // cw) % 32).astype(np.uint32)) & 1
        np.testing.assert_array_equal(back.astype(bool), chosen)
        assert words.shape == (s, -(-n_keys // (32 * cw)) * cw)


def test_deepseek_prefill_attention_through_the_kernel_is_the_loop(interpret):
    """`_prefill_attention` over the latent and indexer caches, two chunks
    (the second resumed at 19 with a padded tail), the kernel against the
    loop."""
    cfg = dsv.DeepseekV32Config.tiny()
    rng = np.random.default_rng(8)
    dims = dsv._dims(cfg)
    w = {n: jnp.asarray(rng.normal(size=(dims[a], dims[b])) * 0.1, jnp.float32) for n, a, b in dsv.ATTN_MATRICES}
    w.update({"q_a_layernorm": jnp.ones(cfg.q_lora_rank), "kv_a_layernorm": jnp.ones(cfg.kv_lora_rank),
              "indexer.k_norm.weight": jnp.ones(cfg.index_head_dim), "indexer.k_norm.bias": jnp.zeros(cfg.index_head_dim)})
    x = normal(4, 45, cfg.hidden_size)
    cos, sin = dsv._rope_tables(cfg)
    cos, sin = cos.numpy()[:48], sin.numpy()[:48]
    table = jnp.asarray([3, 1, 5, 2, 7, 4], jnp.int32)

    def chunks():
        lat = jnp.zeros((8, 1, 8, dsv.latent_width(cfg)), jnp.float32)
        idx = jnp.zeros((8, 1, 8, cfg.index_head_dim), jnp.float32)
        outs = []
        for s0, rows in ((0, 19), (19, 26)):
            pad = ((0, 32 - rows), (0, 0))
            out, lat, idx = dsv._prefill_attention(
                cfg, w, jnp.pad(x[s0:s0 + rows], pad), jnp.pad(cos[s0:s0 + rows], pad), jnp.pad(sin[s0:s0 + rows], pad),
                lat, idx, table, jnp.asarray([s0], jnp.int32), jnp.int32(rows))
            outs.append(np.asarray(out[:rows]))
        return np.concatenate(outs)

    before = profiler.flash_pallas_summary().get("mla_prefill", 0)
    got = chunks()
    assert profiler.flash_pallas_summary().get("mla_prefill", 0) == before + 2
    fa._FORCE_INTERPRET = False
    want = chunks()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max())


def test_ling3_mla_prefill_through_the_kernel_is_the_loop(interpret):
    """`ling3._mla_prefill` (Ling-3 and Kimi Linear): the causal form, a chunk
    resumed at 19 over a paged context."""
    cfg = Ling3Config.tiny()
    H, h = cfg.num_attention_heads, cfg.hidden_size
    dn, dr, dv, c = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    rng = np.random.default_rng(6)
    mat = lambda a, b: jnp.asarray(rng.normal(size=(a, b)) * 0.1, jnp.float32)
    w = {"q_proj.weight": mat(h, H * (dn + dr)), "kv_a_proj_with_mqa.weight": mat(h, c + dr),
         "kv_a_layernorm.weight": jnp.ones(c), "kv_b_proj.weight": mat(c, H * (dn + dv)), "o_proj.weight": mat(H * dv, h),
         "g_proj.weight": mat(h, H)}
    x = normal(1, 32, h)
    cos, sin = L._rope_tables(cfg)
    lat = normal(2, 8, 1, 8, dsv.latent_width(cfg)) * 0.3
    table = jnp.asarray([3, 1, 5, 2, 7, 4], jnp.int32)
    run = lambda: np.asarray(L._mla_prefill(cfg, w, x, cos.numpy()[19:51], sin.numpy()[19:51], lat, table,
                                            jnp.asarray([19], jnp.int32), jnp.int32(29))[0][:29])
    got = run()
    fa._FORCE_INTERPRET = False
    want = run()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * np.abs(want).max())


def test_a_traced_call_records_its_geometry(interpret):
    profiler.reset()
    cfg, w, qn, qp, lat = operands(8, 32, 64)
    mask = selection(32, 64, 0, 32, 12)
    dsv._attend_expanded(cfg, w, qn, qp, lat, jnp.int32(2), 16, 8, 0.3, jnp.int32(0), mask)
    dsv._attend_expanded(cfg, w, qn, qp, lat, jnp.int32(2), 16, 8, 0.3, jnp.int32(0))
    got = profiler.mla_prefill_summary()
    assert [(g["rows"], g["heads"], g["kb"], g["heads_per_step"], g["grid_steps"], g["mask"]) for g in got] == [
        (32, 8, 16, 8, 4, "selected"), (32, 8, 16, 8, 4, "causal")]
    assert got[0]["vmem_bytes"] > got[1]["vmem_bytes"] > 0  # the packed mask's blocks
    assert profiler.flash_pallas_summary()["mla_prefill"] == 2
    profiler.reset()
    assert profiler.mla_prefill_summary() == []


def test_a_refused_shape_on_the_tpu_takes_the_loop_and_counts_a_fallback(monkeypatch):
    """The tiny widths are not whole lanes: on the TPU the dispatcher logs
    the refusal as a Pallas fallback and answers with the loop."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    profiler.reset()
    cfg, w, qn, qp, lat = operands(4, 24, 64)
    want = np.asarray(mp.mla_prefill(qn, qp, lat, w, 2, 16, 8, 0.3, 0, None, True))
    got = np.asarray(dsv._attend_expanded(cfg, w, qn, qp, lat, jnp.int32(2), 16, 8, 0.3, jnp.int32(0)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert "mla_prefill" not in profiler.flash_pallas_summary()
    assert any(r.startswith("mla_prefill: ") for r in profiler.flash_fallback_summary())
    profiler.reset()
