"""CPU tests of what the long-context DeepSeek-V3.2 cell adds to the
yardstick: the cell through `run_cell` with its control and one fault, its
per-layer readers, the operation counts, and the reference's own pieces.
Tiny sizes (`tiny_dsv32`), one process."""

import json

import numpy as np
import pytest

import tiny
import tiny_dsv32
from benchmarks import flops_deepseek_v32 as F, reference_deepseek_v32 as ref, run as R
from benchmarks import weights_deepseek_v32 as W

E2E = ["itl_p95_ms", "serve_tok_s", "setup_s"]
CELL = "dsv32_serve.longctx16"


def test_cell_runs_sound_and_its_control_and_a_fault_are_not_correct(monkeypatch):
    """What `run.py` and `control.py` do on the chip, and a token altered
    where it is produced."""
    _, _, metrics = R.load_cell(CELL)
    ctx = tiny_dsv32.ctx(seed=2_900_000_123, tracing=False)
    sound = R.run_cell(ctx, {}, E2E)
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["checks"]["compiles_in_window"]["value"] == 0
    # the readers over the run's window and counters (no trace on the CPU)
    ctx.trace_window = (ctx.window["t0"], ctx.window["t1"])
    ctx.spans = [("engine.prefill", ctx.window["t0"], ctx.window["t0"] + 0.25 * ctx.window["seconds"]),
                 ("engine.prefill_chunk", ctx.window["t0"], ctx.window["t0"] + 0.1),
                 ("engine.decode", ctx.window["t0"], ctx.window["t1"])]
    values = R.read_metrics(ctx, metrics)
    assert set(values) == set(metrics) == {
        "step_mfu.serve_dsv32", "step_hbm_pct.serve_dsv32", "engine.prefill_share_pct.dsv32"}
    assert values["engine.prefill_share_pct.dsv32"]["value"] == pytest.approx(25.0, abs=0.5)
    assert all(0 < v["value"] for v in values.values())
    assert ctx.counters["sparse_attn"]["rows_over_topk"] > 0  # selection bites
    assert 0 < ctx.counters["moe"]["picks_held"] < 4 * ctx.counters["moe"]["tokens"]

    res = R.run_cell(tiny_dsv32.ctx(seed=2_900_000_123, control=True), {}, E2E)
    assert res["correct"] is False
    assert res["checks"]["logit_gap_mean"]["value"] > 5 * res["checks"]["logit_gap_mean"]["limit"]

    from paddle_tpu.inference.engine import ContinuousBatchingEngine as Engine

    emit = Engine._emit
    monkeypatch.setattr(Engine, "_emit", lambda self, s, req, tok: emit(
        self, s, req, (tok + 1) % 256 if len(req.tokens) % 7 == 3 else tok))
    res = R.run_cell(tiny_dsv32.ctx(seed=7), {}, E2E)
    assert res["correct"] is False
    assert res["checks"]["logit_gap_mean"]["value"] > res["checks"]["logit_gap_mean"]["limit"]


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent commit has neither counter nor span: each reader returns
    nothing and does not raise."""
    _, _, metrics = R.load_cell(CELL)
    ctx = tiny_dsv32.ctx()
    ctx.window, ctx.counters = {}, {"serving": {}, "slots": 3}
    assert R.read_metrics(ctx, metrics) == {}


def test_configuration_file_keeps_every_catalog_width():
    cfg = R.load_json(R.HERE / "configs/deepseek-v3.2-ep16-serve5.json")
    for key in ("source", "published", "reduced", "assumed", "deployment", "numerics"):
        assert key in cfg
    published = dict(
        hidden_size=7168, intermediate_size=18432, moe_intermediate_size=2048, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_attention_heads=128, index_n_heads=64, index_head_dim=128, index_topk=2048,
        num_experts_per_tok=8, n_group=8, topk_group=4, routed_scaling_factor=2.5,
        first_k_dense_replace=3, n_shared_experts=1)
    assert {k: cfg[k] for k in published} == published
    assert set(cfg["reduced"]) == set(cfg["published"])
    mc = W.model_cfg(cfg)
    assert (mc["n_routed_experts"], mc["experts_held"], mc["first_k_dense_replace"]) == (256, 16, 1)
    assert W.model_cfg(mc) is mc
    p = F.param_counts(mc)
    assert round(p["held"] / 1e6) == 4635  # ISSUE 29's count: 9.27 GB in bfloat16
    e = cfg["engine"]
    arena = e["slots"] * e["max_len"] * mc["num_hidden_layers"] * 2 * (512 + 64 + 128)
    assert round(arena / 1e7) == 277  # 302 as held: the latent row is padded to 640


def test_operation_counts_grow_with_context_only_in_the_indexer_past_top_k():
    mc = W.model_cfg(tiny_dsv32.config())
    k = mc["index_topk"]
    d = [F.forward_flops_decode(mc, n) for n in (k, k + 1, k + 2)]
    per_key = 2 * mc["index_n_heads"] * (mc["index_head_dim"] + 1) * mc["num_hidden_layers"]
    assert d[1] - d[0] == d[2] - d[1] == per_key
    assert F.forward_flops_decode(mc, k) - F.forward_flops_decode(mc, k - 1) > per_key
    assert F.picks_here(mc) == 4 * 4 / 16
    whole = F.forward_flops_prompt(mc, 40)
    assert whole > 40 * F.token_flops(mc)
    assert F.decode_bytes(mc, 2, 3, 100, 32) == 2 * (
        2 * F.param_counts(mc)["non_expert"] + 3 * F.param_counts(mc)["expert"]
        + mc["num_hidden_layers"] * (100 * 16 + 32 * 24))


def test_reference_selects_exactly_top_k_with_ties_to_the_lower_position():
    import jax.numpy as jnp

    score = jnp.asarray([[1.0, 3.0, 3.0, 2.0, -jnp.inf], [0.5, -jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf]])
    assert np.asarray(ref.top_mask(score, 2)).tolist() == [
        [False, True, True, False, False], [True, False, False, False, False]]
    assert np.asarray(ref.top_mask(score, 1)).tolist()[0] == [False, True, False, False, False]


def test_yarn_keeps_fast_dimensions_and_stretches_slow_ones():
    cfg = R.load_json(R.HERE / "configs/deepseek-v3.2-ep16-serve5.json")
    plain = 1.0 / (10000.0 ** (np.arange(0, 64, 2) / 64))
    got = ref.yarn_inv_freq(cfg)
    assert got[0] == plain[0] and got[-1] == pytest.approx(plain[-1] / 40)
    assert np.all(np.diff(got / plain) <= 1e-12)
    assert ref.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)


def test_result_line_of_the_cell_is_the_contracts(capsys):
    res = R.run_cell(tiny_dsv32.ctx(seed=11, seconds=0.5), {}, E2E)
    R.report(res)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["metrics"]) == set(E2E) and line["device"]["platform"] == "cpu"
