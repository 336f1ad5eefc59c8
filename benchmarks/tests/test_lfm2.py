"""CPU tests of what the generation cell of LFM2 adds to the yardstick: the
cell through `run_cell` with its control and one fault, its per-layer
readers, the configuration's file against the published numbers, and the
bytes and operations counted.  Tiny sizes (`tiny_lfm2`), one process.  Every
expert is held (a pipeline stage holds whole layers), so no share of the
expert layer is tied to the uncut one here."""

import json
from types import SimpleNamespace

import pytest

import tiny  # noqa: F401  (sets the platform and the path)
import tiny_lfm2
from benchmarks import flops_lfm2 as F, run as R
from benchmarks import weights_lfm2 as W

E2E = ["itl_p95_ms", "serve_tok_s", "setup_s"]
CELL = "lfm2_serve.gen128"
METRICS = {"step_mfu.serve_lfm2", "step_hbm_pct.serve_lfm2", "engine.prefill_share_pct.lfm2",
           "paged_walk_roofline.lfm2", "grouped_experts_roofline.lfm2"}


def test_cell_runs_sound_and_its_control_and_a_fault_are_not_correct(monkeypatch):
    _, _, metrics = R.load_cell(CELL)
    assert set(metrics) == METRICS
    ctx = tiny_lfm2.ctx(seed=3_900_000_143, tracing=False)
    sound = R.run_cell(ctx, {}, E2E)
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["checks"]["compiles_in_window"]["value"] == 0
    cut = [r for r in ctx.window["records"] if r.cut]
    assert cut and all(r.req.finish_reason == "cancelled" and not r.failed() for r in cut)
    # the readers over the run's window and counters, a trace made to fit them
    walk = ctx.counters["kv_walk"]
    ctx.trace_window = (ctx.window["t0"], ctx.window["t1"])
    ctx.spans = [("engine.prefill", ctx.window["t0"], ctx.window["t0"] + 0.25 * ctx.window["seconds"])]
    ctx.counters["traced_decode"] = {"steps": walk["steps"], "rows_in_reach": walk["rows_in_reach"]}
    ctx.trace = {"op_counts": {"%paged_walk_decode.1 = bf16[4,1,4,32]": 2 * walk["steps"],
                               "%grouped_experts.3 = f32[4,64]": 8 * walk["steps"]},
                 "ops": {"%paged_walk_decode.1 = bf16[4,1,4,32]": 1.0, "%grouped_experts.3 = f32[4,64]": 1.0}}
    values = R.read_metrics(ctx, metrics)
    assert set(values) == METRICS and all(0 < v["value"] for v in values.values())
    assert values["engine.prefill_share_pct.lfm2"]["value"] == pytest.approx(25.0, abs=0.5)
    cfg = W.model_cfg(ctx.cfg)
    want = 100 * F.walk_bytes(cfg, walk["rows_in_reach"]) / tiny.peaks()["hbm_bytes_per_s"]  # over 1 s
    assert values["paged_walk_roofline.lfm2"]["value"] == pytest.approx(want, rel=1e-6)

    res = R.run_cell(tiny_lfm2.ctx(seed=3_900_000_143, control=True), {}, E2E)
    assert res["correct"] is False
    assert res["checks"]["logit_gap_mean"]["value"] > 5 * res["checks"]["logit_gap_mean"]["limit"]

    from paddle_tpu.inference.engine import ContinuousBatchingEngine as Engine

    emit = Engine._emit
    monkeypatch.setattr(Engine, "_emit", lambda self, s, req, tok: emit(
        self, s, req, (tok + 1) % 256 if len(req.tokens) % 7 == 3 else tok))
    res = R.run_cell(tiny_lfm2.ctx(seed=7), {}, E2E)
    assert res["correct"] is False


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent commit has no tail or K/V-row counter: each reader returns
    nothing and does not raise."""
    _, _, metrics = R.load_cell(CELL)
    ctx = tiny_lfm2.ctx()
    ctx.window, ctx.counters = {}, {"serving": {}, "slots": 3}
    assert R.read_metrics(ctx, metrics) == {}


@pytest.mark.parametrize("calls,reads", [(200, True), (197, True), (205, False), (0, False)])
def test_walk_reader_holds_its_guard(calls, reads):
    from benchmarks.readers import kv_walk_roofline as reader

    ctx = SimpleNamespace(cfg=R.load_json(R.HERE / "configs/lfm2-8b-a1b-serve9.json"),
                          peaks=tiny.peaks(), log=lambda line: None,
                          counters={"traced_decode": {"steps": 100, "rows_in_reach": 100 * 2 * 128 * 3125}},
                          trace={"op_counts": {"%paged_walk_decode.1 = x": calls},
                                 "ops": {"%paged_walk_decode.1 = x": calls * 1e-3}})
    got = reader.read(ctx, {"match": ["paged_walk_decode"]})
    # 128 slots x 3,125 rows x 2,048 B = 819 MB a call: 1.0 ms at 819 GB/s, read in 1 ms
    assert (got == pytest.approx(100.0, rel=1e-3)) if reads else got is None


def test_configuration_file_keeps_every_published_number():
    cfg = R.load_json(R.HERE / "configs/lfm2-8b-a1b-serve9.json")
    for key in ("source", "published", "reduced", "assumed", "deployment", "numerics", "init", "engine"):
        assert key in cfg
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b-serve9"][0]
    assert set(entry["reduced"]) == set(cfg["reduced"]) and entry["source"] == cfg["source"]
    widths = {"hidden_size": 2048, "intermediate_size": 7168, "moe_intermediate_size": 1792,
              "num_attention_heads": 32, "num_key_value_heads": 8, "num_experts": 32, "num_experts_per_tok": 4,
              "vocab_size": 65536, "conv_L_cache": 3, "rope_theta": 1000000, "norm_eps": 1e-5,
              "routed_scaling_factor": 1, "norm_topk_prob": True, "use_expert_bias": True, "conv_bias": False}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["layer_types"] == cfg["published"]["layer_types"][1:10]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"], cfg["published"]["num_dense_layers"]) == (9, 1, 2)
    mc = W.model_cfg(cfg)
    p = F.param_counts(mc)
    # ISSUE 43's count: a conv mixer 16.78 M, an attention mixer 10.49 M, an expert 11.01 M, 3.136 B held
    assert round(p["conv"] / 1e4) == 1678 and round(p["attention"] / 1e4) == 1049
    assert p["expert"] * 2 == 22_020_096 and round(p["held"] / 1e6) == 3136
    assert (p["conv_layers"], p["attn_layers"], p["moe_layers"]) == (7, 2, 8)
    assert F.kv_row_bytes(mc) == 2048


def test_result_line_of_the_cell_is_the_contracts(capsys):
    res = R.run_cell(tiny_lfm2.ctx(seed=11, seconds=0.5), {}, E2E)
    R.report(res)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["metrics"]) == set(E2E) and line["device"]["platform"] == "cpu"
