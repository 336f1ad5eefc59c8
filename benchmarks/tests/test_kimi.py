"""CPU tests of what the long-reasoning cell of Kimi-Linear adds to the
yardstick: the cell through `run_cell` with its control and one fault, its
per-layer readers, the configuration's file against the catalog's row, and
the bytes and operations counted.  Tiny sizes (`tiny_kimi`), one process."""

import json
from types import SimpleNamespace

import pytest

import tiny  # noqa: F401  (sets the platform and the path)
import tiny_kimi
from benchmarks import flops_kimi_linear as F, run as R
from benchmarks import weights_kimi_linear as W

E2E = ["itl_p95_ms", "serve_tok_s", "setup_s"]
CELL = "kimi_serve.longreason32"
METRICS = {"step_hbm_pct.serve_kimi", "engine.prefill_share_pct.kimi", "latent_walk_roofline.kimi",
           "grouped_experts_roofline.kimi"}


def test_cell_runs_sound_and_its_control_and_a_fault_are_not_correct(monkeypatch):
    _, _, metrics = R.load_cell(CELL)
    assert set(metrics) == METRICS
    ctx = tiny_kimi.ctx(seed=3_900_000_123, tracing=False)
    sound = R.run_cell(ctx, {}, E2E)
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["checks"]["compiles_in_window"]["value"] == 0
    cut = [r for r in ctx.window["records"] if r.cut]
    assert cut and all(r.req.finish_reason == "cancelled" and not r.failed() for r in cut)
    # the readers over the run's window and counters, a trace made to fit them
    walk = ctx.counters["latent_walk"]
    ctx.trace_window = (ctx.window["t0"], ctx.window["t1"])
    ctx.spans = [("engine.prefill", ctx.window["t0"], ctx.window["t0"] + 0.25 * ctx.window["seconds"])]
    ctx.counters["traced_decode"] = {"steps": walk["steps"], "rows_in_reach": walk["rows_in_reach"]}
    ctx.trace = {"op_counts": {"%paged_walk_decode.1 = bf16[4,1,4,128]": walk["steps"],
                               "%grouped_experts.3 = f32[4,64]": 4 * walk["steps"]},
                 "ops": {"%paged_walk_decode.1 = bf16[4,1,4,128]": 1.0, "%grouped_experts.3 = f32[4,64]": 1.0}}
    values = R.read_metrics(ctx, metrics)
    assert set(values) == METRICS and all(0 < v["value"] for v in values.values())
    assert values["engine.prefill_share_pct.kimi"]["value"] == pytest.approx(25.0, abs=0.5)
    cfg = W.model_cfg(ctx.cfg)
    want = 100 * F.walk_bytes(cfg, walk["rows_in_reach"]) / tiny.peaks()["hbm_bytes_per_s"]  # over 1 s
    assert values["latent_walk_roofline.kimi"]["value"] == pytest.approx(want, rel=1e-6)

    res = R.run_cell(tiny_kimi.ctx(seed=3_900_000_123, control=True), {}, E2E)
    assert res["correct"] is False
    assert res["checks"]["logit_gap_mean"]["value"] > 5 * res["checks"]["logit_gap_mean"]["limit"]

    from paddle_tpu.inference.engine import ContinuousBatchingEngine as Engine

    emit = Engine._emit
    monkeypatch.setattr(Engine, "_emit", lambda self, s, req, tok: emit(
        self, s, req, (tok + 1) % 256 if len(req.tokens) % 7 == 3 else tok))
    res = R.run_cell(tiny_kimi.ctx(seed=7), {}, E2E)
    assert res["correct"] is False


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent commit has no latent-row counter: each reader returns
    nothing and does not raise."""
    _, _, metrics = R.load_cell(CELL)
    ctx = tiny_kimi.ctx()
    ctx.window, ctx.counters = {}, {"serving": {}, "slots": 3}
    assert R.read_metrics(ctx, metrics) == {}


@pytest.mark.parametrize("calls,reads", [(100, True), (98, True), (103, False), (0, False)])
def test_walk_reader_holds_its_guard(calls, reads):
    from benchmarks.readers import latent_walk_roofline as reader

    ctx = SimpleNamespace(cfg=R.load_json(R.HERE / "configs/kimi-linear-48b-a3b-ep2-serve5.json"),
                          peaks=tiny.peaks(), log=lambda line: None,
                          counters={"traced_decode": {"steps": 100, "rows_in_reach": 100 * 32 * 20_000}},
                          trace={"op_counts": {"%paged_walk_decode.1 = x": calls},
                                 "ops": {"%paged_walk_decode.1 = x": calls * 1e-3}})
    got = reader.read(ctx, {"match": ["paged_walk_decode"]})
    # 32 slots x 20k rows x 1,280 B = 819 MB a call: 1.0 ms at 819 GB/s, read in 1 ms
    assert (got == pytest.approx(100.0, rel=1e-3)) if reads else got is None


def test_configuration_file_keeps_every_catalog_number():
    cfg = R.load_json(R.HERE / "configs/kimi-linear-48b-a3b-ep2-serve5.json")
    for key in ("source", "published", "reduced", "assumed", "deployment", "numerics", "init", "engine"):
        assert key in cfg
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == "kimi-linear-48b-a3b-ep2-serve5"][0]
    assert set(entry["reduced"]) == set(cfg["reduced"]) and entry["source"] == cfg["source"]
    lin = cfg["linear_attn_config"]
    assert (lin["head_dim"], lin["num_heads"], lin["short_conv_kernel_size"]) == (128, 32, 4)
    assert (lin["full_attn_layers"], lin["kda_layers"]) == ([4], [1, 2, 3, 5])
    mc = W.model_cfg(cfg)
    assert (mc["num_experts"], mc["experts_held"], mc["n_group"], mc["num_experts_per_tok"]) == (256, 128, 1, 8)
    assert [W.layer_kind(mc, l) for l in range(5)] == ["kda", "kda", "kda", "mla", "kda"]
    p = F.param_counts(mc)
    # ISSUE 39's count: a KDA layer 39.51 M, an MLA layer 29.11 M, an expert 7.078 M, 4.283 B held
    assert round(p["kda"] / 1e4) == 3951 and round(p["mla"] / 1e4) == 2911
    assert round(p["expert"] / 1e3) == 7078 and round(p["held"] / 1e6) == 4283


def test_result_line_of_the_cell_is_the_contracts(capsys):
    res = R.run_cell(tiny_kimi.ctx(seed=11, seconds=0.5), {}, E2E)
    R.report(res)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["metrics"]) == set(E2E) and line["device"]["platform"] == "cpu"
