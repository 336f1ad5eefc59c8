"""CPU tests of what the mixed cell of Mellum2 adds to the yardstick: the cell
through `run_cell` with its control and two faults (a token altered, the
window switched off), its per-layer readers, and the configuration's file
against the catalog's numbers and `BENCHMARK.json`.  Tiny sizes
(`tiny_mellum2`), one process."""

import json

import numpy as np
import pytest

import tiny  # noqa: F401  (sets the platform and the path)
import tiny_mellum2
from benchmarks import flops_mellum2 as F, run as R
from benchmarks.kinds import serve_closed_mellum2 as K

E2E = ["itl_p95_ms", "serve_tok_s", "setup_s"]
CELL = "mellum2_serve.mixed32"
NEW_METRICS = {"step_mfu.serve_mellum2", "step_hbm_pct.serve_mellum2", "engine.prefill_share_pct.mellum2",
               "paging.window_pages_per_slot_peak.mellum2", "paged_walk_roofline.mellum2"}


def fake_trace(ctx, seconds_a_call=2e-5):
    """What a `--trace 1` run leaves for the readers, with no trace on the
    CPU: the traced seconds are the window, spans over a quarter of it, and a
    walk event a layer a counted step."""
    w = ctx.window
    ctx.trace_window = (w["t0"], w["t1"])
    ctx.spans = [("engine.prefill", w["t0"], w["t0"] + 0.25 * w["seconds"]),
                 ("engine.prefill_chunk", w["t0"], w["t0"] + 0.1), ("engine.decode", w["t0"], w["t1"])]
    rows = ctx.counters["window_cache"]["decode"]
    ctx.counters["traced_decode"] = {k: rows[k] for k in ("steps", "rows_in_reach_full", "rows_in_reach_window")}
    calls = 8 * rows["steps"] + 3  # the edges of a trace cut steps in two
    ctx.trace = {"ops": {"%paged_walk_decode.1 = custom-call": 0.75 * calls * seconds_a_call,
                         "%paged_walk_decode.2 = custom-call": 0.25 * calls * seconds_a_call, "%fusion.1": 1.0},
                 "op_counts": {"%paged_walk_decode.1 = custom-call": 0.75 * calls,
                               "%paged_walk_decode.2 = custom-call": 0.25 * calls, "%fusion.1": 10}}


def test_cell_runs_sound_and_its_control_and_a_fault_are_not_correct(monkeypatch):
    """What `run.py` and `control.py` do on the chip, and a token altered
    where it is produced."""
    _, _, metrics = R.load_cell(CELL)
    ctx = tiny_mellum2.ctx(seed=3_500_000_123, tracing=False)
    sound = R.run_cell(ctx, {}, E2E)
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["checks"]["compiles_in_window"]["value"] == 0
    # a request outlasts the window: what is in flight at its close is cancelled, not failed
    cut = [r for r in ctx.window["records"] if r.cut]
    assert cut and all(r.req.finish_reason == "cancelled" and not r.failed() for r in cut)
    # every new metric names a reader that reads this run
    fake_trace(ctx)
    values = R.read_metrics(ctx, metrics)
    assert set(values) == set(metrics) == NEW_METRICS
    assert values["engine.prefill_share_pct.mellum2"]["value"] == pytest.approx(25.0, abs=0.5)
    assert values["paging.window_pages_per_slot_peak.mellum2"]["value"] == 3  # 15 rows back on pages of 8
    assert all(0 < v["value"] for v in values.values())
    assert 0 < values["paged_walk_roofline.mellum2"]["value"] <= 100
    cache, moe = ctx.counters["window_cache"], ctx.counters["moe"]
    assert cache["window"]["released_behind"] > 0 and cache["full"]["slot_pages_peak"] > 3
    assert moe["picks_held"] == 2 * moe["tokens"] and moe["steps"] == cache["decode"]["steps"]
    rows = cache["decode"]
    assert 0 < rows["rows_in_reach_window"] <= 6 * 16 * rows["live_slots"] < 3 * rows["rows_in_reach_full"]
    assert set(ctx.counters["arena_bytes"]) == {"k", "v", "k.window", "v.window"}

    res = R.run_cell(tiny_mellum2.ctx(seed=3_500_000_123, control=True), {}, E2E)
    assert res["correct"] is False
    assert res["checks"]["logit_gap_mean"]["value"] > 5 * res["checks"]["logit_gap_mean"]["limit"]

    from paddle_tpu.inference.engine import ContinuousBatchingEngine as Engine

    emit = Engine._emit
    monkeypatch.setattr(Engine, "_emit", lambda self, s, req, tok: emit(
        self, s, req, (tok + 1) % 256 if len(req.tokens) % 7 == 3 else tok))
    res = R.run_cell(tiny_mellum2.ctx(seed=7), {}, E2E)
    assert res["correct"] is False
    assert res["checks"]["logit_gap_mean"]["value"] > res["checks"]["logit_gap_mean"]["limit"]


@pytest.mark.parametrize("side", ["program", "reference"])
def test_a_window_switched_off_on_either_side_fails_the_comparison(monkeypatch, side):
    """The program's sliding layers reading every key, or the reference's:
    `logit_gap_mean` is over its limit, and nothing else is."""
    if side == "program":
        from paddle_tpu.models import mellum2 as M

        monkeypatch.setattr(M.Mellum2Config, "window", lambda self, layer: None)
    else:
        inner = K.reference_logits
        monkeypatch.setattr(K, "reference_logits", lambda *a, **kw: inner(*a, **{**kw, "window": False}))
    res = R.run_cell(tiny_mellum2.ctx(seed=9), {}, E2E)
    assert res["correct"] is False and res["failed"] == 0
    failing = {n for n, c in res["checks"].items() if c["value"] > c["limit"]}
    assert failing == {"logit_gap_mean"}
    assert res["checks"]["logit_gap_mean"]["value"] > 5 * res["checks"]["logit_gap_mean"]["limit"]


def test_walk_roofline_is_silent_when_the_match_holds_another_kernel_and_never_counts_pages():
    ctx = tiny_mellum2.ctx()
    _, _, metrics = R.load_cell(CELL)
    m = {"paged_walk_roofline.mellum2": metrics["paged_walk_roofline.mellum2"]}
    ctx.window = {"t0": 0.0, "t1": 1.0, "seconds": 1.0}
    ctx.counters = {"window_cache": {"decode": {"steps": 10, "rows_in_reach_full": 4000, "rows_in_reach_window": 960}}}
    fake_trace(ctx, seconds_a_call=1e-6)
    bytes_a_call = (4000 + 960) / 10 / 8 * F.kv_row_bytes(ctx.cfg)
    want = 100.0 * (bytes_a_call / ctx.peaks["hbm_bytes_per_s"]) / 1e-6
    assert R.read_metrics(ctx, m)["paged_walk_roofline.mellum2"]["value"] == pytest.approx(want, rel=1e-6)
    ctx.trace["op_counts"]["%paged_walk_decode.3 = custom-call"] = 80  # a ninth walk a step joins the match
    ctx.trace["ops"]["%paged_walk_decode.3 = custom-call"] = 1e-3
    assert R.read_metrics(ctx, m) == {}


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent commit has neither counter nor span: each reader returns
    nothing and does not raise."""
    _, _, metrics = R.load_cell(CELL)
    ctx = tiny_mellum2.ctx()
    ctx.window, ctx.counters = {}, {"serving": {}, "slots": 3}
    assert R.read_metrics(ctx, metrics) == {}
    ctx.trace = {"ops": {}, "op_counts": {}}
    assert R.read_metrics(ctx, metrics) == {}
    assert K.rows_counted(object()) == {"steps": 0, "rows_in_reach_full": 0, "rows_in_reach_window": 0}


def test_configuration_file_keeps_every_catalog_number_and_benchmark_json_matches_the_files():
    cfg = R.load_json(R.HERE / "configs/mellum2-12b-a2.5b-serve8.json")
    for key in ("source", "published", "reduced", "assumed", "deployment", "numerics", "init", "engine"):
        assert key in cfg
    published = dict(
        hidden_size=2304, intermediate_size=7168, moe_intermediate_size=896, head_dim=128,
        num_attention_heads=32, num_key_value_heads=4, num_experts=64, num_experts_per_tok=8,
        norm_topk_prob=True, sliding_window=1024, vocab_size=98304, rms_norm_eps=1e-06,
        attention_bias=False, tie_word_embeddings=False, max_window_layers=0, use_sliding_window=True)
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"] == {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert cfg["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert cfg["mlp_layer_types"] == ["sparse"] * 8 and cfg["num_hidden_layers"] == 8
    assert (cfg["published"]["num_hidden_layers"], cfg["published"]["max_position_embeddings"]) == (28, 131072)
    assert cfg["engine"] == {"slots": 32, "max_len": 32768, "page_size": 128, "prefill_buckets": [512, 1024, 2048],
                             "queue_depth": 32, "prefix_cache": False}
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == "mellum2-12b-a2.5b-serve8"][0]
    assert set(entry["reduced"]) <= set(cfg["reduced"]) and entry["source"] == cfg["source"]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types", "max_position_embeddings"]
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    on_disk = R.load_json(R.HERE / "workloads" / f"{CELL}.json")
    assert (cell["config"], cell["chips"], cell["why"]) == (on_disk["config"], 1, on_disk["why"])
    assert on_disk["params"] == {
        "clients": 32, "pool": 64, "prompt_len": {"median": 2048, "sigma": 1.2, "min": 256, "max": 28672},
        "answer_len": {"median": 1536, "sigma": 0.6, "min": 256, "max": 4096}, "max_total": 32767,
        "resume": "even", "check_requests": 3, "trace_seconds": 3.0, "limits": on_disk["params"]["limits"]}
    assert all(CELL in m["workloads"] for m in bench["end_to_end"] if m["name"] in ("itl_p95_ms", "serve_tok_s"))
    listed = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert set(listed) == NEW_METRICS and set(R.end_to_end_names(CELL, bench)) == set(E2E)
    for name, m in listed.items():
        f = R.load_json(R.HERE / "metrics" / f"{name}.json")
        assert (f["layer"], f["unit"], f["moves"], f["workloads"]) == (m["layer"], m["unit"], m["moves"], [CELL])
        assert (R.HERE / "readers" / f"{f['reader']}.py").exists()
    # the bytes ISSUE 35 reckoned: weights 7.59 GB, a page of K and V 262,144 B a layer
    assert round(2 * F.param_counts(cfg)["held"] / 1e7) == 759 and 128 * F.kv_row_bytes(cfg) == 262_144


def test_the_pool_is_the_one_issue_35_describes():
    from benchmarks import traffic

    p = R.load_json(R.HERE / "workloads" / f"{CELL}.json")["params"]
    pool = traffic.request_pool(p)
    prompts, answers = np.array([n for n, _ in pool]), np.array([m for _, m in pool])
    assert (prompts.min(), prompts.max(), round(prompts.mean())) == (256, 28672, 3936)
    assert ((prompts <= 1024).sum(), (prompts > 8192).sum(), (prompts > 16384).sum()) == (18, 8, 3)
    assert (answers.min(), answers.max(), round(answers.mean())) == (360, 4096, 1772)
    first = [next(s) for s in [K.base.request_stream(p, 3, 98304)] for _ in range(32)]
    assert all(len(ids) + rest <= p["max_total"] for ids, rest in first)
    # the streams a window opens on: 32 resumed prompts, the longest over 16k
    assert 100_000 < sum(len(ids) for ids, _ in first) < 250_000 and max(len(ids) for ids, _ in first) > 16_384


def test_result_line_of_the_cell_is_the_contracts(capsys):
    res = R.run_cell(tiny_mellum2.ctx(seed=11, seconds=0.5), {}, E2E)
    R.report(res)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["metrics"]) == set(E2E) and line["device"]["platform"] == "cpu"
