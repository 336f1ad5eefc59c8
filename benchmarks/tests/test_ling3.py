"""CPU tests of what the reasoning cell of Ling-3.0-flash adds to the
yardstick: the cell through `run_cell` with its control and one fault, the
resumed start, a cancelled request, its per-layer readers, the operation
counts and the configuration's file.  Tiny sizes (`tiny_ling3`), one process."""

import json

import numpy as np
import pytest

import tiny  # noqa: F401  (sets the platform and the path)
import tiny_ling3
from benchmarks import flops_ling3 as F, run as R
from benchmarks import weights_ling3 as W
from benchmarks.kinds import serve_closed_ling3 as K

E2E = ["itl_p95_ms", "serve_tok_s", "setup_s"]
CELL = "ling3_serve.reason64"


def test_cell_runs_sound_and_its_control_and_a_fault_are_not_correct(monkeypatch):
    """What `run.py` and `control.py` do on the chip, and a token altered
    where it is produced."""
    _, _, metrics = R.load_cell(CELL)
    ctx = tiny_ling3.ctx(seed=2_900_000_123, tracing=False)
    sound = R.run_cell(ctx, {}, E2E)
    assert sound["correct"] is True and sound["failed"] == 0
    assert sound["checks"]["compiles_in_window"]["value"] == 0
    # a request outlasts the window: what is in flight at its close is cancelled, not failed
    cut = [r for r in ctx.window["records"] if r.cut]
    assert cut and all(r.req.finish_reason == "cancelled" and not r.failed() for r in cut)
    # the readers over the run's window and counters (no trace on the CPU)
    ctx.trace_window = (ctx.window["t0"], ctx.window["t1"])
    ctx.spans = [("engine.prefill", ctx.window["t0"], ctx.window["t0"] + 0.25 * ctx.window["seconds"]),
                 ("engine.prefill_chunk", ctx.window["t0"], ctx.window["t0"] + 0.1),
                 ("engine.decode", ctx.window["t0"], ctx.window["t1"])]
    values = R.read_metrics(ctx, metrics)
    assert set(values) == set(metrics) == {
        "step_mfu.serve_ling3", "step_hbm_pct.serve_ling3", "engine.prefill_share_pct.ling3"}
    assert values["engine.prefill_share_pct.ling3"]["value"] == pytest.approx(25.0, abs=0.5)
    assert all(0 < v["value"] for v in values.values())
    linear = ctx.counters["linear_attn"]
    per_slot = F.state_bytes_per_slot(W.model_cfg(ctx.cfg), dtype_bytes=4)
    assert linear["state_bytes_read"] == linear["state_bytes_written"] == linear["live_slots"] * per_slot
    assert 0 < ctx.counters["moe"]["picks_held"] < 4 * ctx.counters["moe"]["tokens"]
    assert set(ctx.counters["arena_bytes"]) == {"latent", "kda_state", "conv_tail"}

    res = R.run_cell(tiny_ling3.ctx(seed=2_900_000_123, control=True), {}, E2E)
    assert res["correct"] is False
    assert res["checks"]["logit_gap_mean"]["value"] > 5 * res["checks"]["logit_gap_mean"]["limit"]

    from paddle_tpu.inference.engine import ContinuousBatchingEngine as Engine

    emit = Engine._emit
    monkeypatch.setattr(Engine, "_emit", lambda self, s, req, tok: emit(
        self, s, req, (tok + 1) % 256 if len(req.tokens) % 7 == 3 else tok))
    res = R.run_cell(tiny_ling3.ctx(seed=7), {}, E2E)
    assert res["correct"] is False
    assert res["checks"]["logit_gap_mean"]["value"] > res["checks"]["logit_gap_mean"]["limit"]


def test_resumed_start_gives_the_stated_phases():
    """The stream's first `clients` requests carry floor((i + 0.5) / clients *
    answer) of their answer in the prompt and ask for the rest; later ones
    are whole; every seed takes the pool in the same order."""
    from benchmarks import traffic

    p = R.load_json(R.HERE / "workloads" / f"{CELL}.json")["params"]
    pool = traffic.request_pool(p)
    assert len(pool) == 64 and all(n + m <= p["max_total"] for n, m in pool)
    order = np.random.default_rng(K.ORDER).permutation(64)
    a, b = K.request_stream(p, 1, 39296), K.request_stream(p, 2, 39296)
    total = []
    for i in range(130):
        (ids, rest), (ids2, rest2) = next(a), next(b)
        n, m = pool[order[i % 64]]
        done = int((i + 0.5) / 64 * m) if i < 64 else 0
        assert (len(ids), rest) == (n + done, m - done) == (len(ids2), rest2)
        assert ids.min() >= 1 and ids.max() < 39296 and not np.array_equal(ids, ids2)
        total.append(len(ids))
    # what set-up prefills and the streams hold at window open: ISSUE 33's "some 400k", "0.2k-30k"
    assert 300_000 < sum(total[:64]) < 500_000 and min(total[:64]) < 1_000 and 16_000 < max(total[:64]) < 32_768
    with pytest.raises(ValueError, match="resume"):
        next(K.request_stream(dict(p, resume="late"), 1, 100))


def test_a_request_the_kind_cancelled_is_not_failed_but_another_cancelled_one_is():
    class Req:
        error, finish_reason = None, "cancelled"

    r = K.Record(np.zeros(3, np.int32), 10)
    r.req, r.times = Req(), [0.1, 0.2]
    assert r.failed()  # nobody said the kind cut it: a request that did not run to its length
    r.cut = True
    assert not r.failed()
    r.req = type("R", (), {"error": None, "finish_reason": "error"})()
    assert r.failed()


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent commit has neither counter nor span: each reader returns
    nothing and does not raise."""
    _, _, metrics = R.load_cell(CELL)
    ctx = tiny_ling3.ctx()
    ctx.window, ctx.counters = {}, {"serving": {}, "slots": 3}
    assert R.read_metrics(ctx, metrics) == {}


def test_configuration_file_keeps_every_catalog_number():
    cfg = R.load_json(R.HERE / "configs/ling-3.0-flash-ep4-serve7.json")
    for key in ("source", "published", "reduced", "assumed", "deployment", "numerics", "init", "engine"):
        assert key in cfg
    published = dict(
        hidden_size=2560, intermediate_size=6144, moe_intermediate_size=768,
        moe_shared_expert_intermediate_size=768, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, head_dim=128, num_attention_heads=32,
        num_key_value_heads=32, num_experts_per_tok=8, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, first_k_dense_replace=2, layer_group_size=6,
        short_conv_kernel_size=4, kda_lower_bound=-5, rope_theta=6000000, q_lora_rank=None)
    assert {k: cfg[k] for k in published} == published
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    entry = [c for c in bench["configs"] if c["name"] == "ling-3.0-flash-ep4-serve7"][0]
    assert set(entry["reduced"]) <= set(cfg["reduced"]) and entry["source"] == cfg["source"]
    assert {k: cfg["published"][k] for k in entry["reduced"]} == dict(
        num_hidden_layers=42, num_experts=512, vocab_size=157184, max_position_embeddings=131072)
    mc = W.model_cfg(cfg)
    assert (mc["num_experts"], mc["experts_held"], mc["dense_layers_kept"]) == (512, 128, 1)
    assert W.model_cfg(mc) is mc
    assert [W.layer_kind(mc, l) for l in range(7)] == ["kda", "kda", "kda", "kda", "mla", "kda", "kda"]
    assert [W.is_moe(mc, l) for l in range(7)] == [False] + [True] * 6
    p = F.param_counts(mc)
    assert round(p["kda"] / 1e5) == 526 and round(p["mla"] / 1e5) == 320  # ISSUE 33: 52.6 M, 32.0 M
    assert round(p["held"] / 1e7) == 517  # 5.17 B parameters, 10.34 GB in bfloat16
    assert round(F.state_bytes_per_slot(mc) / 1e5) == 130  # 13.0 MB a slot


def test_operation_counts_grow_with_context_in_the_latent_layer_alone():
    mc = W.model_cfg(tiny_ling3.config())
    d = [F.forward_flops_decode(mc, n) for n in (10, 11, 12)]
    assert d[1] - d[0] == d[2] - d[1] == 2 * 4 * (2 * 32 + 8)  # one MLA layer, latent space
    assert F.picks_here(mc) == 4 * 4 / 16
    assert F.forward_flops_prompt(mc, 40) > 40 * F.token_flops(mc)
    p = F.param_counts(mc)
    assert (p["kda_layers"], p["mla_layers"], p["moe_layers"], p["dense_layers"]) == (6, 1, 6, 1)
    assert F.decode_bytes(mc, 2, 3, 1000, 50) == 2 * (2 * p["non_expert"] + 3 * p["expert"] + 50 * 40) + 1000


def test_result_line_of_the_cell_is_the_contracts(capsys):
    res = R.run_cell(tiny_ling3.ctx(seed=11, seconds=0.5), {}, E2E)
    R.report(res)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["metrics"]) == set(E2E) and line["device"]["platform"] == "cpu"
