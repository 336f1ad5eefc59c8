"""CPU tests of the yardstick: name resolution, traffic, operation counts,
the trace reduction, the reference, and the harness's two ends (no chip, and
the result line).  Tiny sizes, one process, no child, no TPU topology."""

import io
import json
import random
import shutil
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

import tiny
from tiny import interpret  # noqa: F401  (a fixture)
from benchmarks import flops, run as R, trace_reduce as tr, traffic

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


# -- everything is a file, found by name ---------------------------------------

def test_new_cell_config_kind_and_reader_are_only_new_files(tmp_path):
    root = tmp_path / "benchmarks"
    shutil.copytree(R.HERE, root, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "configs/new-config.json").write_text(json.dumps({"hidden_size": 8}))
    (root / "workloads/new.cell.json").write_text(json.dumps(
        {"config": "new-config", "kind": "new_kind", "params": {"x": 3}, "why": "", "who": ""}))
    (root / "metrics/new.metric.json").write_text(json.dumps(
        {"layer": "l", "unit": "n", "moves": "new_e2e", "workloads": ["new.cell"],
         "reader": "new_reader", "args": {"times": 2}}))
    (root / "kinds/new_kind.py").write_text(
        "from .. import traffic\n"
        "def run(ctx):\n"
        "    ctx.counters['x'] = ctx.params['x']\n"
        "    ctx.trace_dir = None\n"
        "    return {'end_to_end': {'new_e2e': {'value': 1.5, 'unit': 'n'},\n"
        "            'setup_s': {'value': 0.1, 'unit': 's'}}, 'attempted': 1, 'failed': 0,\n"
        "            'checks': {'exact': {'value': 0, 'limit': 0}}, 'memory_peak_bytes': 7}\n")
    (root / "readers/new_reader.py").write_text(
        "def read(ctx, args):\n    return ctx.counters['x'] * args['times']\n")
    cell, cfg, metrics = R.load_cell("new.cell", root)
    assert cfg == {"hidden_size": 8} and list(metrics) == ["new.metric"]
    ctx = R.RunContext("new.cell", cell, cfg, cell["params"], 1, 1.0, False, device=tiny.DEVICE)
    res = R.run_cell(ctx, metrics, ["new_e2e", "setup_s"], root)
    assert res["correct"] and res["metrics"]["new_e2e"]["value"] == 1.5
    assert R.read_metrics(ctx, metrics, root) == {"new.metric": {"value": 6.0, "unit": "n"}}
    assert all(p.read_bytes() == b for p, b in before.items())  # nothing edited


def test_benchmark_json_agrees_with_the_files():
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell, _, metrics = R.load_cell(w["name"])
        assert cell["config"] == w["config"] and w["name"].endswith("." + w["traffic"])
        listed = {m["name"] for m in bench["per_layer"] if w["name"] in m["workloads"]}
        assert listed == set(metrics)
        e2e = R.end_to_end_names(w["name"], bench)
        assert "setup_s" in e2e and len(e2e) >= 2
        for m in bench["per_layer"]:
            if w["name"] in m["workloads"]:
                assert m["moves"] in e2e
                assert metrics[m["name"]]["layer"] == m["layer"]
    for c in bench["configs"]:
        cfg = R.load_json(R.ROOT / c["file"])
        assert cfg["source"] == c["source"] and set(cfg["reduced"]) == set(c["reduced"])


# -- traffic -----------------------------------------------------------------------

def chat_params():
    return R.load_json(R.HERE / "workloads/mistral7b_serve.chat32.json")["params"]


def test_traffic_same_seed_same_requests_and_clips():
    p = chat_params()
    take = lambda seed: [(ids.tolist(), n) for (ids, n), _ in zip(
        traffic.request_stream(p, seed, 32768), range(70))]
    a, b, c = take(2**31 + 9), take(2**31 + 9), take(4)
    assert a == b and a != c
    for ids, n in a:
        assert p["prompt_len"]["min"] <= len(ids) <= p["prompt_len"]["max"]
        assert p["answer_len"]["min"] <= n <= p["answer_len"]["max"]
        assert len(ids) + n <= p["max_total"] and min(ids) >= 1 and max(ids) < 32768
    # every seed gets the same set of sizes, in another order
    sizes = lambda reqs: sorted((len(i), n) for i, n in reqs[: p["pool"]])
    assert sizes(a) == sizes(c) == sorted(traffic.request_pool(p))
    assert [len(i) for i, _ in a[: p["pool"]]] != [len(i) for i, _ in c[: p["pool"]]]


def test_train_tokens_same_seed_same_batch_rows_differ():
    a = np.asarray(traffic.train_tokens(2**31 + 3, 4, 2, 128, 256))
    b = np.asarray(traffic.train_tokens(2**31 + 3, 4, 2, 128, 256))
    c = np.asarray(traffic.train_tokens(2**31 + 3, 5, 2, 128, 256))
    assert a.shape == (2, 129) and (a == b).all() and (a != c).any()
    assert (a[0] != a[1]).any() and a.min() >= 0 and a.max() < 256


# -- operations and bytes ------------------------------------------------------------

def test_flops_against_a_hand_count():
    cfg = dict(hidden_size=8, intermediate_size=16, num_attention_heads=2,
               num_key_value_heads=1, head_dim=4, vocab_size=32, num_hidden_layers=3)
    # a layer: q 8x8, k 8x4, v 8x4, o 8x8, gate/up/down 3 x 8x16 = 576
    p = flops.param_counts(cfg)
    assert p["layer_matmul"] == 64 + 32 + 32 + 64 + 384 == 576
    assert p["head"] == 256 and p["total"] == 3 * 576 + 512 + 8 * 7
    # causal attention, 5 tokens: 15 pairs x 4 x 2 heads x 4 dims x 3 layers
    assert flops.attention_flops(cfg, 5, 5, True) == 15 * 4 * 2 * 4 * 3 == 1440
    assert flops.forward_flops_prompt(cfg, 5) == 2 * 3 * 576 * 5 + 2 * 256 + 1440
    # one token over 9 of context: 9 pairs
    assert flops.forward_flops_decode(cfg, 9) == 2 * (3 * 576 + 256) + 9 * 4 * 2 * 4 * 3
    assert flops.train_flops_per_step(cfg, 2, 5) == 6 * (1728 + 256) * 10 + 3 * 2 * 1440
    peak = {"flops_per_s": {"bfloat16": 100.0}, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(200.0, 10.0, peak) == (2.0, "compute")
    assert flops.roofline_seconds(50.0, 10.0, peak) == (1.0, "memory")


# -- the trace reduction -----------------------------------------------------------

def test_union_gaps_and_labels():
    busy = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert tr.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    spans = [("a", 0, 4), ("b", 3.5, 5)]
    assert tr.label_gap((3, 5), spans) == "b"
    assert tr.label_gap((8, 10), spans) == "host (no span)"


def seeded(seeds, grid=None, reverse=False):
    """Gaps and spans from each seed: nested spans, twins that overlap a gap
    equally, spans of no length, spans wholly outside the window, gaps that no
    span touches; on a `grid` most overlaps tie and the list's order decides."""
    for seed in seeds:
        r = random.Random(seed)
        point = (lambda: float(r.randrange(grid))) if grid else (lambda: r.uniform(0, 1000))
        cuts = sorted({point() for _ in range(2 * r.randrange(1, 60))})
        gaps = [(cuts[i], cuts[i + 1]) for i in range(0, len(cuts) - 1, 2)]
        spans = []
        for i in range(r.randrange(1, 80)):
            a = 0.8 * point() + r.choice([-1200.0, 1200.0]) * (r.random() < 0.1)
            kind = r.random()
            d = r.uniform(0, 3) if kind < 0.6 else r.uniform(0, 300) if kind < 0.95 else 0.0
            d = float(int(d)) if grid else d
            spans.append((f"s{i % 7}", a, a + d))
            if r.random() < 0.3:
                spans.append((f"n{i % 5}", a, a + d) if r.random() < 0.5
                             else (f"n{i % 5}", a + d / 4, a + d / 2))
        yield gaps, spans[::-1] if reverse else spans


def recorded_train_trace():
    """The gaps and spans that `reduce` makes of the recorded v5e train trace."""
    meta = R.load_json(R.HERE / "tests/recorded_trace.json")
    profile = tr.load(str(R.HERE / "tests/recorded_trace.xplane.pb"))
    (lo, hi), = tr.host_events(profile, tr.WINDOW_EVENT)
    shift = lo - meta["window_perf"][0] * 1e9
    spans = [(n, a * 1e9 + shift, b * 1e9 + shift) for n, a, b in meta["spans"]]
    (ev,) = tr.device_ops(profile).values()
    busy = tr.union([(max(a, lo), min(b, hi)) for _, a, b in ev if b > lo and a < hi])
    yield tr.gaps(busy, lo, hi), spans


def recorded_serve_cut():
    """A cut of a traced window of `mistral7b_serve.chat32` on a v5e (PR 27):
    the device's intervals and the engine's spans that meet them, in the
    order the engine recorded them, in ns from the cut's start."""
    cut = R.load_json(R.HERE / "tests/recorded_serve_cut.json")
    busy = tr.union([tuple(iv) for iv in cut["device_intervals"]])
    yield tr.gaps(busy, cut["lo"], cut["hi"]), [tuple(s) for s in cut["spans"]]


def ring_full():
    """200,000 gaps against 400,000 spans, the ring's capacity."""
    r = random.Random(27)
    t, gaps = 0.0, []
    for _ in range(200_000):
        g0 = t + r.uniform(1e5, 5e5)
        t = g0 + r.uniform(1e3, 4e4)
        gaps.append((g0, t))
    spans = []
    for i in range(400_000):
        a = r.uniform(-0.1 * t, 1.1 * t)
        d = r.choice([r.uniform(1e4, 1e6), r.uniform(1e7, 3e8), r.uniform(1e9, 5e9)])
        spans.append((f"engine.{i % 4}", a, a + d))
    yield gaps, spans


LABEL_CASES = {
    "seeded": lambda: seeded(range(60)),
    "seeded_list_reversed": lambda: seeded(range(60), reverse=True),
    "ties_on_a_grid": lambda: seeded(range(60), grid=50),
    "ties_on_a_grid_list_reversed": lambda: seeded(range(60), grid=50, reverse=True),
    "spans_outside_only": lambda: [([(10.0, 20.0), (30.0, 31.0)], [
        ("a", 0.0, 10.0), ("b", 31.0, 40.0), ("c", 20.0, 30.0), ("d", -5.0, -1.0)])],
    "zero_spans": lambda: [([(0.0, 1.0), (2.0, 3.0)], [])],
    "recorded_train_trace": recorded_train_trace,
    "recorded_serve_cut": recorded_serve_cut,
    "ring_full": ring_full,
}


@pytest.mark.parametrize("case", list(LABEL_CASES))
def test_label_gaps_is_label_gap_of_every_gap(case):
    n = 0
    for gaps, spans in LABEL_CASES[case]():
        t = time.perf_counter()
        got = tr.label_gaps(gaps, spans)
        assert time.perf_counter() - t < 60
        assert len(got) == len(gaps)
        check = range(len(gaps))
        if len(gaps) * len(spans) > 1e8:  # `label_gap` on all of them would take hours
            check = random.Random(27).sample(check, 20)
        for i in check:
            assert got[i] == tr.label_gap(gaps[i], spans), (case, i, gaps[i])
        n += len(gaps)
    assert n


def test_reduce_a_recorded_trace():
    """A few train steps recorded on a v5e (PR 24): the numbers the file
    holds, counted by hand once."""
    path = R.HERE / "tests/recorded_trace.xplane.pb"
    meta = R.load_json(R.HERE / "tests/recorded_trace.json")
    lines = []
    out = tr.reduce(tr.load(str(path)), [tuple(s) for s in meta["spans"]],
                    tuple(meta["window_perf"]), log=lines.append)
    assert lines == ["1100 device operations, 905 idle gaps labelled by 4 spans of 4 handed over"]
    assert out["planes"] == 1
    assert out["window_s"] == pytest.approx(meta["window_s"], rel=1e-6)
    assert out["busy_s"] == pytest.approx(meta["busy_s"], rel=1e-6)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"][0][0] == meta["top_op"]
    assert sum(t for _, t in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-3)
    assert {n for n, _ in out["idle_gaps"]} <= {s[0] for s in meta["spans"]} | {"host (no span)"}


def test_read_spans_keeps_the_traced_windows_spans_and_says_when_the_ring_dropped(capsys):
    import paddle_tpu as paddle
    from benchmarks.kinds import serve_closed
    from paddle_tpu.obs import trace as obs

    paddle.set_flags({"FLAGS_trace": True, "FLAGS_obs_buffer_events": 16})  # the least it takes
    obs.reset()
    try:
        now = time.perf_counter()  # the traced window is (2, 4) from now
        for name, a, b in ([("dropped", 2, 3)] + [("before", 0, 1.5)] * 13
                           + [("inside", 2.5, 3), ("across", 1, 5), ("after", 4.5, 6)]):
            obs.record(name, "t", t0=now + a, t1=now + b)
        ctx = tiny.serve_ctx()
        ctx.trace_window = (now + 2, now + 4)
        serve_closed.read_spans(ctx)
    finally:
        paddle.set_flags({"FLAGS_trace": False, "FLAGS_obs_buffer_events": 4096})
        obs.reset()
    assert [n for n, _, _ in ctx.spans] == ["inside", "across"]
    (_, a, b), _ = ctx.spans
    assert a == pytest.approx(now + 2.5, abs=1e-3) and b == pytest.approx(now + 3, abs=1e-3)
    err = capsys.readouterr().err
    assert "2 spans meet the traced window, of 17 recorded and 1 dropped" in err
    assert "INCOMPLETE" in err


def test_kernel_roofline_is_silent_when_another_kernel_joins_the_match():
    """The recorded trace holds 2 steps of the 2-layer train cell: a forward
    and two backward flash calls a layer a step, 12 `tpu_custom_call`s."""
    from benchmarks.readers import kernel_roofline

    path = R.HERE / "tests/recorded_trace.xplane.pb"
    ctx = tiny.train_ctx()
    ctx.trace = tr.reduce(tr.load(str(path)))
    assert tr.ops_matching(ctx.trace["op_counts"], ["tpu_custom_call"]) == 12
    ctx.counters = {"traced_work": {"flash_train": {"flops": 1e12, "bytes": 0, "steps": 2}}}
    args = R.load_json(R.HERE / "metrics/flash_train_roofline.json")["args"]
    assert 0 < kernel_roofline.read(ctx, args) < 100
    ctx.trace["op_counts"]["%new = custom-call(), custom_call_target=\"tpu_custom_call\""] = 2
    assert kernel_roofline.read(ctx, args) is None


# -- the reference -------------------------------------------------------------------

def test_reference_agrees_with_the_program_in_float32():
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from benchmarks import reference, weights as W
    from benchmarks.kinds.common import LLAMA_KEYS
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = tiny.train_ctx().cfg
    model = LlamaForCausalLM(LlamaConfig(**{k: cfg[k] for k in LLAMA_KEYS}))
    made = W.make(11, cfg, W.all_leaves(cfg), jnp.float32)
    for name, p in model.named_parameters():
        p._data = made[name]
    ids = np.random.default_rng(0).integers(0, 256, (2, 24)).astype(np.int32)
    want = np.asarray(model(paddle.to_tensor(ids)).numpy())
    x = reference.outer_weights(11, cfg)["llama.embed_tokens.weight"][jnp.asarray(ids)]
    cos, sin = reference.rope_tables(cfg, 24)
    for layer in range(cfg["num_hidden_layers"]):
        x = reference.block(cfg, reference.f32_linear, reference.layer_weights(11, cfg, layer),
                            x, cos, sin)
    got = np.asarray(reference.head_logits(cfg, reference.f32_linear,
                                           reference.outer_weights(11, cfg), x))
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


# -- the harness's two ends ------------------------------------------------------------

def test_main_without_a_tpu_exits_nonzero_and_prints_no_metric(capsys):
    with pytest.raises(SystemExit) as e:
        R.main(["--workload", "mistral7b_train.seq4096", "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert "metrics" not in capsys.readouterr().out


@pytest.mark.parametrize("make_ctx,e2e,metric_cell", [
    (tiny.train_ctx, ["train_tok_s", "setup_s"], "mistral7b_train.seq4096"),
    (tiny.serve_ctx, ["itl_p95_ms", "serve_tok_s", "setup_s"], "mistral7b_serve.chat32"),
])
def test_last_line_has_exactly_the_contracts_keys(make_ctx, e2e, metric_cell, interpret):
    ctx = make_ctx()
    buf = io.StringIO()
    with redirect_stdout(buf):
        R.report(R.run_cell(ctx, {}, e2e))
    last = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert list(last)[:5] == CONTRACT_KEYS and list(last)[-1] == "checks"
    assert set(last) == set(CONTRACT_KEYS) | {"checks"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) == set(e2e)
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(last["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    for c in last["checks"].values():
        assert c["value"] <= c["limit"]
