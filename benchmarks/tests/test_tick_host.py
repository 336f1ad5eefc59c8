"""CPU tests of `readers/tick_host.py` on hand-built contexts: from the
engine's counters, from its `engine.tick.<phase>` spans, and nothing to read
from a program that has neither."""

import pytest

import tiny  # noqa: F401  (sets the platform and the path)
from benchmarks import run as R
from benchmarks.readers import tick_host

CELLS = {"mistral7b_serve.chat32", "dsv32_serve.longctx16", "ling3_serve.reason64", "mellum2_serve.mixed32"}


def ctx(**kw):
    c = R.RunContext("hand", {}, {}, {}, 1, 1.0, True)
    for k, v in kw.items():
        setattr(c, k, v)
    return c


def tick_spans(t, live_ms, wait_ms):
    """One steady tick's four spans from `t`: prepare, dispatch, wait,
    deliver; `live_ms` is split over the three that are the host's."""
    part = live_ms / 3e3
    return [("engine.tick.prepare", t, t + part), ("engine.tick.dispatch", t + part, t + 2 * part),
            ("engine.tick.wait", t + 2 * part, t + 2 * part + wait_ms / 1e3),
            ("engine.tick.deliver", t + 2 * part + wait_ms / 1e3, t + 3 * part + wait_ms / 1e3)]


def test_from_counters_is_the_engines_own_mean(capsys):
    phases = {"evict": 0.01, "admit": 0.2, "prepare": 0.1, "dispatch": 0.05, "wait": 1.5, "deliver": 0.12, "other": 0.02}
    tick = {"steps": 100, "wall_s": 2.0, "phases_s": phases, "host_s": 0.5, "host_ms_mean": 5.0, "wait_share": 0.75,
            "longest": [{"ms": 210.0, "at_s": 7.0, "phases_ms": {**dict.fromkeys(phases, 0.0), "admit": 200.0, "wait": 10.0}}]}
    c = ctx(counters={"serving": {"tick": tick}})
    assert tick_host.read(c, {"from": "counters"}) == 5.0
    log = capsys.readouterr().err
    assert "wait_share 0.7500" in log and "admit 2.000" in log and "(210.0, 'admit')" in log


@pytest.mark.parametrize("serving", [{}, {"occupancy_mean": 0.9}, {"tick": {"steps": 0, "host_ms_mean": None}}])
def test_counters_without_a_tick_give_nothing(serving):
    """The parent's `serving_summary()` has no `tick`; a window with no step
    has no mean."""
    assert tick_host.read(ctx(counters={"serving": serving}), {"from": "counters"}) is None
    assert tick_host.read(ctx(), {"from": "counters"}) is None


def test_from_spans_counts_what_starts_in_the_traced_seconds_and_leaves_wait_out(capsys):
    spans = [("engine.decode", 0.0, 9.0)]
    for i in range(12):  # 12 ticks inside (10.0, 11.2): 3 ms of host, 7 of wait each
        spans += tick_spans(10.0 + 0.1 * i + 0.01, 3.0, 7.0)
    # spans that start before the traced seconds and reach into them are left out, whole
    spans += [("engine.tick.dispatch", 9.98, 9.99), ("engine.tick.deliver", 9.99, 10.05)]
    spans += [("engine.tick.admit", 10.5, 10.56)]  # one admission: 60 ms over 12 steps
    c = ctx(spans=spans, trace_window=(10.0, 11.2))
    assert tick_host.read(c, {"from": "spans"}) == pytest.approx(3.0 + 60.0 / 12)
    log = capsys.readouterr().err
    assert "12 steps" in log and "wait 7.000" in log and "admit 5.000" in log


def test_from_spans_under_ten_steps_or_without_spans_gives_nothing():
    spans = [s for i in range(9) for s in tick_spans(10.0 + 0.1 * i, 3.0, 7.0)]
    assert tick_host.read(ctx(spans=spans, trace_window=(10.0, 11.2)), {"from": "spans"}) is None
    assert tick_host.read(ctx(spans=[("engine.decode", 10.0, 11.0)], trace_window=(10.0, 11.2)), {"from": "spans"}) is None
    assert tick_host.read(ctx(spans=spans), {"from": "spans"}) is None  # an untraced run


def test_the_two_metrics_list_the_four_serve_cells_and_read_through_run():
    """The metric files as `run.py` loads them for a cell, and the entries
    of `BENCHMARK.json` beside them."""
    bench = {m["name"]: m for m in R.load_json(R.ROOT / "BENCHMARK.json")["per_layer"]}
    for name, source, origin in (("engine.host_tick_ms", "program_counter", "counters"),
                                 ("engine.host_tick_ms.profiled", "program_span", "spans")):
        m = R.load_json(R.HERE / "metrics" / f"{name}.json")
        assert set(m["workloads"]) == set(bench[name]["workloads"]) == CELLS
        assert (m["reader"], m["args"], m["unit"], m["moves"]) == ("tick_host", {"from": origin}, "ms", "serve_tok_s")
        assert (bench[name]["source"], bench[name]["layer"], bench[name]["better"]) == (source, m["layer"], "lower")
    _, _, metrics = R.load_cell("ling3_serve.reason64")
    ours = {k: v for k, v in metrics.items() if k.startswith("engine.host_tick_ms")}
    tick = {"steps": 20, "wall_s": 0.3, "phases_s": dict.fromkeys(("evict", "admit", "prepare", "dispatch", "wait", "deliver", "other"), 0.0),
            "host_ms_mean": 4.0, "wait_share": 0.7, "longest": []}
    spans = [s for i in range(20) for s in tick_spans(10.0 + 0.05 * i, 6.0, 7.0)]
    values = R.read_metrics(ctx(counters={"serving": {"tick": tick}}, spans=spans, trace_window=(10.0, 11.2)), ours)
    assert values == {"engine.host_tick_ms": {"value": 4.0, "unit": "ms"},
                      "engine.host_tick_ms.profiled": {"value": pytest.approx(6.0), "unit": "ms"}}
