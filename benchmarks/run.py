"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`--workload X` is `workloads/X.json`; its `config` is `configs/<config>.json`,
its `kind` is `kinds/<kind>.py`, and every `metrics/*.json` that lists X is
read by its `readers/<reader>.py`.  A new cell, configuration, kind or
per-layer metric is new files and an entry in `BENCHMARK.json`.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
before any work.  The last line of stdout is the result object.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def load_json(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class RunContext:
    """What a kind and the readers get.  The kind fills `window`,
    `counters`, `spans` and `trace_dir`; `trace` is the reduced trace.
    With `control` set (`control.py`) the kind puts the control's readings in
    the program's place before it compares."""
    workload: str
    cell: dict
    cfg: dict
    params: dict
    seed: int
    seconds: float
    tracing: bool
    control: bool = False
    peaks: dict | None = None
    device: dict | None = None
    t_start: float = T_START
    scratch: Path = ROOT / ".bench_scratch"
    window: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)
    trace_dir: str | None = None
    trace_window: tuple | None = None
    trace: dict | None = None

    def log(self, msg):
        print(f"[bench +{time.perf_counter() - self.t_start:6.1f}s] {msg}",
              file=sys.stderr, flush=True)


def load_cell(name, root=HERE):
    cell = load_json(root / "workloads" / f"{name}.json")
    cfg = load_json(root / "configs" / f"{cell['config']}.json")
    metrics = {}
    for path in sorted((root / "metrics").glob("*.json")):
        m = load_json(path)
        if name in m["workloads"]:
            metrics[path.stem] = m
    return cell, cfg, metrics


def end_to_end_names(name, bench):
    """The end-to-end metrics `BENCHMARK.json` gives this cell."""
    return [m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or name in m["workloads"]]


def load_module(group, name, root=HERE):
    """`<root>/<group>/<name>.py` as a module of this package, so that its
    relative imports find the yardstick's modules."""
    full = f"benchmarks.{group}.{name}"
    if root == HERE:
        return importlib.import_module(full)
    spec = importlib.util.spec_from_file_location(full, root / group / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def place_compile_cache():
    """Before jax is imported: the cache stays where the environment puts
    it, else at a fixed path inside the checkout."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def find_chip(chips):
    """The device as jax reports it; exits when it is no TPU or too few."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if device["platform"] != "tpu":
        sys.exit(f"benchmarks/run.py: no TPU (jax reports {device}); nothing is measured off the chip")
    if device["count"] < chips:
        sys.exit(f"benchmarks/run.py: the cell asks for {chips} chip(s), jax reports {device['count']}")
    peaks = load_json(HERE / "peaks.json")
    if device["kind"] not in peaks:
        sys.exit(f"benchmarks/run.py: no peaks for device kind {device['kind']!r} in peaks.json")
    return device, peaks[device["kind"]]


def reduce_trace(ctx):
    from benchmarks import trace_reduce

    try:
        t = time.perf_counter()
        profile = trace_reduce.load(trace_reduce.find_xplane(ctx.trace_dir))
        ctx.log(f"trace loaded in {time.perf_counter() - t:.1f}s")
        t = time.perf_counter()
        ctx.trace = trace_reduce.reduce(profile, ctx.spans, ctx.trace_window, log=ctx.log)
        ctx.log(f"trace reduced in {time.perf_counter() - t:.1f}s")
    finally:  # a trace is tens of MB; a check makes hundreds of runs
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)


def read_metrics(ctx, metrics, root=HERE):
    """Each per-layer metric through its reader; a reader that finds nothing
    returns None and the metric is left out."""
    out = {}
    for name, m in metrics.items():
        value = load_module("readers", m["reader"], root).read(ctx, m.get("args", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(ctx, metrics, e2e_names, root=HERE):
    """Everything after the look for a chip: the kind's set-up, window and
    comparison, then the result object."""
    outcome = load_module("kinds", ctx.cell["kind"], root).run(ctx)
    device = dict(ctx.device or {}, memory_peak_bytes=outcome["memory_peak_bytes"])
    if ctx.tracing:
        reduce_trace(ctx)
        device.update(busy_s=ctx.trace["busy_s"], window_s=ctx.trace["window_s"])
        t = time.perf_counter()
        values = read_metrics(ctx, metrics, root)
        ctx.log(f"{len(values)} of {len(metrics)} per-layer metrics read in "
                f"{time.perf_counter() - t:.1f}s")
    else:
        missing = [n for n in e2e_names if n not in outcome["end_to_end"]]
        if missing:
            raise KeyError(f"kind {ctx.cell['kind']} did not report {missing}")
        values = {n: outcome["end_to_end"][n] for n in e2e_names}
    checks = outcome["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {
        "correct": bool(correct),
        "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
        "metrics": values,
        "device": device,
    }
    if ctx.tracing:
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    result["checks"] = checks
    return result


def report(result):
    """Each number compared beside its limit as the last lines of stderr,
    the result object as the last line of stdout."""
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def run_on_chip(workload, seed, seconds, tracing=False, control=False, t_start=T_START):
    """One run of a cell as `BENCHMARK.json` and the files describe it, on
    the chips it asks for; exits when they are not there.  `t_start` is when
    this run's set-up began: the process's start, for the run of a process."""
    bench = load_json(ROOT / "BENCHMARK.json")
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}[workload]
    cell, cfg, metrics = load_cell(workload)
    place_compile_cache()
    import paddle_tpu  # noqa: F401  the system under test; absent, nothing runs

    device, peaks = find_chip(chips)
    ctx = RunContext(workload, cell, cfg, cell["params"], seed, seconds, tracing,
                     control=control, peaks=peaks, device=device, t_start=t_start)
    return run_cell(ctx, metrics, end_to_end_names(workload, bench))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    report(run_on_chip(args.workload, args.seed, args.seconds, bool(args.trace)))


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)  # jax's teardown has aborted after correct output before (PR 3)
