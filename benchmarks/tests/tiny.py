"""Tiny sizes for the CPU tests: the cells' own files with every size cut."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from benchmarks import run as R  # noqa: E402

SIZES = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=256, num_hidden_layers=2,
             max_position_embeddings=512)
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
# set as the cells' limits are, from readings at THIS size on the CPU: sound
# runs read loss 2e-5, grad 9e-4, change 2e-3, logit 0 at most over 3-4 seeds;
# the float8 control reads grad 1.4e-2 and logit 6e-3 at least
LIMITS = {"loss_gap": 1e-3, "grad_gap": 4e-3, "change_gap": 5e-2, "logit_gap": 2e-3}


@pytest.fixture()
def interpret():
    """Pallas kernels in interpret mode for the test that asks for it."""
    from paddle_tpu.ops import flash_attention as fa

    old, fa._FORCE_INTERPRET = fa._FORCE_INTERPRET, True
    try:
        yield
    finally:
        fa._FORCE_INTERPRET = old


def peaks():
    return R.load_json(R.HERE / "peaks.json")["TPU v5 lite"]


def train_ctx(seed=5, seconds=0.3, tracing=False, control=False, **limits):
    cfg = dict(R.load_json(R.HERE / "configs/mistral-7b-v0.3-train2.json"), **SIZES)
    cell = R.load_json(R.HERE / "workloads/mistral7b_train.seq4096.json")
    cell["params"].update(batch=2, seqlen=128, trace_steps=2)
    cell["params"]["limits"] = {**LIMITS, **limits}
    return R.RunContext("tiny_train", cell, cfg, cell["params"], seed, seconds, tracing,
                        control=control, peaks=peaks(), device=DEVICE)


def serve_ctx(seed=5, seconds=1.0, tracing=False, control=False, **limits):
    cfg = dict(R.load_json(R.HERE / "configs/mistral-7b-v0.3-serve12.json"), **SIZES)
    cfg["engine"] = {"slots": 4, "max_len": 256, "prefill_buckets": [64, 128]}
    cell = R.load_json(R.HERE / "workloads/mistral7b_serve.chat32.json")
    cell["params"].update(
        clients=4, pool=8, max_total=255, check_requests=3, trace_seconds=0.3,
        prompt_len={"median": 40, "sigma": 0.8, "min": 8, "max": 128},
        answer_len={"median": 8, "sigma": 0.7, "min": 4, "max": 16})
    cell["params"]["limits"] = {**LIMITS, **limits}
    return R.RunContext("tiny_serve", cell, cfg, cell["params"], seed, seconds, tracing,
                        control=control, peaks=peaks(), device=DEVICE)
