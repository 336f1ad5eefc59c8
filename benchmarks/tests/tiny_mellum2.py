"""The mixed cell of Mellum2 with every size cut, for the CPU tests: two whole
periods (sliding, sliding, sliding, full, twice), a window of 16 on pages of 8,
8 experts with 2 picked, a YaRN table that bites (original 64), prompts in
chunks of 32 several windows long, answers long enough that a request outlasts
the window."""

import copy

import tiny
from benchmarks import run as R

SIZES = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    sliding_window=16, num_experts=8, num_experts_per_tok=2, max_position_embeddings=256)
# set as the cell's limit is, from readings at THIS size on the CPU (float32):
# sound runs read 0 to 1e-6 over 4 seeds; the float8 control reads 2.5e-3 at least
LIMITS = {"logit_gap_mean": 3e-4}


def config():
    cfg = dict(R.load_json(R.HERE / "configs/mellum2-12b-a2.5b-serve8.json"), **SIZES)
    cfg["rope_parameters"] = copy.deepcopy(cfg["rope_parameters"])
    cfg["rope_parameters"]["full_attention"].update(original_max_position_embeddings=64, factor=4)
    cfg["numerics"] = dict(cfg["numerics"], weights="float32")
    cfg["init"] = dict(cfg["init"], matrix_std=0.05)
    cfg["engine"] = {"slots": 4, "max_len": 256, "page_size": 8, "prefill_buckets": [16, 32],
                     "queue_depth": 4, "prefix_cache": False}
    return cfg


def ctx(seed=5, seconds=1.0, tracing=False, control=False, **limits):
    cfg = config()
    cell = R.load_json(R.HERE / "workloads/mellum2_serve.mixed32.json")
    cell["params"].update(
        clients=4, pool=8, max_total=255, check_requests=2, trace_seconds=0.3,
        prompt_len={"median": 40, "sigma": 0.8, "min": 8, "max": 100},
        answer_len={"median": 120, "sigma": 0.5, "min": 60, "max": 150})
    cell["params"]["limits"] = {**LIMITS, **limits}
    return R.RunContext("tiny_mellum2", cell, cfg, cell["params"], seed, seconds, tracing,
                        control=control, peaks=tiny.peaks(), device=tiny.DEVICE)
