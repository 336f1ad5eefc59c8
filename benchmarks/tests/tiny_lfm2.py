"""The generation cell of LFM2 with every size cut, for the CPU tests: the
configuration's nine layers (conv dense, then attention and three
convolutions, twice), 8 experts of which a token picks 4, head size 16
(packed rows 32 wide), prompts in chunks of 32 that resume a slot's tails,
answers long enough that a request outlasts the window."""

import tiny
from benchmarks import run as R

SIZES = dict(vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
             num_attention_heads=4, num_key_value_heads=2, num_experts=8, max_position_embeddings=256)
# set as the cell's limit is, from readings at THIS size on the CPU (float32):
# sound runs read 0 to 1e-6; the float8 control reads 1.8e-3
LIMITS = {"logit_gap_mean": 1e-4}


def config():
    cfg = dict(R.load_json(R.HERE / "configs/lfm2-8b-a1b-serve9.json"), **SIZES)
    cfg["numerics"] = dict(cfg["numerics"], weights="float32")
    cfg["init"] = dict(cfg["init"], matrix_std=0.05)
    cfg["engine"] = {"slots": 4, "max_len": 256, "prefill_buckets": [16, 32], "queue_depth": 4, "page_size": 16}
    return cfg


def ctx(seed=5, seconds=1.0, tracing=False, control=False, **limits):
    cfg = config()
    cell = R.load_json(R.HERE / "workloads/lfm2_serve.gen128.json")
    cell["params"].update(
        clients=4, pool=8, max_total=255, check_requests=2, trace_seconds=0.3,
        prompt_len={"median": 40, "sigma": 0.8, "min": 8, "max": 100},
        answer_len={"median": 120, "sigma": 0.5, "min": 60, "max": 150})
    cell["params"]["limits"] = {**LIMITS, **limits}
    return R.RunContext("tiny_lfm2", cell, cfg, cell["params"], seed, seconds, tracing,
                        control=control, peaks=tiny.peaks(), device=tiny.DEVICE)
