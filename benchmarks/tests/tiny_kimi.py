"""The long-reasoning cell of Kimi-Linear with every size cut, for the CPU
tests: the configuration's five layers (KDA dense, KDA, KDA, MLA, KDA), 16
routed experts of which 4 are held, prompts in chunks of 32 that resume a
slot's state, answers long enough that a request outlasts the window."""

import tiny
from benchmarks import run as R

SIZES = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_experts=4, num_experts_per_token=4, model_max_length=256)
# set as the cell's limit is, from readings at THIS size on the CPU (float32):
# sound runs read 0 to 1e-6; the float8 control reads 0.01 at least
LIMITS = {"logit_gap_mean": 2e-3}


def config():
    cfg = dict(R.load_json(R.HERE / "configs/kimi-linear-48b-a3b-ep2-serve5.json"), **SIZES)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"], head_dim=16, num_heads=4)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["numerics"] = dict(cfg["numerics"], weights="float32")
    cfg["init"] = dict(cfg["init"], matrix_std=0.05)
    cfg["engine"] = {"slots": 4, "max_len": 256, "prefill_buckets": [16, 32], "queue_depth": 4}
    return cfg


def ctx(seed=5, seconds=1.0, tracing=False, control=False, **limits):
    cfg = config()
    cell = R.load_json(R.HERE / "workloads/kimi_serve.longreason32.json")
    cell["params"].update(
        clients=4, pool=8, max_total=255, check_requests=2, trace_seconds=0.3,
        prompt_len={"median": 40, "sigma": 0.8, "min": 8, "max": 100},
        answer_len={"median": 120, "sigma": 0.5, "min": 60, "max": 150})
    cell["params"]["limits"] = {**LIMITS, **limits}
    return R.RunContext("tiny_kimi", cell, cfg, cell["params"], seed, seconds, tracing,
                        control=control, peaks=tiny.peaks(), device=tiny.DEVICE)
