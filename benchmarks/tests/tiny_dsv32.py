"""The long-context DeepSeek-V3.2 cell with every size cut, for the CPU tests:
16 routed experts of which 4 are held, contexts of 3-12 times a top-k of 16,
prompts in chunks of 32."""

import tiny
from benchmarks import run as R

SIZES = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, dense_layers_kept=1, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=2, index_head_dim=16, index_topk=16, n_routed_experts=4, n_group=4,
    topk_group=2, num_experts_per_tok=4, max_position_embeddings=256)
# set as the cell's limit is, from readings at THIS size on the CPU (float32):
# sound runs read 0 over 4 seeds; the float8 control reads 0.12 at least
LIMITS = {"logit_gap_mean": 2e-3}


def config():
    cfg = dict(R.load_json(R.HERE / "configs/deepseek-v3.2-ep16-serve5.json"), **SIZES)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], original_max_position_embeddings=64)
    cfg["numerics"] = dict(cfg["numerics"], weights="float32")
    cfg["init"] = dict(cfg["init"], matrix_std=0.05)
    cfg["engine"] = {"slots": 3, "max_len": 256, "prefill_buckets": [16, 32]}
    return cfg


def ctx(seed=5, seconds=1.0, tracing=False, control=False, **limits):
    cfg = config()
    cell = R.load_json(R.HERE / "workloads/dsv32_serve.longctx16.json")
    cell["params"].update(
        clients=3, pool=6, max_total=255, check_requests=2, trace_seconds=0.3,
        prompt_len={"median": 80, "sigma": 0.6, "min": 40, "max": 200},
        answer_len={"median": 8, "sigma": 0.5, "min": 4, "max": 16})
    cell["params"]["limits"] = {**LIMITS, **limits}
    return R.RunContext("tiny_dsv32", cell, cfg, cell["params"], seed, seconds, tracing,
                        control=control, peaks=tiny.peaks(), device=tiny.DEVICE)
