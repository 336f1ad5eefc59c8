"""What the kinds share: building the program's model on the seed's weights,
the trace window, and the gap measures of `correct`."""

from __future__ import annotations

import contextlib
import statistics
import time

import numpy as np

LLAMA_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
              "rms_norm_eps", "rope_theta")


def build_model(ctx):
    """`LlamaForCausalLM` at the configuration's sizes with the seed's
    weights, matrices in bfloat16 (the served type) and norms in float32,
    made on the device in one jitted call."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    from .. import weights as W

    cfg = ctx.cfg
    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("the Llama path derives head_dim from hidden_size / heads")
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**{k: cfg[k] for k in LLAMA_KEYS}))
    log_memory(ctx, "the program's own model is built")
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    log_memory(ctx, "it is cast to bfloat16")
    made = W.make(ctx.seed, cfg, W.all_leaves(cfg), jnp.bfloat16)
    named = dict(model.named_parameters())
    if set(named) != set(made):
        raise KeyError(f"leaf names differ: {sorted(set(named) ^ set(made))[:6]}")
    for name, p in named.items():
        if tuple(p.shape) != tuple(made[name].shape):
            raise ValueError(f"{name}: {p.shape} != {made[name].shape}")
        p._data = made[name]
    log_memory(ctx, "the seeded weights are loaded")
    return model


def memory_peak_bytes():
    """The peak on the fullest chip, as jax reports it: the process's, so
    set-up's transients count in it."""
    import jax

    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()))


def log_memory(ctx, when):
    """Bytes in use now beside the peak so far, on the fullest chip: what the
    window holds, which the process's peak does not tell."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    ctx.log(f"memory when {when}: in use {max(s.get('bytes_in_use', 0) for s in stats)}, "
            f"peak so far {max(s.get('peak_bytes_in_use', 0) for s in stats)}")


@contextlib.contextmanager
def traced_window(ctx):
    """Profile what runs inside, marked with the event the reduction looks
    for; notes the window on the host's clock to tie the two."""
    import jax

    from ..trace_reduce import WINDOW_EVENT

    ctx.trace_dir = str(ctx.scratch / "trace")
    jax.profiler.start_trace(ctx.trace_dir)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(WINDOW_EVENT):
            yield
            t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    ctx.trace_window = (t0, t1)


def norm_gap(got, want):
    """Worst leaf: the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    floor = statistics.median(want.values())
    worst, leaf = 0.0, None
    for k, w in want.items():
        g = abs(got[k] - w) / max(w, floor)
        if g > worst:
            worst, leaf = g, k
    return worst, {"leaf": leaf, "got": got.get(leaf), "want": want.get(leaf), "median": floor}


def percentile(values, q):
    """Nearest-rank percentile of all values, q in (0, 100]."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1)])
