"""Kind `train_step`: one compiled AMP-O2 AdamW step, driven back to back.

Set-up builds ONE object, the `to_static` step with its model and optimizer
state, drives it through `check_steps` steps on the seed's first batches
(reading each loss, the first gradient's norms from Adam's first moment,
and the parameters' change), and hands the same object to the window.  When
the window has closed and the state is freed, the plain reference follows
the same first steps and the two are compared.

params: batch, seqlen, check_steps, trace_steps, limits{loss_gap, grad_gap,
change_gap}.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from .. import traffic
from .. import weights as W
from .common import build_model, log_memory, memory_peak_bytes, norm_gap, traced_window


def build_step(ctx):
    """The step, its model and optimizer: the object the window drives."""
    import paddle_tpu as paddle

    hp = ctx.cfg["optimizer"]
    model = build_model(ctx)
    opt = paddle.optimizer.AdamW(
        learning_rate=hp["learning_rate"], beta1=hp["beta1"], beta2=hp["beta2"],
        epsilon=hp["epsilon"], weight_decay=hp["weight_decay"],
        parameters=model.parameters())
    model, opt = paddle.amp.decorate(model, opt, level=ctx.cfg["amp"]["level"],
                                     dtype=ctx.cfg["amp"]["dtype"])

    @paddle.jit.to_static
    def train_step(ids, labels):
        loss, _ = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return train_step, model, opt


def leaf_state(model, opt, slot):
    """{leaf: array} of the optimizer's view of each leaf, from its
    `state_dict()`: its first moment, or its float32 weight (the master copy
    where there is one)."""
    import jax.numpy as jnp

    state = opt.state_dict()
    masters = state.get("master_weights", {})
    out = {}
    for name, p in model.named_parameters():
        if slot == "moment1":
            # a step that never reached the optimizer leaves no moment: nought
            m = state.get(f"{p.name}_moment1")
            out[name] = jnp.zeros(p.shape, jnp.float32) if m is None else m._data
        else:
            out[name] = masters.get(p.name, p)._data
    return out


def norms_of(tree, minus=None, scale=1.0):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            a[k].astype(jnp.float32) - (b[k] if b else 0.0)))) * scale for k in a}

    return {k: float(v) for k, v in f(tree, minus or {}).items()}


def change_norms(ctx, model, opt):
    """Norm of (weight now - weight as made from the seed), a block of
    leaves at a time."""
    import jax.numpy as jnp

    cfg = ctx.cfg
    now = leaf_state(model, opt, "weight")
    blocks = [W.outer_leaves(cfg)] + [W.layer_leaves(cfg, l)
                                      for l in range(cfg["num_hidden_layers"])]
    out = {}
    for leaves in blocks:
        began = W.make(ctx.seed, cfg, leaves, jnp.float32)
        out.update(norms_of({n: now[n] for n in began}, minus=began))
    return out


def run(ctx):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import profiler

    p, cfg = ctx.params, ctx.cfg
    batch, seqlen, vocab = p["batch"], p["seqlen"], cfg["vocab_size"]
    profiler.reset_flash_pallas()
    profiler.reset_flash_fallbacks()
    train_step, model, opt = build_step(ctx)

    def feed(step):
        tok = traffic.train_tokens(ctx.seed, step, batch, seqlen, vocab)
        return tok, paddle.to_tensor(tok[:, :-1]), paddle.to_tensor(tok[:, 1:])

    # the first steps, through the window's own call and feed
    got = {"losses": []}
    fed = []
    for step in range(1, p["check_steps"] + 1):
        tok, ids, labels = feed(step)
        fed.append(np.asarray(tok))
        got["losses"].append(float(train_step(ids, labels).numpy()))
        if step == 1:
            got["grad_norms"] = norms_of(leaf_state(model, opt, "moment1"),
                                         scale=1.0 / (1.0 - cfg["optimizer"]["beta1"]))
            ctx.log(f"first step done (compiled or loaded), loss {got['losses'][0]:.4f}")
    got["change_norms"] = change_norms(ctx, model, opt)
    traces_before = train_step.trace_count + train_step.aot_hits
    ctx.log(f"check steps done, losses {got['losses']}")

    # the window: whole steps, each ended by fetching its loss
    step = p["check_steps"] + 1
    nxt = feed(step)
    losses, n_traced = [], 0
    log_memory(ctx, "window opens")
    setup_s = time.perf_counter() - ctx.t_start
    t0 = time.perf_counter()
    while True:
        if ctx.tracing and len(losses) == 2 and not n_traced:
            with traced_window(ctx):
                for _ in range(p["trace_steps"]):
                    _, ids, labels = nxt
                    a = time.perf_counter()
                    loss = train_step(ids, labels)
                    step += 1
                    nxt = feed(step)
                    b = time.perf_counter()
                    losses.append(float(loss.numpy()))
                    ctx.spans += [("train.dispatch", a, b),
                                  ("train.fetch_loss", b, time.perf_counter())]
                    n_traced += 1
        _, ids, labels = nxt
        loss = train_step(ids, labels)
        step += 1
        nxt = feed(step)  # dispatched behind the step, ahead of the wait
        losses.append(float(loss.numpy()))
        t1 = time.perf_counter()
        if t1 - t0 >= ctx.seconds:
            break
    del nxt, ids, labels, loss
    window_s = t1 - t0
    tokens = len(losses) * batch * seqlen
    ctx.window = {"t0": t0, "t1": t1, "seconds": window_s, "steps": len(losses),
                  "tokens": tokens, "batch": batch, "seqlen": seqlen}
    from .. import flops

    ctx.counters = {
        # what the traced steps' flash forward and backward must compute
        "traced_work": {"flash_train": {
            "flops": n_traced * flops.flash_train_flops(cfg, batch, seqlen), "bytes": 0,
            "steps": n_traced}},
        "flash_pallas": profiler.flash_pallas_summary(),
        "flash_fallbacks": profiler.flash_fallback_summary(),
        "compiles_in_window": train_step.trace_count + train_step.aot_hits - traces_before,
    }
    ctx.log(f"window {window_s:.3f}s, {len(losses)} steps, last loss {losses[-1]:.4f}")
    peak = memory_peak_bytes()

    # free the program's state, then let the reference follow the first steps
    del train_step, model, opt
    gc.collect()
    jax.clear_caches()
    from .. import reference

    t_ref = time.perf_counter()
    batches = [(t[:, :-1], t[:, 1:]) for t in fed]
    want = reference.train_steps(cfg, ctx.seed, batches)
    ctx.log(f"reference followed {len(fed)} steps in {time.perf_counter() - t_ref:.1f}s")
    if ctx.control:  # the reference in float8, in the program's place
        got = reference.train_steps(cfg, ctx.seed, batches, linear=reference.fp8_linear)
    checks = compare(got, want, p["limits"])
    checks["compiles_in_window"] = {"value": ctx.counters["compiles_in_window"], "limit": 0}
    checks["flash_fallbacks"] = {"value": sum(ctx.counters["flash_fallbacks"].values()), "limit": 0}
    return {
        "end_to_end": {
            "train_tok_s": {"value": tokens / window_s, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        },
        "attempted": len(losses) + len(got["losses"]),
        "failed": int(sum(not np.isfinite(x) for x in losses + got["losses"])),
        "checks": checks,
        "memory_peak_bytes": peak,
    }


def compare(got, want, limits):
    """The numbers of `correct`, each beside its limit."""
    loss_gap = max(abs(g - w) / abs(w) for g, w in zip(got["losses"], want["losses"]))
    grad_gap, grad_leaf = norm_gap(got["grad_norms"], want["grad_norms"])
    # leaves whose gradient is nought to rounding in the reference move under
    # Adam by round-off alone: left out of the change by a rule on the
    # reference's gradient, under a thousandth of the median leaf's
    gmed = float(np.median(list(want["grad_norms"].values())))
    moved = [k for k, g in want["grad_norms"].items() if g >= 1e-3 * gmed]
    change_gap, change_leaf = norm_gap({k: got["change_norms"][k] for k in moved},
                                       {k: want["change_norms"][k] for k in moved})
    return {
        "loss_gap": {"value": float(loss_gap), "limit": limits["loss_gap"]},
        "grad_gap": {"value": float(grad_gap), "limit": limits["grad_gap"], **grad_leaf},
        "change_gap": {"value": float(change_gap), "limit": limits["change_gap"], **change_leaf},
    }
